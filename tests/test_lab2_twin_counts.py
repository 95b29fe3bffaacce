"""Lab 2's compiled twin counts what the object checker counts on
PrimaryBackupTest test18's deployment — a view server, two servers, two
clients with ONE APPEND each to ONE key, every timer live — from the
root: ``pb_spec(2, 2, 1, shared_key=True)`` through the strict sharded
engine, cumulative unique states depth by depth.

Depth 8 is the first where the order of the two APPENDs shows: the
default twin (one last-executed seq a client) counts 8,133 there, the
object checker 8,135.  The frozen-timer exhaust from test18's synced
view (134 states; the default twin finds 118) runs through the lab
entry, where a staged root can be derived:
``tests/test_lab2_entry.py`` ``test18-exhaust``.  With the flag off the
spec is what it was before it had one, lane for lane."""

import dataclasses
import json
import os

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.search.search import BFS  # noqa: E402
from dslabs_tpu.tpu.sharded import (ShardedTensorSearch,  # noqa: E402
                                    make_mesh)
from dslabs_tpu.tpu.specs import compile_pb_protocol, pb_spec  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The object checker's, from the root (this sandbox, PR 47: depth 12 in
# 510 s, depth 13 in 967 s); the configuration pins the same.
OBJECT = {1: 5, 2: 22, 3: 82, 4: 261, 5: 711, 6: 1721, 7: 3837, 8: 8135,
          9: 16769, 10: 33939, 11: 67399, 12: 130523, 13: 244879}


def _object_count(depth):
    import tests.test_lab2_entry as E
    from dslabs_tpu.labs.clientserver.kv_workload import (
        APPENDS_LINEARIZABLE, append_same_key_workload)
    from dslabs_tpu.search.settings import SearchSettings

    settings = SearchSettings().add_invariant(APPENDS_LINEARIZABLE)
    settings.set_max_depth(depth).max_time(3000)
    res = BFS(settings).run(E._state(append_same_key_workload(1), 2, 2))
    assert res.end_condition.name == "SPACE_EXHAUSTED"
    return res.discovered_count


def _twin_counts(depth, shared_key=True, **caps):
    p = dataclasses.replace(
        compile_pb_protocol(2, 2, 1, shared_key=shared_key), goals={})
    out = ShardedTensorSearch(
        p, make_mesh(1), chunk_per_device=256, strict=True,
        max_depth=depth, ev_budget=(40, 8),
        **dict(dict(frontier_cap=1 << 13, visited_cap=1 << 16), **caps)
    ).run()
    assert (out.dropped, out.visited_overflow) == (0, 0)
    return {lv["depth"]: lv["unique"] for lv in out.levels}


def test_the_twin_counts_the_object_checkers_states_to_depth_8():
    got = _twin_counts(8)
    assert got == {d: OBJECT[d] for d in range(1, 9)}
    # live, at the depth where the order first shows (22 s of checker)
    assert _object_count(8) == got[8] == 8135
    assert _object_count(5) == got[5]


def test_the_default_twin_cannot_tell_the_orders_apart():
    """Why the flag exists: two APPENDs to one key leave ``xy`` or
    ``yx``, and the default twin's one seq a client holds neither."""
    got = _twin_counts(8, shared_key=False)
    assert {d: got[d] for d in range(1, 8)} == {
        d: OBJECT[d] for d in range(1, 8)}
    assert got[8] == 8133 < OBJECT[8]


@pytest.mark.slow
def test_the_twin_counts_the_object_checkers_states_to_depth_13():
    """As deep as the counts are pinned; the object checker alone takes
    a quarter of an hour to depth 13, so the live comparison stops at
    depth 10 (100 s) and the rest holds the twin to the pinned."""
    got = _twin_counts(13, frontier_cap=1 << 18, visited_cap=1 << 21)
    assert got == OBJECT
    assert _object_count(10) == got[10]


def test_the_configuration_pins_these_counts():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lab2-primarybackup-s2c2.json")) as fh:
        pinned = json.load(fh)["reference_counts"]
    assert {int(d): n for d, n in pinned.items()} == {
        d: OBJECT[d] for d in range(1, len(pinned) + 1)}
    assert len(pinned) >= 12


# ----------------------------------------------------- the flag, off and on

def _shape(spec):
    p = spec.compile()
    return {"layout": spec._layout(), "tags": (spec._mtag, spec._ttag),
            "widths": (p.n_nodes, p.node_width, p.msg_width,
                       p.timer_width, p.net_cap, p.timer_cap),
            "domains": p.lane_domains, "name": p.name}


def test_the_flag_off_is_the_spec_as_it_was():
    """``pb_spec(2, 2, 1)``: 33 node lanes, messages of 8, a network of
    32, four timers of 4 a node — 370 lanes, 83 packed words, 5 of the
    lanes delta — and
    whatever the new arguments default to changes nothing."""
    from dslabs_tpu.tpu.packing import derive_packing

    off = _shape(pb_spec(2, 2, 1))
    assert off == _shape(pb_spec(2, 2, 1, net_cap=32, timer_cap=4,
                                 shared_key=False))
    assert off["widths"] == (5, 33, 8, 4, 32, 4)
    assert off["name"] == "pb-gen"
    names = [f for (_k, _i, f) in off["layout"][0]]
    assert "ord" not in names and "res" not in names
    lanes = 33 + 32 * 8 + 5 * 4 * 4 + 1
    assert lanes == 370
    pk = derive_packing(pb_spec(2, 2, 1).compile(), lanes, delta=True)
    assert (pk.words, len(pk.delta_lanes)) == (83, 5)
    # the caps are arguments now: the lab ladder can climb
    wide = pb_spec(2, 2, 1, net_cap=64, timer_cap=6).compile()
    assert (wide.net_cap, wide.timer_cap) == (64, 6)


def test_the_flag_on_adds_the_order_and_nothing_else():
    on, off = _shape(pb_spec(2, 2, 1, shared_key=True)), _shape(
        pb_spec(2, 2, 1))
    assert on["name"] == "pb-gen-shared"
    added = set(on["layout"][0]) - set(off["layout"][0])
    assert added == {("server", 0, "ord"), ("server", 1, "ord"),
                     ("client", 0, "res"), ("client", 1, "res")}
    assert on["widths"] == (5, 33 + 4 * 2, 8, 4, 32, 4)
    assert on["tags"] == off["tags"]
    with pytest.raises(ValueError, match="one APPEND a client"):
        pb_spec(2, 2, 2, shared_key=True)
