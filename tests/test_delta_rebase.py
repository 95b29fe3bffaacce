"""The sharded engine under delta lanes (``Field(delta=)``): the level
base, the re-base the promote makes when it moves, and what the level
records say of both (PR 47).

The twin here is hand-countable.  One node with two counters and two
messages that are never consumed: ``INC`` adds one to ``t`` and to
``c``, ``SKIP`` to ``c`` alone.  Level d holds the d + 1 states ``c`` =
d, ``t`` = 0..d, so

* ``t``'s minimum stays 0 and its maximum is the depth: its delta lane
  never re-bases and fills its window (``bits`` = 3: 0..7) at depth 8;
* ``c`` is the depth in every state: declared a delta lane too
  (``steps_delta``), its base moves at EVERY level, and the promote
  re-encodes every row the level appended.

Lab 2's twin — the tree's one protocol with delta lanes — is held to
the same on a root whose view numbers are all at least 2."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu.compiler import (Field, MessageType,  # noqa: E402
                                     NodeKind, ProtocolSpec)
from dslabs_tpu.tpu.engine import CapacityOverflow  # noqa: E402
from dslabs_tpu.tpu.sharded import (ShardedTensorSearch,  # noqa: E402
                                    make_mesh)

SHOWN = ("depth", "unique", "explored", "next_frontier", "chunks",
         "write_blocks", "probe_cols")


def clock_spec(bits=3, steps_delta=True):
    steps = (Field("c", delta=bits) if steps_delta
             else Field("c", hi=15))
    spec = ProtocolSpec(
        "clock", nodes=[NodeKind("n", 1, (Field("t", delta=bits), steps))],
        messages=[MessageType("INC", ()), MessageType("SKIP", ())],
        timers=[], net_cap=4, timer_cap=2)

    @spec.on("n", "INC")
    def inc(ctx, m):
        ctx.put("t", ctx.get("t") + 1)
        ctx.put("c", ctx.get("c") + 1)
        ctx.send("INC", 0)

    @spec.on("n", "SKIP")
    def skip(ctx, m):
        ctx.put("c", ctx.get("c") + 1)
        ctx.send("SKIP", 0)

    spec.initial_messages.append(("INC", 0, 0, {}))
    spec.initial_messages.append(("SKIP", 0, 0, {}))
    return spec


def clock_search(n_devices=1, max_depth=6, **kw):
    spec_kw = {k: kw.pop(k) for k in ("bits", "steps_delta") if k in kw}
    return ShardedTensorSearch(
        clock_spec(**spec_kw).compile(), make_mesh(n_devices),
        chunk_per_device=8, frontier_cap=64, visited_cap=1 << 10,
        strict=True, max_depth=max_depth, **kw)


def _dispatches(search):
    """Run ``search``; ``(outcome, [dispatch tag, ...])``."""
    tags = []
    dispatch = search._dispatch

    def counted(tag, fn, *args):
        tags.append(tag)
        return dispatch(tag, fn, *args)

    search._dispatch = counted
    return search.run(), tags


# --------------------------------------------------- the codec's one add

def test_rebase_words_is_unpack_then_pack():
    """``LanePacking.rebase_words``: one addend a word moves a packed
    row from one base to another, bit for bit what the oracle's unpack
    against the old base and pack against the new one give — on lab 2's
    own descriptor, whose five delta lanes share words with other
    fields, for bases that rise and one that does not move."""
    from dslabs_tpu.tpu.packing import derive_packing
    from dslabs_tpu.tpu.specs import compile_pb_protocol

    p = compile_pb_protocol(2, 2, 1, shared_key=True)
    lanes = (p.node_width + p.net_cap * p.msg_width
             + p.n_nodes * p.timer_cap * p.timer_width + 1)
    pk = derive_packing(p, lanes, delta=True)
    dl = pk.delta_lanes
    assert pk.has_delta and len(dl) == 5 and (pk.width[dl] == 8).all()
    rng = np.random.default_rng(47)
    rows = np.stack([pk.lo[i] + rng.integers(
        0, max((1 << min(int(pk.width[i]), 31)) - 2, 1), 512)
        for i in range(lanes)], axis=1)
    old, new = np.zeros(lanes, np.int64), np.zeros(lanes, np.int64)
    old[dl], new[dl] = [3, 2, 5, -1, 7], [5, 2, 9, 4, 7]
    rows[:, dl] = new[dl] + rng.integers(0, 200, (512, len(dl)))
    rows = rows.astype(np.int32)
    step = np.asarray(pk.rebase_words(old.astype(np.int32),
                                      new.astype(np.int32)))
    assert step.shape == (pk.words,) and np.count_nonzero(step) == 3
    moved = pk.pack_np(rows, old) + step          # int32: wraps
    assert np.array_equal(moved, pk.pack_np(rows, new))
    assert np.array_equal(pk.unpack_np(moved, new), rows)


# ---------------------------------------------- a base that moves: exact

@pytest.mark.parametrize("n_devices", [1, 4])
def test_packed_equals_raw_while_the_base_moves(n_devices):
    """Counts, write blocks and probe columns of every level are the
    raw wire's, on one device and on a four-device mesh, in a search
    whose base moves at every level; and the level records count the
    re-bases exactly."""
    packed, tags = _dispatches(clock_search(n_devices))
    raw = clock_search(n_devices, mesh_pack=False).run()
    assert packed.end_condition == raw.end_condition == "DEPTH_EXHAUSTED"
    assert ([{k: lv[k] for k in SHOWN} for lv in packed.levels]
            == [{k: lv[k] for k in SHOWN} for lv in raw.levels])
    assert [lv["unique"] for lv in packed.levels] == [3, 6, 10, 15, 21, 28]
    assert packed.bytes_per_state == 4 and raw.bytes_per_state > 4
    # level d appends d + 1 rows, all re-encoded: c's base is the depth.
    # The last level is depth-limited: nothing it appends is promoted.
    assert [lv["rebased"] for lv in packed.levels] == [1, 1, 1, 1, 1, 0]
    assert [lv["rebase_rows"] for lv in packed.levels] == [2, 3, 4, 5, 6, 0]
    # t runs 0..d against a base of 0; c is one above its base
    assert [lv["delta_peak"] for lv in packed.levels] == [1, 2, 3, 4, 5, 6]
    assert "rebased" not in raw.levels[0]
    assert tags.count("sharded.promote_rebase") == 5
    assert tags.count("sharded.promote") == 0


def test_a_base_that_stays_dispatches_no_re_encode():
    """``c`` bounded instead: ``t``'s minimum is 0 at every level, so no
    level re-bases — every promote is the program that moves counters
    only — while the window fills as before."""
    search = clock_search(1, steps_delta=False)
    out, tags = _dispatches(search)
    assert [lv["unique"] for lv in out.levels] == [3, 6, 10, 15, 21, 28]
    assert [lv["rebased"] for lv in out.levels] == [0] * 6
    assert [lv["rebase_rows"] for lv in out.levels] == [0] * 6
    assert [lv["delta_peak"] for lv in out.levels] == [1, 2, 3, 4, 5, 6]
    assert tags.count("sharded.promote_rebase") == 0
    assert tags.count("sharded.promote") == 5


def test_the_spill_tier_wired_never_re_bases():
    """With the spill tier wired a level may end in a drain and a
    re-inject instead of a promote, so the engine leaves the base where
    the root put it: counts are the raw wire's, no re-base is
    dispatched, and ``c`` fills its window from the root's base."""
    packed, tags = _dispatches(clock_search(1, spill=True))
    raw = clock_search(1, spill=True, mesh_pack=False).run()
    assert ([{k: lv[k] for k in SHOWN} for lv in packed.levels]
            == [{k: lv[k] for k in SHOWN} for lv in raw.levels])
    assert [lv["unique"] for lv in packed.levels] == [3, 6, 10, 15, 21, 28]
    assert [lv["rebased"] for lv in packed.levels] == [0] * 6
    assert [lv["rebase_rows"] for lv in packed.levels] == [0] * 6
    assert [lv["delta_peak"] for lv in packed.levels] == [1, 2, 3, 4, 5, 6]
    assert tags.count("sharded.promote_rebase") == 0
    assert tags.count("sharded.promote") == 5


def test_a_value_past_its_window_raises():
    """Three bits hold 0..7 above the base: at depth 8 ``t`` = 8 is out
    of its window in a live successor, and the level's sync raises."""
    assert clock_search(1, max_depth=7).run().unique_states == 36
    with pytest.raises(CapacityOverflow, match="depth 8"):
        clock_search(1, max_depth=9).run()


def test_the_re_base_program_names_its_scope():
    """``promote.rebase`` is the re-encode's scope in the program the
    host dispatches when the base moved, and in no other."""
    from dslabs_tpu.tpu import telemetry as tel_mod

    search = clock_search(1)
    search.aot_warmup()
    exes = search._aot_exes
    rebase = f"/{tel_mod.SCOPE_PREFIX}promote.rebase/"
    assert rebase in exes["promote_rebase"].as_text()
    assert rebase not in exes["promote"].as_text()
    assert rebase not in exes["superstep"].as_text()
    assert {s for s, _named in tel_mod.scopes_of_hlo(
        exes["promote_rebase"].as_text()).values()} == {
            "promote", "promote.rebase"}


# ------------------------------------------- lab 2's twin, a staged root

def _pb_root(search, view):
    """The twin's initial state moved into a synced view ``view``: the
    view server, both servers and both clients hold its number.  (No
    reachable state: a root for the codec, whose every delta lane
    starts above zero.)"""
    import jax.numpy as jnp

    from dslabs_tpu.tpu.specs import pb_spec

    at = pb_spec(2, 2, 1, shared_key=True).decode_tables()[2]
    state = search.initial_state()
    nodes = np.asarray(state["nodes"]).copy()
    nodes[..., at[("vs", 0, "vn")]] = view
    nodes[..., at[("vs", 0, "prim")]] = 1
    nodes[..., at[("vs", 0, "back")]] = 2
    for s in range(2):
        nodes[..., at[("server", s, "svn")]] = view
        nodes[..., at[("server", s, "sp")]] = 1
        nodes[..., at[("server", s, "sb")]] = 2
    for c in range(2):
        nodes[..., at[("client", c, "cvn")]] = view
        nodes[..., at[("client", c, "cp")]] = 1
        nodes[..., at[("client", c, "cb")]] = 2
    return dict(state, nodes=jnp.asarray(nodes))


def _pb_search(n_devices, **kw):
    import dataclasses

    from dslabs_tpu.tpu.specs import compile_pb_protocol

    p = dataclasses.replace(
        compile_pb_protocol(2, 2, 1, timer_cap=2, shared_key=True),
        goals={})
    return ShardedTensorSearch(
        p, make_mesh(n_devices), chunk_per_device=64,
        frontier_cap=1 << 11, visited_cap=1 << 14, strict=True,
        max_depth=4, ev_budget=(40, 8), **kw)


@pytest.mark.parametrize("n_devices", [
    1, pytest.param(4, marks=pytest.mark.slow)])
def test_lab2_packed_equals_raw_where_the_base_is_not_zero(n_devices):
    """Lab 2's twin, packed against raw, level for level, on two
    searches of one engine each.  From a root in view 3 the level-0
    base is 3 in all five delta lanes.  From the twin's own root the
    base MOVES at level 6 — by then every state has seen a ping, so
    ``vn``'s minimum is 1 — and the promote re-encodes the level's
    1,010 rows."""
    packed, raw = _pb_search(n_devices), _pb_search(n_devices,
                                                    mesh_pack=False)
    carry = packed._init_carry(_pb_root(packed, 3))
    assert np.asarray(carry["pb_cur"]).reshape(n_devices, -1).tolist() == [
        [3] * 5] * n_devices

    def both(depth, root):
        outs = []
        for search in (packed, raw):
            search.max_depth = depth
            outs.append(search.run(
                initial=None if root is None else _pb_root(search, root),
                check_initial=False))
        assert ([{k: lv[k] for k in SHOWN} for lv in outs[0].levels]
                == [{k: lv[k] for k in SHOWN} for lv in outs[1].levels])
        return outs[0]

    staged = both(4, 3)
    assert [lv["unique"] for lv in staged.levels] == [9, 43, 151, 447]
    assert [lv["delta_peak"] for lv in staged.levels] == [0, 1, 1, 1]
    out = both(7, None)
    assert [lv["unique"] for lv in out.levels] == [
        5, 22, 82, 261, 711, 1721, 3837]
    assert [lv["rebased"] for lv in out.levels] == [0, 0, 0, 0, 0, 1, 0]
    assert [lv["rebase_rows"] for lv in out.levels] == [
        0, 0, 0, 0, 0, 1010, 0]
    assert [lv["delta_peak"] for lv in out.levels] == [1, 2, 2, 2, 2, 3, 3]
