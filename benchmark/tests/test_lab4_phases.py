"""Driver ``lab4_phases`` rehearsed on the CPU through the harness's own
runner, as ``test_lab_phases.py`` rehearses its lab 3 sibling — on
``shardtx-suite``'s own deployment (the twins are small; what a CPU test
cannot hold is ``commit``'s 130 K states), with a cycle cut to the Join
search and two shallow done-pruned exhausts of the joined state: every
kind of ``start`` (root, goal of, start of) and the added client.  The
broken timed path and the control come out as not correct, and the four
``.lab4`` readers compute from recorded annotations and give ``None``
where a program writes none of them."""

import copy
import dataclasses

import pytest

from control import narrowed_fingerprint
from helpers import run_cell, tiny_cell
from test_rehearsal import LAST_LINE_KEYS, _break_entry, failed_checks

LAB = {"entry_overhead_s.lab", "search_s.lab", "warmup_s.lab",
       "engine_build_s.lab", "warm_run_s.lab", "replay_s.lab",
       "recompile_s.lab", "dispatches_per_call.lab",
       "engine_cache_hit_pct.lab", "ladder_attempts_per_call.suite"}
LAB4 = {"ladder_wasted_s.lab4", "root_validate_s.lab4",
        "expand_us_per_state.lab4", "superstep_roofline.lab4"}


def small_cell(**params):
    cell = tiny_cell("shardtx-suite", **{
        "cycle": ["join", "exhaust3", "exhaust4"],
        "traced_phases": ["exhaust3"], **params})
    config = copy.deepcopy(cell.config)
    exhaust = config["phases"]["exhaust6"]
    config["phases"].update(
        exhaust3=dict(exhaust, start="goal of join", adds="client",
                      max_depth=3),
        exhaust4=dict(exhaust, start="start of exhaust3", max_depth=4))
    counts = config["exhaust_counts"]["by_max_depth"]
    config["reference"].update({
        f"exhaust{d}": {"end_condition": "SPACE_EXHAUSTED",
                        "discovered_count": counts[str(d)]}
        for d in (3, 4)})
    return dataclasses.replace(cell, config=config)


def test_lab4_phases_rehearsal_builds_the_last_line():
    res, lines = run_cell(small_cell(), seconds=1)
    assert set(res) == LAST_LINE_KEYS and res["correct"] is True
    assert failed_checks(lines) == []
    assert set(res["metrics"]) == {"verdict_s", "setup_s"}
    assert res["attempted"] == 3 and res["failed"] == 0   # one cycle
    for want in ("check call0.join.reference.terminal_depth: value=4 "
                 "limit=4 ok",
                 "check call1.exhaust3.reference.discovered_count: "
                 "value=142 limit=142 ok",
                 "check call1.exhaust3.discovered_count: value=142 "
                 "limit=142 ok",
                 "check call2.exhaust4.discovered_count: value=467 "
                 "limit=467 ok"):
        assert want in lines


def test_lab4_phases_traced_rehearsal_reads_the_per_layer_metrics():
    res, lines = run_cell(small_cell(), seconds=1, trace=True)
    assert res["correct"] is True and failed_checks(lines) == []
    m = res["metrics"]
    assert set(m) == LAB | LAB4
    # the slice is the one staged call: its root validated, no rung
    # climbed, nothing thrown away
    assert m["ladder_attempts_per_call.suite"]["value"] == 1.0
    assert m["ladder_wasted_s.lab4"]["value"] == 0.0
    assert 0 < m["root_validate_s.lab4"]["value"] \
        < m["engine_build_s.lab"]["value"]
    assert m["expand_us_per_state.lab4"]["value"] > 0
    assert 0 < m["superstep_roofline.lab4"]["value"] < 100
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]


def test_lab4_phases_traced_rehearsal_on_a_climbed_ladder(monkeypatch):
    """Rung 0's visited table holds 16 slots a device: the traced
    exhaust overflows it in its search and answers on rung 1; the two
    ladder readers agree and the wasted seconds are a search's."""
    from dslabs_tpu.tpu import backend

    monkeypatch.setattr(backend, "_LADDER",
                        [(1 << 9, 1 << 4), (1 << 9, 1 << 12)])
    with pytest.warns(RuntimeWarning, match="capacity pressure"):
        res, lines = run_cell(small_cell(cycle=["join", "exhaust3"]),
                              seconds=1, trace=True)
    assert res["correct"] is True and failed_checks(lines) == []
    m = res["metrics"]
    assert m["ladder_attempts_per_call.suite"]["value"] == 2.0
    assert 0 < m["ladder_wasted_s.lab4"]["value"] \
        < m["search_s.lab"]["value"] + m["engine_build_s.lab"]["value"] \
        + m["warm_run_s.lab"]["value"] + 60
    assert m["engine_cache_hit_pct.lab"]["value"] == 100.0
    assert 0 < m["superstep_roofline.lab4"]["value"] < 100


def _miscount(res):
    if res.end_condition.name == "SPACE_EXHAUSTED":
        res.discovered_count += 1


def _another_goal_depth(res):
    if res.end_condition.name == "GOAL_FOUND":
        res.goal_matching_state._depth += 1


@pytest.mark.parametrize("breaker,failing", [
    (_miscount, ["call1.exhaust3.discovered_count",
                 "call2.exhaust4.discovered_count"]),
    (_another_goal_depth, ["call0.join.terminal_depth"])])
def test_lab4_phases_broken_path_is_not_correct(monkeypatch, breaker,
                                                failing):
    _break_entry(monkeypatch, breaker)
    res, lines = run_cell(small_cell(), seconds=1)
    assert res["correct"] is False and res["failed"] == len(failing)
    assert failed_checks(lines) == failing


def test_lab4_phases_control_narrow_fingerprint_is_not_correct():
    """Engines are traced from the fingerprint that stands when they are
    BUILT, and the lab entry keeps them: the control needs a table with
    none of the tests' before it (and leaves none of its own)."""
    from dslabs_tpu.tpu import backend

    backend.clear_cache()
    try:
        with narrowed_fingerprint():
            res, lines = run_cell(small_cell(), seconds=1)
    finally:
        backend.clear_cache()
    assert res["correct"] is False
    assert any(f.endswith(".discovered_count")
               for f in failed_checks(lines)), failed_checks(lines)


# --------------------------------------- the readers, on recorded notes

def _notes():
    call = {"call": 7}
    ns = 1e9
    return [
        dict(call, name="entry.tensor_bfs", start=0.0, end=20 * ns),
        dict(call, name="entry.bind", attempt=0, start=0.0, end=0.0),
        dict(call, name="entry.derive_root", attempt=0, start=0.0,
             end=1 * ns),
        dict(call, name="entry.root.validate", cached=1, start=0.0,
             end=0.25 * ns),
        dict(call, name="entry.search", attempt=0, start=1 * ns,
             end=6 * ns),
        dict(call, name="search.level", depth=1, explored0=0, explored=3,
             next_frontier=3, start=1 * ns, end=2 * ns),
        dict(call, name="search.level", depth=2, explored0=3,
             start=2 * ns, end=6 * ns),           # the overflow ended it
        dict(call, name="entry.capacity_retry", attempt=0, explored=40,
             overflow="frontier", start=6 * ns, end=6 * ns),
        dict(call, name="entry.bind", attempt=1, start=6 * ns, end=6 * ns),
        dict(call, name="entry.derive_root", attempt=1, start=6 * ns,
             end=7 * ns),
        dict(call, name="entry.root.validate", cached=1, start=6 * ns,
             end=6.25 * ns),
        dict(call, name="entry.search", attempt=1, start=7 * ns,
             end=19 * ns),
        dict(call, name="search.level", depth=1, explored0=0, explored=3,
             next_frontier=3, start=7 * ns, end=8 * ns),
        dict(call, name="search.level", depth=2, explored0=3, explored=40,
             next_frontier=20, start=8 * ns, end=12 * ns),
        dict(call, name="search.level", depth=3, explored0=40,
             start=12 * ns, end=19 * ns),         # the goal ended it
    ]


def _run(notes, **more):
    return dict({"_program_spans": {"path": None, "notes": notes,
                                    "bench": {}},
                 "params": {"cycle": ["join", "commit"],
                            "traced_phases": ["commit"]},
                 "calls": [{"kind": "join"},
                           {"kind": "commit",
                            "states_explored": 100}]}, **more)


def test_lab4_readers_compute_from_recorded_notes():
    from benchmark.harness import lab_call_trace

    per_layer = {m.name: m.compute for m in small_cell().per_layer}
    run = _run(_notes())
    # attempt 0's root and search: 1 + 5 s; attempt 1's are the answer's
    assert per_layer["ladder_wasted_s.lab4"](run) == 6.0
    assert per_layer["ladder_attempts_per_call.suite"](run) == 2.0
    assert per_layer["root_validate_s.lab4"](run) == 0.5
    # explored: the answer's 100 and the doomed rung's 40; expanded: the
    # rows of the levels that closed (1; then 1 + 3)
    assert lab_call_trace.counts(run) == (140, 5)
    # ... an attempt at a time, each at the bytes a row of ITS engine
    # (the recorded notes name no kept engine)
    assert lab_call_trace.attempts(run) == [
        {"explored": 40, "expanded": 1, "bytes_per_state": None},
        {"explored": 100, "expanded": 4, "bytes_per_state": None}]


def test_lab4_readers_give_none_where_the_program_writes_nothing():
    per_layer = {m.name: m.compute for m in small_cell().per_layer}
    # the parent's annotations: no ``attempt`` on the search's stages,
    # no ``entry.root.validate``, no ``explored`` on the mark
    old = [{k: v for k, v in n.items()
            if not (k == "attempt" and n["name"] != "entry.bind")
            and k != "explored"}
           for n in _notes() if n["name"] != "entry.root.validate"]
    run = _run(old)
    for name in LAB4:
        assert per_layer[name](run) is None, name
    assert per_layer["ladder_attempts_per_call.suite"](run) == 2.0
    # and no slice at all
    for name in LAB4:
        assert per_layer[name]({"trace": None, "cell": "nope"}) is None
