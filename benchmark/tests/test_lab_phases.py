"""Driver ``lab_phases`` rehearsed on the CPU through the harness's own
runner, as ``test_rehearsal.py`` rehearses the other drivers — at a small
lab 3 twin: ONE PaxosServer (test27's singleton group) and test22's two
clients, three dependent phases (a partitioned goal search, its goal
state searched again, and exhausted to a depth limit).  The broken
timed path and the control come out as not correct."""

import copy
import dataclasses

import pytest

from control import narrowed_fingerprint
from helpers import run_cell, tiny_cell
from test_rehearsal import LAST_LINE_KEYS, _break_entry, failed_checks

INVARIANTS = ["RESULTS_OK", "LOGS_CONSISTENT_ALL_SLOTS"]
PHASES = {
    "decide": {"start": "root", "partition": ["server1", "client1"],
               "timers_off": [], "invariants": INVARIANTS,
               "goals": [{"negate": "NONE_DECIDED"}], "prunes": [],
               "max_time": 60, "max_depth": None},
    "finish": {"start": "goal of decide",
               "partition": ["server1", "client2"],
               "timers_off": ["client1"], "invariants": INVARIANTS,
               "goals": ["CLIENTS_DONE"], "prunes": [], "max_time": 60,
               "max_depth": None},
    # timers stay on here (test22 freezes them): with them off this
    # twin's space is 3 states, too few for the control to alias any
    "exhaust": {"start": "goal of decide",
                "partition": ["server1", "client2"], "timers_off": [],
                "invariants": INVARIANTS, "goals": [],
                "prunes": ["CLIENTS_DONE"], "max_time": 20,
                "max_depth": 6},
}
REFERENCE = {"decide": {"end_condition": "GOAL_FOUND",
                        "terminal_depth": 2},
             "finish": {"end_condition": "GOAL_FOUND"},
             "exhaust": {"end_condition": "SPACE_EXHAUSTED"}}


def small_cell(**params):
    cell = tiny_cell("paxos3-suite", **{
        "cycle": list(PHASES), "traced_phases": ["finish"], **params})
    config = copy.deepcopy(cell.config)
    config["deployment"]["object_state"]["servers"] = 1
    config.update(phases=PHASES, reference=REFERENCE)
    return dataclasses.replace(cell, config=config)


def test_lab_phases_rehearsal_builds_the_last_line():
    res, lines = run_cell(small_cell(), seconds=1)
    assert set(res) == LAST_LINE_KEYS and res["correct"] is True
    assert failed_checks(lines) == []
    assert set(res["metrics"]) == {"verdict_s", "setup_s"}
    assert res["attempted"] == 3 and res["failed"] == 0   # one cycle
    # the staged phases started from decide's own goal state: their
    # depths and counts are the object checker's from that state
    assert any(ln.startswith("check call2.exhaust.discovered_count:")
               and ln.endswith(" ok") for ln in lines)
    assert "check call0.decide.reference.terminal_depth: value=2 " \
           "limit=2 ok" in lines


@pytest.mark.parametrize("cycle,traced", [
    (["decide", "finish", "exhaust"], "finish"),
    (["decide", "exhaust"], "exhaust")])    # the cell's own since PR 29
def test_lab_phases_traced_rehearsal_reads_the_per_layer_metrics(cycle,
                                                                 traced):
    res, lines = run_cell(small_cell(cycle=cycle, traced_phases=[traced]),
                          seconds=1, trace=True)
    assert res["correct"] is True and failed_checks(lines) == []
    assert res["attempted"] == len(cycle) and res["failed"] == 0
    m = res["metrics"]
    assert set(m) >= {"entry_overhead_s.lab", "search_s.lab",
                      "warmup_s.lab", "engine_build_s.lab",
                      "warm_run_s.lab", "replay_s.lab", "recompile_s.lab",
                      "dispatches_per_call.lab", "derive_root_s.suite",
                      "root_replay_events.suite",
                      "ladder_attempts_per_call.suite"}
    # the slice is the one staged call: its root is decide's goal state,
    # two events deep, derived on the ladder's first rung
    assert m["root_replay_events.suite"]["value"] == 2.0
    assert m["ladder_attempts_per_call.suite"]["value"] == 1.0
    assert 0 < m["derive_root_s.suite"]["value"] \
        <= m["engine_build_s.lab"]["value"]
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert any(n == "call." + traced for n, _s
               in res["breakdown"]["idle_gaps"])


def _miscount(res):
    if res.end_condition.name == "SPACE_EXHAUSTED":
        res.discovered_count += 1


def _another_goal_depth(res):
    from dslabs_tpu.testing.predicates import CLIENTS_DONE

    if res.goals == [CLIENTS_DONE]:         # the finish phase
        res.goal_matching_state._depth += 1


@pytest.mark.parametrize("breaker,failing", [
    (_miscount, ["call2.exhaust.discovered_count"]),
    (_another_goal_depth, ["call1.finish.terminal_depth"])])
def test_lab_phases_broken_path_is_not_correct(monkeypatch, breaker,
                                               failing):
    """One call of the window's cycle comes out wrong."""
    _break_entry(monkeypatch, breaker)
    res, lines = run_cell(small_cell(), seconds=1)
    assert res["correct"] is False and res["failed"] == 1
    assert failed_checks(lines) == failing


def test_lab_phases_control_narrow_fingerprint_is_not_correct():
    with narrowed_fingerprint():
        res, lines = run_cell(small_cell(), seconds=1)
    assert res["correct"] is False
    assert any(f.endswith("exhaust.discovered_count")
               for f in failed_checks(lines))


def test_suite_readers_read_every_field_the_program_writes(capsys):
    """``staged_ops`` and the ladder mark's ``attempt`` / ``overflow``
    are in no metric's value: their readers print them."""
    per_layer = {m.name: m.compute for m in small_cell().per_layer}
    notes = [
        {"name": "entry.tensor_bfs", "call": 4, "start": 0.0, "end": 9e9},
        {"name": "entry.bind", "call": 4, "attempt": 0},
        {"name": "entry.root.replay", "call": 4, "events": 7,
         "staged_ops": 1},
        {"name": "entry.capacity_retry", "call": 4, "attempt": 0,
         "overflow": "frontier overflow"},
        {"name": "entry.bind", "call": 4, "attempt": 1},
        {"name": "entry.root.replay", "call": 4, "events": 7,
         "staged_ops": 1}]
    run = {"_program_spans": {"path": None, "notes": notes, "bench": {}}}
    assert per_layer["ladder_attempts_per_call.suite"](run) == 2.0
    assert per_layer["root_replay_events.suite"](run) == 14.0
    err = capsys.readouterr().err
    assert "call 4 left rung 0: frontier overflow" in err
    assert "events 14, staged_ops 2" in err
