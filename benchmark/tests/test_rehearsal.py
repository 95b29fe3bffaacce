"""Each driver rehearsed on the CPU at tiny caps through the harness's
own runner (the look for a chip left out), as ``tests/test_chip_smoke.py``
rehearses ``chip_smoke``'s phases; the broken timed path and the control
come out as not correct; and ``run.py`` refuses the CPU."""

import importlib.util
import json
import os

import pytest

from helpers import ROOT, run_cell, tiny_cell
from control import narrowed_fingerprint

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def failed_checks(lines):
    return [ln.split(":")[0].split()[1] for ln in lines
            if ln.startswith("check ") and ln.endswith("FAILED")]


def load_run_py():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_py", os.path.join(ROOT, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", ["paxos3-deep", "lab1-entry"])
def test_run_py_refuses_a_backend_that_is_not_a_tpu(cell, capsys):
    rc = load_run_py().main(["--workload", cell, "--seed", "1",
                             "--seconds", "1", "--trace", "0"])
    cap = capsys.readouterr()
    assert rc != 0
    assert cap.out == ""                       # no result line
    assert "not a TPU" in cap.err and "nothing was run" in cap.err


def test_run_py_refuses_an_unknown_cell(capsys):
    assert load_run_py().main(["--workload", "nope", "--seed", "1",
                               "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


# ------------------------------------------------------------ timeboxed_bfs

def test_timeboxed_bfs_rehearsal_builds_the_last_line():
    res, lines = run_cell(tiny_cell("paxos3-deep", max_depth=5),
                          seconds=60)
    assert set(res) == LAST_LINE_KEYS and res["correct"] is True
    assert failed_checks(lines) == []
    assert set(res["metrics"]) == {"states_per_s", "setup_s"}
    assert res["metrics"]["states_per_s"]["value"] > 0
    assert res["attempted"] == 5 and res["failed"] == 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(res["device"])
    # every number compared is printed beside its limit
    assert "check unique.depth4: value=713 limit=713 ok" in lines
    assert "check unique.depth5: value=3258 limit=3258 ok" in lines


def test_timeboxed_bfs_traced_rehearsal_reads_the_per_layer_metrics():
    res, lines = run_cell(
        tiny_cell("paxos3-deep", max_depth=5, trace_min_frontier_rows=100),
        seconds=60, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    assert set(m) >= {"dispatches_per_level.deep", "useful_ratio.deep",
                      "superstep_us_per_state.deep",
                      "superstep_roofline.deep", "compile_s"}
    # exact counters of the traced level (level 4: 162 -> 713 unique)
    assert m["useful_ratio.deep"]["value"] == pytest.approx(
        100 * 713 / 2457, rel=1e-9)
    assert m["dispatches_per_level.deep"]["value"] >= 2
    assert 0 < m["superstep_roofline.deep"]["value"] < 100
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert any(name == "superstep" for name, _s in bd["idle_gaps"])


def _break_supervisor(monkeypatch, breaker):
    """The timed path broken underneath: the supervisor's run hands back
    what ``breaker`` made of the true outcome."""
    from dslabs_tpu.tpu.supervisor import SearchSupervisor

    real = SearchSupervisor.run

    def run(self, *a, **kw):
        out = real(self, *a, **kw)
        if self.max_secs is not None:       # the window's run only
            breaker(out)
        return out

    monkeypatch.setattr(SearchSupervisor, "run", run)


def _lose_a_state(out):
    out.levels[2]["unique"] -= 1


def _drop(out):
    out.dropped = 3


def _retry(out):
    out.retries = 1


def _stop_early(out):
    del out.levels[2:]


@pytest.mark.parametrize("breaker,failing", [
    (_lose_a_state, "unique.depth3"), (_drop, "dropped"),
    (_retry, "retries"), (_stop_early, "completed_depth")])
def test_timeboxed_bfs_broken_path_is_not_correct(monkeypatch, breaker,
                                                  failing):
    _break_supervisor(monkeypatch, breaker)
    res, lines = run_cell(tiny_cell("paxos3-deep", max_depth=4),
                          seconds=60)
    assert res["correct"] is False
    assert failed_checks(lines) == [failing]
    if failing in ("dropped", "retries"):
        assert res["failed"] == res["attempted"]


def test_a_compile_inside_the_window_is_not_correct(monkeypatch):
    from benchmark.harness import cache

    real = cache.CompileEvents.snapshot
    calls = []

    def snapshot(self):
        got = real(self)
        calls.append(got)
        if len(calls) == 2:                 # the snapshot at window end
            got.misses += 1
        return got

    monkeypatch.setattr(cache.CompileEvents, "snapshot", snapshot)
    res, lines = run_cell(tiny_cell("paxos3-deep", max_depth=3),
                          seconds=60)
    assert res["correct"] is False
    assert failed_checks(lines) == ["window.persistent_cache_misses"]


def test_timeboxed_bfs_control_narrow_fingerprint_is_not_correct():
    with narrowed_fingerprint():
        res, lines = run_cell(tiny_cell("paxos3-deep", max_depth=5),
                              seconds=120)
    assert res["correct"] is False
    assert "unique.depth4" in failed_checks(lines)


# ---------------------------------------------------------------- lab_calls

def test_lab_calls_rehearsal_builds_the_last_line():
    res, lines = run_cell(tiny_cell("lab1-entry"), seconds=1)
    assert set(res) == LAST_LINE_KEYS and res["correct"] is True
    assert failed_checks(lines) == []
    assert set(res["metrics"]) == {"verdict_s", "setup_s"}
    assert res["attempted"] == 3 and res["failed"] == 0   # one cycle
    assert "check call0.exhaust.discovered_count: value=255 limit=255 ok" \
        in lines        # seed 2**31+17 rotates the cycle by 1


def test_lab_calls_traced_rehearsal_reads_the_per_layer_metrics():
    res, _ = run_cell(tiny_cell("lab1-entry"), seconds=1, trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) >= {"entry_overhead_s.lab", "search_s.lab",
                                   "warmup_s.lab"}
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert any(n.startswith("call.") for n, _s
               in res["breakdown"]["idle_gaps"])


def _break_entry(monkeypatch, breaker):
    from dslabs_tpu.tpu import backend

    real = backend.tensor_bfs

    def tensor_bfs(state, settings=None, **kw):
        res = real(state, settings, **kw)
        breaker(res)
        return res

    monkeypatch.setattr(backend, "tensor_bfs", tensor_bfs)


def _miscount(res):
    if res.end_condition.name == "SPACE_EXHAUSTED":
        res.discovered_count += 1


def _wrong_verdict(res):
    from dslabs_tpu.search.results import EndCondition

    if res.end_condition.name == "INVARIANT_VIOLATED":
        res.end_condition = EndCondition.SPACE_EXHAUSTED


@pytest.mark.parametrize("breaker,failing", [
    (_miscount, "call0.exhaust.discovered_count"),
    (_wrong_verdict, "call1.violation.end_condition")])
def test_lab_calls_broken_path_is_not_correct(monkeypatch, breaker,
                                              failing):
    _break_entry(monkeypatch, breaker)
    res, lines = run_cell(tiny_cell("lab1-entry"), seconds=1)
    assert res["correct"] is False and res["failed"] == 1
    assert failing in failed_checks(lines)


def test_lab_calls_control_narrow_fingerprint_is_not_correct():
    with narrowed_fingerprint():
        res, lines = run_cell(tiny_cell("lab1-entry"), seconds=1)
    assert res["correct"] is False
    assert any(f.endswith("exhaust.discovered_count")
               for f in failed_checks(lines))
