"""The program's own spans and scopes, reduced to the ten per-layer
metrics PR 25 added (``harness/program_spans.py`` and its readers under
``layer_metrics/``): on small CPU traces recorded by
``record_program_fixture.py`` (one lab call; the Paxos configuration's
level 4), whose exact counters are pinned; on a trace from before PR 25,
which has none of it; and through the runner, as ``test_rehearsal.py``
drives it, which prints all ten."""

import gzip
import json
import os
import shutil

import pytest

from helpers import ROOT, run_cell, tiny_cell

from benchmark.harness import manifest, program_spans

HERE = os.path.dirname(os.path.abspath(__file__))
LAB = ("engine_build_s.lab", "warm_run_s.lab", "replay_s.lab",
       "recompile_s.lab", "dispatches_per_call.lab")
DEEP = ("expand_us_per_state.deep", "insert_us_per_state.deep",
        "scope_coverage_pct.deep", "trace_lower_s")
MESH = ("exchange_us_per_state.mesh4",)
SIDE = json.load(open(os.path.join(HERE, "fixtures", "program-spans.json")))


def reader(name):
    return manifest.load_module(
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"),
        name).compute


@pytest.fixture
def slice_of():
    """``slice_of(fixture, cell)`` lays a recorded trace where a traced
    run of ``cell`` would have left it."""
    made = []

    def lay(fixture, cell):
        out = os.path.join(ROOT, ".bench_trace", cell, "plugins",
                           "profile", "fixture")
        os.makedirs(out)
        made.append(os.path.join(ROOT, ".bench_trace", cell))
        src = os.path.join(HERE, "fixtures", fixture + ".xplane.pb.gz")
        with gzip.open(src, "rb") as fh, \
                open(os.path.join(out, "f.xplane.pb"), "wb") as dst:
            dst.write(fh.read())
        return {"cell": cell, "trace": {"from": fixture}, "chips": 1}

    yield lay
    for d in made:
        shutil.rmtree(d, ignore_errors=True)


def test_one_lab_call_reduces_to_its_phases(slice_of, capsys):
    run = slice_of("program-lab", "_fixture-lab")
    got = {m: reader(m)(run) for m in LAB}
    want = SIDE["lab"]["metrics"]
    assert got == pytest.approx({m: want[m] for m in LAB}, rel=1e-9)
    # exact: the exhaust call of lab 1's cycle makes 39 dispatches
    assert got["dispatches_per_call.lab"] == 39.0
    (call,) = program_spans.calls(run)
    # the stages and the call's self time are the whole of the call,
    # which is the benchmark's own span around it to within a millisecond
    assert sum(call["stage_s"].values()) + call["self_s"] == \
        pytest.approx(call["wall_s"], rel=1e-12)
    assert 0 <= call["self_s"] < 0.01 * call["wall_s"]
    assert got["recompile_s.lab"] < call["wall_s"]
    err = capsys.readouterr().err
    assert "info lab calls by phase" in err and "dispatches [39]" in err
    # no level was traced: the Paxos readers have nothing to read
    assert [reader(m)(run) for m in DEEP[:3] + MESH] == [None] * 4


def test_one_paxos_level_reduces_to_its_scopes(slice_of, monkeypatch,
                                               capsys):
    monkeypatch.setattr(
        program_spans, "_scopes_of",
        lambda program: {k: tuple(v) for k, v
                         in SIDE["paxos"]["scopes"].items()})
    run = slice_of("program-paxos", "_fixture-paxos")
    run["traced_depth"] = SIDE["paxos"]["traced_depth"]
    want = SIDE["paxos"]["metrics"]
    level = program_spans.traced_level(run)
    # exact: level 4 of the Paxos configuration explores 1,939 states
    # (518 before it, 2,457 after) and leaves 713 unique
    assert (level["explored0"], level["explored"], level["unique"]) == \
        (518, 2457, 713)
    table = program_spans.scope_table(run)
    assert table["explored"] == 1939
    for m in DEEP[:3]:
        assert reader(m)(run) == pytest.approx(want[m], rel=1e-9), m
    assert reader("trace_lower_s")(run) >= 0
    # the metrics are rows of the table's NAMED half: what an
    # operation's neighbours suggest is kept apart and counts nowhere
    named = sum(table["named"].values())
    parts = (reader("expand_us_per_state.deep")(run)
             + reader("insert_us_per_state.deep")(run)
             + sum(program_spans.scope_us_per_state(run, (s,))
                   for s in ("flags", "pack", "trace_meta", "route",
                             "exchange", "level_sync", "promote")))
    assert parts == pytest.approx(1e6 * named / 1939, rel=1e-9)
    total = named + sum(table["near"].values()) + table["unscoped"]
    assert sum(table["near"].values()) > 0 and table["unscoped"] >= 0
    assert reader("scope_coverage_pct.deep")(run) == pytest.approx(
        100 * named / total, rel=1e-9)
    assert 50 < reader("scope_coverage_pct.deep")(run) < 100
    # one chip: no exchange metric; on a mesh the same table gives one
    assert reader("exchange_us_per_state.mesh4")(run) is None
    assert reader("exchange_us_per_state.mesh4")(dict(run, chips=4)) > 0
    assert "info superstep by scope, level 4" in capsys.readouterr().err
    # a slice the timer cut covers no whole level
    cut = dict(slice_of("program-paxos", "_fixture-paxos-cut"),
               traced_depth=4, trace_cut_by_timer=True)
    assert reader("insert_us_per_state.deep")(cut) is None


@pytest.mark.parametrize("fixture", ["lab1-two-calls-1chip", None])
def test_a_program_without_spans_gives_none(slice_of, fixture):
    """PR 24's chip trace has no ``dslabs:`` annotation (the parent
    commit's runs look like this); nor has a run that wrote no trace."""
    run = (slice_of(fixture, "_fixture-parent") if fixture
           else {"cell": "_fixture-none", "trace": {}, "chips": 1})
    run["traced_depth"] = 8
    for m in LAB + DEEP[:3] + MESH:
        assert reader(m)(dict(run, chips=4)) is None, m


# What a traced rehearsal printed before PR 25, which it still has to
# (``test_rehearsal.py`` holds a traced line to EXACTLY these sets, so
# its two traced tests fail on a program that writes spans until a
# ``benchmark`` PR may edit them; their other assertions are kept green
# here).
OUTSIDE_DEEP = {"dispatches_per_level.deep", "useful_ratio.deep",
                "superstep_us_per_state.deep", "superstep_roofline.deep",
                "compile_s"}
OUTSIDE_LAB = {"entry_overhead_s.lab", "search_s.lab", "warmup_s.lab"}


def test_the_rehearsals_print_all_ten():
    one, _ = run_cell(
        tiny_cell("paxos3-deep", max_depth=5,
                  trace_min_frontier_rows=100), seconds=60, trace=True)
    assert one["correct"] is True
    m = one["metrics"]
    assert set(m) == OUTSIDE_DEEP | set(DEEP)
    assert m["useful_ratio.deep"]["value"] == pytest.approx(
        100 * 713 / 2457, rel=1e-9)
    assert m["dispatches_per_level.deep"]["value"] >= 2
    assert 0 < m["superstep_roofline.deep"]["value"] < 100
    assert 0 < m["scope_coverage_pct.deep"]["value"] < 100
    mesh, _ = run_cell(
        tiny_cell("paxos3-deep-mesh4", max_depth=5,
                  trace_min_frontier_rows=100), seconds=60, trace=True)
    assert mesh["correct"] is True
    assert OUTSIDE_DEEP | set(DEEP + MESH) <= set(mesh["metrics"])
    for m in DEEP + MESH:
        assert mesh["metrics"][m]["value"] >= 0, m
    assert not [n for n, _s in mesh["breakdown"]["device_ops"]
                if "lambda" in n]
    lab, _ = run_cell(tiny_cell("lab1-entry"), seconds=1, trace=True)
    assert lab["correct"] is True
    assert set(lab["metrics"]) == OUTSIDE_LAB | set(LAB)
    assert 0 < lab["device"]["busy_s"] <= lab["device"]["window_s"]
    # seed 2**31+17's cycle: exhaust, violation, goal — 39, 8 and 28
    assert lab["metrics"]["dispatches_per_call.lab"]["value"] == 25.0
