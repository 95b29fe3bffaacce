"""``harness/idle_by_span.py``: a lab call's idle seconds by the
innermost span the host had open, its dispatches cut by the module runs
inside them, the in-module gap share and the eager programs — on a small
hand-made call whose every number is worked out below, through the
runner on a traced rehearsal of ``lab1-entry`` (and on that slice less
ISSUE 38's spans: what the parent side of its check reads), and on
traces with no ``dslabs:`` span, or no device operation, at all."""

import json
import os
import statistics

import pytest

from helpers import ROOT, run_cell, tiny_cell
from test_program_spans import slice_of  # noqa: F401  (the fixture)

from benchmark.harness import idle_by_span, manifest

METRICS = ("idle_named_pct.lab", "level_host_s.lab", "dispatch_host_ms.lab",
           "program_gap_pct.lab", "eager_programs_per_call.lab")
LAB_CELLS = ("lab1-entry", "paxos3-suite", "shardtx-suite")
US = 1e3                        # the trace's clock is in nanoseconds


def reader(name):
    return manifest.load_module(
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"),
        name).compute


def note(name, start, end, call=1, **fields):
    return dict(fields, name=name, start=start * US, end=end * US, call=call)


def events(*rows):
    return [(s * US, e * US, name) for s, e, name in rows]


# One call of 1,000 us (times in us).  The host's spans:
#
#   entry.tensor_bfs   0 ............................................ 1000
#   entry.search         100 ................................ 900
#   search.start         100-140
#   search.carry             140 .. 200  (dispatch.init 150-180 inside)
#   search.level               200 .................. 800
#   dispatch.superstep             220 ...... 600
#   level.trace_meta                              620 ... 780
#   dispatch.promote                                        800-820
#   entry.replay                                                  910-960
#
# and the device's module runs with the operations inside them:
#
#   jit_convert     110-120  (eager, under search.start)      op 110-120
#   jit_init_carry  185-195  (began after dispatch.init closed, still
#                            under search.carry)              op 185-195
#   jit_superstep   300-400  ops 300-330, 360-400  (a 30 us gap: idle)
#   jit_superstep   450-550  ops 450-500, 510-550  (a 10 us gap: under
#                            the 20 us an idle gap needs, but no
#                            operation ran in it)
#   jit_promote     830-850  (began after dispatch.promote closed)
#   jit_step        920-940  (eager, under entry.replay)
NOTES = [
    note("entry.tensor_bfs", 0, 1000, key="toy"),
    note("entry.search", 100, 900),
    note("search.start", 100, 140),
    note("search.carry", 140, 200),
    note("dispatch.init", 150, 180),
    note("search.level", 200, 800),
    note("dispatch.superstep", 220, 600),
    note("level.trace_meta", 620, 780, rows=3, bytes=400),
    note("dispatch.promote", 800, 820),
    note("entry.replay", 910, 960),
    note("compile.event", 905, 905),            # a mark covers nothing
    note("dispatch.superstep", 20, 80, call=2),  # another call's
]
MODULES = events((110, 120, "jit_convert(7)"), (185, 195, "jit_init_carry(1)"),
                 (300, 400, "jit_superstep(2)"), (450, 550, "jit_superstep(2)"),
                 (830, 850, "jit_promote(3)"), (920, 940, "jit_step(9)"))
OPS = events((110, 120, "convert.1"), (185, 195, "fusion.1"),
             (300, 330, "fusion.2"), (360, 400, "fusion.3"),
             (450, 500, "fusion.2"), (510, 550, "fusion.3"),
             (830, 850, "copy.4"), (920, 940, "fusion.9"))
# a second device that also worked through the call's first 100 us: the
# call is read on the one that worked least
DEVICES = {0: {"ops": OPS, "modules": MODULES},
           1: {"ops": OPS + events((0, 100, "fusion.0")),
               "modules": MODULES + events((0, 100, "jit_other(5)"))}}


@pytest.fixture(scope="module")
def call():
    (got,) = idle_by_span.calls_of(NOTES, DEVICES)
    return got


def us(seconds):
    return pytest.approx(seconds * 1e6, abs=1e-6)


def test_idle_goes_to_the_innermost_span(call):
    assert (call["call"], call["device"]) == (1, 0)
    assert call["wall_s"] * 1e6 == us(1000e-6)
    # busy: 10 + 10 + 30 + 40 + 50 + 40 + 20 + 20; the 10 us gap inside
    # the second superstep run is neither busy nor idle
    assert call["busy_s"] * 1e6 == us(220e-6)
    assert call["idle_s"] * 1e6 == us(770e-6)
    got = {k: round(v * 1e6, 6) for k, v in call["idle_by_span"].items()}
    assert got == {
        "entry.tensor_bfs": 150,        # 0-100, 900-910, 960-1000
        "search.start": 30,             # 100-110, 120-140
        "search.carry": 20,             # 140-150, 180-185, 195-200
        "dispatch.init": 30,            # 150-180
        "dispatch.superstep": 210,      # 220-300, 330-360, 400-450, 550-600
        "level.trace_meta": 160,
        "search.level": 60,             # 200-220, 600-620, 780-800: its own
        "dispatch.promote": 20,
        "entry.search": 60,             # 820-830, 850-900
        "entry.replay": 30}             # 910-920, 940-960


def test_the_sum_identity_and_the_named_share(call):
    groups = {k: round(v * 1e6, 6) for k, v in call["idle_by_group"].items()}
    assert groups == {"dispatch": 260, "host": 210, "container": 270,
                      "stage": 30}
    assert sum(groups.values()) == pytest.approx(call["idle_s"] * 1e6)
    assert call["named_pct"] == pytest.approx(100 * (1 - 270 / 770))
    assert "off by 0.000%" in idle_by_span._line(call)


def test_a_dispatch_is_lead_runs_between_and_tail(call):
    assert call["dispatches"] == 3
    # init and promote close before their programs start: all lead;
    # the superstep waits: 220-300 lead, two runs, 400-450, 550-600
    assert {k: round(v * 1e6, 6) for k, v in call["dispatch_s"].items()} \
        == {"lead": 30 + 80 + 20, "run": 200, "between": 50, "tail": 50}
    # of the runs' 200 us only the 30 us gap is idle
    assert {k: round(v * 1e6, 6)
            for k, v in call["dispatch_idle_s"].items()} \
        == {"lead": 130, "run": 30, "between": 50, "tail": 50}
    assert call["dispatch_host_ms"] == pytest.approx((130 + 50 + 50) / 3e3)
    # entry.search's 800 us less the 30 + 380 + 20 under a dispatch
    assert call["level_host_s"] * 1e6 == us(370e-6)


def test_the_gap_share_inside_module_runs_and_the_eager_programs(call):
    assert call["module_runs"] == 6
    assert call["module_s"] * 1e6 == us(260e-6)
    # no operation ran in 30 + 10 of the 260 us, whatever a gap's length
    assert call["program_gap_pct"] == pytest.approx(100 * 40 / 260)
    # jit_init_carry and jit_promote began under no dispatch span, but
    # they are the seam's own programs, named after their sites: the
    # count asks a run's NAME, not where its start fell
    assert call["eager_programs"] == 2
    assert [r[:2] for r in call["eager_top"]] == [["jit_convert", 1],
                                                  ["jit_step", 1]]
    assert call["eager_top"][1][2] == pytest.approx(20e-6)


def test_a_device_clock_that_is_off_is_moved_to_fit_the_waiting_dispatches():
    """Two waiting dispatches (200-600, 700-900), each holding its
    program's run (300-400, 750-850) as recorded: no move.  A device
    whose clock reads 250 us late has the first run end at 650, outside
    its dispatch: moved back by the least that fits every pair, 200 us
    (the second run then ends with its span).  One that reads early is
    moved forward; a slice whose spans and runs cannot be paired, or
    that no move fits, is read as it is."""
    notes = [note("entry.tensor_bfs", 0, 1000),
             note("dispatch.superstep", 200, 600),
             note("dispatch.promote", 610, 620),
             note("dispatch.superstep", 700, 900)]
    mods = events((300, 400, "jit_superstep(2)"), (640, 650, "jit_promote(3)"),
                  (750, 850, "jit_superstep(2)"))

    def moved(by):
        return [(s + by * US, e + by * US, n) for s, e, n in mods]

    assert idle_by_span.clock_fit(notes, mods) == 0
    assert idle_by_span.clock_fit(notes, moved(250)) == -200 * US
    assert idle_by_span.clock_fit(notes, moved(-150)) == 100 * US
    assert idle_by_span.clock_fit(notes, mods[:2]) == 0         # one run
    assert idle_by_span.clock_fit(notes, []) == 0
    wide = events((300, 400, "jit_superstep(2)"), (650, 950, "jit_superstep(2)"))
    assert idle_by_span.clock_fit(notes, wide) == 0             # none fits
    late = {0: {"modules": moved(250),
                "ops": [(s, e, "fusion.1") for s, e, _n in moved(250)]}}
    (got,) = idle_by_span.calls_of(notes, late)
    assert got["clock_ms"] == pytest.approx(-0.2)
    # as read: the first run 550-650 and the second 1000-1100 outside
    # their spans; moved: 350-450 and 800-900, the promote's 690-700
    assert {k: round(v * 1e6, 6) for k, v in got["dispatch_s"].items()} \
        == {"lead": 150 + 10 + 100, "run": 200, "between": 0,
            "tail": 150 + 0}
    assert "clock moved -0.200 ms" in idle_by_span._line(got)


def test_the_pieces():
    segs = idle_by_span.innermost(
        [{"name": "a", "start": 0, "end": 10},
         {"name": "b", "start": 2, "end": 6},
         {"name": "mark", "start": 4, "end": 4},
         {"name": "late", "start": 12, "end": 30}], 0, 20)
    assert segs == [(0, 2, "a"), (2, 6, "b"), (6, 10, "a"),
                    (10, 12, "none"), (12, 20, "late")]
    assert idle_by_span.by_segment([(1, 3), (5, 11)], segs) == {
        "a": 1 + 4, "b": 1 + 1, "none": 1}
    assert idle_by_span.gaps_in([(0, 4), (5, 7), (30, 40)], 2, 20,
                                min_gap_ns=2) == [(7, 20)]
    assert idle_by_span.dispatch_parts(
        {"start": 0, "end": 10}, [(2, 3), (3, 5), (8, 12), (12, 14)]) == [
        (0, 2, "lead"), (2, 5, "run"), (5, 8, "between"), (8, 10, "run")]
    assert [idle_by_span.group(n) for n in (
        "dispatch.init", "entry.search", "search.level", "search.start",
        "search.carry", "level.trace_meta", "entry.root.eager",
        "entry.root.replay", "entry.tensor_dfs")] == [
        "dispatch", "container", "container", "host", "host", "host",
        "host", "stage", "container"]


def test_a_traced_rehearsal_prints_the_five_and_a_parent_reads_four(capsys):
    """``lab1-entry`` at tiny caps through the runner, traced: the five
    are on the line.  The same slice with ISSUE 38's spans taken out is
    what the parent's program writes (``dispatch.*`` and the stages):
    four readers give the very same numbers, and the idle seconds those
    spans had named fall back to the containers."""
    from benchmark.harness import program_spans, trace

    lab, _ = run_cell(tiny_cell("lab1-entry"), seconds=1, trace=True)
    assert lab["correct"] is True
    assert set(METRICS) <= set(lab["metrics"])
    err = capsys.readouterr().err
    assert err.count("info idle by span, call") == 3    # one cycle
    path = program_spans.xplane_path({"cell": "lab1-entry"})
    notes, _bench = program_spans.read_annotations(path)
    devices, _host = trace.read(path)
    change = idle_by_span.calls_of(notes, devices)
    parent = idle_by_span.calls_of(
        [n for n in notes if idle_by_span.group(n["name"]) != "host"],
        devices)
    assert len(change) == len(parent) == 3
    mean = statistics.fmean
    assert lab["metrics"]["idle_named_pct.lab"]["value"] == pytest.approx(
        mean(c["named_pct"] for c in change))
    for c, p in zip(change, parent):
        for g in (c, p):
            assert sum(g["idle_by_group"].values()) == pytest.approx(
                g["idle_s"], rel=0.01)
        assert {k: c[k] for k in c if not k.startswith(("idle_by", "named"))} \
            == {k: p[k] for k in p if not k.startswith(("idle_by", "named"))}
        assert p["idle_by_group"]["host"] == 0 < c["idle_by_group"]["host"]
        assert p["named_pct"] < c["named_pct"] <= 100
        assert p["idle_by_group"]["container"] == pytest.approx(
            c["idle_by_group"]["container"] + c["idle_by_group"]["host"])
    # seed 2**31+17's cycle: exhaust, violation, goal
    assert [c["dispatches"] for c in change] == [35, 4, 24]
    assert lab["metrics"]["eager_programs_per_call.lab"]["value"] == \
        mean(c["eager_programs"] for c in change)
    # and a deep cell's line has none of them
    deep = manifest.load_cell(ROOT, "paxos3-deep")
    assert not set(METRICS) & {m.name for m in deep.per_layer}


def test_spans_without_a_device_operation_give_none(slice_of):
    """``program-lab`` keeps a lab call's annotations and drops its
    operations: there is no idle time to split, and nothing raises."""
    run = slice_of("program-lab", "_fixture-idle")
    assert idle_by_span.calls(run) is None
    assert [reader(m)(run) for m in METRICS] == [None] * len(METRICS)


@pytest.mark.parametrize("fixture", ["lab1-two-calls-1chip", None])
def test_a_trace_without_spans_gives_none(slice_of, fixture):
    """PR 24's chip trace has no ``dslabs:`` annotation; nor has a run
    that wrote no trace."""
    run = (slice_of(fixture, "_fixture-idle-none") if fixture
           else {"cell": "_fixture-none", "trace": {}, "chips": 1})
    assert idle_by_span.calls(run) is None
    assert [reader(m)(run) for m in METRICS] == [None] * len(METRICS)


def test_the_five_metrics_are_found_by_name_in_the_lab_cells_alone():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in man["workloads"]:
        names = [m.name for m in manifest.load_cell(ROOT, w["name"]).per_layer]
        mine = [n for n in names if n in METRICS]
        assert mine == (list(METRICS) if w["name"] in LAB_CELLS else []), \
            w["name"]
    # appended: the entries that were there keep their places
    assert [m["name"] for m in man["per_layer"]][-5:] == list(METRICS)
    for m in man["per_layer"][-5:]:
        assert (m["moves"], m["workloads"]) == ("verdict_s", list(LAB_CELLS))
