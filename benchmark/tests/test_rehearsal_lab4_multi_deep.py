"""Driver ``timeboxed_bfs_lab4_multi`` rehearsed on the CPU at tiny caps
and at the SMALL group size — ``setupStates(2, 2, 1, 10)``,
``tests/fixtures/lab4_multi_small.py`` (an n = 3 engine is minutes of
compile here) — through the harness's own runner, as ``test_rehearsal_lab4_deep.py`` rehearses
``timeboxed_bfs_lab4``: the last line, the per-layer metrics of a traced
level (``gpaxos_handlers_pct.deep`` among them), and the control, which
comes out not correct by the counts.  And the new metric's reader on a
recorded trace."""

import dataclasses

import pytest

from helpers import run_cell, tiny_cell
from control import narrowed_fingerprint
from test_program_spans import SIDE, reader, slice_of  # noqa: F401
from test_rehearsal import LAST_LINE_KEYS, failed_checks

from benchmark.harness import program_spans
from tests.fixtures.lab4_multi_small import at_small_size

CELL = "shardkv-n3-deep"
ROOT_CHECK = ("check reference.root_is_the_twins: value="
              "['shardstore-multi-g2x2-w1', [2, 2, 10, 1]] limit="
              "['shardstore-multi-g2x2-w1', [2, 2, 10, 1]] ok")


def small_cell(**params):
    cell = tiny_cell(CELL, **params)
    return dataclasses.replace(cell, config=dict(
        at_small_size(cell.config), must_pass_depth=3))


def test_rehearsal_builds_the_last_line():
    res, lines = run_cell(small_cell(max_depth=4), seconds=120)
    assert set(res) == LAST_LINE_KEYS and res["correct"] is True
    assert failed_checks(lines) == []
    assert set(res["metrics"]) == {"states_per_s", "setup_s"}
    assert res["attempted"] == 4 and res["failed"] == 0
    assert ROOT_CHECK in lines
    assert "check unique.depth3: value=180 limit=180 ok" in lines
    assert "check unique.depth4: value=681 limit=681 ok" in lines


def test_traced_rehearsal_reads_the_per_layer_metrics():
    res, _lines = run_cell(
        small_cell(max_depth=4, trace_min_frontier_rows=100),
        seconds=120, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    assert set(m) >= {"dispatches_per_level.deep", "useful_ratio.deep",
                      "superstep_us_per_state.deep",
                      "superstep_roofline.deep", "expand_us_per_state.deep",
                      "insert_us_per_state.deep", "pack_us_per_state.deep",
                      "write_blocks_per_step.deep", "event_resteps_pct.deep",
                      "grid_fill_pct.deep", "compile_s",
                      "gpaxos_handlers_pct.deep"}
    # the traced level is level 4: 138 rows (180 - 42), 180 -> 681 unique
    assert 0 < m["gpaxos_handlers_pct.deep"]["value"] < 100
    assert 0 < m["superstep_roofline.deep"]["value"] < 100
    assert 0 <= m["event_resteps_pct.deep"]["value"] < 100


def test_control_narrow_fingerprint_is_not_correct():
    with narrowed_fingerprint():
        res, lines = run_cell(small_cell(max_depth=3), seconds=120)
    assert res["correct"] is False
    assert any(name.startswith("unique.depth")
               for name in failed_checks(lines))


# ------------------------------------ the new reader on a recorded trace

def _relabelled(to):
    """The recorded Paxos level's scope map with every operation that
    names ``expand.handlers`` itself renamed by ``to(operation)``."""
    return {k: ((to(k), True) if tuple(v) == ("expand.handlers", True)
                else tuple(v))
            for k, v in SIDE["paxos"]["scopes"].items()}


def _read(slice_of, monkeypatch, scopes, cell):
    monkeypatch.setattr(program_spans, "_scopes_of",
                        lambda program: scopes)
    run = slice_of("program-paxos", cell)
    run["traced_depth"] = SIDE["paxos"]["traced_depth"]
    return (reader("gpaxos_handlers_pct.deep")(run),
            program_spans.scope_table(run)["named"])


def test_the_reader_splits_the_handlers_by_fragment(slice_of, monkeypatch):
    plain = {k: tuple(v) for k, v in SIDE["paxos"]["scopes"].items()}
    got, named = _read(slice_of, monkeypatch, plain, "_fixture-gpaxos-0")
    # a program whose handlers name no fragment: nothing to read
    assert got is None and named["expand.handlers"] > 0
    handlers = named["expand.handlers"]
    got, named = _read(slice_of, monkeypatch, _relabelled(
        lambda op: "expand.handlers.gpaxos"), "_fixture-gpaxos-1")
    assert got == 100.0 and "expand.handlers" not in named
    got, _ = _read(slice_of, monkeypatch, _relabelled(
        lambda op: "expand.handlers.spec"), "_fixture-gpaxos-2")
    assert got == 0.0
    ops = sorted(k for k, v in plain.items()
                 if v == ("expand.handlers", True))
    half, quarter = set(ops[::2]), set(ops[1::4])
    got, named = _read(slice_of, monkeypatch, _relabelled(
        lambda op: "expand.handlers.gpaxos" if op in half
        else "expand.handlers.spec" if op in quarter
        else "expand.handlers"), "_fixture-gpaxos-3")
    gpaxos, spec = (named["expand.handlers.gpaxos"],
                    named["expand.handlers.spec"])
    assert gpaxos + spec + named["expand.handlers"] == pytest.approx(
        handlers, rel=1e-9)
    # the plumbing's own operations are in neither side of the share
    assert got == pytest.approx(100 * gpaxos / (gpaxos + spec), rel=1e-9)
    assert 0 < got < 100
    # the other readers count a fragment's operations as the handlers'
    assert reader("expand_us_per_state.deep")(
        {**slice_of("program-paxos", "_fixture-gpaxos-4"),
         "traced_depth": SIDE["paxos"]["traced_depth"]}) > 0
