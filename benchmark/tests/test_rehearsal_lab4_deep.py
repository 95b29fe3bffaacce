"""Driver ``timeboxed_bfs_lab4`` rehearsed on the CPU at tiny caps through
the harness's own runner (the look for a chip left out), as
``test_rehearsal.py`` rehearses ``timeboxed_bfs``: the last line, the
per-layer metrics of a traced level (the two readers of PR 36 among
them), and the control, which comes out not correct by the counts."""

import pytest

from helpers import run_cell, tiny_cell
from control import narrowed_fingerprint
from test_rehearsal import LAST_LINE_KEYS, failed_checks

CELL = "shardkv-deep"


def test_rehearsal_builds_the_last_line():
    res, lines = run_cell(tiny_cell(CELL, max_depth=5), seconds=60)
    assert set(res) == LAST_LINE_KEYS and res["correct"] is True
    assert failed_checks(lines) == []
    assert set(res["metrics"]) == {"states_per_s", "setup_s"}
    assert res["attempted"] == 5 and res["failed"] == 0
    assert ("check reference.root_is_the_twins: value=['shardstore-g2-c2-w2',"
            " [[1], [2]]] limit=['shardstore-g2-c2-w2', [[1], [2]]] ok"
            in lines)
    assert "check unique.depth4: value=1431 limit=1431 ok" in lines
    assert "check unique.depth5: value=5389 limit=5389 ok" in lines


def test_traced_rehearsal_reads_the_per_layer_metrics():
    res, _lines = run_cell(
        tiny_cell(CELL, max_depth=5, trace_min_frontier_rows=100),
        seconds=60, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    assert set(m) >= {"dispatches_per_level.deep", "useful_ratio.deep",
                      "superstep_us_per_state.deep",
                      "superstep_roofline.deep", "expand_us_per_state.deep",
                      "insert_us_per_state.deep", "pack_us_per_state.deep",
                      "write_blocks_per_step.deep", "event_resteps_pct.deep",
                      "grid_fill_pct.deep", "compile_s"}
    # exact counters of the traced level (level 4: 272 rows in 5 chunks
    # of 64, 342 -> 1431 unique, 788 -> 4144 explored)
    assert m["useful_ratio.deep"]["value"] == pytest.approx(
        100 * 1431 / 4144, rel=1e-9)
    assert m["event_resteps_pct.deep"]["value"] == 0.0
    assert m["grid_fill_pct.deep"]["value"] == pytest.approx(
        100 * 3356 / (5 * 64 * 48), rel=1e-9)
    assert 0 < m["superstep_roofline.deep"]["value"] < 100


def test_control_narrow_fingerprint_is_not_correct():
    with narrowed_fingerprint():
        res, lines = run_cell(tiny_cell(CELL, max_depth=4), seconds=120)
    assert res["correct"] is False
    assert "unique.depth1" in failed_checks(lines)   # 7 for 11
