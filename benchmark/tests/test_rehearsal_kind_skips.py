"""``kind_skips_pct.deep`` (PR 49) through the harness's own runner, on
the CPU at tiny caps: a traced level of ``shardkv-deep`` (no chunk
re-steps, both kinds live in every step) reads 0, and one of
``shardkv-n3-deep`` at the small group size (its root's timers overflow
the window's 8 slots, so chunks step twice and the second pass holds no
message) reads what the level's own counters give: one skip a re-step."""

import pytest

from helpers import run_cell, tiny_cell
from test_rehearsal_lab4_multi_deep import small_cell


def test_a_level_that_never_resteps_skips_nothing():
    res, _lines = run_cell(
        tiny_cell("shardkv-deep", max_depth=5, trace_min_frontier_rows=100),
        seconds=60, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    assert m["event_resteps_pct.deep"]["value"] == 0.0
    assert m["kind_skips_pct.deep"]["value"] == 0.0
    assert m["kind_skips_pct.deep"]["unit"] == "%"


def test_a_restepped_chunk_skips_its_empty_kind():
    res, _lines = run_cell(
        small_cell(max_depth=4, trace_min_frontier_rows=100),
        seconds=120, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    resteps = m["event_resteps_pct.deep"]["value"]
    assert 0 < resteps < 100
    # every re-step of this twin is the timers' (a network of a few
    # messages against 40 slots): it skips the message kind, and no
    # first pass skips anything
    assert m["kind_skips_pct.deep"]["value"] == pytest.approx(
        resteps / 2, rel=1e-9)
