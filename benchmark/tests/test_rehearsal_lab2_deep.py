"""Driver ``timeboxed_bfs_lab2`` rehearsed on the CPU at tiny caps through
the harness's own runner (the look for a chip left out), as
``test_rehearsal_lab4_deep.py`` rehearses ``timeboxed_bfs_lab4``: the
last line, every per-layer reader the manifest lists for ``pb-deep`` on
a traced level (PR 47's two among them), and the control, which comes
out not correct by the counts."""

import json
import os

import pytest

from helpers import DEV, ROOT, run_cell, tiny_cell
from control import narrowed_fingerprint
from test_rehearsal import LAST_LINE_KEYS, failed_checks

CELL = "pb-deep"


def test_rehearsal_builds_the_last_line():
    res, lines = run_cell(tiny_cell(CELL, max_depth=6), seconds=60)
    assert set(res) == LAST_LINE_KEYS and res["correct"] is True
    assert failed_checks(lines) == []
    assert set(res["metrics"]) == {"states_per_s", "setup_s"}
    assert res["attempted"] == 6 and res["failed"] == 0
    assert ("check reference.root_is_the_twins: value=['pb-gen-shared', 2, "
            "2, 1, True, 32, 2] limit=['pb-gen-shared', 2, 2, 1, True, 32, 2]"
            " ok" in lines)
    assert "check bytes_per_state: value=304 limit=304 ok" in lines
    assert "check unique.depth5: value=711 limit=711 ok" in lines
    assert "check unique.depth6: value=1721 limit=1721 ok" in lines


def test_set_up_holds_the_tables_place_until_the_first_carry():
    """``prepare`` makes a buffer of the table's exact shape first and
    lets it go at the first ``sharded.init``: after set-up only the two
    fences are left, and the supervisor is observed by nobody."""
    from benchmark.harness import runner
    from dslabs_tpu.tpu import visited

    cell = tiny_cell(CELL)
    held = cell.driver.hold_table_place(cell)
    cap = cell.config["engine"]["visited_cap"]
    assert held[2].shape == visited.table_shape(cap)
    assert held[2].nbytes == 16 * cap
    assert [int(b.size) for b in held[1::2]] == [1, 1]
    ctx = runner.Context(cell=cell, dev=dict(DEV), seed=7, trace=False,
                         events=None, tracer=None)
    cell.driver.prepare(ctx)
    assert ctx.state["sup"].dispatch_observer is None
    assert [int(b.size) for b in ctx.state["fences"]] == [1, 1]


def test_traced_rehearsal_reads_every_listed_per_layer_metric():
    """The traced level is 5 (179 rows open it, 450 close it); the base
    moves at level 6 alone, the promote INTO level 5 moves counters
    only.  Every reader the manifest lists for the cell returns a
    number here but ``peak_hbm_gb``, which is None off the chip (the
    CPU reports no memory statistics)."""
    res, _lines = run_cell(
        tiny_cell(CELL, max_depth=7, trace_min_frontier_rows=100),
        seconds=60, trace=True)
    assert res["correct"] is True
    m = res["metrics"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        listed = {x["name"] for x in json.load(fh)["per_layer"]
                  if CELL in x.get("workloads", ())}
    assert listed - set(m) == {"peak_hbm_gb"}
    assert {"promote_us_per_state.deep", "rebased_levels_pct.deep"} <= listed
    # exact counters: one level of seven re-based (level 6)
    assert m["rebased_levels_pct.deep"]["value"] == pytest.approx(
        100 / 7, rel=1e-9)
    assert m["event_resteps_pct.deep"]["value"] == 0.0
    assert m["useful_ratio.deep"]["value"] == pytest.approx(
        100 * 711 / 3039, rel=1e-9)
    assert 0 < m["promote_us_per_state.deep"]["value"] < m[
        "superstep_us_per_state.deep"]["value"]
    assert 0 < m["superstep_roofline.deep"]["value"] < 100


def test_the_new_readers_say_nothing_where_there_is_nothing_to_read():
    """A program without delta lanes (every accepted cell's), or a run
    with no traced level: None, not an error."""
    from benchmark.harness import manifest

    cell = manifest.load_cell(ROOT, CELL)
    readers = {m.name: m.compute for m in cell.per_layer}
    bare = {"levels": [{"depth": 1, "unique": 5, "explored": 9}],
            "traced_depth": None, "trace": {"programs": {}}}
    assert readers["rebased_levels_pct.deep"](bare) is None
    assert readers["promote_us_per_state.deep"](bare) is None
    assert readers["rebased_levels_pct.deep"]({"levels": []}) is None


def test_control_narrow_fingerprint_is_not_correct():
    """The control's usual half of a row is no control here: a lab 2 row
    is its 41 node lanes, 32 message slots of which a shallow state
    fills a quarter, and timers that never differ, so the first half
    already tells every shallow state from every other.  A tenth (33
    lanes: the nodes less the second client) does not."""
    with narrowed_fingerprint(keep=0.1):
        res, lines = run_cell(tiny_cell(CELL, max_depth=4), seconds=120)
    assert res["correct"] is False
    assert any(name.startswith("unique.depth")
               for name in failed_checks(lines))
