"""Driver ``timeboxed_swarm`` rehearsed on the CPU at a small shape —
test25's three servers under test26's two clients, a fleet of 4,096
walkers bounded at 32 events, the ladder's own top rung for caps (the
five-server fleet is 85 s of compile here) — through the harness's own
runner: the last line, the per-layer metrics of a traced round, the
stderr table's scopes, and the control, which comes out not correct by
the warm-up's count of fresh states against the distinct rows the
harness digested itself.  4,096 walkers, not a few hundred: the equality of the
cumulative fresh counts at depth 2 holds where the fleet's first two
steps, made in lock step from the root, see all 38 states (2,048 see 37)."""

import dataclasses
import json

from helpers import run_cell
from control import narrowed_fingerprint
from test_rehearsal import LAST_LINE_KEYS, failed_checks

from benchmark.harness import manifest, walk_spans
from helpers import ROOT

CELL = "paxos5-random"
# the object BFS on the three-server state (my CPU runs, PR 43)
COUNTS_N3 = {"1": 8, "2": 38, "3": 162, "4": 713, "5": 3258, "6": 15102}
# the small fleet's first 16 steps from the root (bounds from 8: some
# walkers restart, and each hides the one state its probe ended on)
WARMUP_STEPS_N3 = 16


def small_cell(**fleet):
    cell = manifest.load_cell(ROOT, CELL)
    cfg = json.loads(json.dumps(cell.config))
    cfg["deployment"]["object_state"]["servers"] = 3
    cfg["search"]["max_depth"] = 32
    cfg["protocol"].update(name="paxos-n3-c2-w1-s3", net_cap=128,
                           timer_cap=10)
    cfg["fleet"].update(dict(walkers=4096, steps_per_round=8,
                             visited_cap=1 << 20), **fleet)
    cfg["reference"].update(counts=COUNTS_N3, drawn=32, replayed=8,
                            warmup_steps=WARMUP_STEPS_N3)
    workload = dict(cell.workload, params=dict(cell.params,
                                               trace_after_secs=0))
    return dataclasses.replace(cell, config=cfg, workload=workload)


def test_rehearsal_builds_the_last_line():
    res, lines = run_cell(small_cell(), seconds=4)
    assert failed_checks(lines) == []
    assert set(res) == LAST_LINE_KEYS and res["correct"] is True
    assert set(res["metrics"]) == {"states_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert ("check reference.root_is_the_twins: value="
            "['paxos-n3-c2-w1-s3', 128, 10] limit="
            "['paxos-n3-c2-w1-s3', 128, 10] ok") in lines
    assert "check fresh.cumulative.depth1: value=8 limit=8 ok" in lines
    assert "check fresh.cumulative.depth2: value=38 limit=38 ok" in lines
    assert "check replay.diverged: value=[] limit=[] ok" in lines


def test_traced_rehearsal_reads_the_per_layer_metrics(capfd):
    res, lines = run_cell(small_cell(), seconds=4, trace=True)
    assert failed_checks(lines) == [] and res["correct"] is True
    m = res["metrics"]
    assert set(m) >= {"walk_us_per_step.swarm", "fresh_pct.swarm",
                      "restarts_pct.swarm", "round_roofline.swarm",
                      "compile_s", "trace_lower_s"}
    assert 0 < m["round_roofline.swarm"]["value"] < 100
    assert 0 < m["fresh_pct.swarm"]["value"] <= 100
    assert 0 < m["restarts_pct.swarm"]["value"] < 100
    table = capfd.readouterr().err
    assert "info walk step by scope" in table
    for scope in ("walk.pick", "walk.restart", "walk.history",
                  "expand.handlers", "expand.canon", "visited_insert"):
        assert scope in table, scope


def test_control_narrow_fingerprint_is_not_correct():
    with narrowed_fingerprint():
        res, lines = run_cell(small_cell(), seconds=4)
    assert res["correct"] is False
    # the shallow counts do not move under a key of half a row (8 and
    # 38 still: the first collision of this space lies at depth 7); the
    # warm-up's count of fresh states against the rows themselves does
    assert failed_checks(lines) == ["warmup.fresh_is_the_distinct_rows"]


def test_walk_spans_give_nothing_without_a_traced_round():
    run = {"cell": CELL, "trace": None}
    assert walk_spans.traced_round(run) is None
    assert walk_spans.round_device_secs(run) is None
    assert walk_spans.scope_table(run) is None
