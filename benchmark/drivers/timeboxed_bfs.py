"""Driver ``timeboxed_bfs``: one strict BFS from the root, run until the
window's clock ends it, through ``SearchSupervisor(ladder=("sharded",),
strict=True, aot_warmup=True)`` on a mesh of the cell's chips.

Set-up builds the ONE supervisor the window drives, compiles (or loads)
its programs, and warms them with a shallow run of the same object.
After the window the object checker (``dslabs_tpu/search/search.py``,
which shares no code with ``tpu/``) searches the seeded ``SearchState``
to the configuration's ``reference_live_depth``; ``correct`` holds the
timed run's cumulative unique count at every completed depth to that
and to the configuration's pinned ``reference_counts``.

Cell parameters (``workloads/<cell>.json`` ``params``): ``warmup_depth``,
``max_depth`` (null: the clock ends the run), ``trace_min_frontier_rows``
and ``trace_max_secs`` (the traced slice is the first whole level that
starts with more frontier rows than that)."""

from __future__ import annotations

import dataclasses
import importlib
import time

from benchmark.harness import spans as spans_mod
from benchmark.harness import states
from benchmark.harness.runner import (GUARANTEE_COUNTERS, at_least,
                                      equal)



def build_protocol(spec: dict):
    mod, _, fn = spec["factory"].partition(":")
    protocol = getattr(importlib.import_module(mod), fn)(**spec["kwargs"])
    if spec.get("strip_goals"):
        protocol = dataclasses.replace(protocol, goals={})
    return protocol


def build_supervisor(cell, max_depth):
    """The supervisor of the cell's configuration, at its caps, on a
    mesh of the cell's chips.  A failover would change what is being
    measured, so the ladder has one rung."""
    from dslabs_tpu.tpu.sharded import make_mesh
    from dslabs_tpu.tpu.supervisor import RetryPolicy, SearchSupervisor

    eng = cell.config["engine"]
    return SearchSupervisor(
        build_protocol(cell.config["protocol"]), ladder=("sharded",),
        mesh=make_mesh(cell.chips), chunk=eng["chunk"],
        frontier_cap=eng["frontier_cap"], visited_cap=eng["visited_cap"],
        max_depth=max_depth, strict=True,
        ev_budget=tuple(eng["ev_budget"]),
        policy=RetryPolicy(max_retries=3), aot_warmup=True)


def prepare(ctx) -> None:
    params = ctx.cell.params
    with spans_mod.span("construct"):
        sup = build_supervisor(ctx.cell, params["warmup_depth"])
    ctx.note("warm-up run of the same supervisor")
    with spans_mod.span("warmup"):
        warm = sup.run()
    if warm.end_condition != "DEPTH_EXHAUSTED":
        raise RuntimeError(f"warm-up ended {warm.end_condition}")
    ctx.state.update(sup=sup, compile_s=float(warm.compile_secs))


def measure(ctx, seconds: float) -> dict:
    params = ctx.cell.params
    sup = ctx.state["sup"]
    sup.max_depth, sup.max_secs = params.get("max_depth"), seconds
    recorder = None
    if ctx.trace:
        recorder = spans_mod.level_recorder(
            ctx.tracer, int(params["trace_min_frontier_rows"]))
        sup.telemetry = recorder
    try:
        with spans_mod.span("search"):
            out = sup.run()
    finally:
        if recorder is not None:
            recorder.finish()
    levels = list(out.levels or [])
    counters = {k: int(getattr(out, k)) for k in GUARANTEE_COUNTERS}
    measured = {
        "outcome": {
            "end_condition": out.end_condition, "depth": int(out.depth),
            "unique_states": int(out.unique_states),
            "states_explored": int(out.states_explored),
            "elapsed_secs": float(out.elapsed_secs),
            "platform": out.platform, "device_kind": out.device_kind,
            "mesh_width": out.mesh_width,
            "bytes_per_state": out.bytes_per_state, **counters},
        "levels": levels,
        "compile_s": ctx.state["compile_s"],
        # levels started, and levels that broke a guarantee: the
        # counters are the run's, so any non-zero one fails them all
        "attempted": len(levels) + (out.end_condition == "TIME_EXHAUSTED"),
        "failed": (len(levels) if any(counters.values()) else 0),
    }
    if recorder is not None:
        measured["dispatches_by_level"] = recorder.dispatches_by_level
        measured["traced_depth"] = recorder.traced_depth
    return measured


def reference_counts(ctx, upto: int) -> dict:
    """Cumulative unique counts at depths 1..upto by the object checker
    on the seeded state (a depth-limited BFS each: the checker reports
    one count a run)."""
    from dslabs_tpu.search.search import BFS

    spec = ctx.cell.config["deployment"]["object_state"]
    counts = {}
    for d in range(1, upto + 1):
        res = BFS(states.settings({"max_depth": d, "max_time": 600})
                  ).run(states.build(spec, ctx.seed))
        counts[d] = int(res.discovered_count)
    return counts


def verify(ctx, measured: dict) -> list:
    cfg = ctx.cell.config
    pinned = {int(d): int(n) for d, n in cfg["reference_counts"].items()}
    live_depth = int(cfg["reference_live_depth"])
    t = time.time()
    live = reference_counts(ctx, live_depth)
    ctx.note(f"object checker to depth {live_depth}: {live} in "
             f"{time.time() - t:.1f}s")
    out = measured["outcome"]
    got = {int(lv["depth"]): int(lv["unique"])
           for lv in measured["levels"]}
    checks = [equal("platform", out["platform"], ctx.dev["platform"]),
              equal("mesh_width", out["mesh_width"], ctx.cell.chips)]
    for d in sorted(pinned):
        if d <= live_depth:
            checks.append(equal(f"reference.live_vs_pinned.depth{d}",
                                live[d], pinned[d]))
        if d in got:
            limit = live[d] if d <= live_depth else pinned[d]
            checks.append(equal(f"unique.depth{d}", got[d], limit))
    checks.append(at_least("completed_depth", max(got, default=0),
                           int(cfg["must_pass_depth"])))
    checks += [equal(k, out[k], 0) for k in GUARANTEE_COUNTERS]
    return checks


def end_to_end(ctx, measured: dict) -> dict:
    out = measured["outcome"]
    return {"states_per_s": out["unique_states"] / out["elapsed_secs"]}
