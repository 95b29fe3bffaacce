"""The traced round of a swarm cell, read from the slice: the device's
time inside it, and that time split by the walk step's scopes.

``drivers/timeboxed_swarm.py`` runs the profiler over ONE whole round
(``measured["traced_round"]``: the walker steps it advanced); the
program writes the round as a ``dslabs:swarm.round`` span inside the
seam's ``dispatch.round`` and names its stages in the executable's text
(``telemetry.program_scopes("swarm_round")``: ``walk.pick``,
``walk.restart``, ``walk.history`` and the BFS step's own names where
the stage is the same work).  A program that writes none of this gives
None everywhere."""

from __future__ import annotations

import bisect
import sys
from typing import Optional

from benchmark.harness import program_spans, trace

PROGRAM = "swarm_round"
MODULE = "jit_" + PROGRAM
SPAN = "swarm.round"


def traced_round(run: dict) -> Optional[dict]:
    """The ``swarm.round`` span the slice covered, with the driver's
    counts of that round, or None."""
    if "_traced_round" in run:
        return run["_traced_round"]
    run["_traced_round"] = None
    got = program_spans.load(run) if run.get("trace") else None
    counts = run.get("traced_round")
    spans = [n for n in (got or {}).get("notes", ())
             if n["name"] == SPAN]
    if (counts is None or run.get("trace_cut_by_timer")
            or len(spans) != 1 or not counts["explored"]):
        if run.get("trace"):
            print(f"info no traced round to read: the driver counted "
                  f"{counts}, the slice holds {len(spans)} {SPAN} spans "
                  f"among {len((got or {}).get('notes', ()))} of the "
                  f"program's annotations, cut by the timer: "
                  f"{run.get('trace_cut_by_timer')}", file=sys.stderr,
                  flush=True)
        return None
    run["_traced_round"] = dict(spans[0], **counts)
    return run["_traced_round"]


SLACK_NS = 5e6     # the device's clock can sit a millisecond off the host's


def _round_runs(run: dict):
    """``(devices, runs)``: the trace's devices and, for each, the
    ``(start, end)`` of the round program's runs that belong to the
    traced round — whose midpoint lies inside its ``swarm.round`` span,
    give or take ``SLACK_NS``: the span is on the host's clock and the
    runs on the device's (PERF.md section 6, PR 38), and a round is one
    run that starts a launch's latency after its span."""
    if "_round_runs" not in run:
        run["_round_runs"] = None
        rnd = traced_round(run)
        if rnd is not None:
            devices, _host = trace.read(program_spans.load(run)["path"])
            lo, hi = rnd["start"] - SLACK_NS, rnd["end"] + SLACK_NS
            runs = {k: [(s, e) for s, e, name in d["modules"]
                        if trace.program_name(name) == MODULE
                        and lo <= (s + e) / 2 < hi]
                    for k, d in devices.items()}
            if runs and all(runs.values()):
                run["_round_runs"] = (devices, runs)
            else:
                seen = sorted({(trace.program_name(name),
                                round((s - rnd["start"]) / 1e6, 3),
                                round((e - rnd["end"]) / 1e6, 3))
                               for d in devices.values()
                               for s, e, name in d["modules"]})[:8]
                print(f"info no run of {MODULE} in the traced round: "
                      f"module runs (name, ms from the span's start to "
                      f"theirs, ms from its end to theirs) {seen}",
                      file=sys.stderr, flush=True)
    return run["_round_runs"]


def round_device_secs(run: dict) -> Optional[float]:
    """Device seconds (per chip) of the round program's runs in the
    traced round."""
    got = _round_runs(run)
    if got is None:
        return None
    _devices, runs = got
    return sum(e - s for rs in runs.values() for s, e in rs) / 1e9 / len(runs)


def scope_table(run: dict) -> Optional[dict]:
    """Device self-seconds (per chip) of the round program's operations
    inside the traced round, by scope — ``program_spans.scope_table``'s
    shape (``named``, ``near``, ``unscoped``), over walker steps — and
    the table on stderr, in microseconds a walker step."""
    if "_walk_scope_table" in run:
        return run["_walk_scope_table"]
    run["_walk_scope_table"] = None
    got = _round_runs(run)
    scopes = program_spans._scopes_of(PROGRAM) if got is not None else None
    if not scopes:
        return None
    rnd = traced_round(run)
    devices, runs = got
    named, near, unscoped = {}, {}, {}
    for k, d in devices.items():
        mine = sorted(runs[k])
        starts = [m[0] for m in mine]
        for (s, _e, name), self_s in trace.self_by_event(d["ops"]):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mine[i][1]:
                continue
            scope, by_name = scopes.get(name.split(" ")[0].lstrip("%"),
                                        (None, False))
            into, key = ((unscoped, name) if scope is None
                         else (named if by_name else near, scope))
            into[key] = into.get(key, 0.0) + self_s
    if not (named or near or unscoped):
        return None
    n = len(devices)
    table = {"named": {k: v / n for k, v in named.items()},
             "near": {k: v / n for k, v in near.items()},
             "unscoped": sum(unscoped.values()) / n,
             "steps": int(rnd["explored"])}
    run["_walk_scope_table"] = table
    per = 1e6 / table["steps"]
    total = (sum(table["named"].values()) + sum(table["near"].values())
             + table["unscoped"])
    rows = ", ".join(
        f"{k} {per * table['named'].get(k, 0.0):.4f}"
        f"+{per * table['near'].get(k, 0.0):.4f}"
        for k in sorted(set(table["named"]) | set(table["near"]),
                        key=lambda k: -(table["named"].get(k, 0.0)
                                        + table["near"].get(k, 0.0))))
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:5]
    print(f"info walk step by scope, round {rnd['round']} "
          f"({rnd['steps']} steps of the fleet, {table['steps']} walker "
          f"steps advanced), us a walker step (device self time per "
          f"chip), as named by the operation itself + as guessed from "
          f"its neighbours': {rows}; unscoped "
          f"{per * table['unscoped']:.4f} "
          f"({100 * table['unscoped'] / total:.1f} %); all "
          f"{per * total:.4f}; largest unscoped operations "
          f"{[[k, v / n] for k, v in top]}", file=sys.stderr, flush=True)
    return table
