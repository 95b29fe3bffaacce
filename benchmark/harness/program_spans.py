"""The program's own spans, marks and scopes, read from a traced slice.

``harness/spans.py`` writes the benchmark's spans around the calls INTO
the program; this reads what the program writes about itself
(``dslabs_tpu/tpu/telemetry.py``): the ``dslabs:<name>`` annotations of
its phases, marks and dispatches, with their stats, which lie on the
trace's own clock beside the device's operations; and, through
``telemetry.program_scopes``, the ``dslabs.<scope>`` stage each
operation of the superstep belongs to.  A program that has none of this
(the commits before PR 25) gives ``None`` everywhere, and the metric is
left out of the line.
"""

from __future__ import annotations

import bisect
import glob
import os
import statistics
import sys
from typing import Dict, List, Optional

from benchmark.harness import trace

PREFIX = "dslabs:"
BENCH_PREFIX = "bench:"         # harness/spans.py's own
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SUPERSTEP = "superstep"               # the program; ``jit_superstep``
# the stages of one lab call, as the entry point names them
ENGINE_BUILD = ("entry.bind", "entry.build_engine", "entry.derive_root")
STAGES = ENGINE_BUILD + ("entry.warm_run", "entry.search", "entry.replay",
                         "entry.recheck", "entry.probe")


def xplane_path(run: dict) -> Optional[str]:
    """The slice's ``.xplane.pb``, found as ``Tracer.xplane()`` finds
    it: the newest under ``<checkout>/.bench_trace/<cell>/``."""
    found = sorted(glob.glob(os.path.join(
        ROOT, ".bench_trace", str(run.get("cell")), "plugins", "profile",
        "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_annotations(path: str):
    """``(notes, bench)``: every ``dslabs:`` annotation of the host's
    threads as ``{"name", "start", "end", **stats}``, times in
    nanoseconds on the trace's clock, sorted by start; and the
    benchmark's own ``bench:`` spans as ``{name: [seconds, ...]}``."""
    import jax.profiler

    notes = []
    bench: Dict[str, List[float]] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = str(ev.name)
                if name.startswith(BENCH_PREFIX):
                    bench.setdefault(name[len(BENCH_PREFIX):], []).append(
                        float(ev.duration_ns) / 1e9)
                if not name.startswith(PREFIX):
                    continue
                note = {k: v for k, v in ev.stats}
                note.update(name=name[len(PREFIX):],
                            start=float(ev.start_ns),
                            end=float(ev.start_ns + ev.duration_ns))
                notes.append(note)
    notes.sort(key=lambda n: (n["start"], -n["end"]))
    return notes, bench


def load(run: dict) -> Optional[dict]:
    """``{"path", "notes", "bench"}`` of the run's slice, read once a
    run; None if there is no slice or the program wrote nothing into
    it."""
    if "_program_spans" not in run:
        path = xplane_path(run) if run.get("trace") else None
        notes, bench = read_annotations(path) if path else ([], {})
        run["_program_spans"] = (
            {"path": path, "notes": notes, "bench": bench}
            if notes else None)
    return run["_program_spans"]


def secs(note: dict) -> float:
    return (note["end"] - note["start"]) / 1e9


# ------------------------------------------------------- the traced level

def traced_level(run: dict) -> Optional[dict]:
    """The ``search.level`` phase of the level the slice covered, closed
    with its counters, or None (no whole level was traced)."""
    got = load(run)
    depth = run.get("traced_depth")
    if got is None or depth is None or run.get("trace_cut_by_timer"):
        return None
    for note in got["notes"]:
        if (note["name"] == "search.level" and note.get("depth") == depth
                and "explored" in note and "explored0" in note):
            return note
    return None


def _scopes_of(program: str) -> Optional[Dict[str, tuple]]:
    try:
        from dslabs_tpu.tpu import telemetry

        return telemetry.program_scopes(program)
    except (ImportError, AttributeError):
        return None             # a program from before PR 25


def scope_table(run: dict) -> Optional[dict]:
    """Device self-seconds (per chip) of the superstep's operations
    inside the traced level, by the scope of each operation:
    ``{"named": {scope: seconds}, "near": {scope: seconds}, "unscoped":
    seconds, "explored": states explored in the level, "unscoped_top":
    [[operation, seconds], ...]}``.  An operation is the superstep's if
    it began inside a run of ``jit_superstep``.  ``named``: the
    operation's own ``op_name`` in the executable's text gives the
    scope, and only these seconds go into a metric.  ``near``: the
    compiler's own operations, which name none, under the scope their
    neighbours agree on (``telemetry.scopes_of_hlo``) — a guess, kept
    apart; one that still has none is ``unscoped``."""
    if "_scope_table" in run:
        return run["_scope_table"]
    run["_scope_table"] = None
    level = traced_level(run)
    scopes = _scopes_of(SUPERSTEP) if level is not None else None
    if not scopes:
        return None
    devices, _host = trace.read(load(run)["path"])
    module = "jit_" + SUPERSTEP
    named: Dict[str, float] = {}
    near: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    for d in devices.values():
        mods = sorted(d["modules"])
        starts = [m[0] for m in mods]
        for (s, _e, name), self_s in trace.self_by_event(d["ops"]):
            if not level["start"] <= s < level["end"]:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if (i < 0 or s >= mods[i][1]
                    or trace.program_name(mods[i][2]) != module):
                continue
            scope, by_name = scopes.get(name.split(" ")[0].lstrip("%"),
                                        (None, False))
            into, key = ((unscoped, name) if scope is None
                         else (named if by_name else near, scope))
            into[key] = into.get(key, 0.0) + self_s
    if not (named or near or unscoped):
        return None
    n = len(devices)
    table = {
        "named": {k: v / n for k, v in named.items()},
        "near": {k: v / n for k, v in near.items()},
        "unscoped": sum(unscoped.values()) / n,
        "explored": int(level["explored"]) - int(level["explored0"]),
        "unscoped_top": [[k, v / n] for k, v in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:trace.TOP]],
    }
    run["_scope_table"] = table
    per_state = 1e6 / table["explored"]
    total = (sum(table["named"].values()) + sum(table["near"].values())
             + table["unscoped"])
    rows = ", ".join(
        f"{k} {per_state * table['named'].get(k, 0.0):.4f}"
        f"+{per_state * table['near'].get(k, 0.0):.4f}"
        for k in sorted(set(table["named"]) | set(table["near"]),
                        key=lambda k: -(table["named"].get(k, 0.0)
                                        + table["near"].get(k, 0.0))))
    print(f"info superstep by scope, level {level['depth']}, us/state "
          f"(device self time per chip over {table['explored']} states "
          f"explored), as named by the operation itself + as guessed "
          f"from its neighbours': {rows}; unscoped "
          f"{per_state * table['unscoped']:.4f}; all "
          f"{per_state * total:.4f} = named "
          f"{per_state * sum(table['named'].values()):.4f} + guessed "
          f"{per_state * sum(table['near'].values()):.4f} + unscoped; "
          f"largest unscoped operations {table['unscoped_top'][:5]}",
          file=sys.stderr, flush=True)
    return table


def scope_us_per_state(run: dict, scopes: tuple) -> Optional[float]:
    """Microseconds per state explored of the operations that name one
    of ``scopes`` themselves (a name ending in ``.`` stands for every
    scope that starts with it)."""
    table = scope_table(run)
    if table is None or not table["explored"]:
        return None
    got = sum(v for k, v in table["named"].items()
              if any(k == s or (s.endswith(".") and k.startswith(s))
                     for s in scopes))
    return 1e6 * got / table["explored"]


# --------------------------------------------------------- the lab's calls

def calls(run: dict) -> Optional[List[dict]]:
    """The entry-point calls that lie whole inside the slice, in order:
    ``{"call", "wall_s", "stage_s": {stage: seconds}, "self_s",
    "dispatches", "compile_s"}``.  ``self_s`` is the call's seconds in
    no stage; ``compile_s`` the seconds covered by its
    ``compile.event`` marks (each mark ends an interval of its ``secs``:
    traces nest and a cache load lies inside its backend compile, so
    the covered time is taken, not the sum)."""
    if "_calls" in run:
        return run["_calls"]
    run["_calls"] = None
    got = load(run)
    if got is None:
        return None
    out = []
    for root in got["notes"]:
        if root["name"] not in ("entry.tensor_bfs", "entry.tensor_dfs"):
            continue
        mine = [n for n in got["notes"]
                if n.get("call") == root.get("call") and n is not root]
        stage_s = {s: 0.0 for s in STAGES}
        for n in mine:
            if n["name"] in stage_s:
                stage_s[n["name"]] += secs(n)
        marks = [(n["end"] - 1e9 * float(n.get("secs", 0.0)), n["end"])
                 for n in mine if n["name"] == "compile.event"]
        out.append({
            "call": root.get("call"), "wall_s": secs(root),
            "stage_s": stage_s,
            "self_s": secs(root) - sum(stage_s.values()),
            "dispatches": sum(1 for n in mine
                              if n["name"].startswith("dispatch.")),
            "compile_s": trace.total(trace.union(marks)) / 1e9,
        })
    if not out:
        return None
    run["_calls"] = out
    around = [w for name, walls in got["bench"].items()
              if name.startswith("call.") for w in walls] or [float("nan")]
    mean = statistics.fmean
    stages = ", ".join(f"{s[len('entry.'):]} "
                       f"{mean(c['stage_s'][s] for c in out):.4f}"
                       for s in STAGES)
    print(f"info lab calls by phase, mean seconds over {len(out)} traced "
          f"calls: {stages}, self {mean(c['self_s'] for c in out):.4f}, "
          f"entry {mean(c['wall_s'] for c in out):.4f}; the benchmark's "
          f"own span around the call {mean(around):.4f}; compile events "
          f"cover {mean(c['compile_s'] for c in out):.4f}; dispatches "
          f"{[c['dispatches'] for c in out]}", file=sys.stderr, flush=True)
    return out


def mean_per_call(run: dict, value) -> Optional[float]:
    """Mean over the traced calls of ``value(call)``."""
    got = calls(run)
    if not got:
        return None
    return statistics.fmean(value(c) for c in got)


def stage_seconds(run: dict, stages: tuple) -> Optional[float]:
    return mean_per_call(
        run, lambda c: sum(c["stage_s"][s] for s in stages))
