"""Why the chip waits in a lab call: the device's idle seconds and its
module runs, read against the program's own spans.

``trace.reduce`` names a gap by the ``bench:`` span the host had open,
which for a lab cell is the one span around the whole call.  This reads
the same slice against what the program writes about itself
(``dslabs_tpu/tpu/telemetry.py`` ``PHASES``), for each entry-point call
that lies in the slice:

* the idle seconds of the worst device (gaps of at least 20 us between
  its operations, as ``trace.reduce`` takes them), each gap cut at the
  spans' edges and given to the INNERMOST ``dslabs:`` span open there.
  A CONTAINER (the call's root, ``entry.derive_root``, ``entry.search``,
  ``entry.warm_run``, ``search.level``) holds other spans: idle seconds
  left to one are seconds nobody has named yet;
* for every ``dispatch.*`` span the ``XLA Modules`` runs that BEGAN
  inside it: lead (span start to the first run's start), the runs,
  between the runs, tail (the last run's end to the span's end).  A
  dispatch that does not wait (the promote) ends before its program
  starts: it is all lead, and its run is found where it began;
  The device's clock is first fitted to the host's (``clock_fit``);
* inside the call's module runs, the share in which no operation ran:
  ``busy_s`` is a union of OPERATIONS, so a program that trickles small
  ones reads as idle though the device never left it;
* the module runs of the call that are not a dispatch site's own
  program: eager operations and jitted helpers called past the
  ``_dispatch`` seam, which opens no span around them.

Four of the five ``.lab`` readers need only PR 25's ``dispatch.*`` and
stage spans; ``idle_named_pct.lab`` reads low on a program without
ISSUE 38's spans.  A trace with no ``dslabs:`` span at all gives
``None``."""

from __future__ import annotations

import statistics
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.harness import lab_call_trace, program_spans, trace

ROOTS = lab_call_trace.ROOTS
CONTAINERS = ROOTS + ("entry.derive_root", "entry.search",
                      "entry.warm_run", "search.level")
SEARCH_STAGES = ("entry.search", "entry.warm_run")
# the host's own work around the dispatches (ISSUE 38's spans)
HOST_SPANS = ("search.", "level.", "entry.root.eager")
DISPATCH = "dispatch."
# the dispatch that reads its program's result back before it returns
# (``sharded._superstep_call``), and that program
WAITS, WAITS_FOR = "dispatch.superstep", "jit_superstep"
MIN_GAP_NS = 20e3               # trace.reduce's ``min_gap_s``
TOP = 5

Segment = Tuple[float, float, str]


def group(name: str) -> str:
    """Which term of the sum identity a span's idle seconds go to."""
    if name.startswith(DISPATCH):
        return "dispatch"
    if name in CONTAINERS:
        return "container"
    return "host" if name.startswith(HOST_SPANS) else "stage"


def innermost(spans: Iterable[dict], lo: float, hi: float) -> List[Segment]:
    """``[lo, hi)`` cut at every span's edges, each piece named by the
    span open there that started last (of two that started together the
    shorter): the innermost, where spans nest.  Pieces no span covers
    are named ``none``; marks (zero length) cover nothing."""
    spans = sorted((s for s in spans if s["end"] > s["start"]
                    and s["end"] > lo and s["start"] < hi),
                   key=lambda s: s["start"])
    edges = sorted({lo, hi} | {min(max(t, lo), hi) for s in spans
                               for t in (s["start"], s["end"])})
    out: List[Segment] = []
    live: List[dict] = []
    i = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(spans) and spans[i]["start"] <= a:
            live.append(spans[i])
            i += 1
        live = [s for s in live if s["end"] > a]
        top = max(live, key=lambda s: (s["start"], -s["end"]),
                  default=None)
        out.append((a, b, top["name"] if top else "none"))
    return out


def by_segment(intervals: List[trace.Interval],
               segments: List[Segment]) -> Dict[str, float]:
    """Nanoseconds of ``intervals`` (merged, sorted) by the name of the
    segment (sorted, non-overlapping) they fall in."""
    got: Dict[str, float] = {}
    j = 0
    for a, b, name in segments:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < b:
            got[name] = got.get(name, 0.0) + max(
                0.0, min(b, intervals[k][1]) - max(a, intervals[k][0]))
            k += 1
    return got


def gaps_in(busy: List[trace.Interval], lo: float, hi: float,
            min_gap_ns: float = MIN_GAP_NS) -> List[trace.Interval]:
    """The idle intervals of ``[lo, hi)``: what lies between the busy
    intervals (merged), for at least ``min_gap_ns``."""
    out, prev = [], lo
    for s, e in list(busy) + [(hi, hi)]:
        s = min(max(s, lo), hi)
        if s - prev >= min_gap_ns:
            out.append((prev, s))
        prev = max(prev, min(e, hi))
    return out


def dispatch_parts(span: dict, runs: List[trace.Interval]) -> List[Segment]:
    """One ``dispatch.*`` span cut into ``lead``, ``run``, ``between``
    and ``tail`` by the module runs that began inside it."""
    s, e = span["start"], span["end"]
    mine = trace.union((a, min(b, e)) for a, b in runs if s <= a < e)
    if not mine:
        return [(s, e, "lead")]
    out: List[Segment] = [(s, mine[0][0], "lead")]
    for (a, b), nxt in zip(mine, mine[1:] + [None]):
        out.append((a, b, "run"))
        out.append((b, nxt[0], "between") if nxt else (b, e, "tail"))
    return [seg for seg in out if seg[1] > seg[0]]


def _seconds(by_name: Dict[str, float]) -> Dict[str, float]:
    return {k: v / 1e9 for k, v in by_name.items()}


def one_call(root: dict, notes: List[dict], devices: dict) -> dict:
    """What the module's docstring lists, for the call ``root`` opens;
    ``notes`` are the ``dslabs:`` annotations that carry its id,
    ``devices`` what ``calls_of`` made of the trace's."""
    lo, hi = root["start"], root["end"]
    # the worst device: the one that worked least inside the call
    ordinal = min(sorted(devices), key=lambda o: trace.clip(
        devices[o]["busy"], [(lo, hi)]))
    busy = devices[ordinal]["busy"]
    gaps = gaps_in(busy, lo, hi)
    idle_by_span = by_segment(gaps, innermost([root] + notes, lo, hi))
    by_group = {g: 0.0 for g in ("dispatch", "host", "container", "stage")}
    for name, ns in idle_by_span.items():
        by_group[group(name)] += ns
    idle = trace.total(gaps)
    # module runs that began inside the call, and the dispatches' parts
    mods = sorted((s, e, trace.program_name(n))
                  for s, e, n in devices[ordinal]["modules"]
                  if lo <= s < hi)
    runs = trace.union((s, min(e, hi)) for s, e, _n in mods)
    spans = [n for n in notes if n["name"].startswith(DISPATCH)]
    parts = {k: 0.0 for k in ("lead", "run", "between", "tail")}
    idle_parts = dict(parts)
    mod_runs = [m[:2] for m in mods]
    for sp in spans:
        segs = dispatch_parts(sp, mod_runs)
        for a, b, k in segs:
            parts[k] += b - a
        for k, ns in by_segment(gaps, segs).items():
            idle_parts[k] += ns
    windows = trace.union((n["start"], n["end"]) for n in spans)
    # Every launch is asynchronous: a dispatch that does not wait closes
    # before its program starts, and an eager operation issued just
    # before a dispatch can start inside it.  So the count asks a run's
    # NAME, not where its start fell: the seam's own programs are named
    # after their sites (``dispatch.promote`` launches ``jit_promote``).
    seams = tuple({"jit_" + n["name"][len(DISPATCH):] for n in spans})
    eager: Dict[str, List[float]] = {}
    for s, e, name in mods:
        if not name.startswith(seams):
            eager.setdefault(name, []).append((e - s) / 1e9)
    stages = trace.union((n["start"], n["end"]) for n in notes
                         if n["name"] in SEARCH_STAGES)
    module_ns = trace.total(runs)
    return {
        "call": root.get("call"), "device": ordinal,
        "clock_ms": devices[ordinal]["fit_ns"] / 1e6,
        "wall_s": (hi - lo) / 1e9,
        "busy_s": trace.clip(busy, [(lo, hi)]) / 1e9,
        "idle_s": idle / 1e9,
        "idle_by_span": _seconds(idle_by_span),
        "idle_by_group": _seconds(by_group),
        "named_pct": (100.0 * (1 - by_group["container"] / idle)
                      if idle else None),
        "level_host_s": (trace.total(stages)
                         - trace.clip(windows, stages)) / 1e9,
        "dispatches": len(spans),
        "dispatch_s": _seconds(parts),
        "dispatch_idle_s": _seconds(idle_parts),
        "dispatch_host_ms": (1e3 * (parts["lead"] + parts["between"]
                                    + parts["tail"]) / 1e9 / len(spans)
                             if spans else None),
        "module_runs": len(mods), "module_s": module_ns / 1e9,
        "program_gap_pct": (100.0 * (1 - trace.clip(busy, runs)
                                     / module_ns) if module_ns else None),
        "eager_programs": sum(len(v) for v in eager.values()),
        "eager_top": sorted(([k, len(v), sum(v)] for k, v in eager.items()),
                            key=lambda r: (-r[1], r[0]))[:TOP],
    }


def clock_fit(notes: List[dict], modules: List[trace.Event]) -> float:
    """Nanoseconds to move one device's events by, so that they fit the
    host's.  The profiler lays the device's clock against the host's
    anew in every session, and a session can be off by more than a lab
    superstep lasts (PERF.md, PR 38: one slice of ``lab1-entry`` read
    its supersteps outside the dispatches that waited for them).  A
    dispatch that WAITS holds its program's run whole — the k-th
    ``dispatch.superstep`` span starts before the k-th ``jit_superstep``
    run does and ends after it — and the least move that lets every one
    do so is taken: 0 where they already do, and where the slice's
    spans and runs cannot be paired (other counts, or no move fits
    all)."""
    spans = sorted((n["start"], n["end"]) for n in notes
                   if n["name"] == WAITS)
    runs = sorted((s, e) for s, e, n in modules
                  if trace.program_name(n) == WAITS_FOR)
    if not spans or len(spans) != len(runs):
        return 0.0
    lo = max(a - s for (a, _b), (s, _e) in zip(spans, runs))
    hi = min(b - e for (_a, b), (_s, e) in zip(spans, runs))
    return min(max(0.0, lo), hi) if lo <= hi else 0.0


def calls_of(notes: List[dict], devices: dict) -> List[dict]:
    """``one_call`` for every entry-point call among ``notes``, each
    device's events first moved by its ``clock_fit`` (``clock_ms`` on
    every call: the move of the device it was read on) and its
    operations merged into busy intervals, once a slice."""
    moved = {}
    for o, d in devices.items():
        fit = clock_fit(notes, d["modules"])
        moved[o] = {
            "fit_ns": fit,
            "busy": trace.union((s + fit, e + fit) for s, e, _n in d["ops"]),
            "modules": [(s + fit, e + fit, n) for s, e, n in d["modules"]]}
    return [one_call(root, [n for n in notes if n is not root
                            and n.get("call") == root.get("call")], moved)
            for root in notes if root["name"] in ROOTS]


def _line(call: dict) -> str:
    def row(d: Dict[str, float]) -> str:
        return ", ".join(f"{k} {v:.4f}" for k, v in sorted(
            d.items(), key=lambda kv: -kv[1]))

    g = call["idle_by_group"]
    parts, idle = call["dispatch_s"], call["dispatch_idle_s"]
    off = (100.0 * abs(sum(g.values()) - call["idle_s"]) / call["idle_s"]
           if call["idle_s"] else 0.0)
    named = {k: v for k, v in call["idle_by_span"].items()
             if group(k) != "container"}
    kept = {k: v for k, v in call["idle_by_span"].items()
            if group(k) == "container"}
    return (
        f"info idle by span, call {call['call']}: wall "
        f"{call['wall_s']:.4f}s, device {call['device']} (clock moved "
        f"{call['clock_ms']:+.3f} ms) busy "
        f"{call['busy_s']:.4f}, idle {call['idle_s']:.4f} = in "
        f"dispatch.* {g['dispatch']:.4f} + host spans {g['host']:.4f} + "
        f"containers {g['container']:.4f} + other stages "
        f"{g['stage']:.4f} (off by {off:.3f}%); named "
        f"{call['named_pct'] or 0.0:.1f}%; by innermost span: "
        f"{row(named)}; left to containers: {row(kept)}; "
        f"{call['dispatches']} dispatches: lead {parts['lead']:.4f} "
        f"(idle {idle['lead']:.4f}), module runs {parts['run']:.4f} "
        f"(idle {idle['run']:.4f}), between {parts['between']:.4f} "
        f"(idle {idle['between']:.4f}), tail {parts['tail']:.4f} (idle "
        f"{idle['tail']:.4f}), host "
        f"{call['dispatch_host_ms'] or 0.0:.3f} ms a dispatch; outside "
        f"dispatches {call['level_host_s']:.4f}s of the search stages; "
        f"{call['module_runs']} module runs {call['module_s']:.4f}s, no "
        f"operation in {call['program_gap_pct'] or 0.0:.2f}%; eager "
        f"(not a dispatch site's program) {call['eager_programs']}, most run "
        f"[program, runs, seconds] {call['eager_top']}")


def calls(run: dict) -> Optional[List[dict]]:
    """The traced calls of the run's slice, read once a run and printed
    as one ``info`` line each on stderr; None where the slice holds no
    ``dslabs:`` span (or no device operation to be idle between)."""
    if "_idle_by_span" not in run:
        run["_idle_by_span"] = None
        got = program_spans.load(run)
        devices = (lab_call_trace._devices(run)
                   if got is not None and got["path"] is not None else None)
        if devices:
            out = calls_of(got["notes"], devices)
            for call in out:
                print(_line(call), file=sys.stderr, flush=True)
            run["_idle_by_span"] = out
    return run["_idle_by_span"]


def mean_per_call(run: dict, key: str) -> Optional[float]:
    """Mean over the traced calls of ``call[key]``, None where there is
    no call or one has nothing to read."""
    values = [c[key] for c in calls(run) or ()]
    if not values or any(v is None for v in values):
        return None
    return statistics.fmean(values)
