"""The traced lab call(s) of a staged slice, read for metrics that set
the DEVICE's work in a call against what the call searched — the
``.lab4`` readers.  ``program_spans.scope_table`` does this for one
traced LEVEL of a process with one engine; a lab process keeps several
engines (a twin a phase, an engine a ladder rung and predicate set), so
here

* the states are the call's, a ladder attempt at a time
  (``attempts``): what the answering search explored (the driver's
  record) and what every attempt that an ``entry.capacity_retry`` mark
  ended had explored (the mark's ``explored``); the frontier rows
  expanded are those of the levels that CLOSED (``search.level`` with
  ``next_frontier``) — the level a goal or an overflow ended gives no
  such counter and is left out, which can only lower a roofline share;
  the bytes a row are those of the engine the attempt leased (a higher
  rung binds a wider network, whose rows pack to more);
* an operation's scope comes from the text of the superstep of the
  engine its ATTEMPT leased (``engine`` on ``entry.build_engine``, among
  the lab entry's kept engines), by the operation's name, held to its
  result shape.

A program that writes none of this gives ``None``."""

from __future__ import annotations

import bisect
import re
import sys
from typing import Dict, List, Optional, Tuple

from benchmark.harness import program_spans, trace

ROOTS = ("entry.tensor_bfs", "entry.tensor_dfs")
ATTEMPT_STAGES = ("entry.derive_root", "entry.warm_run", "entry.search")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+ = .*)$")


def traced(run: dict) -> Optional[Tuple[List[dict], List[dict]]]:
    """``(notes, records)``: the ``dslabs:`` annotations that carry the
    id of an entry-point call of the slice, and the driver's records of
    those calls (the cell's ``traced_phases``, in the window's first
    cycle); None where the two do not match up."""
    got = program_spans.load(run)
    if got is None:
        return None
    ids = {n.get("call") for n in got["notes"] if n["name"] in ROOTS}
    params = run.get("params", {})
    phases = set(params.get("traced_phases", ()))
    records = [c for c in run.get("calls", ())[:len(params.get("cycle", ()))]
               if c["kind"] in phases]
    if not ids or len(ids) != len(records):
        return None
    return [n for n in got["notes"] if n.get("call") in ids], records


def _devices(run: dict) -> dict:
    """The slice's device operations and module runs, read once a run."""
    if "_lab_call_devices" not in run:
        run["_lab_call_devices"] = trace.read(
            program_spans.load(run)["path"])[0]
    return run["_lab_call_devices"]


def wasted_seconds(notes: List[dict]) -> Optional[float]:
    """Seconds of the attempt stages on attempts that a capacity retry
    ended; 0.0 where no rung was climbed, None where the stages do not
    say which attempt they belong to."""
    stages = [n for n in notes if n["name"] in ATTEMPT_STAGES]
    if not stages or any("attempt" not in n for n in stages):
        return None
    ended = {int(n["attempt"]) for n in notes
             if n["name"] == "entry.capacity_retry"}
    return sum(program_spans.secs(n) for n in stages
               if int(n["attempt"]) in ended)


def _kept_engines() -> Dict[int, object]:
    """``{serial: engine}`` of the lab entry's kept engines
    (``telemetry.KEPT_SUPERSTEP``); empty for a program from before
    PR 33."""
    try:
        from dslabs_tpu.tpu import telemetry

        return {e.serial: e for e in telemetry.registered_programs(
            telemetry.KEPT_SUPERSTEP)}
    except (ImportError, AttributeError):
        return {}


def _closed_rows(search: Optional[dict], levels: List[dict]) -> int:
    """Frontier rows of the levels that closed inside ``search`` (an
    ``entry.search`` span; ``levels`` sorted by start)."""
    rows, frontier = 0, 1
    for lv in levels if search else ():
        if not search["start"] <= lv["start"] < search["end"]:
            continue
        if "next_frontier" not in lv:
            break
        rows += frontier
        frontier = int(lv["next_frontier"])
    return rows


def attempts(run: dict) -> Optional[List[dict]]:
    """The ladder attempts of the traced call(s), in order, each with
    the states it ``explored``, the frontier rows it ``expanded`` and
    its engine's ``bytes_per_state`` (None where the engine is not
    among the kept ones)."""
    got = traced(run)
    if got is None:
        return None
    notes, records = got
    if any("attempt" not in n for n in notes if n["name"] == "entry.search"):
        return None
    roots = sorted((n for n in notes if n["name"] in ROOTS),
                   key=lambda n: n["start"])
    levels = [n for n in notes if n["name"] == "search.level"]
    kept = _kept_engines()
    out = []
    for root, record in zip(roots, records):
        stage = {(n["name"], int(n["attempt"])): n for n in notes
                 if n.get("call") == root.get("call") and "attempt" in n}
        for a in sorted(a for name, a in stage if name == "entry.bind"):
            # a doomed attempt's count is its mark's, the answer's the
            # driver's record
            explored = stage.get(("entry.capacity_retry", a), {
                "explored": record.get("states_explored")}).get("explored")
            if explored is None:
                return None
            serial = stage.get(("entry.build_engine", a), {}).get("engine")
            engine = None if serial is None else kept.get(int(serial))
            out.append({
                "explored": int(explored),
                "expanded": _closed_rows(stage.get(("entry.search", a)),
                                         levels),
                "bytes_per_state": getattr(getattr(engine, "search", None),
                                           "bytes_per_state", None)})
    return out or None


def counts(run: dict) -> Optional[Tuple[int, int]]:
    """``(states explored, frontier rows expanded)`` by the traced
    call(s), all ladder attempts together."""
    got = attempts(run)
    if got is None:
        return None
    return (sum(a["explored"] for a in got),
            sum(a["expanded"] for a in got))


def dispatch_device_secs(run: dict) -> Optional[float]:
    """Device busy seconds (per chip) inside the traced calls'
    ``dispatch.*`` annotations."""
    got = traced(run)
    if got is None or program_spans.load(run)["path"] is None:
        return None
    windows = trace.union((n["start"], n["end"]) for n in got[0]
                          if n["name"].startswith("dispatch."))
    devices = _devices(run)
    if not windows or not devices:
        return None
    return sum(trace.clip(trace.union((s, e) for s, e, _n in d["ops"]),
                          windows)
               for d in devices.values()) / 1e9 / len(devices)


def _engine_maps(notes: List[dict]) -> Optional[Dict[int, dict]]:
    """``{(call, attempt): {instruction: (scope, named by itself, result
    shape)}}``: for every ladder attempt of the traced calls, the text
    of the superstep of the engine that attempt leased — the
    ``engine`` serial on its ``entry.build_engine`` span, looked up among
    the lab entry's kept engines (``telemetry.KEPT_SUPERSTEP``)."""
    kept = _kept_engines()
    if not kept:
        return None             # a program from before PR 33
    from dslabs_tpu.tpu import telemetry

    maps = {}
    for n in notes:
        if n["name"] != "entry.build_engine" or "engine" not in n:
            continue
        engine = kept.get(int(n["engine"]))
        if engine is None:
            return None
        text = engine.as_text()
        scopes = telemetry.scopes_of_hlo(text)
        by_name = maps.setdefault((n.get("call"), int(n["attempt"])), {})
        for line in text.splitlines():
            m = _LINE.match(line)
            if m is None:
                continue
            name, _, shape = trace.short_op(m.group(1)).partition(" ")
            if name in scopes:
                by_name[name] = scopes[name] + (shape,)
    return maps or None


def scope_seconds(run: dict) -> Optional[Dict[str, float]]:
    """Device self-seconds (per chip) of the ``superstep`` programs'
    operations inside the traced calls, by the scope each operation
    NAMES itself (``program_spans.scope_table``'s ``named``).  An
    operation belongs to the attempt whose ``entry.warm_run`` or
    ``entry.search`` it began in."""
    if "_lab_call_scopes" in run:
        return run["_lab_call_scopes"]
    run["_lab_call_scopes"] = None
    got = traced(run)
    if got is None or program_spans.load(run)["path"] is None:
        return None
    maps = _engine_maps(got[0])
    if not maps:
        return None
    runs = sorted((n["start"], n["end"], (n.get("call"), int(n["attempt"])))
                  for n in got[0] if n["name"] in ATTEMPT_STAGES[1:]
                  and "attempt" in n)
    starts = [r[0] for r in runs]
    devices = _devices(run)
    module = "jit_" + program_spans.SUPERSTEP
    named: Dict[str, float] = {}
    other = {"guessed": 0.0, "unknown": 0.0}
    for d in devices.values():
        mods = sorted(d["modules"])
        mod_starts = [m[0] for m in mods]
        for (s, _e, op), self_s in trace.self_by_event(d["ops"]):
            i = bisect.bisect_right(mod_starts, s) - 1
            if (i < 0 or s >= mods[i][1]
                    or trace.program_name(mods[i][2]) != module):
                continue
            j = bisect.bisect_right(starts, s) - 1
            by_name = (maps.get(runs[j][2], {})
                       if j >= 0 and s < runs[j][1] else {})
            name, _, shape = op.partition(" ")
            scope, by_itself, want = by_name.get(name, (None, False, ""))
            if scope is None or (shape and want and shape != want):
                other["unknown"] += self_s
            elif by_itself:
                named[scope] = named.get(scope, 0.0) + self_s
            else:
                other["guessed"] += self_s
    if not named:
        return None
    n = len(devices)
    named = {k: v / n for k, v in named.items()}
    run["_lab_call_scopes"] = named
    print(f"info superstep by scope in the traced lab call(s), device "
          f"self seconds per chip, as named by the operation itself: "
          + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
              named.items(), key=lambda kv: -kv[1]))
          + "; not counted: "
          + ", ".join(f"{k} {v / n:.4f}" for k, v in other.items())
          + f" ({len(maps)} attempts' programs)",
          file=sys.stderr, flush=True)
    return named
