"""From a profiler trace (``.xplane.pb``) to numbers.

Kept with the benchmark so that every PR computes the same number in the
same way.  Read with nothing but JAX (``jax.profiler.ProfileData``).

What the trace holds, as the v5e writes it: one plane per chip
(``/device:TPU:<n>``) whose ``XLA Ops`` line carries one event per HLO
operation run (nested: a ``while`` spans its body's operations; the
``Async XLA Ops`` line's copies lie inside them and are not read) and whose
``XLA Modules`` line carries one event per program run; and the host's
plane (``/host:CPU``), one line per thread, where the benchmark's
``bench:<what>`` annotations are.  All timestamps are nanoseconds from
the start of the trace, on one clock.

On the CPU backend there is no device plane: operations run on the
host's XLA threads and carry an ``hlo_module`` stat.  ``reduce`` reads
those as one pseudo-device so that the reduction can be rehearsed
without the chip; a run on the CPU is refused long before it gets here.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.harness.spans import PREFIX

Interval = Tuple[float, float]          # (start_ns, end_ns)
Event = Tuple[float, float, str]        # (start_ns, end_ns, name)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "psum", "ppermute")
TOP = 10


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def self_by_event(events: List[Event]) -> List[Tuple[Event, float]]:
    """``(event, self seconds)``: each event counted for the time no
    event nested inside it covers (a ``while`` is not charged its body).
    Events of one line nest or follow one another, never cross."""
    out: List[Tuple[Event, float]] = []
    stack: List[list] = []       # [event, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0][1] <= upto:
            ev, self_ns = stack.pop()
            out.append((ev, max(self_ns, 0.0) / 1e9))

    for ev in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(ev[0])
        if stack:
            stack[-1][1] -= (min(ev[1], stack[-1][0][1]) - ev[0])
        stack.append([ev, ev[1] - ev[0]])
    close(float("inf"))
    return out


def clip(intervals: List[Interval], windows: List[Interval]) -> float:
    """Nanoseconds of ``intervals`` (merged) that lie inside
    ``windows`` (merged)."""
    got, j = 0.0, 0
    for s, e in intervals:
        while j < len(windows) and windows[j][1] <= s:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < e:
            got += max(0.0, min(e, windows[k][1]) - max(s, windows[k][0]))
            k += 1
    return got


def program_name(module_event_name: str) -> str:
    """``jit_superstep(1234)`` -> ``jit_superstep``."""
    return re.sub(r"\(\d+\)$", "", module_event_name).strip()


def short_op(name: str) -> str:
    """The chip names an operation by its whole HLO line, ``%copy.5894 =
    u32[2097152,8,4]{0,2,1:T(4,128)} copy(...)``: keep the name and the
    result's shape, ``copy.5894 u32[2097152,8,4]`` — the shape is what
    tells the visited table from a frontier chunk."""
    m = re.match(r"%?([^\s=]+) = (\([^)]*\)|[^\s{]+)", name)
    if not m:
        return name.lstrip("%")[:80]
    return f"{m.group(1)} {m.group(2)[:48]}"


def op_kind(name: str) -> str:
    """``fusion.123 u32[8]`` -> ``fusion``, ``all-to-all.4`` ->
    ``all-to-all``: the operation without its number."""
    return re.sub(r"[.\d]+$", "", name.split(" ")[0].lstrip("%"))


def is_collective(name: str) -> bool:
    """The chip writes ``lax.all_to_all`` as ``all_to_all.12`` and the
    level sync's sums as ``all-reduce.3``."""
    return op_kind(name).replace("_", "-").startswith(COLLECTIVES)


def _events(line, rename=str) -> List[Event]:
    return [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
             rename(str(ev.name))) for ev in line.events]


def _stat(ev, key: str):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def read(path: str):
    """``(devices, host)``: per device ``{"ops": [Event], "modules":
    [Event]}``, and the host's ``bench:`` annotations as Events."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    host: List[Event] = []
    cpu_ops: Dict[int, List[Event]] = {}
    cpu_mods: Dict[int, Dict[tuple, list]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(
                int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"].extend(_events(line, short_op))
                elif line.name == MODULES_LINE:
                    dev["modules"].extend(_events(line))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = str(ev.name)
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if name.startswith(PREFIX):
                    host.append((s, e, name[len(PREFIX):]))
                    continue
                module = _stat(ev, "hlo_module")
                if module is None or name.startswith("end:"):
                    continue
                ordinal = int(_stat(ev, "device_ordinal") or 0)
                cpu_ops.setdefault(ordinal, []).append((s, e, name))
                run = cpu_mods.setdefault(ordinal, {}).setdefault(
                    (str(module), _stat(ev, "run_id")), [s, e])
                run[0], run[1] = min(run[0], s), max(run[1], e)
    if not devices:
        # CPU rehearsal: the host's XLA threads stand for the device.
        for ordinal, ops in cpu_ops.items():
            devices[ordinal] = {
                "ops": ops,
                "modules": [(s, e, mod) for (mod, _run), (s, e)
                            in cpu_mods[ordinal].items()]}
    return devices, host


def _innermost(host: List[Event], at: float) -> str:
    best: Optional[Event] = None
    for s, e, name in host:
        if s <= at < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "none"


def reduce(path: str, window_s: Optional[float] = None,
           min_gap_s: float = 20e-6) -> dict:
    """The numbers the per-layer readers and the result line take from
    one trace.  Times in seconds.

    ``busy_s``       union of the device's operation intervals, averaged
                     over the devices (``busy_s_by_device`` has each)
    ``window_s``     the traced window: ``window_s`` as given (the
                     harness's clock around the slice), else first event
                     to last
    ``idle_share``   1 - busy/window on the WORST device
    ``programs``     device seconds per program (module runs), averaged
                     over the devices
    ``busy_in_span`` device busy seconds while the host had a ``bench:``
                     span of that name open, averaged over the devices
    ``op_self_s``    device self-seconds per operation name, averaged
    ``collective_s`` device seconds in collective operations, averaged
    ``device_ops``   top operations ``[[program/op, seconds], ...]``
    ``idle_gaps``    idle seconds by the innermost ``bench:`` span the
                     host had open ``[[span, seconds], ...]``
    """
    devices, host = read(path)
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")
    n = len(devices)
    lo = min(s for d in devices.values() for s, _e, _n in d["ops"])
    hi = max(e for d in devices.values() for _s, e, _n in d["ops"])
    if host:
        lo = min(lo, min(s for s, _e, _n in host))
        hi = max(hi, max(e for _s, e, _n in host))
    span_s = (hi - lo) / 1e9
    win = window_s if window_s is not None else span_s
    busy_by_dev: Dict[int, float] = {}
    programs: Dict[str, float] = {}
    op_self: Dict[str, float] = {}
    named_self: Dict[str, float] = {}
    busy_in_span: Dict[str, float] = {}
    collective = 0.0
    gaps: Dict[str, float] = {}
    worst = (-1.0, None)
    span_windows = {name: union((s, e) for s, e, n in host if n == name)
                    for name in {n for _s, _e, n in host}}
    for ordinal, d in sorted(devices.items()):
        busy = union((s, e) for s, e, _n in d["ops"])
        busy_by_dev[ordinal] = total(busy) / 1e9
        if 1 - busy_by_dev[ordinal] / win > worst[0]:
            worst = (1 - busy_by_dev[ordinal] / win, busy)
        for name, windows in span_windows.items():
            busy_in_span[name] = (busy_in_span.get(name, 0.0)
                                  + clip(busy, windows) / 1e9 / n)
        mods = sorted(d["modules"])
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            pn = program_name(name)
            programs[pn] = programs.get(pn, 0.0) + (e - s) / 1e9 / n
        for (s, e, name), secs in self_by_event(d["ops"]):
            op_self[name] = op_self.get(name, 0.0) + secs / n
            if is_collective(name):
                collective += secs / n
            # name an operation by the program it ran in
            i = bisect.bisect_right(starts, s) - 1
            prog = (program_name(mods[i][2])
                    if i >= 0 and s < mods[i][1] else "?")
            key = f"{prog}/{name}"
            named_self[key] = named_self.get(key, 0.0) + secs / n
    # idle gaps of the worst device, by what the host was doing
    prev = lo
    for s, e in list(worst[1]) + [(hi, hi)]:
        if (s - prev) / 1e9 >= min_gap_s:
            who = _innermost(host, (prev + s) / 2)
            gaps[who] = gaps.get(who, 0.0) + (s - prev) / 1e9
        prev = max(prev, e)

    def top(d: Dict[str, float]) -> List[list]:
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:TOP]]

    return {
        "devices": n,
        "busy_s": sum(busy_by_dev.values()) / n,
        "busy_s_by_device": busy_by_dev,
        "window_s": win,
        "span_s": span_s,
        "idle_share": worst[0],
        "programs": programs,
        "busy_in_span": busy_in_span,
        "op_self_s": op_self,
        "collective_s": collective,
        "device_ops": top(named_self),
        "idle_gaps": top(gaps),
    }
