"""One run of one cell: set-up, the timed window, the comparison that
decides ``correct``, the metrics, the last line.

The chip is looked for by ``run.py`` before this is called; everything
else of a run is here, so that the tests can drive it on the CPU with
the look left out (and see ``correct`` come out false when the timed
path is broken underneath)."""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

from benchmark.harness import device as device_mod
from benchmark.harness.cache import CompileEvents
from benchmark.harness.manifest import Cell
from benchmark.harness.spans import Tracer

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit."""
    name: str
    value: Any
    limit: Any
    ok: bool

    def line(self) -> str:
        return (f"check {self.name}: value={self.value!r} "
                f"limit={self.limit!r} {'ok' if self.ok else 'FAILED'}")


# The guarantees a strict search may never trade away: all must be 0.
GUARANTEE_COUNTERS = ("dropped", "visited_overflow", "retries",
                      "failovers", "knob_retries")


def equal(name: str, value, limit) -> Check:
    return Check(name, value, limit, value == limit)


def at_least(name: str, value, limit) -> Check:
    return Check(name, value, f">={limit}", value >= limit)


@dataclasses.dataclass
class Context:
    """What a driver is handed."""
    cell: Cell
    dev: Dict[str, Any]
    seed: int
    trace: bool
    events: CompileEvents
    tracer: Optional[Tracer]
    t0: float = 0.0
    state: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def note(self, msg: str) -> None:
        """Progress on stderr: nothing is shown while a chip call runs,
        so the tail of stderr says where a cut run had got to."""
        print(f"[{self.cell.name} +{time.time() - self.t0:6.1f}s] {msg}",
              file=sys.stderr, flush=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, dev: dict,
        t0: float, out=None) -> dict:
    """Run ``cell`` and return the result object (also printed, as the
    last line of ``out``).  ``dev`` is the device block ``run.py``'s
    check returned; ``t0`` the process's start."""
    out = out if out is not None else sys.stdout
    events = CompileEvents()
    tracer = None
    if trace:
        tracer = Tracer(os.path.join(cell.root, ".bench_trace", cell.name),
                        float(cell.params.get("trace_max_secs", 30)))
    ctx = Context(cell=cell, dev=dev, seed=seed, trace=trace,
                  events=events, tracer=tracer, t0=t0)
    driver = cell.driver
    try:
        ctx.note(f"set-up (seed {seed}, trace {int(trace)})")
        driver.prepare(ctx)
        at_start = events.snapshot()
        window_start = time.time()
        setup_s = window_start - t0
        ctx.note(f"window of {seconds:g}s (set-up took {setup_s:.1f}s; "
                 f"compile events so far {at_start.as_dict()})")
        measured = driver.measure(ctx, float(seconds))
        window_s = time.time() - window_start
        in_window = events.snapshot().minus(at_start)
    finally:
        if tracer is not None:
            tracer.close()
    ctx.note(f"window closed after {window_s:.1f}s; comparing")
    measured.update(
        cell=cell.name, chips=cell.chips, config=cell.config,
        params=cell.params, setup_s=setup_s, window_s=window_s,
        compile_events_setup=at_start.as_dict(),
        compile_events_window=in_window.as_dict(),
        memory_peak_bytes=device_mod.peak_bytes())
    checks: List[Check] = list(driver.verify(ctx, measured))
    # Nothing compiles inside the measured window: no program was
    # compiled and written to the persistent cache there.
    checks.append(equal("window.persistent_cache_misses",
                        in_window.misses, 0))
    for c in checks:
        print(c.line(), file=out)
    print(f"info compile events in set-up {at_start.as_dict()} "
          f"in window {in_window.as_dict()}", file=out)
    device = dict(dev, memory_peak_bytes=measured["memory_peak_bytes"])
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": int(measured["attempted"]),
        "failed": int(measured["failed"]),
    }
    metrics: Dict[str, dict] = {}
    if not trace:
        values = dict(driver.end_to_end(ctx, measured), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m.name] = {"value": values[m.name], "unit": m.unit}
    else:
        from benchmark.harness import trace as trace_mod

        xplane = tracer.xplane()
        if xplane is None:
            raise RuntimeError("the traced slice wrote no .xplane.pb "
                               f"under {tracer.out_dir}")
        reduced = trace_mod.reduce(xplane, window_s=tracer.window_s)
        measured["trace"] = reduced
        measured["peaks"] = device_mod.peaks(BENCH_DIR, dev["kind"])
        measured["trace_cut_by_timer"] = tracer.cut_by_timer
        for m in cell.per_layer:
            v = m.compute(measured)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        print(f"info traced slice: window {reduced['window_s']:.3f}s, "
              f"busy by device {reduced['busy_s_by_device']}, idle share "
              f"(worst device) {reduced['idle_share']:.4f}, programs "
              f"{reduced['programs']}", file=out)
    result["metrics"] = metrics
    result["device"] = device
    print(json.dumps(result), file=out, flush=True)
    return result
