"""The benchmark's own spans and its traced slice.

Spans are written from the benchmark's files, around the calls into each
layer (spans inside the program are the ``tracing`` issue's).  Each is a
``jax.profiler.TraceAnnotation`` named ``bench:<what>``, so that in a
traced run the host's doing sits on the profiler's own clock beside the
device's operations.
"""

from __future__ import annotations

import glob
import os
import shutil
import threading
import time
from typing import List, Optional, Tuple

PREFIX = "bench:"


def span(name: str):
    """A host span ``bench:<name>`` in the profiler's trace (a context
    manager; costs nothing when no trace is being taken)."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(PREFIX + name)


class Tracer:
    """One ``jax.profiler`` slice a run: ``start()`` once, ``stop()`` at
    the slice's end or by a timer after ``max_secs`` — whichever is
    first.  The trace directory is inside the checkout."""

    def __init__(self, out_dir: str, max_secs: float):
        self.out_dir = out_dir
        self.max_secs = max_secs
        self.started: Optional[float] = None
        self.stopped: Optional[float] = None
        self.cut_by_timer = False
        self._lock = threading.Lock()
        self._timer: Optional[threading.Timer] = None

    @property
    def running(self) -> bool:
        return self.started is not None and self.stopped is None

    def start(self) -> None:
        import jax.profiler

        with self._lock:
            if self.started is not None:
                return
            shutil.rmtree(self.out_dir, ignore_errors=True)
            os.makedirs(self.out_dir, exist_ok=True)
            # Device operations and TraceAnnotations only: the Python
            # tracer would slow the host that the slice is there to see.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.out_dir,
                                     profiler_options=options)
            # the slice is what lies between the profiler's coming up
            # and the call that ends it (which then writes the file)
            self.started = time.time()
            self._timer = threading.Timer(self.max_secs, self._cut)
            self._timer.daemon = True
            self._timer.start()

    def _cut(self) -> None:
        if self.stop():
            self.cut_by_timer = True

    def stop(self) -> bool:
        """True if this call ended the slice."""
        import jax.profiler

        with self._lock:
            if not self.running:
                return False
            self.stopped = time.time()
            jax.profiler.stop_trace()
        if self._timer is not None:
            self._timer.cancel()
        return True

    def close(self) -> None:
        """End of the run: stop, and wait for the timer's thread."""
        self.stop()
        if self._timer is not None \
                and self._timer is not threading.current_thread():
            self._timer.join(timeout=60)

    @property
    def window_s(self) -> Optional[float]:
        if self.started is None or self.stopped is None:
            return None
        return self.stopped - self.started

    def xplane(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def level_recorder(tracer: Optional[Tracer], min_frontier_rows: int):
    """A ``telemetry.Telemetry`` recorder for one traced Paxos run, made
    here so that the drivers share it.  It counts dispatches per level
    (exact), annotates every dispatch and level into the profiler's
    trace, and runs ``tracer`` over the first WHOLE level that starts
    with more than ``min_frontier_rows`` frontier rows (summed over the
    devices): a whole level, so that the level's own exact counters
    (explored, unique, dispatches) are the slice's."""
    from dslabs_tpu.tpu.telemetry import Telemetry

    class LevelRecorder(Telemetry):
        def __init__(self):
            super().__init__(ring=64)
            self.dispatches_by_level: List[dict] = []   # index = depth-1
            self.dispatch_ends: List[Tuple[str, float]] = []
            self.traced_depth: Optional[int] = None
            self._enter_level()

        def _enter_level(self) -> None:
            self._open: dict = {}        # dispatches by site, this level
            self._level_note = span("level")
            self._level_note.__enter__()

        def record_dispatch(self, search, tag, hook, fn, *args):
            site = tag.partition(".")[2]
            self._open[site] = self._open.get(site, 0) + 1
            try:
                with span(site):
                    return super().record_dispatch(search, tag, hook,
                                                   fn, *args)
            finally:
                self.dispatch_ends.append((site, time.time()))

        def on_level(self, engine, record):
            super().on_level(engine, record)
            self._level_note.__exit__(None, None, None)
            self.dispatches_by_level.append(dict(self._open))
            depth = int(record["depth"])
            if tracer is not None:
                if tracer.running and self.traced_depth == depth:
                    tracer.stop()
                elif tracer.started is None:
                    pd = record.get("per_device") or {}
                    rows = (sum(pd["frontier"]) if pd.get("frontier")
                            else int(record["next_frontier"]))
                    if rows > min_frontier_rows:
                        self.traced_depth = depth + 1
                        tracer.start()
            self._enter_level()

        def finish(self) -> None:
            """The run is over (mid-level, as a rule): close the open
            level's annotation."""
            if self._level_note is not None:
                self._level_note.__exit__(None, None, None)
                self._level_note = None

    return LevelRecorder()
