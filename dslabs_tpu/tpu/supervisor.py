"""Fault-tolerant search supervisor: retry, watchdog, engine failover.

The north-star deployment is an hours-long accelerator job, and before
this module ANY transient device error, preemption, or wedged TPU killed
a run outright.  The supervisor gives the framework the same spine a
production training/inference stack assumes:

* **One dispatch boundary.**  Every device dispatch in the hot loops —
  the sharded chunk step / level promote / stats sync (sharded.py), the
  single-device wave step / promote / scalar sync (engine.py
  ``_run_device``), and the host loop's expand — funnels through
  ``TensorSearch._dispatch(tag, fn, *args)``.  With no supervisor
  installed that is a zero-cost passthrough; the supervisor installs a
  :class:`DispatchBoundary` there.
* **Failure classification + bounded retry.**  Transient runtime errors
  (XLA RESOURCE_EXHAUSTED / UNAVAILABLE / ABORTED, preemptions,
  :class:`TransientDeviceError` from the fault harness) retry in place
  with exponential backoff + deterministic jitter up to
  ``RetryPolicy.max_retries``.  Fatal errors and exhausted budgets
  raise :class:`EngineFailure`.
* **Wall-clock watchdog.**  With ``RetryPolicy.deadline_secs`` set,
  each dispatch runs on a watchdog thread; a dispatch exceeding its
  deadline (wedged device) is ABANDONED — :class:`DispatchTimeout`,
  classified wedged, no retry — and the supervisor restarts on the
  next rung from the last checkpoint.
* **Engine failover ladder.**  :class:`SearchSupervisor` runs the
  search on the first healthy rung of ``sharded -> device -> host``
  (the host loop is the parity oracle — every rung has identical
  verdict semantics), resuming each rung from the shared
  engine-agnostic checkpoint (tpu/checkpoint.py) when one exists.
  Semantic errors (``CapacityOverflow``, ``CheckpointMismatch``)
  propagate unchanged — failover can never mask a wrong-config verdict.
* **Deterministic fault injection.**  A :class:`FaultPlan` installed at
  the same boundary makes every recovery path exercisable in CI on CPU
  ("dispatch k of engine E raises", "dispatch j hangs") — see
  tests/test_supervisor.py and ``make fault-smoke``.
* **Process isolation.**  The in-process watchdog can only ABANDON a
  wedged dispatch (the blocked daemon thread leaks — counted on
  ``SearchOutcome.abandoned_threads`` and warned about past
  ``DSLABS_ABANDONED_WARN``).  ``SearchSupervisor(
  process_isolation=True, protocol_factory="module:callable")`` runs
  the ladder through the dispatch warden instead (tpu/warden.py): each
  rung is a SPAWNED CHILD heartbeating over a pipe, a silent child is
  SIGKILLed and reaped, and the next rung's child resumes from the
  unified checkpoint — nothing leaks, and a hard runtime wedge cannot
  take the supervising process down.

* **Elastic degraded-mesh ladder.**  ``SearchSupervisor(elastic=True)``
  expands the ``"sharded"`` rung into a WIDTH ladder
  ``sharded(D) -> sharded(D/2) -> ... -> sharded(2) -> device -> host``
  (:func:`expand_ladder`): losing one chip — or a wedge/fatal error the
  rung cannot absorb — costs HALF the mesh, not all of it, because the
  engine-agnostic checkpoint re-shards the frontier and re-inserts the
  visited keys per owner on whatever mesh resumes it
  (tpu/checkpoint.py).  Every shrink is a ``mesh_shrunk`` telemetry
  event and the verdict carries ``mesh_width`` / ``mesh_shrinks``.
* **Adaptive in-rung degradation.**  A classified OOM/capacity dispatch
  failure (:func:`classify_oom`: MemoryError, RESOURCE_EXHAUSTED /
  out-of-memory markers) first retries IN PLACE from the checkpoint
  with SHRUNK knobs — chunk size and the superstep chunk budget halve
  per re-level, a bounded ladder of ``max_knob_shrinks``
  (DSLABS_KNOB_SHRINKS) — before burning a rung: a transient memory
  spike costs a re-level, not a mesh.  Re-levels are ``knobs_shrunk``
  telemetry events and ``SearchOutcome.knob_retries``.

* **Portfolio mode.**  ``SearchSupervisor(portfolio=True)`` runs the
  device-sharded swarm explorer (tpu/swarm.py) as a CONCURRENT lane
  beside the BFS ladder — the reference's BFS + RandomDFS portfolio
  (SURVEY §2.4) on the accelerator.  The first terminal verdict
  (violation / exception / goal) wins and the losing lane is cancelled
  at its next loop boundary; exhaustive BFS verdicts stay
  authoritative.  Swarm witnesses arrive minimized and
  replay-verified (``SearchOutcome.witness``); swarm rounds
  checkpoint/resume beside the BFS dump.  See docs/swarm.md.

Every recovery ends in the normal ``SearchOutcome`` end-condition
vocabulary — never a silent partial verdict — with ``retries``,
``failovers``, ``engine``, and ``resumed_from_depth`` reported on the
outcome.
"""

from __future__ import annotations

import dataclasses
import os
import random
import signal
import threading
import time
import warnings
from typing import Dict, List, Optional, Tuple

from dslabs_tpu.tpu import checkpoint as ckpt_mod

__all__ = ["TransientDeviceError", "DispatchTimeout", "EngineFailure",
           "SupervisorExhausted", "RetryPolicy", "FaultRule", "FaultPlan",
           "DispatchBoundary", "SearchSupervisor", "classify_failure",
           "classify_oom", "classify_child_death", "CHILD_RC_FAILED",
           "expand_ladder", "install_retry"]

# In-process watchdog abandonment LEAKS a blocked daemon thread (a
# wedged XLA runtime cannot be interrupted from Python).  Past this many
# still-blocked threads the boundary warns that the process is
# degrading and process isolation (tpu/warden.py) is the right mode.
ABANDONED_WARN_THRESHOLD = int(os.environ.get("DSLABS_ABANDONED_WARN",
                                              "2"))


class TransientDeviceError(RuntimeError):
    """A retryable device/runtime failure (the injectable stand-in for
    an XLA transient status on real hardware)."""


class DispatchTimeout(RuntimeError):
    """A dispatch exceeded its wall-clock deadline (wedged device).
    Never retried in place — the dispatch was abandoned, so the rung's
    device state is unknown; recovery is failover-from-checkpoint."""


class EngineFailure(RuntimeError):
    """A rung of the ladder failed past recovery-in-place.  ``kind`` is
    ``"fatal"`` / ``"retries_exhausted"`` / ``"wedged"`` /
    ``"capacity"`` (a classified CapacityOverflow the capacity ladder
    answered with a spill-enabled retry — docs/capacity.md); ``cause``
    is the underlying exception."""

    def __init__(self, engine: str, kind: str, cause: BaseException):
        super().__init__(f"{engine} engine failed ({kind}): "
                         f"{type(cause).__name__}: {cause}")
        self.engine = engine
        self.kind = kind
        self.cause = cause


class SupervisorExhausted(RuntimeError):
    """Every rung of the failover ladder failed.  ``failures`` holds the
    per-rung :class:`EngineFailure` chain — the full recovery story is
    attributable, never a bare crash."""

    def __init__(self, failures: List[EngineFailure]):
        super().__init__(
            "all failover rungs failed: "
            + "; ".join(str(f) for f in failures))
        self.failures = failures


# Status markers that make a real runtime error retryable: the set a
# production JAX stack treats as preemption/transient (jaxlib surfaces
# them inside XlaRuntimeError messages).
_TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "UNAVAILABLE", "ABORTED",
                      "DEADLINE_EXCEEDED", "preempt", "slice restart",
                      "connection reset")
# Exception TYPE NAMES treated as runtime-layer errors (matched by name:
# jaxlib's concrete classes move between versions and must not be a hard
# import dependency).
_RUNTIME_ERROR_NAMES = ("XlaRuntimeError", "JaxRuntimeError")

# Errors the boundary must NEVER absorb: semantic/config failures where
# retry or failover would mask a wrong answer, plus interrupts.
def _passthrough_types() -> tuple:
    from dslabs_tpu.tpu.engine import CapacityOverflow

    return (CapacityOverflow, ckpt_mod.CheckpointMismatch,
            KeyboardInterrupt, SystemExit)


def classify_failure(exc: BaseException) -> str:
    """``"transient"`` (retry in place), ``"wedged"`` (abandon, fail
    over), or ``"fatal"`` (fail over)."""
    if isinstance(exc, DispatchTimeout):
        return "wedged"
    if isinstance(exc, TransientDeviceError):
        return "transient"
    if type(exc).__name__ in _RUNTIME_ERROR_NAMES or isinstance(
            exc, MemoryError):
        msg = str(exc)
        if any(m.lower() in msg.lower() for m in _TRANSIENT_MARKERS):
            return "transient"
    return "fatal"


# Markers of a memory/capacity-shaped failure: what the adaptive
# knob-shrink ladder answers with an in-place re-level (halved chunk +
# superstep budget, resume from checkpoint) before burning a rung.
_OOM_MARKERS = ("resource_exhausted", "out of memory", "hbm oom",
                "allocation failure", "oom-kill")


def classify_oom(exc: Optional[BaseException]) -> bool:
    """True when a failure looks like memory/capacity exhaustion — a
    MemoryError, or a runtime error whose message carries an OOM
    marker.  Such failures are worth an in-place knob-shrink retry
    (smaller chunks need less live HBM) where an arbitrary fatal error
    is not."""
    if exc is None:
        return False
    if isinstance(exc, MemoryError):
        return True
    msg = str(exc).lower()
    return any(m in msg for m in _OOM_MARKERS)


# Exit code a warden/service child uses after REPORTING a classified
# failure over its pipe — a clean "failed", as opposed to an abrupt
# crash/kill.  Lives here (not tpu/warden.py) because the taxonomy
# below is the SHARED vocabulary: the warden's rung failover, the
# elastic ladder's in-process classify_oom, and the service scheduler's
# retry policy (dslabs_tpu/service/scheduler.py) all agree through it
# on what an "oom" is.
CHILD_RC_FAILED = 3

# Stderr-tail markers for the child-death taxonomy: everything
# classify_oom recognises in an exception MESSAGE, plus the exception
# NAMES a dying child's traceback tail shows instead (classify_oom
# gets the live object and uses isinstance; a reaped child leaves only
# text).
_OOM_STDERR_MARKERS = _OOM_MARKERS + ("memoryerror",)


def classify_child_death(exitcode: Optional[int],
                         killed_by_warden: bool,
                         stderr_markers=()) -> str:
    """The ONE child-death taxonomy (ISSUE 11 satellite: the warden's
    exit-code classifier and :func:`classify_oom` used to disagree —
    an abrupt exit whose stderr carried a MemoryError traceback was a
    "crash" to the warden but OOM-shaped to the elastic ladder, so the
    scheduler's retry policy and the knob-shrink re-level pulled in
    different directions).  Pinned by the table-driven test in
    tests/test_service.py:

    * ``wedge``  — the supervising parent SIGKILLed the child after
      heartbeat silence (a hung dispatch / wedged runtime);
    * ``oom``    — an UNPROMPTED SIGKILL (the kernel OOM killer or an
      external ``kill -9``), OR any other abrupt death whose
      ``stderr_markers`` text carries one of the :func:`classify_oom`
      markers (a MemoryError traceback, RESOURCE_EXHAUSTED, an
      oom-kill notice) — either way the memory/host is suspect and the
      right answer is a knob-shrink re-level, not a plain retry;
    * ``failed`` — the child exited :data:`CHILD_RC_FAILED` after
      reporting a classified in-child failure over its pipe;
    * ``crash``  — anything else: another signal (SIGSEGV, SIGBUS, …)
      or an abrupt nonzero exit with no report and no OOM marker.

    ``stderr_markers`` is any iterable of text (a stderr tail, a
    heartbeat detail string); it refines only the abrupt-death kinds —
    a warden kill stays a wedge and a clean report stays failed even
    when earlier stderr chatter mentioned memory."""
    if killed_by_warden:
        return "wedge"
    if exitcode == CHILD_RC_FAILED:
        return "failed"
    if exitcode is not None and exitcode < 0:
        if -exitcode == signal.SIGKILL:
            return "oom"
    elif exitcode == 0:
        return "crash"     # rc 0 with no result: still an abrupt death
    text = " ".join(str(s) for s in stderr_markers).lower()
    if text and any(m in text for m in _OOM_STDERR_MARKERS):
        return "oom"
    return "crash"


def expand_ladder(ladder, full_width: Optional[int] = None,
                  elastic: bool = False):
    """Expand a rung-name ladder into ``(rung, width)`` specs.  With
    ``elastic`` set, every ``"sharded"`` entry becomes the degraded-
    mesh width ladder ``sharded(D) -> sharded(D/2) -> ... ->
    sharded(2)`` (width ``None`` = the full mesh) so a failing mesh
    degrades by halves instead of cliff-dropping to one device.  The
    engine NAME stays ``"sharded"`` for every width — fault plans,
    retry budgets, and dispatch tags keep one stable vocabulary."""
    specs = []
    for rung in ladder:
        specs.append((rung, None))
        if rung == "sharded" and elastic and (full_width or 0) > 2:
            w = int(full_width)
            while w > 2:
                w = max(2, w // 2)
                specs.append(("sharded", w))
    return specs


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded-retry + watchdog knobs (docs/resilience.md)."""

    max_retries: int = 3          # per ENGINE rung, across its dispatches
    backoff_base: float = 0.05    # first-retry sleep, seconds
    backoff_factor: float = 2.0
    backoff_max: float = 2.0
    jitter: float = 0.25          # +/- fraction of the backoff, seeded
    deadline_secs: Optional[float] = None   # per-dispatch watchdog; None = off
    # Watchdog deadline for the FIRST dispatch at each (engine, site)
    # tag: that call pays the XLA compile, which dwarfs a steady-state
    # dispatch — a steady-state deadline would misread every cold
    # compile as a wedge.  None = 10 x deadline_secs.
    deadline_first_secs: Optional[float] = None
    seed: int = 0

    def first_deadline(self) -> Optional[float]:
        if self.deadline_secs is None:
            return None
        if self.deadline_first_secs is not None:
            return self.deadline_first_secs
        return 10.0 * self.deadline_secs


@dataclasses.dataclass
class FaultRule:
    """One deterministic fault: dispatches ``at .. at+count-1`` of
    ``engine`` (None = any rung) either raise ``error()`` or hang for
    ``hang_secs`` (interruptibly — the watchdog's abandon releases the
    thread).  ``count=None`` fires forever.  ``site`` (the tag suffix,
    e.g. ``"spill_drain"``) narrows the rule to one dispatch SITE and
    switches the ``at``/``count`` window to that site's own dispatch
    index — how the spill-path fault matrix targets
    evict/refilter/reinject dispatches deterministically."""

    kind: str                      # "raise" | "hang"
    at: int = 0
    count: Optional[int] = 1
    engine: Optional[str] = None
    error: type = TransientDeviceError
    message: str = "injected fault"
    hang_secs: float = 3600.0
    site: Optional[str] = None


class FaultPlan:
    """A deterministic schedule of dispatch-boundary faults.

    Indexing is per-engine: each rung counts its own dispatches from 0,
    and RETRIES ADVANCE THE INDEX (a retry is a new dispatch), so
    ``raise_at(k, count=2)`` means "the dispatch reaching index k fails,
    its first retry fails too, the second retry succeeds"."""

    def __init__(self):
        self.rules: List[FaultRule] = []
        self.fired: int = 0
        # Every firing, attributably: (engine, site, kind, index) — the
        # chaos soak (tpu/chaos.py) asserts its fault count and site
        # coverage from this log.
        self.fired_log: List[tuple] = []

    def raise_at(self, at: int, error: type = TransientDeviceError,
                 engine: Optional[str] = None, count: Optional[int] = 1,
                 message: str = "injected fault",
                 site: Optional[str] = None) -> "FaultPlan":
        self.rules.append(FaultRule("raise", at=at, count=count,
                                    engine=engine, error=error,
                                    message=message, site=site))
        return self

    def raise_always(self, error: type = TransientDeviceError,
                     engine: Optional[str] = None,
                     message: str = "injected fault") -> "FaultPlan":
        return self.raise_at(0, error=error, engine=engine, count=None,
                             message=message)

    def hang_at(self, at: int, engine: Optional[str] = None,
                secs: float = 3600.0, count: Optional[int] = 1,
                site: Optional[str] = None) -> "FaultPlan":
        self.rules.append(FaultRule("hang", at=at, count=count,
                                    engine=engine, hang_secs=secs,
                                    site=site))
        return self

    def match(self, engine: str, index: int, site: Optional[str] = None,
              site_index: Optional[int] = None) -> Optional[FaultRule]:
        for r in self.rules:
            if r.engine is not None and r.engine != engine:
                continue
            if r.site is not None:
                # Site rules window on the SITE's own dispatch index
                # (e.g. "the second spill_drain of the device rung").
                if r.site != site or site_index is None:
                    continue
                idx = site_index
            else:
                idx = index
            if idx < r.at:
                continue
            if r.count is not None and idx >= r.at + r.count:
                continue
            self.fired += 1
            self.fired_log.append((engine, site, r.kind, idx))
            return r
        return None


class DispatchBoundary:
    """The retry/watchdog/fault-injection wrapper every hot-loop device
    dispatch funnels through (``TensorSearch._dispatch``).

    Install on a search with :meth:`install`; tags are
    ``"<engine>.<site>"`` (e.g. ``"sharded.superstep"``) and the engine
    half keys both the fault plan and the per-rung dispatch/retry
    counters.
    """

    def __init__(self, policy: Optional[RetryPolicy] = None,
                 plan: Optional[FaultPlan] = None,
                 observer=None, telemetry=None):
        self.policy = policy or RetryPolicy()
        self.plan = plan
        # Optional telemetry recorder (tpu/telemetry.py): retry and
        # wedge decisions become flight-recorder events, and spans read
        # ``retries`` off this boundary via ``search._dispatch_boundary``.
        self.telemetry = telemetry
        self.retries = 0
        self.timeouts = 0
        self.counts: Dict[str, int] = {}
        self.site_counts: Dict[tuple, int] = {}
        self._engine_retries: Dict[str, int] = {}
        self._rng = random.Random(self.policy.seed)
        # Optional per-dispatch observer, called as
        # ``observer(phase, tag, index, depth)`` with phase ``"start"``
        # before the wrapped call and ``"done"`` after it returns — the
        # warden child's heartbeat emitter rides here (tpu/warden.py).
        # Observer exceptions flow through the normal classification.
        self.observer = observer
        # Watchdog-abandoned daemon threads (the in-process mode's
        # unavoidable leak: a wedged XLA dispatch cannot be interrupted
        # from Python, only abandoned).  Tracked so the degradation is
        # VISIBLE — SearchOutcome.abandoned_threads — and warned about
        # past ABANDONED_WARN_THRESHOLD.
        self.abandoned: List[threading.Thread] = []

    def abandoned_alive(self) -> int:
        """Watchdog-abandoned daemon threads still blocked right now."""
        return sum(1 for t in self.abandoned if t.is_alive())

    def reset_budget(self, engine: str) -> None:
        """Grant ``engine`` a fresh retry budget.  The supervisor calls
        this at every rung (and knob-shrink re-level) start: the
        elastic ladder reuses the engine NAME across its width rungs,
        but the retry budget is per-RUNG — retries spent on the 8-wide
        mesh must not starve the 4-wide one."""
        self._engine_retries.pop(engine, None)

    def install(self, search, engine: Optional[str] = None) -> None:
        """Route ``search``'s dispatches through this boundary.  The
        optional ``engine`` override renames the tag prefix (the
        supervisor uses the rung name so plans written against the
        ladder vocabulary match)."""
        # Per-site watchdog deadline scales, read LIVE from the search:
        # a fused superstep dispatch legitimately runs a whole level's
        # chunk work, so the sharded engine publishes
        # ``_dispatch_deadline_scales = {"superstep": <trip count>}``
        # and the steady-state deadline stretches accordingly
        # (deadline_secs stays calibrated to single-dispatch
        # granularity for every other site).
        self._scales_src = (
            lambda: getattr(search, "_dispatch_deadline_scales", None))
        # Live BFS depth for the observer's heartbeats: every run loop
        # publishes ``_current_depth`` as levels complete.
        self._depth_src = (
            lambda: int(getattr(search, "_current_depth", 0)))
        # Telemetry spans read the retry counter off this attribute to
        # report retries-per-dispatch without new plumbing.
        search._dispatch_boundary = self
        # A (re)installed search may carry freshly built programs — a
        # degraded-width mesh or a knob-shrunk chunk size compiles new
        # executables — so the first dispatch at each tag earns the
        # compile-inclusive grace deadline again.  Without this reset a
        # knob-shrink re-level's first compile would run under the
        # steady deadline and read as a wedge.
        self._seen_tags = set()
        if engine is None:
            search._dispatch_hook = self.dispatch
        else:
            def hook(tag, fn, *args, _e=engine):
                return self.dispatch(
                    _e + "." + tag.split(".", 1)[-1], fn, *args)
            search._dispatch_hook = hook

    # ------------------------------------------------------------ dispatch

    def _depth(self) -> int:
        src = getattr(self, "_depth_src", None)
        return src() if src is not None else 0

    def dispatch(self, tag: str, fn, *args):
        engine = tag.split(".", 1)[0]
        passthrough = _passthrough_types()
        site = tag.split(".", 1)[-1]
        while True:
            idx = self.counts.get(engine, 0)
            self.counts[engine] = idx + 1
            sidx = self.site_counts.get((engine, site), 0)
            self.site_counts[(engine, site)] = sidx + 1
            rule = (self.plan.match(engine, idx, site, sidx)
                    if self.plan else None)
            if rule is not None and self.telemetry is not None:
                # Injections are first-class flight-log events: a chaos
                # soak's recovery timeline names every fault it threw
                # (tpu/chaos.py plans mark themselves ``chaos``).
                self.telemetry.event(
                    "chaos_inject" if getattr(self.plan, "chaos", False)
                    else "fault_inject",
                    engine=engine, site=site, index=idx,
                    fault=rule.kind)
            try:
                if self.observer is not None:
                    # Observer runs INSIDE the try: a fault it raises
                    # (the warden test matrix injects there) is
                    # classified like any dispatch failure, and a retry
                    # re-announces the attempt.
                    self.observer("start", tag, idx, self._depth())
                if rule is not None and rule.kind == "raise":
                    # Raised BEFORE fn runs: the dispatch args (donated
                    # carries included) are untouched, so a retry of the
                    # same call is always well-defined.
                    raise rule.error(f"{rule.message} "
                                     f"[{engine} dispatch {idx}]")
                if self.policy.deadline_secs is not None:
                    out = self._watchdog_call(tag, fn, args, rule)
                else:
                    out = fn(*args)
                if self.observer is not None:
                    self.observer("done", tag, idx, self._depth())
                return out
            except passthrough:
                raise
            except DispatchTimeout as e:
                # The abandoned dispatch may have consumed its donated
                # buffers; there is nothing sound to retry in place.
                self.timeouts += 1
                if self.telemetry is not None:
                    self.telemetry.event("wedged", engine=engine,
                                         site=site, index=idx)
                raise EngineFailure(engine, "wedged", e)
            except Exception as e:  # noqa: BLE001 — classified below
                if classify_failure(e) != "transient":
                    raise EngineFailure(engine, "fatal", e)
                used = self._engine_retries.get(engine, 0)
                if used >= self.policy.max_retries:
                    raise EngineFailure(engine, "retries_exhausted", e)
                self._engine_retries[engine] = used + 1
                self.retries += 1
                if self.telemetry is not None:
                    self.telemetry.event("retry", engine=engine,
                                         site=site, index=idx,
                                         attempt=used + 1,
                                         error=type(e).__name__)
                time.sleep(self._backoff(used))

    def _backoff(self, attempt: int) -> float:
        p = self.policy
        base = min(p.backoff_base * (p.backoff_factor ** attempt),
                   p.backoff_max)
        # Deterministic jitter (seeded RNG): desynchronises retry storms
        # without making CI runs unreproducible.
        return base * (1.0 + p.jitter * (2.0 * self._rng.random() - 1.0))

    def _deadline_scale(self, tag: str) -> float:
        src = getattr(self, "_scales_src", None)
        if src is None:
            return 1.0
        scales = src()
        if not scales:
            return 1.0
        return float(scales.get(tag.split(".", 1)[-1], 1.0))

    def _watchdog_call(self, tag: str, fn, args, rule):
        """Run one dispatch on a watchdog thread; abandon it at the
        deadline.  The first dispatch at each tag gets the compile-
        inclusive grace deadline (RetryPolicy.first_deadline); sites
        with a published deadline scale (superstep granularity — see
        :meth:`DispatchBoundary.install`) stretch the steady-state
        deadline by that factor.  An injected hang waits interruptibly
        AND checks for abandonment before touching the real dispatch,
        so an abandoned fault thread exits cleanly instead of racing
        device work in the background."""
        release = threading.Event()
        box: List[Tuple[str, object]] = []
        seen = getattr(self, "_seen_tags", None)
        if seen is None:
            seen = self._seen_tags = set()
        scaled = self.policy.deadline_secs * self._deadline_scale(tag)
        deadline = (scaled if tag in seen
                    else max(self.policy.first_deadline(), scaled))
        seen.add(tag)

        def work():
            try:
                if rule is not None and rule.kind == "hang":
                    release.wait(rule.hang_secs)
                    if release.is_set():
                        return          # abandoned: never run the dispatch
                box.append(("ok", fn(*args)))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                box.append(("err", e))

        th = threading.Thread(target=work, daemon=True,
                              name=f"dslabs-dispatch-{tag}")
        th.start()
        th.join(deadline)
        if th.is_alive():
            release.set()
            # The leak is unavoidable in-process (Python cannot
            # interrupt a blocked XLA call) but must never be
            # invisible: count the still-blocked threads, warn past
            # the threshold, and let the supervisor surface the live
            # count on SearchOutcome.abandoned_threads.
            self.abandoned = [t for t in self.abandoned if t.is_alive()]
            self.abandoned.append(th)
            n_alive = len(self.abandoned)
            if n_alive >= ABANDONED_WARN_THRESHOLD:
                warnings.warn(
                    f"{n_alive} watchdog-abandoned dispatch threads "
                    "are still blocked in this process (a wedged XLA "
                    "runtime cannot be interrupted from Python); the "
                    "in-process ladder is degrading — use process "
                    "isolation (tpu/warden.py, SearchSupervisor("
                    "process_isolation=True)) for hang-proof recovery",
                    RuntimeWarning, stacklevel=2)
            raise DispatchTimeout(
                f"dispatch {tag!r} exceeded its {deadline}s deadline "
                "(wedged device); abandoned")
        kind, val = box[0]
        if kind == "err":
            raise val
        return val


def install_retry(search, policy: Optional[RetryPolicy] = None,
                  plan: Optional[FaultPlan] = None) -> DispatchBoundary:
    """Wrap a single engine's dispatches with retry/backoff (no ladder):
    the light-touch entry point the search backend uses so lab searches
    survive transient device errors without changing verdict flow."""
    boundary = DispatchBoundary(policy, plan)
    boundary.install(search)
    return boundary


# ------------------------------------------------------------- supervisor

class SearchSupervisor:
    """Run a tensor search with retry, watchdog, checkpointing, and the
    engine failover ladder.

    ``ladder`` names the rungs to try in order (default
    ``("sharded", "device", "host")``); each rung is built from the
    shared protocol/limits, has the boundary installed, and — when a
    ``checkpoint_path`` is configured and a fingerprint-matching dump
    exists — resumes from the last checkpoint instead of the root.  A
    rung that fails past recovery (fatal error, exhausted retries,
    wedged dispatch) is abandoned and the next rung takes over; its
    verdict is identical by construction (the host loop is the parity
    oracle the device engines are tested against).  The returned
    ``SearchOutcome`` carries ``retries`` / ``failovers`` / ``engine``
    / ``resumed_from_depth`` so no degradation is ever silent."""

    def __init__(self, protocol,
                 ladder: Tuple[str, ...] = ("sharded", "device", "host"),
                 mesh=None,
                 policy: Optional[RetryPolicy] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 0,
                 strict: bool = True,
                 max_depth: Optional[int] = None,
                 max_secs: Optional[float] = None,
                 chunk: int = 1 << 10,
                 frontier_cap: int = 1 << 14,
                 visited_cap: int = 1 << 20,
                 ev_budget=None,
                 aot_warmup: bool = False,
                 dispatch_observer=None,
                 process_isolation: bool = False,
                 protocol_factory: Optional[str] = None,
                 factory_kwargs: Optional[dict] = None,
                 protocol_transform: Optional[str] = None,
                 warden_kwargs: Optional[dict] = None,
                 portfolio: bool = False,
                 swarm_kwargs: Optional[dict] = None,
                 spill=False,
                 telemetry=None,
                 elastic: Optional[bool] = None,
                 max_knob_shrinks: Optional[int] = None):
        for rung in ladder:
            if rung not in ("sharded", "device", "host"):
                raise ValueError(f"unknown ladder rung {rung!r}")
        self.protocol = protocol
        self.ladder = tuple(ladder)
        self.mesh = mesh
        self.policy = policy or RetryPolicy()
        self.fault_plan = fault_plan
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.strict = strict
        self.max_depth = max_depth
        self.max_secs = max_secs
        self.chunk = chunk
        self.frontier_cap = frontier_cap
        self.visited_cap = visited_cap
        self.ev_budget = ev_budget
        # AOT warm-up of the sharded rung's programs at build time —
        # compile wall-time lands on SearchOutcome.compile_secs instead
        # of inside the first run's measured window (the benchmark's
        # drivers build their supervisor with it on).
        self.aot_warmup = aot_warmup
        self.dispatch_observer = dispatch_observer
        # Process isolation (tpu/warden.py): the accelerator-facing
        # search loop runs in a SPAWNED CHILD supervised over a pipe —
        # a wedged runtime is SIGKILLed and the next rung's child
        # resumes from the unified checkpoint, instead of the
        # in-process watchdog's leaked-thread abandonment.  The child
        # rebuilds the protocol from ``protocol_factory``
        # ("module:callable" + ``factory_kwargs``, optionally piped
        # through ``protocol_transform``) because live protocol
        # objects hold closures a process boundary cannot carry.
        self.process_isolation = process_isolation
        self.protocol_factory = protocol_factory
        self.factory_kwargs = factory_kwargs
        self.protocol_transform = protocol_transform
        self.warden_kwargs = warden_kwargs
        # Portfolio mode (ISSUE 5, docs/swarm.md): run the swarm
        # explorer (tpu/swarm.py) as a CONCURRENT lane beside the BFS
        # ladder — BFS proves shallow exhaustiveness while diversified
        # deep walkers hunt deep-narrow violations; the first TERMINAL
        # verdict (violation / exception / goal) wins and the losing
        # lane is cancelled at its next loop boundary.  Exhaust
        # verdicts stay BFS-authoritative (a swarm TIME_EXHAUSTED never
        # outranks a BFS SPACE/DEPTH_EXHAUSTED).
        self.portfolio = portfolio
        self.swarm_kwargs = swarm_kwargs
        # The CAPACITY LADDER (ISSUE 6, tpu/spill.py, docs/capacity.md).
        # ``spill=False`` (default): CapacityOverflow passes through
        # unwrapped — the historical contract, still pinned by tests.
        # ``spill="ladder"``: CapacityOverflow becomes a CLASSIFIED,
        # RECOVERABLE failure — the failing rung is rebuilt with the
        # host-RAM spill tier enabled and resumes from the checkpoint;
        # a second overflow escalates to an 8x larger host tier before
        # the next rung takes over.  ``spill=True`` (or a
        # spill.SpillConfig): every rung runs spill-enabled from the
        # start.
        if spill not in (False, True, "ladder"):
            from dslabs_tpu.tpu import spill as spill_mod

            if not isinstance(spill, spill_mod.SpillConfig):
                raise ValueError(
                    "spill must be False, True, 'ladder', or a "
                    f"spill.SpillConfig — got {spill!r}")
        self.spill = spill
        if portfolio and process_isolation:
            raise ValueError(
                "portfolio=True and process_isolation=True are "
                "mutually exclusive (the swarm lane runs in-process)")
        # Unified telemetry (tpu/telemetry.py): attached to every rung
        # it builds, so dispatch spans, rung/failover events, and the
        # final outcome all land in one flight log.
        self.telemetry = telemetry
        # Elastic degraded-mesh ladder (ISSUE 9, docs/resilience.md):
        # expand the "sharded" rung into sharded(D) -> sharded(D/2) ->
        # ... -> sharded(2) so a fatal/wedged mesh rung costs half the
        # chips, not all of them.  Default off (the pinned historical
        # ladder); DSLABS_ELASTIC=1 flips the default.
        if elastic is None:
            elastic = os.environ.get(
                "DSLABS_ELASTIC", "").strip().lower() in ("1", "on",
                                                          "true", "yes")
        self.elastic = bool(elastic)
        # Adaptive in-rung degradation: how many in-place knob-shrink
        # re-levels (halved chunk + superstep budget, resume from
        # checkpoint) an OOM-classified failure gets before the rung
        # burns.
        if max_knob_shrinks is None:
            max_knob_shrinks = int(
                os.environ.get("DSLABS_KNOB_SHRINKS", "2") or "2")
        self.max_knob_shrinks = int(max_knob_shrinks)
        self.knob_retries = 0
        self.mesh_shrinks = 0
        self._degraded_meshes: Dict[int, object] = {}
        self.boundary: Optional[DispatchBoundary] = None
        self.failures: List[EngineFailure] = []
        # Engines are cached per rung so repeated run() calls (e.g. the
        # bench's warm-up-then-measure pattern) reuse the compiled
        # programs; limits are refreshed from the supervisor per run.
        self._engines: Dict[str, object] = {}

    def _engine_spill(self):
        """The spill argument engines are BUILT with (None = off):
        False/"ladder" build plain rungs (the ladder retries with a
        config on overflow); True/SpillConfig enable from the start."""
        if self.spill in (False, "ladder"):
            return None
        return self.spill

    def _full_width(self) -> int:
        """The undegraded mesh width (device count of the configured
        mesh, or every visible device)."""
        if self.mesh is not None:
            return int(self.mesh.devices.size)
        import jax

        return len(jax.devices())

    def _mesh_for(self, width: Optional[int]):
        """The mesh a sharded rung runs on: the configured/full mesh
        for ``width=None``, else a cached DEGRADED mesh over the first
        ``width`` devices of the full one — the elastic ladder's
        "rebuild a smaller mesh" step."""
        from dslabs_tpu.tpu.sharded import make_mesh

        if width is None:
            if self.mesh is None:
                import jax

                self.mesh = make_mesh(len(jax.devices()))
            return self.mesh
        mesh = self._degraded_meshes.get(width)
        if mesh is None:
            if self.mesh is not None:
                import numpy as np
                from jax.sharding import Mesh

                devs = list(self.mesh.devices.flat)[:width]
                mesh = Mesh(np.array(devs), self.mesh.axis_names)
            else:
                mesh = make_mesh(width)
            self._degraded_meshes[width] = mesh
        return mesh

    def _build(self, rung: str, spill=None, width: Optional[int] = None,
               shrink: int = 0):
        # Plain full-width rungs keep their historical cache key
        # (external code and tests index self._engines["sharded"]);
        # spill-enabled variants key beside them per host-tier size,
        # degraded-width / knob-shrunk variants per (width, shrink).
        if spill is None and width is None and shrink == 0:
            key = rung
        elif width is None and shrink == 0:
            key = (rung, getattr(spill, "host_cap", True))
        else:
            key = (rung, getattr(spill, "host_cap", None), width, shrink)
        cached = self._engines.get(key)
        if cached is not None:
            cached.max_depth = self.max_depth
            cached.max_secs = self.max_secs
            return cached
        self._engines[key] = s = self._build_fresh(rung, spill, width,
                                                   shrink)
        return s

    def _build_fresh(self, rung: str, spill=None,
                     width: Optional[int] = None, shrink: int = 0):
        from dslabs_tpu.tpu.engine import TensorSearch

        ck = {"checkpoint_path": self.checkpoint_path,
              "checkpoint_every": self.checkpoint_every,
              "spill": spill}
        # The knob-shrink ladder: each re-level halves the chunk (the
        # live-HBM-per-chunk-step knob) — and, below, the superstep
        # chunk budget — so an OOM retry runs strictly lighter.
        chunk = max(1, self.chunk >> shrink)
        if rung == "sharded":
            from dslabs_tpu.tpu.sharded import (SUPERSTEP_CHUNKS,
                                                ShardedTensorSearch)

            return ShardedTensorSearch(
                self.protocol, self._mesh_for(width),
                chunk_per_device=chunk,
                superstep_chunks=max(1, SUPERSTEP_CHUNKS >> shrink),
                frontier_cap=self.frontier_cap,
                visited_cap=self.visited_cap, max_depth=self.max_depth,
                max_secs=self.max_secs, strict=self.strict,
                ev_budget=self.ev_budget,
                aot_warmup=self.aot_warmup, **ck)
        return TensorSearch(
            self.protocol, frontier_cap=self.frontier_cap,
            chunk=chunk, max_depth=self.max_depth,
            max_secs=self.max_secs, ev_budget=self.ev_budget,
            visited_cap=self.visited_cap, strict=self.strict,
            use_host_visited=(rung == "host"), **ck)

    def _resumable(self, search) -> bool:
        if not self.checkpoint_path:
            return False
        fp = ckpt_mod.peek_fingerprint(self.checkpoint_path)
        return fp is not None and fp == search._ckpt_fingerprint()

    def run(self, resume: bool = False, initial=None,
            check_initial: bool = True):
        """Run the search to a verdict across the ladder.  ``resume``
        opts in to resuming the FIRST rung from an existing checkpoint;
        failover rungs always resume when a matching dump exists (that
        is the point of the checkpoint).  With ``process_isolation``
        set, the whole ladder runs warden-supervised child processes
        instead (identical verdict semantics; see tpu/warden.py)."""
        if self.process_isolation:
            return self._run_isolated(resume=resume, initial=initial)
        if self.portfolio:
            return self._run_portfolio(resume, initial, check_initial)
        return self._run_ladder(resume, initial, check_initial)

    def _run_ladder(self, resume, initial, check_initial, cancel=None):
        """The in-process failover ladder (the pre-portfolio ``run``
        body).  ``cancel`` (a threading.Event) is the portfolio lane's
        first-verdict-wins cut — installed on every rung so a cancelled
        BFS returns at its next level boundary.  With ``elastic`` the
        rung list is the EXPANDED degraded-mesh ladder
        (:func:`expand_ladder`), and an OOM-classified failure first
        retries the rung in place with shrunk knobs (the adaptive
        knob-shrink ladder) before failing over."""
        from dslabs_tpu.tpu.engine import CapacityOverflow

        self.boundary = DispatchBoundary(self.policy, self.fault_plan,
                                         observer=self.dispatch_observer,
                                         telemetry=self.telemetry)
        self.failures = []
        self.knob_retries = 0
        self.mesh_shrinks = 0
        specs = expand_ladder(
            self.ladder,
            self._full_width() if self.elastic else None, self.elastic)
        prev_width = None
        for i, (rung, width) in enumerate(specs):
            eff_width = None
            if rung == "sharded":
                eff_width = width or self._full_width()
                if prev_width is not None and eff_width < prev_width:
                    # A burned mesh rung degrades by HALVES, resuming
                    # the unified checkpoint re-sharded to the smaller
                    # owner map — the telemetry recovery timeline shows
                    # every step down.
                    self.mesh_shrinks += 1
                    if self.telemetry is not None:
                        self.telemetry.event("mesh_shrunk",
                                             from_width=prev_width,
                                             to_width=eff_width)
                prev_width = eff_width
            shrink = 0
            out = None
            search = None
            while True:
                search = self._build(rung, self._engine_spill(),
                                     width=width, shrink=shrink)
                self.boundary.install(search, engine=rung)
                self.boundary.reset_budget(rung)
                if self.telemetry is not None:
                    search._telemetry = self.telemetry
                if cancel is not None:
                    search._cancel_event = cancel
                do_resume = ((resume or i > 0 or shrink > 0)
                             and self._resumable(search))
                if self.telemetry is not None:
                    self.telemetry.event("rung", engine=rung, index=i,
                                         resume=bool(do_resume),
                                         width=eff_width or 1,
                                         shrink=shrink)
                try:
                    out = search.run(check_initial=check_initial,
                                     initial=initial, resume=do_resume)
                except EngineFailure as e:
                    if (classify_oom(e.cause)
                            and shrink < self.max_knob_shrinks):
                        # Adaptive in-rung degradation: an OOM-shaped
                        # failure retries IN PLACE from the checkpoint
                        # with halved chunk / superstep budget — a
                        # memory spike costs a re-level, not a mesh.
                        shrink += 1
                        self.knob_retries += 1
                        if self.telemetry is not None:
                            self.telemetry.event(
                                "knobs_shrunk", engine=rung,
                                shrink=shrink,
                                chunk=max(1, self.chunk >> shrink),
                                width=eff_width or 1,
                                error=str(e.cause)[:200])
                        continue
                    self.failures.append(e)
                    if self.telemetry is not None:
                        # (field name `failure`, not `kind` — the
                        # recorder's positional is already `kind`.)
                        self.telemetry.event("failover", engine=rung,
                                             failure=e.kind,
                                             width=eff_width or 1,
                                             error=str(e.cause)[:200])
                except CapacityOverflow as e:
                    if self.spill != "ladder":
                        # The historical contract: semantic/capacity
                        # errors pass through unwrapped unless the
                        # caller opted into the capacity ladder.
                        raise
                    self.failures.append(
                        EngineFailure(rung, "capacity", e))
                    out = self._capacity_retry(rung, width, shrink,
                                               initial, check_initial,
                                               cancel)
                    search = self._last_capacity_search or search
                break
            if out is None:
                continue
            out.engine = rung
            out.mesh_width = eff_width if eff_width is not None else 1
            out.mesh_shrinks = self.mesh_shrinks
            out.knob_retries = self.knob_retries
            # Causal-trace identity (ISSUE 13): a supervised verdict
            # carries the recorder's trace even when a failover rung
            # produced it (each rung's engine stamps from the SAME
            # attached recorder; this is the belt-and-braces copy for
            # rungs built without one).
            if (getattr(out, "trace_id", None) is None
                    and self.telemetry is not None):
                out.trace_id = self.telemetry.trace_id
            out.retries = self.boundary.retries
            out.failovers = len(self.failures)
            out.resumed_from_depth = getattr(
                search, "_resumed_from_depth", 0)
            out.abandoned_threads = self.boundary.abandoned_alive()
            return out
        raise SupervisorExhausted(self.failures)

    def _capacity_retry(self, rung, width, shrink, initial,
                        check_initial, cancel):
        """The capacity ladder's recovery arm (docs/capacity.md): the
        overflowed rung is rebuilt WITH the host-RAM spill tier and
        resumes from the checkpoint (that is the point of the ladder —
        smaller rungs have less capacity, the tier has host RAM); a
        second overflow escalates to an 8x host tier.  Failures land on
        ``self.failures`` with kind ``"capacity"`` so the recovery
        story stays attributable; returns the outcome or None (fall
        through to the next rung)."""
        import dataclasses as _dc

        from dslabs_tpu.tpu import spill as spill_mod
        from dslabs_tpu.tpu.engine import CapacityOverflow

        self._last_capacity_search = None
        base = (self.spill if isinstance(
            self.spill, spill_mod.SpillConfig) else
            spill_mod.SpillConfig())
        for cfg in (base, _dc.replace(base, host_cap=base.host_cap * 8)):
            search = self._build(rung, cfg, width=width, shrink=shrink)
            self.boundary.install(search, engine=rung)
            if self.telemetry is not None:
                search._telemetry = self.telemetry
                self.telemetry.event("capacity_retry", engine=rung,
                                     host_cap=cfg.host_cap)
            if cancel is not None:
                search._cancel_event = cancel
            self._last_capacity_search = search
            try:
                return search.run(check_initial=check_initial,
                                  initial=initial,
                                  resume=self._resumable(search))
            except CapacityOverflow as e:
                self.failures.append(EngineFailure(rung, "capacity", e))
            except EngineFailure as e:
                self.failures.append(e)
                return None
        return None

    # ------------------------------------------------------ portfolio

    def _build_swarm(self):
        from dslabs_tpu.tpu.swarm import SwarmSearch

        kw = dict(self.swarm_kwargs or {})
        kw.setdefault("mesh", self.mesh)
        kw.setdefault("visited_cap", self.visited_cap)
        kw.setdefault("strict", False)
        kw.setdefault("max_secs", self.max_secs)
        kw.setdefault("ev_budget", self.ev_budget)
        if self.checkpoint_path:
            # Swarm rounds checkpoint beside the BFS dump (their
            # fingerprints differ — neither can resume the other's).
            kw.setdefault("checkpoint_path",
                          self.checkpoint_path + ".swarm")
            kw.setdefault("checkpoint_every", self.checkpoint_every)
        return SwarmSearch(self.protocol, **kw)

    def _run_portfolio(self, resume, initial, check_initial):
        """BFS ladder + swarm fleet as concurrent lanes; first terminal
        verdict wins, the loser is cancelled at its next loop boundary.
        Lane outcomes and errors land on ``self.lanes`` so a portfolio
        verdict is always attributable."""
        import threading

        _TERMINAL = ("INVARIANT_VIOLATED", "EXCEPTION_THROWN",
                     "GOAL_FOUND")
        cancel = threading.Event()
        lanes: Dict[str, object] = {}
        self.lanes = lanes

        def record(name, out):
            lanes[name] = out
            if out.end_condition in _TERMINAL:
                lanes.setdefault("winner", name)
                if self.telemetry is not None:
                    # The live monitor's "current lane" feed: a
                    # portfolio watcher sees which lane won, not just
                    # that SOMETHING returned (tpu/telemetry.py
                    # STATUS.json).
                    self.telemetry.event("lane_winner", lane=name,
                                         end=out.end_condition)
                cancel.set()

        def bfs_lane():
            try:
                out = self._run_ladder(resume, initial, check_initial,
                                       cancel=cancel)
                record("bfs", out)
                # Exhaustive BFS verdicts are authoritative: nothing
                # the swarm could still find would change them, so
                # stop the walkers.  (TIME_EXHAUSTED is not — the
                # swarm keeps its remaining budget.)
                if out.end_condition in ("SPACE_EXHAUSTED",
                                         "DEPTH_EXHAUSTED"):
                    lanes.setdefault("winner", "bfs")
                    cancel.set()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                lanes["bfs_err"] = e

        def swarm_lane():
            try:
                sw = self._build_swarm()
                boundary = DispatchBoundary(self.policy,
                                            self.fault_plan,
                                            telemetry=self.telemetry)
                boundary.install(sw, engine="swarm")
                if self.telemetry is not None:
                    sw._telemetry = self.telemetry
                sw._cancel_event = cancel
                out = sw.run(resume=resume, initial=initial,
                             check_initial=False)
                out.engine = "swarm"
                out.retries = boundary.retries
                record("swarm", out)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                lanes["swarm_err"] = e

        if self.telemetry is not None:
            self.telemetry.event("lane", lanes="bfs+swarm")
        threads = [threading.Thread(target=bfs_lane, daemon=True,
                                    name="dslabs-portfolio-bfs"),
                   threading.Thread(target=swarm_lane, daemon=True,
                                    name="dslabs-portfolio-swarm")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        winner = lanes.get("winner")
        if winner is not None:
            return lanes[winner]
        # No terminal verdict: BFS's exhaust outcome is the richer
        # report; a crashed BFS lane falls back to the swarm's.
        if "bfs" in lanes:
            return lanes["bfs"]
        if "swarm" in lanes:
            return lanes["swarm"]
        raise lanes.get("bfs_err") or lanes.get("swarm_err")

    def _run_isolated(self, resume: bool, initial=None):
        """The process-isolation mode: delegate the ladder to a
        :class:`~dslabs_tpu.tpu.warden.Warden` (one spawned child per
        rung, heartbeat-supervised, SIGKILL on wedge, resume from the
        unified checkpoint).  The warden's failure chain lands on
        ``self.failures`` so both modes report recovery the same way."""
        from dslabs_tpu.tpu.warden import Warden

        if initial is not None:
            raise ValueError(
                "process_isolation cannot ship an in-memory initial "
                "state across the process boundary; encode it in the "
                "protocol_factory instead")
        if not self.protocol_factory:
            raise ValueError(
                "process_isolation=True requires protocol_factory="
                "'module:callable' (+ factory_kwargs) — a live protocol "
                "object cannot cross the spawn boundary")
        wkw = dict(self.warden_kwargs or {})
        wkw.setdefault("elastic", self.elastic)
        warden = Warden(
            factory=self.protocol_factory,
            factory_kwargs=self.factory_kwargs,
            transform=self.protocol_transform,
            ladder=self.ladder, policy=self.policy,
            checkpoint_path=self.checkpoint_path,
            checkpoint_every=self.checkpoint_every,
            strict=self.strict, max_depth=self.max_depth,
            max_secs=self.max_secs, chunk=self.chunk,
            frontier_cap=self.frontier_cap,
            visited_cap=self.visited_cap, ev_budget=self.ev_budget,
            aot_warmup=self.aot_warmup, telemetry=self.telemetry,
            **wkw)
        try:
            return warden.run(resume=resume)
        finally:
            self.failures = warden.failures
