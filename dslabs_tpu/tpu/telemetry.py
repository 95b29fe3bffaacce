"""Unified telemetry: dispatch-span flight recorder, metrics, reports.

Before this module every subsystem emitted its own ad-hoc signals —
SearchOutcome counters, warden heartbeat lines, per-level stderr
lines — and a wedged run left almost nothing behind (one scraped stderr
line to explain a hang).  This is the one observability substrate
they all feed, built on the paper's discipline that **every signal must
come from scalar readbacks already paid for**: the recorder never adds
a device dispatch and never reads anything off the device beyond the
fused stats vector the engines already sync (enforced by the
overhead-guard test in tests/test_telemetry.py).

Pieces:

* **Dispatch spans.**  :meth:`Telemetry.attach` hooks the existing
  ``TensorSearch._dispatch`` seam — the one choke point every hot-loop
  device dispatch already funnels through (tpu/supervisor.py).  Each
  dispatch becomes a structured span (engine, site, per-engine index,
  live BFS depth, wall seconds, retries absorbed by the supervisor
  boundary, watchdog deadline-scale, outcome) appended to a bounded
  in-memory ring and — when a ``flight_log`` is configured — streamed
  as JSONL to the **flight-recorder file** beside the checkpoint
  (tpu/checkpoint.py ``default_flight_log``).  The file is opened
  line-buffered append-only and every dispatch writes a begin marker
  BEFORE the device call, so a SIGKILL'd or wedged run leaves a
  readable trail whose torn tail names the in-flight dispatch.

* **Metrics registry.**  Counters / gauges / histograms fed from the
  host scalars the run already holds: per-level fused-stats records
  (all three engines + the swarm's rounds), spill/overflow counters,
  supervisor retry/failover/rung events, and warden heartbeats
  re-emitted from the child→parent JSON protocol.  ``summary()`` is
  the compact JSON block a caller attaches to its own output.

* **Program spans.**  :func:`phase` / :func:`mark` name the host's
  work where it happens — the lab entry point's stages, every level,
  every dispatch, every compile — as ``dslabs:<name>``
  ``jax.profiler.TraceAnnotation`` events, so that in ANY profile (the
  benchmark's traced slice, ``jax.profiler.trace`` around a test) the
  program's doing lies on the device trace's own clock, fields and
  all; with a recorder current (:func:`use`) each also lands in the
  ring and the flight log as a ``phase`` record.  Every name is in
  :data:`PHASES`.  Inside the device programs ``jax.named_scope``
  (``dslabs.<stage>``, :data:`DEVICE_SCOPES`) names the stages of the
  chunk step; :func:`program_scopes` maps a compiled program's
  instructions back to them.  With no profiler running and no recorder
  current a phase costs one ``TraceMe`` activity check.

* **Run reports.**  ``python -m dslabs_tpu.tpu.telemetry report
  <run-dir-or-flight-log>`` renders the flight log alone into per-level
  throughput series, per-site dispatch-latency percentiles, the
  retry/failover/heartbeat timeline, spill and overflow counts, the
  compile-vs-search wall split, and the in-flight dispatch of a torn
  tail.  ``report --json`` emits the same structure machine-readable
  (one schema shared with the grading scripts; pinned by test).
  docs/observability.md documents the span model and the "diagnosing
  a wedge" recipe rides it (docs/resilience.md).

* **Per-device skew (mesh scope).**  The sharded / swarm engines keep
  their pre-``psum`` per-device scalars in the SAME fused stats
  readback (frontier occupancy, visited-table load, states expanded,
  capacity drops — see sharded.py ``stats_local``), so per-level
  records carry ``per_device`` lanes and :func:`skew_metrics`
  (max/mean imbalance + coefficient of variation) at zero added
  transfers.  ``on_level`` feeds them to the registry and warns past
  ``DSLABS_SKEW_WARN``; the report CLI renders a per-device ×
  per-level heatmap.  These are the numbers the owner-hashed
  ``all_to_all`` design (ROADMAP #1) is decided on.

* **Live run monitor.**  A recorder with a run dir atomically rewrites
  ``STATUS.json`` (depth, rate, skew, spill tier, last span, current
  rung/lane, in-flight dispatch) at level/event boundaries —
  ``python -m dslabs_tpu.tpu.telemetry watch <run-dir>`` tails it plus
  the flight log to render a live terminal view of ANY run, including
  a warden child in another process, and survives
  the run being SIGKILLed mid-level (atomic replace = never torn;
  the flight tail names the in-flight dispatch).

Thread-safe (the portfolio runs two lanes against one recorder); pure
host-side Python + stdlib — importing this module never imports jax.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import math
import os
import re
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

__all__ = ["Telemetry", "MetricsRegistry", "Counter", "Gauge",
           "Histogram", "read_flight", "build_report",
           "render_report", "render_sites", "skew_metrics",
           "device_memory_stats", "default_status_path", "load_status",
           "render_watch", "watch_frame",
           "DISPATCH_SITES", "PHASES", "DEVICE_SCOPES", "AOT_PROGRAMS",
           "phase", "mark", "call", "annotate", "use", "current",
           "current_phase", "device_scope", "register_program", "registered_programs",
           "program_scopes", "scopes_of_hlo", "KEPT_SUPERSTEP", "main"]

# THE canonical dispatch-site registry (ISSUE 10): every tag the
# engines route through ``TensorSearch._dispatch``, with the static
# contract each site's lowered program is audited against by the
# soundness sanitizer (dslabs_tpu/analysis/jaxpr_audit.py — the same
# enumeration feeds the ``dispatch.<site>`` names of PHASES below and
# the sanitizer's coverage check, so a new dispatch site that skips
# this table is a loud J0 finding, not silent audit rot).
#
#   hot      — steady-state dispatches of the hot loop
#   donated  — the program's carry is declared jit(donate_argnums=0);
#              the auditor verifies the lowering kept the aliasing
#   multi    — cross-device collectives are EXPECTED (mesh programs);
#              False means any collective is a J4 finding
#   program  — the tag dispatches a lowered device program (False =
#              a bare readback / host helper; nothing to audit)
DISPATCH_SITES = {
    "device.init":           dict(hot=False, donated=False, multi=False,
                                  program=True),
    "device.step":           dict(hot=True, donated=True, multi=False,
                                  program=True),
    "device.promote":        dict(hot=False, donated=True, multi=False,
                                  program=True),
    "device.sync":           dict(hot=False, donated=False, multi=False,
                                  program=False),
    "device.flags":          dict(hot=False, donated=False, multi=False,
                                  program=False),
    "device.spill_drain":    dict(hot=False, donated=True, multi=False,
                                  program=True),
    "device.spill_evict":    dict(hot=False, donated=True, multi=False,
                                  program=True),
    "device.spill_reinject": dict(hot=False, donated=True, multi=False,
                                  program=False),
    "sharded.superstep":     dict(hot=True, donated=True, multi=True,
                                  program=True),
    "sharded.promote":       dict(hot=False, donated=True, multi=True,
                                  program=True),
    # the promote of a level whose delta-lane base moved (twins with
    # ``Field(delta=)``): the counters, and the re-base of the rows
    "sharded.promote_rebase": dict(hot=False, donated=True, multi=True,
                                   program=True),
    "sharded.init":          dict(hot=False, donated=False, multi=True,
                                  program=True),
    "sharded.spill_drain":   dict(hot=False, donated=True, multi=True,
                                  program=True),
    "sharded.spill_evict":   dict(hot=False, donated=True, multi=True,
                                  program=True),
    "sharded.spill_reinject": dict(hot=False, donated=True, multi=True,
                                   program=False),
    "swarm.round":           dict(hot=True, donated=True, multi=True,
                                  program=True),
    "swarm.init":            dict(hot=False, donated=False, multi=True,
                                  program=False),
    "swarm.flags":           dict(hot=False, donated=False, multi=True,
                                  program=False),
    "host.expand":           dict(hot=True, donated=False, multi=False,
                                  program=False),
    # The visited-table bucket probe/insert (ISSUE 12): the jnp path
    # on every backend (the Pallas version is written but refused by
    # Mosaic — scatter — and runs only on an explicit
    # DSLABS_VISITED_PALLAS request) — inlined into every expanding
    # dispatch, and audited/profiled standalone through this site
    # (visited.dispatch_site_program).
    "visited.insert":        dict(hot=True, donated=True, multi=False,
                                  program=True),
    # Batched job lanes (ISSUE 14, tpu/lanes.py): the lane superstep
    # is THE multi-tenant hot path — one dispatch per level advances
    # every resident lane — with the masked promote, the one-hot
    # swap-in/restore splices, and the vmapped root initializer
    # around it.  All single-device programs (J4 applies); the
    # superstep/promote/inject carries are donated (J3 applies).
    "lanes.init":            dict(hot=False, donated=False, multi=False,
                                  program=True),
    "lanes.superstep":       dict(hot=True, donated=True, multi=False,
                                  program=True),
    "lanes.promote":         dict(hot=False, donated=True, multi=False,
                                  program=True),
    "lanes.inject":          dict(hot=False, donated=True, multi=False,
                                  program=True),
    "lanes.restore":         dict(hot=False, donated=True, multi=False,
                                  program=True),
    "lanes.sync":            dict(hot=False, donated=False, multi=False,
                                  program=False),
    "lanes.flags":           dict(hot=False, donated=False, multi=False,
                                  program=False),
    # Capacity round 2 (ISSUE 15): the bit-packed frontier codec
    # (tpu/packing.py) and the symmetry canonicalize pass
    # (tpu/symmetry.py) are FUSED into device.step / host.expand — no
    # standalone dispatch in the hot loop — but each registers a
    # canonical standalone program (like visited.insert) so the jaxpr
    # auditor (J0-J5) and profiler cover the codec lowerings
    # themselves.  Registered only by engines whose descriptor is
    # non-identity / whose reduction is on.
    "packing.pack":          dict(hot=False, donated=False, multi=False,
                                  program=True),
    "packing.unpack":        dict(hot=False, donated=False, multi=False,
                                  program=True),
    "symmetry.canonicalize": dict(hot=False, donated=False, multi=False,
                                  program=True),
}

def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def skew_metrics(values) -> dict:
    """Shard-skew summary of one per-device lane: the slowest-device
    ratio (``imbalance`` = max/mean — 1.0 is a perfectly balanced
    mesh, D is one device doing all the work) and the coefficient of
    variation.  Pure host math over scalars the level sync already
    read; shared by the engines (per-level records), ``on_level``
    (registry + warning), and the report heatmap."""
    vals = [float(v) for v in values]
    n = len(vals)
    if not n:
        return {"max": 0, "mean": 0.0, "imbalance": 1.0, "cv": 0.0}
    mean = sum(vals) / n
    mx = max(vals)
    if mean <= 0:
        return {"max": mx, "mean": round(mean, 3),
                "imbalance": 1.0, "cv": 0.0}
    var = sum((v - mean) ** 2 for v in vals) / n
    return {"max": mx, "mean": round(mean, 3),
            "imbalance": round(mx / mean, 4),
            "cv": round(math.sqrt(var) / mean, 4)}


def device_memory_stats(devices) -> Optional[List[int]]:
    """Per-device HBM high-water (``peak_bytes_in_use``), polled
    host-side via the runtime's memory stats — never a device
    dispatch.  ``None`` when the backend does not report (CPU meshes):
    callers simply omit the lane."""
    out = []
    for d in devices:
        try:
            ms = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — absence of stats is normal
            return None
        out.append(int(ms.get("peak_bytes_in_use",
                              ms.get("bytes_in_use", 0))))
    return out if any(out) else None


def default_status_path(flight_log: Optional[str]) -> Optional[str]:
    """The live-monitor file that pairs with a flight log: the run-dir
    convention is ``STATUS.json`` beside ``flight.jsonl``
    (checkpoint.run_dir_layout); a named phase log
    (``<phase>.flight.jsonl``) gets
    ``<phase>.STATUS.json`` so concurrent phases in one dir never
    clobber each other."""
    if not flight_log:
        return None
    d = os.path.dirname(os.path.abspath(flight_log))
    base = os.path.basename(flight_log)
    if base == "flight.jsonl":
        return os.path.join(d, "STATUS.json")
    for suffix in (".flight.jsonl", ".jsonl"):
        if base.endswith(suffix):
            return os.path.join(d, base[:-len(suffix)] + ".STATUS.json")
    return os.path.join(d, base + ".STATUS.json")


# ------------------------------------------------------------- registry

class Counter:
    """Monotonic count (events, dispatches, retries)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, v: int = 1) -> None:
        self.value += v


class Gauge:
    """Last-written scalar (depth, table load, outcome counters)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def set(self, v) -> None:
        self.value = v


class Histogram:
    """Bounded sample store with percentile readout (span latencies).
    Keeps the most recent ``cap`` observations — a run report wants
    the distribution, not an unbounded host array."""

    __slots__ = ("values", "count", "total", "cap")

    def __init__(self, cap: int = 4096):
        self.values: deque = deque(maxlen=cap)
        self.count = 0
        self.total = 0.0
        self.cap = cap

    def observe(self, v: float) -> None:
        self.values.append(float(v))
        self.count += 1
        self.total += float(v)

    def percentile(self, q: float) -> float:
        if not self.values:
            return 0.0
        vs = sorted(self.values)
        i = min(len(vs) - 1, max(0, int(round(q * (len(vs) - 1)))))
        return vs[i]

    def snapshot(self) -> dict:
        return {"count": self.count,
                "total": round(self.total, 6),
                "p50": round(self.percentile(0.50), 6),
                "p90": round(self.percentile(0.90), 6),
                "p99": round(self.percentile(0.99), 6),
                "max": round(max(self.values, default=0.0), 6)}


class MetricsRegistry:
    """Create-on-touch named metrics; ``snapshot()`` is plain JSON."""

    def __init__(self):
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, Gauge] = {}
        self.histograms: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        c = self.counters.get(name)
        if c is None:
            c = self.counters[name] = Counter()
        return c

    def gauge(self, name: str) -> Gauge:
        g = self.gauges.get(name)
        if g is None:
            g = self.gauges[name] = Gauge()
        return g

    def histogram(self, name: str) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram()
        return h

    def snapshot(self) -> dict:
        return {
            "counters": {k: c.value for k, c in self.counters.items()},
            "gauges": {k: g.value for k, g in self.gauges.items()},
            "histograms": {k: h.snapshot()
                           for k, h in self.histograms.items()},
        }


# -------------------------------------------------------- program spans

# THE table of span and mark names (as DISPATCH_SITES is of sites):
# ``phase``/``mark``/``annotate`` are only ever handed one of these, and
# tests/test_program_spans.py holds every name a run emits to it.
# PERF.md section 3 says which per-layer metric reads which.
AOT_PROGRAMS = ("superstep", "promote", "promote_rebase", "init_carry")
# The lab entry's kept engines register themselves under this name
# (tpu/backend.py ``_Engine``: ``serial``, ``as_text()``), apart from
# the AOT executables that ``program_scopes`` reads.
KEPT_SUPERSTEP = "superstep.kept"
PHASES = (
    "entry.tensor_bfs", "entry.tensor_dfs",     # root of one lab call
    # one of each a ladder attempt (``attempt``), with ``cached`` = 1
    # where the lab entry had kept what the stage would build
    # (tpu/backend.py _Kept); ``entry.bind`` names the ``twin`` it bound,
    # ``entry.build_engine`` the kept ``engine`` (its serial) it leased
    "entry.bind", "entry.build_engine", "entry.derive_root",
    # the trace step built and compiled (absent where it was kept: once
    # a twin and caps a process, in whichever of derive_root, replay or
    # recheck needs it first); a STAGED state's history replayed
    # (events, staged_ops), or VALIDATED as the canonical root a twin's
    # initial state bakes in (lab 4's joined root: no replay under it)
    "entry.root.build", "entry.root.replay", "entry.root.validate",
    # ``attempt`` on these two as well: a rung that overflows throws its
    # warm run and its search away (entry.capacity_retry ends it)
    "entry.warm_run", "entry.search", "entry.replay", "entry.recheck",
    # ``entry.probe``: the dfs entry's swarm probe (``walkers``: the
    # fleet's width, ``max_steps``: its history length = the deepest
    # bound); under it, inside each ``dispatch.round`` the seam opens,
    # ``swarm.round`` (``round``, ``steps``: the budget; at close the
    # fleet's cumulative ``explored``, ``unique``, ``revisits``,
    # ``restarts``, ``overflow_restarts``, ``vis_over``, ``deepest``,
    # ``probes``: restarts by a prune, the bound or a dead end, and
    # ``refused``: steps the twin had no room in its own state for)
    "entry.probe", "swarm.round",
    # mark: a ladder attempt overflowed (``attempt``, ``overflow``, and
    # ``explored``: the states its last stats readback had counted)
    "entry.capacity_retry",
    # one BFS level / wave: ``depth``, ``explored0``; at close
    # ``explored``, ``unique``, ``chunks``, ``next_frontier`` and, from
    # the sharded engine, ``write_blocks`` (scatter blocks its chunk
    # steps wrote, table + append: visited.block_width), ``probe_cols``
    # (bucket columns its probes gathered: the indices handed to the
    # table's gather, a step's live blocks of that width),
    # ``kind_skips`` ((chunk step, event kind) pairs whose handlers and
    # merge the expand skipped, the pass's table holding no event of
    # the kind: engine._expand_chunk; of 2 x ``chunks``) and, at the
    # start, ``frontier0`` (the frontier rows the level starts with, of
    # the device that holds most: ``chunks`` beyond ceil(frontier0 /
    # chunk) are chunk steps re-run at a later event window); from a
    # twin with delta lanes (``Field(delta=)``) also ``rebased`` (1: the
    # level base moved, so this level's promote re-encodes its rows),
    # ``rebase_rows`` (the rows it re-encodes) and ``delta_peak`` (the
    # largest value - base a live successor's delta lane held)
    "search.level",
    # the host's own work around a run's dispatches, each a ``with`` in
    # the frame that does it (ISSUE 38; benchmark/harness/
    # idle_by_span.py sets the device's idle seconds against them).
    # ``search.start``: run() up to its initial check — ONE launch of
    # the engine's root program (row, canonical key, every predicate at
    # the root: ``jit_root_program`` in a profile, ISSUE 39) and its one
    # readback, then host work: the trace's root, the key, the verdict;
    # ``search.carry``: _run_levels up to the first level — the root's
    # owner and home slot from that key (``_root_ids``, host arithmetic)
    # and the carry's initialiser (``dispatch.init`` lies inside it), or
    # a checkpoint's load
    "search.start", "search.carry",
    # under ``search.level``: the level's appended (child, parent,
    # event) rows read back and folded into the host's chain map
    # (``rows`` kept, ``bytes`` read), where the engine records traces
    "level.trace_meta",
    # under ``entry.derive_root``: the eager operations around a staged
    # root's replay (the twin's initial row read back, the replayed row
    # unflattened onto the device)
    "entry.root.eager",
    # one ``ProtocolSpec.compile()`` (tpu/compiler.py): ``spec``, its
    # node ``instances`` and the handler ``invocations`` of its budget
    # dry-run; the seconds add up in ``compile_cache.totals()`` as
    # ``twin_build_s``
    "compile.twin",
    "compile.aot",                  # aot_warmup, one child per program
    # mark: one jax.monitoring event, or one lookup in the executable
    # store (``kind`` store_hit / store_miss, ``fun`` the program)
    "compile.event",
    # the executable store (tpu/compile_cache.py), under the
    # ``compile.aot.<program>`` (or ``entry.root.build``) that asks it:
    # an engine's key built, an entry read and loaded onto the devices,
    # an entry serialized and written
    "compile.store.key", "compile.store.load", "compile.store.write",
    # the swarm's round program (tpu/swarm.py ``_load_round``), asked of
    # the store before its first round as the AOT programs are
    "compile.aot.swarm_round",
) + tuple(f"compile.aot.{name}" for name in AOT_PROGRAMS) + tuple(
    sorted({"dispatch." + tag.split(".", 1)[1] for tag in DISPATCH_SITES}))

# The ``jax.named_scope`` names inside the device programs
# (``dslabs.<scope>``): the stages of the chunk step, the level sync
# and the promote.  Scopes are HLO metadata only.  Under
# ``expand.handlers`` a twin built of fragments adds one level more,
# ``expand.handlers.<fragment>`` (``spec``: the spec's own handlers;
# tpu/compiler.py ``handler_scope``): a reader that asks for
# ``expand.`` counts them with the handlers, one that asks by fragment
# splits them.
DEVICE_SCOPES = (
    "expand.events", "expand.handlers", "expand.canon", "fingerprint",
    "flags", "pack", "trace_meta", "route", "exchange", "visited_insert",
    "append", "level_sync", "promote",
    # inside ``promote``, where a delta twin's level base moved: the
    # re-encode of the promoted rows (tpu/sharded.py ``_rebase_rows``)
    "promote.rebase",
    # the swarm's walk step (tpu/swarm.py): what only a walker does —
    # ids, logits and the categorical pick; the seed gather and the
    # ``where``s of restart resolution; the history's write
    "walk.pick", "walk.restart", "walk.history")

ANNOTATION_PREFIX = "dslabs:"
SCOPE_PREFIX = "dslabs."
# The tests turn this on (tests/conftest.py): a name that is not in
# PHASES then raises where it is used.
check_names = False
_PHASE_SET = frozenset(PHASES)

_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "dslabs_telemetry", default=None)
_CALL: contextvars.ContextVar = contextvars.ContextVar(
    "dslabs_call", default=None)
_PARENT: contextvars.ContextVar = contextvars.ContextVar(
    "dslabs_phase", default=None)
_CALL_SEQ = itertools.count(1)
_TRACE_ME = None
_PACKED = re.compile(r"""[#,="'()]""")


def current() -> Optional["Telemetry"]:
    """The recorder that phases opened in this context write to."""
    return _CURRENT.get()


@contextlib.contextmanager
def use(recorder):
    """Make ``recorder`` current for the body: every phase and mark
    opened inside lands in its ring and flight log, and the lab entry
    point attaches it to the search it builds."""
    token = _CURRENT.set(recorder)
    try:
        yield recorder
    finally:
        _CURRENT.reset(token)


def device_scope(name: str):
    """``jax.named_scope("dslabs.<name>")`` — one stage of a device
    program, named in the HLO's metadata."""
    import jax

    return jax.named_scope(SCOPE_PREFIX + name)


class _NoAnnotation:
    """Stands for a TraceAnnotation while no profiler is running."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **fields):
        pass


_NO_ANNOTATION = _NoAnnotation()


def annotate(name: str, **fields):
    """The profiler half of a phase alone: a ``dslabs:<name>``
    TraceAnnotation whose stats are ``fields`` plus the current call's
    id — or nothing at all while no profiler is running (one
    ``TraceMe`` activity check)."""
    global _TRACE_ME
    if check_names and name not in _PHASE_SET:
        raise ValueError(f"{name!r} is not in telemetry.PHASES")
    if _TRACE_ME is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ME = TraceAnnotation
    if not _TRACE_ME.is_enabled():
        return _NO_ANNOTATION
    call = _CALL.get()
    if call is not None:
        fields.setdefault("call", call)
    # TraceMe packs the stats into the event's name (``name#k=v,k=v#``)
    # and reads them back by its separators and quotes: keep those out
    # of a value, or it swallows the stats after it.
    for k, v in fields.items():
        if isinstance(v, str):
            fields[k] = _PACKED.sub("_", v)
    return _TRACE_ME(ANNOTATION_PREFIX + name, **fields)


class phase:
    """One named span of the host's work: a ``dslabs:<name>``
    annotation in the profiler's trace and, with a recorder current, a
    ``phase`` record.  ``set(**fields)`` adds what is only known at the
    close (a level's counters) to both."""

    __slots__ = ("name", "fields", "_note", "_recorder", "_t0", "_parent",
                 "_token")

    def __init__(self, name: str, **fields):
        self.name = name
        self.fields = fields

    def __enter__(self):
        self._note = annotate(self.name, **self.fields)
        self._note.__enter__()
        self._recorder = _CURRENT.get()
        self._t0 = time.time()
        parent = _PARENT.get()
        self._parent = parent.name if parent is not None else None
        self._token = _PARENT.set(self)
        return self

    def set(self, **fields) -> None:
        self.fields.update(fields)
        self._note.set_metadata(**fields)

    def __exit__(self, *exc):
        self._note.__exit__(*exc)
        _PARENT.reset(self._token)
        if self._recorder is not None:
            self._recorder.record_phase(
                self.name, self._t0, time.time() - self._t0,
                self._parent, self.fields)
        return False


def current_phase() -> Optional[phase]:
    """The innermost phase open in this context (None outside any): for
    code that learns a field of the span it runs under only once it is
    inside — the probe's fleet width under ``entry.probe``."""
    return _PARENT.get()


@contextlib.contextmanager
def call(name: str, **fields):
    """The root phase of one call of an entry point.  Mints the call's
    id (a per-process sequence number): every phase opened inside, and
    every dispatch of the search it builds, carries it."""
    token = _CALL.set(next(_CALL_SEQ))
    try:
        with phase(name, **fields) as ph:
            yield ph
    finally:
        _CALL.reset(token)


def mark(name: str, **fields) -> None:
    """An instant: a zero-length phase."""
    with phase(name, **fields):
        pass


# The compiled programs registered under each name, kept (weakly: a
# program lives as long as the search that compiled it) so that a
# TRACED run can say which stage an operation of the device trace
# belongs to.  Registering is a set insert; an executable's text is
# parsed when first asked for.
_PROGRAMS: Dict[str, "weakref.WeakSet"] = {}
_PROGRAM_SCOPES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")


def register_program(name: str, exe) -> None:
    """Keep the compiled executable ``exe`` under ``name`` (the name
    its module has in a trace, less ``jit_``)."""
    _PROGRAMS.setdefault(name, weakref.WeakSet()).add(exe)


def registered_programs(name: str) -> list:
    """What is live of the things registered as ``name`` (each gives its
    optimised module as ``as_text()``): the AOT executables of a
    program, one an engine that compiled it, or under
    ``KEPT_SUPERSTEP`` the lab entry's kept engines."""
    return list(_PROGRAMS.get(name, ()))


def scopes_of_hlo(text: str) -> Dict[str, Tuple[str, bool]]:
    """``{instruction: (scope, named)}`` of an optimised HLO module's
    text.  ``named``: the scope is the innermost ``dslabs.<scope>`` of
    the instruction's own ``op_name`` (a fusion without one takes its
    root's).  The compiler's own operations carry no ``op_name`` (on
    the chip: copies and reshapes it adds around the program's own,
    6 % of the superstep); each takes the scope of what it
    feeds — if all its scoped users agree — else of what feeds it,
    repeated until nothing changes, and is ``named`` False: a guess by
    neighbourhood, which a reader keeps apart from what the program
    said itself.  Instructions left with no scope are left out."""
    scopes: Dict[str, Tuple[str, bool]] = {}
    root_of: Dict[str, Optional[str]] = {}
    pending: List[tuple] = []
    operands: Dict[str, List[str]] = {}
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(2)
        scope = None
        op = _OP_NAME.search(line)
        if op is not None:
            for part in reversed(op.group(1).split("/")):
                if part.startswith(SCOPE_PREFIX):
                    scope = part[len(SCOPE_PREFIX):]
                    break
        if scope is not None:
            scopes[name] = (scope, True)
        else:
            called = _CALLS.search(line)
            if called is not None:
                pending.append((name, called.group(1)))
        # every %name after the "=": operands (and computations called,
        # which are no instruction's name)
        body = line[m.end():].split(", metadata=")[0]
        operands[name] = _OPERAND.findall(body)
        if m.group(1) and comp is not None:
            root_of[comp] = scope
    for name, called in pending:
        if root_of.get(called) is not None:
            scopes[name] = (root_of[called], True)
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(name)
    changed = True
    while changed:
        changed = False
        for neighbours in (users, operands):
            moved = True
            while moved:
                moved = False
                for name in operands:
                    if name in scopes:
                        continue
                    near = {scopes[n][0] for n in neighbours.get(name, ())
                            if n in scopes}
                    if len(near) == 1:
                        scopes[name] = (near.pop(), False)
                        moved = changed = True
    return scopes


def program_scopes(name: str) -> Optional[Dict[str, Tuple[str, bool]]]:
    """:func:`scopes_of_hlo` of THE program registered as ``name``.
    None if there is none, if the backend gives no text — or if two
    live programs of that name differ (a second engine in the process):
    which of them a trace ran cannot be told from here, and no
    attribution is better than the other engine's."""
    maps: List[dict] = []
    for exe in registered_programs(name):
        if exe not in _PROGRAM_SCOPES:
            try:
                text = exe.as_text()
            except Exception:  # noqa: BLE001 — a backend without text
                text = None
            _PROGRAM_SCOPES[exe] = scopes_of_hlo(text) if text else None
        if _PROGRAM_SCOPES[exe] not in maps:
            maps.append(_PROGRAM_SCOPES[exe])
    return maps[0] if len(maps) == 1 else None


# ------------------------------------------------------------- recorder

class Telemetry:
    """The per-run recorder.  ``attach(search)`` routes the search's
    ``_dispatch`` seam through :meth:`record_dispatch`; engines feed
    per-level fused-stats records via :meth:`on_level` and final
    outcomes via :meth:`on_outcome`; the supervisor/warden feed
    recovery events via :meth:`event`.  Everything lands in the ring
    buffer, the metrics registry, and (when configured) the JSONL
    flight-recorder file."""

    def __init__(self, flight_log: Optional[str] = None,
                 ring: Optional[int] = None,
                 engine_hint: Optional[str] = None,
                 status_path: Optional[str] = None,
                 trace_id: Optional[str] = None,
                 parent_span: Optional[str] = None):
        # Causal-trace context (ISSUE 13, tpu/tracing.py): inherited
        # from env when not given explicitly — the service sets
        # DSLABS_TRACE_ID/DSLABS_PARENT_SPAN on every warden launch and
        # the warden forwards them to its children, so a child's
        # recorder stamps the whole flight log into the submit's causal
        # tree without any new plumbing at the engines.
        from dslabs_tpu.tpu import tracing as tracing_mod

        env_trace, env_parent = tracing_mod.current_trace()
        self.trace_id = trace_id or env_trace
        self.parent_span = parent_span or env_parent
        self.span_id = tracing_mod.new_span_id()
        if ring is None:
            try:
                ring = int(os.environ.get("DSLABS_TELEMETRY_RING",
                                          "512"))
            except ValueError:
                ring = 512
        self.ring: deque = deque(maxlen=ring)
        self.registry = MetricsRegistry()
        self.levels: List[dict] = []
        self.events: deque = deque(maxlen=512)
        self.flight_log = flight_log
        self.engine_hint = engine_hint
        self._counts: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._t0 = time.time()
        self._fh = None
        self.flight_error: Optional[str] = None
        # Live-monitor state (STATUS.json): the last level/event/outcome
        # scalars, atomically rewritten so ``telemetry watch`` in any
        # other process can render this run.  Derived from the flight
        # log's location unless given explicitly; None = monitor off.
        self.status_path = (status_path
                            or default_status_path(flight_log))
        self._status_secs = _env_float("DSLABS_STATUS_SECS", 1.0)
        self._status_last = 0.0
        self._status: Dict[str, object] = {}
        self._prev_explored: Dict[str, int] = {}
        # Rate accounting (ISSUE 13 satellite): the cumulative rate is
        # explored / summed level wall over the WHOLE run; the sliding
        # window keeps the last DSLABS_RATE_WINDOW (explored-delta,
        # wall) pairs so a long run's STATUS shows current speed, not
        # the average over an hour of history.  Per engine — a
        # failover rung restarts its own series.
        try:
            self._rate_window_n = max(1, int(os.environ.get(
                "DSLABS_RATE_WINDOW", "8") or 8))
        except ValueError:
            self._rate_window_n = 8
        self._level_wall: Dict[str, float] = {}
        self._rate_window: Dict[str, deque] = {}
        self._open_dispatch: Optional[dict] = None
        self._warned_skew = False
        if flight_log:
            # Line-buffered append: each record hits the OS on its own
            # write, so a SIGKILL leaves complete lines (the reader
            # tolerates one torn tail line).  An unwritable location
            # (a read-only FS) degrades to
            # RAM-only recording, never takes the run down.
            try:
                d = os.path.dirname(os.path.abspath(flight_log))
                os.makedirs(d, exist_ok=True)
                self._fh = open(flight_log, "a", buffering=1)
            except OSError as e:
                self.flight_error = f"{type(e).__name__}: {e}"
                self.flight_log = None
                self.status_path = status_path  # only if explicit
        self._write({"t": "meta", "started": round(self._t0, 3),
                     "pid": os.getpid(), "hint": engine_hint,
                     "trace_id": self.trace_id,
                     "parent_span": self.parent_span,
                     "span_id": self.span_id})

    @classmethod
    def for_checkpoint(cls, checkpoint_path: str, **kw) -> "Telemetry":
        """The run-dir convention: flight log beside the dump
        (tpu/checkpoint.py ``default_flight_log``)."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        kw.setdefault("flight_log",
                      ckpt_mod.default_flight_log(checkpoint_path))
        return cls(**kw)

    # ----------------------------------------------------------- plumbing

    def _ts(self) -> float:
        return round(time.time() - self._t0, 4)

    def _write(self, rec: dict) -> None:
        if self._fh is None:
            return
        try:
            self._fh.write(json.dumps(rec) + "\n")
        except (OSError, ValueError):
            self._fh = None           # disk gone / closed: record in RAM only

    def _write_status(self, force: bool = False) -> None:
        """Atomically rewrite STATUS.json (tmp + ``os.replace``, so a
        reader — or a SIGKILL — never sees a torn file).  Called with
        ``self._lock`` held, from the feeds the run already makes:
        level boundaries, recovery events, outcomes, and (throttled by
        ``DSLABS_STATUS_SECS``) dispatch begin markers.  Pure host
        file IO — never a device dispatch or readback; failures
        disable the monitor, never the run."""
        if self.status_path is None:
            return
        now = time.time()
        if not force and now - self._status_last < self._status_secs:
            return
        self._status_last = now
        last_span = next((r for r in reversed(self.ring)
                          if r["t"] == "span"), None)
        st = {
            "t": "status", "pid": os.getpid(),
            "hint": self.engine_hint,
            "updated": round(now, 3),
            "uptime": round(now - self._t0, 1),
            "spans": sum(self._counts.values()),
            "levels": len(self.levels),
            "last_span": last_span,
            "in_flight": self._open_dispatch,
            "flight_log": self.flight_log,
            # Live mesh width (ISSUE 9): how many devices the current
            # rung is actually running on — fed by per-device level
            # lanes and mesh_shrunk/rung events, so `telemetry watch`
            # shows a degraded mesh the moment it shrinks.  Always
            # present (schema-pinned); None until the first feed.
            "mesh_width": None,
            # Live skew aggregate (ISSUE 18 satellite): running
            # imbalance_max/mean/cv over the per-level explored lanes
            # — the rebalance health of the CURRENT run, visible in
            # `telemetry watch` instead of only in the outcome.
            # Always present (schema-pinned); None until a sharded
            # level reports per-device lanes.
            "skew_agg": None,
            # Causal-trace identity (ISSUE 13): STATUS.json carries the
            # same trace context as the flight log, so a live monitor
            # frame is linkable to the submit that caused the run.
            # Always present (schema-pinned); None outside a trace.
            "trace_id": self.trace_id,
            "parent_span": self.parent_span,
            "span_id": self.span_id,
            **self._status,
        }
        tmp = self.status_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(json.dumps(st))
            os.replace(tmp, self.status_path)
        except OSError:
            self.status_path = None

    def close(self) -> None:
        with self._lock:
            self._write_status(force=True)
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None

    def attach(self, search):
        """Route ``search``'s dispatches through this recorder (the
        engine's ``_dispatch`` checks ``_telemetry``).  Returns the
        search for chaining."""
        search._telemetry = self
        return search

    # ----------------------------------------------------------- dispatch

    def record_dispatch(self, search, tag: str, hook, fn, *args):
        """THE span source: called by ``TensorSearch._dispatch`` for
        every hot-loop device dispatch.  Wraps the existing hook chain
        (supervisor boundary included) — never an extra device call,
        never a readback; everything recorded is a host scalar the
        dispatch already produced."""
        engine, _, site = tag.partition(".")
        with self._lock:
            idx = self._counts.get(engine, 0)
            self._counts[engine] = idx + 1
        depth = int(getattr(search, "_current_depth", 0) or 0)
        boundary = getattr(search, "_dispatch_boundary", None)
        r0 = boundary.retries if boundary is not None else 0
        scales = getattr(search, "_dispatch_deadline_scales", None) or {}
        scale = float(scales.get(site, 1.0))
        start = {"t": "dispatch", "ts": self._ts(), "tag": tag,
                 "i": idx, "depth": depth}
        if self.trace_id:
            start["trace"] = self.trace_id
        with self._lock:
            self._write(start)
            self._open_dispatch = start
            self._write_status()
        t0 = time.time()
        outcome = "ok"
        try:
            if hook is None:
                return fn(*args)
            return hook(tag, fn, *args)
        except BaseException as e:  # noqa: BLE001 — recorded, re-raised
            outcome = type(e).__name__
            raise
        finally:
            wall = time.time() - t0
            retries = ((boundary.retries - r0)
                       if boundary is not None else 0)
            span = {"t": "span", "ts": self._ts(), "tag": tag,
                    "engine": engine, "site": site, "i": idx,
                    "depth": depth, "wall": round(wall, 6),
                    "retries": retries, "scale": scale,
                    "outcome": outcome}
            if self.trace_id:
                span["trace"] = self.trace_id
            with self._lock:
                self.ring.append(span)
                self._write(span)
                self._open_dispatch = None
                self.registry.counter(f"dispatches.{engine}").inc()
                self.registry.histogram(f"dispatch_secs.{tag}").observe(
                    wall)
                if retries:
                    self.registry.counter("retries").inc(retries)
                if outcome != "ok":
                    self.registry.counter(
                        f"dispatch_errors.{outcome}").inc()

    @contextlib.contextmanager
    def span(self, tag: str, **fields):
        """Manual span for host-side work that is not a device dispatch
        (the profiling tools' timed blocks).  Same
        record shape, same registry feeds."""
        engine, _, site = tag.partition(".")
        with self._lock:
            idx = self._counts.get(engine, 0)
            self._counts[engine] = idx + 1
            start = {"t": "dispatch", "ts": self._ts(), "tag": tag,
                     "i": idx, "depth": 0}
            self._write(start)
            self._open_dispatch = start
            self._write_status()
        t0 = time.time()
        outcome = "ok"
        try:
            yield self
        except BaseException as e:  # noqa: BLE001 — recorded, re-raised
            outcome = type(e).__name__
            raise
        finally:
            wall = time.time() - t0
            span = {"t": "span", "ts": self._ts(), "tag": tag,
                    "engine": engine, "site": site, "i": idx,
                    "depth": 0, "wall": round(wall, 6), "retries": 0,
                    "scale": 1.0, "outcome": outcome, **fields}
            if self.trace_id:
                span["trace"] = self.trace_id
            with self._lock:
                self.ring.append(span)
                self._write(span)
                self._open_dispatch = None
                self.registry.counter(f"dispatches.{engine}").inc()
                self.registry.histogram(f"dispatch_secs.{tag}").observe(
                    wall)

    def record_phase(self, name: str, t0: float, wall: float,
                     parent: Optional[str], fields: dict) -> None:
        """One closed :class:`phase` (or mark, ``wall`` 0).  A record
        type of its own: span counts stay equal to dispatch counts."""
        rec = {"t": "phase", "name": name,
               "ts": round(t0 - self._t0, 4), "wall": round(wall, 6),
               "call": fields.get("call", _CALL.get()), "parent": parent,
               **fields}
        if self.trace_id:
            rec["trace"] = self.trace_id
        with self._lock:
            self._write(rec)
            if name == "compile.event":
                # One lab call makes a thousand of these (every jitted
                # jnp helper it re-traces): they would push everything
                # else out of the ring, so they go to the flight log
                # and to a histogram of their own seconds.
                self.registry.histogram(
                    f"compile_secs.{fields.get('kind')}").observe(
                    float(fields.get("secs", 0.0)))
                return
            self.ring.append(rec)
            self.registry.histogram(f"phase_secs.{name}").observe(wall)

    # -------------------------------------------------------- other feeds

    def event(self, kind: str, **fields) -> None:
        """Recovery/operational event (supervisor retry/failover/rung,
        warden heartbeat/child_death, spill evict/reinject, …)."""
        rec = {"t": "event", "ts": self._ts(), "kind": kind, **fields}
        if self.trace_id:
            rec.setdefault("trace", self.trace_id)
        with self._lock:
            self.events.append(rec)
            self._write(rec)
            self.registry.counter(f"events.{kind}").inc()
            # Live-monitor feeds: the current ladder rung / portfolio
            # lane and the spill tier's size ride STATUS.json so the
            # watch view shows where a run IS, not just how fast.
            if kind in ("rung", "capacity_retry"):
                self._status["rung"] = {k: v for k, v in rec.items()
                                        if k not in ("t", "ts")}
                if fields.get("width"):
                    self._status["mesh_width"] = fields["width"]
                self._write_status(force=True)
            elif kind in ("mesh_shrunk", "knobs_shrunk"):
                # Elastic-ladder degradations (ISSUE 9): the live
                # monitor shows the CURRENT width and the last
                # resilience action, not just that a rung changed.
                self._status["resilience"] = {
                    k: v for k, v in rec.items() if k not in ("t", "ts")}
                if fields.get("to_width"):
                    self._status["mesh_width"] = fields["to_width"]
                self._write_status(force=True)
            elif kind in ("lane", "lane_winner", "failover",
                          "child_death"):
                self._status["lane"] = {k: v for k, v in rec.items()
                                        if k not in ("t", "ts")}
                self._write_status(force=True)
            elif kind.startswith("spill"):
                self._status["spill"] = {k: v for k, v in rec.items()
                                         if k not in ("t", "ts")}
                self._write_status()
            else:
                self._write_status()

    def on_level(self, engine: str, record: dict) -> None:
        """One completed BFS level / wave / swarm round, described by
        the host scalars of the fused stats readback the engine already
        paid for (depth, wall, explored, unique, next_frontier, …)."""
        rec = {"t": "level", "ts": self._ts(), "engine": engine,
               **record}
        skew = rec.get("skew")
        with self._lock:
            self.levels.append(rec)
            self._write(rec)
            self.registry.counter(f"levels.{engine}").inc()
            self.registry.gauge(f"depth.{engine}").set(
                record.get("depth", 0))
            self.registry.gauge(f"explored.{engine}").set(
                record.get("explored", 0))
            self.registry.gauge(f"unique.{engine}").set(
                record.get("unique", 0))
            if record.get("wall") is not None:
                self.registry.histogram(f"level_secs.{engine}").observe(
                    float(record["wall"]))
            if record.get("load_factor") is not None:
                self.registry.gauge(f"load_factor.{engine}").set(
                    record["load_factor"])
            # Mesh-scope skew feeds (per-device lanes already in the
            # record — the engines read them off the SAME fused stats
            # vector, zero added transfers).
            if skew:
                work = skew.get("explored") or next(iter(skew.values()))
                self.registry.gauge(f"skew.{engine}").set(
                    work.get("imbalance", 1.0))
                self.registry.gauge(f"skew_cv.{engine}").set(
                    work.get("cv", 0.0))
                self.registry.histogram(
                    f"skew_imbalance.{engine}").observe(
                    float(work.get("imbalance", 1.0)))
                # Running skew aggregate (ISSUE 18 satellite): the live
                # monitor's one-glance answer to "is this run
                # imbalanced" — worst and mean per-level imbalance over
                # the explored lanes, plus the worst cv, schema-pinned
                # as STATUS.json's ``skew_agg`` block.
                agg = self._status.get("skew_agg") or {
                    "imbalance_max": 1.0, "imbalance_mean": 0.0,
                    "cv_max": 0.0, "levels": 0}
                n = agg["levels"]
                imb = float(work.get("imbalance", 1.0))
                agg["imbalance_max"] = max(agg["imbalance_max"], imb)
                agg["imbalance_mean"] = round(
                    (agg["imbalance_mean"] * n + imb) / (n + 1), 3)
                agg["cv_max"] = max(
                    agg["cv_max"], round(float(work.get("cv", 0.0)), 3))
                agg["imbalance_max"] = round(agg["imbalance_max"], 3)
                agg["levels"] = n + 1
                self._status["skew_agg"] = agg
            # Live monitor: cumulative rate over the whole run PLUS a
            # sliding-window rate over the last N level records (the
            # satellite fix: one number for billing-grade averages,
            # one for "how fast is it going RIGHT NOW").
            explored = int(record.get("explored", 0) or 0)
            delta = explored - self._prev_explored.get(engine, 0)
            self._prev_explored[engine] = explored
            wall = float(record.get("wall", 0.0) or 0.0)
            wall_total = self._level_wall.get(engine, 0.0) + wall
            self._level_wall[engine] = wall_total
            win = self._rate_window.get(engine)
            if win is None:
                win = self._rate_window[engine] = deque(
                    maxlen=self._rate_window_n)
            win.append((delta, wall))
            win_d = sum(d for d, _ in win)
            win_w = sum(w for _, w in win)
            pd = record.get("per_device") or {}
            if pd.get("explored"):
                # The per-device lanes ARE the live mesh width — a
                # degraded rung's level records carry fewer lanes.
                self._status["mesh_width"] = len(pd["explored"])
            if record.get("lanes") is not None:
                # Batched-child monitor block (ISSUE 14, tpu/lanes.py):
                # per-lane job/depth/explored, schema-pinned so
                # `telemetry watch` renders every resident lane of one
                # lane-batch process.
                self._status["lanes"] = record["lanes"]
            if record.get("spill") is not None:
                # Async-drain wall split (ISSUE 15c): per-level host
                # drain seconds vs blocked seconds — the live monitor
                # shows how much of the spill detour is hidden behind
                # device compute.
                self._status["drain"] = record["spill"]
            if record.get("faults") is not None:
                # Fault-scenario block (ISSUE 19): cumulative fault
                # events by family, schema-pinned so `telemetry watch`
                # shows how much of the run is fault interleavings.
                self._status["faults"] = record["faults"]
                for k, v in record["faults"].items():
                    self.registry.gauge(f"faults.{k}").set(int(v))
            self._status.update({
                "engine": engine,
                "depth": record.get("depth", 0),
                "explored": explored,
                "unique": record.get("unique", 0),
                "rate_per_min": round(explored / wall_total * 60.0, 1)
                if wall_total > 0 else None,
                "rate_per_min_window": round(win_d / win_w * 60.0, 1)
                if win_w > 0 else None,
                "level_wall": wall,
                "load_factor": record.get("load_factor"),
                "skew": skew,
                "per_device": record.get("per_device"),
            })
            self._write_status(force=True)
        if skew:
            work = skew.get("explored") or next(iter(skew.values()))
            warn_at = _env_float("DSLABS_SKEW_WARN", 3.0)
            if (not self._warned_skew
                    and len(record.get("per_device", {})
                            .get("explored", ())) > 1
                    and work.get("mean", 0.0) >= 64
                    and work.get("imbalance", 1.0) >= warn_at):
                self._warned_skew = True
                import warnings

                warnings.warn(
                    f"shard skew: slowest-device imbalance "
                    f"{work['imbalance']:.2f}x (cv {work['cv']:.2f}) "
                    f"at depth {record.get('depth')} on engine "
                    f"{engine} (>= DSLABS_SKEW_WARN={warn_at}) — the "
                    "mesh is load-imbalanced; see the per-device "
                    "heatmap in `telemetry report` and "
                    "docs/observability.md",
                    RuntimeWarning, stacklevel=3)

    # Outcome scalars worth a gauge + the outcome record (all plain
    # host ints the verdict already carries).
    _OUTCOME_FIELDS = (
        "states_explored", "unique_states", "depth", "retries",
        "failovers", "resumed_from_depth", "visited_overflow",
        "dropped", "spilled_keys", "host_tier_hits",
        "respilled_frontier", "walker_restarts", "swarm_overflow",
        "child_restarts", "killed_dispatches", "abandoned_threads",
        "mesh_width", "mesh_shrinks", "knob_retries",
        "fault_events", "partition_events", "crash_events",
        "drop_events", "dup_events")

    def on_outcome(self, out, engine: Optional[str] = None) -> None:
        """Ingest a SearchOutcome's accounting: one ``outcome`` record
        plus gauges for every counter (spill, overflow, recovery) and
        the capacity-round-2 block (bytes_per_state / pack_ratio /
        symmetry_perms — ISSUE 15, schema-pinned in STATUS.json)."""
        eng = engine or getattr(out, "engine", None) or "search"
        rec = {"t": "outcome", "ts": self._ts(), "engine": eng,
               "end_condition": out.end_condition,
               "elapsed_secs": round(float(out.elapsed_secs), 4),
               "compile_secs": round(float(out.compile_secs), 4)}
        trace = getattr(out, "trace_id", None) or self.trace_id
        if trace:
            rec["trace"] = trace
        with self._lock:
            for f in self._OUTCOME_FIELDS:
                v = int(getattr(out, f, 0) or 0)
                rec[f] = v
                if v:
                    self.registry.gauge(f"outcome.{f}").set(v)
            self.registry.gauge("outcome.compile_secs").set(
                rec["compile_secs"])
            bps = getattr(out, "bytes_per_state", None)
            if bps:
                cap_block = {
                    "bytes_per_state": int(bps),
                    "bytes_per_state_unpacked": int(
                        getattr(out, "bytes_per_state_unpacked", 0)
                        or 0),
                    "pack_ratio": float(
                        getattr(out, "pack_ratio", 1.0) or 1.0),
                    "symmetry_perms": int(
                        getattr(out, "symmetry_perms", 0) or 0)}
                rec["capacity"] = cap_block
                self._status["capacity"] = cap_block
                self.registry.gauge("capacity.bytes_per_state").set(
                    cap_block["bytes_per_state"])
                self.registry.gauge("capacity.pack_ratio").set(
                    cap_block["pack_ratio"])
                if cap_block["symmetry_perms"]:
                    self.registry.gauge(
                        "capacity.symmetry_perms").set(
                        cap_block["symmetry_perms"])
            if int(getattr(out, "fault_events", 0) or 0):
                # Fault-scenario block (ISSUE 19): same schema as the
                # engines' per-level ``faults`` record.
                flt_block = {
                    k: int(getattr(out, k, 0) or 0)
                    for k in ("partition_events", "crash_events",
                              "drop_events", "dup_events",
                              "fault_events")}
                rec["faults"] = flt_block
                self._status["faults"] = flt_block
                for k, v in flt_block.items():
                    self.registry.gauge(f"faults.{k}").set(v)
            self._write(rec)
            self.events.append(rec)
            self._status["end_condition"] = out.end_condition
            self._write_status(force=True)

    # ------------------------------------------------------------ summary

    def summary(self) -> dict:
        """The compact JSON block a caller attaches to its output:
        span totals, per-site latency snapshots, event counts, and the
        flight-log path for the deep dive."""
        with self._lock:
            sites = {name[len("dispatch_secs."):]: h.snapshot()
                     for name, h in
                     self.registry.histograms.items()
                     if name.startswith("dispatch_secs.")}
            events = {name[len("events."):]: c.value
                      for name, c in self.registry.counters.items()
                      if name.startswith("events.")}
            out = {
                "spans": sum(self._counts.values()),
                "dispatches": dict(self._counts),
                "sites": sites,
                "events": events,
                "levels": len(self.levels),
                "flight_log": self.flight_log,
            }
            if self.status_path:
                out["status"] = self.status_path
            if self.flight_error:
                out["flight_error"] = self.flight_error
            sk = self._status.get("skew")
            if sk:
                out["skew"] = sk
            return out


# ------------------------------------------------------- flight reading

def read_flight(path: str) -> List[dict]:
    """Parse a flight-recorder JSONL file, tolerating ONE torn tail
    line (the signature of a SIGKILL mid-write).  A torn line anywhere
    else raises — the file is corrupt, not merely truncated."""
    records: List[dict] = []
    with open(path) as f:
        lines = f.read().splitlines()
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            if i == len(lines) - 1:
                break                     # torn tail: expected crash shape
            raise
    return records


# --------------------------------------------------------------- report

def _resolve_flight(path: str) -> str:
    """Accept a flight log OR a run directory (the checkpoint's dir):
    a directory resolves to its ``flight.jsonl`` or the newest
    ``*.flight.jsonl`` inside it."""
    if os.path.isdir(path):
        cand = os.path.join(path, "flight.jsonl")
        if os.path.exists(cand):
            return cand
        logs = sorted(
            (os.path.join(path, f) for f in os.listdir(path)
             if f.endswith(".flight.jsonl") or f.endswith(".jsonl")),
            key=lambda p: os.path.getmtime(p))
        if logs:
            return logs[-1]
        raise FileNotFoundError(f"no flight log (*.jsonl) in {path}")
    return path


def build_report(records: List[dict]) -> dict:
    """Aggregate a flight log's records into the run-report structure
    (everything the renderer needs, derived from the log alone)."""
    spans = [r for r in records if r.get("t") == "span"]
    levels = [r for r in records if r.get("t") == "level"]
    events = [r for r in records if r.get("t") == "event"]
    outcomes = [r for r in records if r.get("t") == "outcome"]
    meta = next((r for r in records if r.get("t") == "meta"), None)

    sites: Dict[str, Histogram] = {}
    first_wall: Dict[str, float] = {}
    for s in spans:
        h = sites.setdefault(s["tag"], Histogram())
        h.observe(s.get("wall", 0.0))
        first_wall.setdefault(s["tag"], float(s.get("wall", 0.0)))
    total_wall = sum(float(s.get("wall", 0.0)) for s in spans)
    compile_wall = sum(first_wall.values())

    # Per-level throughput series: explored is cumulative, so the rate
    # uses the delta against the previous record of the same engine.
    series: Dict[str, List[dict]] = {}
    prev: Dict[str, int] = {}
    for lv in levels:
        eng = lv.get("engine", "?")
        d = int(lv.get("explored", 0)) - prev.get(eng, 0)
        prev[eng] = int(lv.get("explored", 0))
        wall = float(lv.get("wall", 0.0)) or 1e-9
        series.setdefault(eng, []).append(dict(lv, delta_explored=d,
                                               rate=round(d / wall, 1)))

    # Recovery timeline: events plus retry-absorbing spans, time-sorted.
    timeline = sorted(
        (events
         + [s for s in spans if s.get("retries")]
         + [s for s in spans if s.get("outcome") not in (None, "ok")]),
        key=lambda r: r.get("ts", 0.0))

    # In-flight dispatch: a begin marker with no matching span means
    # the process died (or is wedged) inside that device call.
    open_dispatch = None
    done = {(s["tag"], s["i"]) for s in spans}
    for r in records:
        if r.get("t") == "dispatch" and (r["tag"], r["i"]) not in done:
            open_dispatch = r
    counts = {}
    for o in outcomes:
        for k in ("spilled_keys", "host_tier_hits", "respilled_frontier",
                  "visited_overflow", "dropped", "retries", "failovers",
                  "walker_restarts", "swarm_overflow", "mesh_shrinks",
                  "knob_retries"):
            if o.get(k):
                counts[k] = counts.get(k, 0) + int(o[k])
    # Capacity round 2 (ISSUE 15): the last outcome's packing /
    # symmetry block, plus the summed per-level drain-overlap walls.
    capacity = next((o["capacity"] for o in reversed(outcomes)
                     if o.get("capacity")), None)
    # Fault scenarios (ISSUE 19): the last outcome's fault-family block.
    faults = next((o["faults"] for o in reversed(outcomes)
                   if o.get("faults")), None)
    drain = {}
    for lv in levels:
        sp = lv.get("spill")
        if isinstance(sp, dict):
            for k, v in sp.items():
                try:
                    drain[k] = round(drain.get(k, 0.0) + float(v), 4)
                except (TypeError, ValueError):
                    pass
    # Program spans (phase records), by the call they belong to: per
    # call and name, how many and how many seconds; marks (wall 0) are
    # counted, not timed.  Calls in the order they were made; phases
    # outside any call under "-".
    calls: Dict[str, Dict[str, dict]] = {}
    for r in records:
        if r.get("t") != "phase":
            continue
        key = "-" if r.get("call") is None else str(r["call"])
        row = calls.setdefault(key, {}).setdefault(
            r["name"], {"n": 0, "secs": 0.0, "parent": r.get("parent")})
        row["n"] += 1
        row["secs"] = round(row["secs"] + float(r.get("wall", 0.0)), 6)
    return {"meta": meta, "n_spans": len(spans),
            "phases": calls,
            "sites": {t: h.snapshot() for t, h in sites.items()},
            "series": series, "timeline": timeline,
            "outcomes": outcomes, "counts": counts,
            "capacity": capacity, "faults": faults,
            "drain": drain or None,
            "total_wall": round(total_wall, 3),
            "compile_wall": round(compile_wall, 3),
            "in_flight": open_dispatch}


def render_report(report: dict, source: str = "") -> str:
    """The human-readable run report (pinned sections: the golden test
    asserts these headers — keep them stable)."""
    out: List[str] = []
    out.append(f"== dslabs run report: {source or 'flight log'} ==")
    meta = report.get("meta") or {}
    if meta:
        out.append(f"meta: pid {meta.get('pid')} "
                   f"hint={meta.get('hint')}")
    out.append(
        f"spans: {report['n_spans']} dispatches across "
        f"{len(report['sites'])} sites; device wall "
        f"{report['total_wall']:.3f}s "
        f"(first-dispatch/compile {report['compile_wall']:.3f}s, "
        f"steady {report['total_wall'] - report['compile_wall']:.3f}s)")

    out.append("")
    out.append("-- dispatch latency by site --")
    out.append(f"{'site':34s} {'n':>6s} {'p50ms':>9s} {'p90ms':>9s} "
               f"{'p99ms':>9s} {'maxms':>9s} {'total_s':>9s}")
    for tag in sorted(report["sites"]):
        s = report["sites"][tag]
        out.append(f"{tag:34s} {s['count']:6d} {s['p50']*1e3:9.2f} "
                   f"{s['p90']*1e3:9.2f} {s['p99']*1e3:9.2f} "
                   f"{s['max']*1e3:9.2f} {s['total']:9.3f}")

    out.append("")
    out.append("-- per-level throughput --")
    if not report["series"]:
        out.append("(no level records)")
    for eng in sorted(report["series"]):
        out.append(f"[engine {eng}]")
        out.append(f"{'depth':>6s} {'wall_s':>8s} {'explored':>10s} "
                   f"{'unique':>10s} {'next':>10s} {'states/s':>10s}")
        for lv in report["series"][eng]:
            out.append(
                f"{lv.get('depth', 0):6d} {lv.get('wall', 0.0):8.3f} "
                f"{lv.get('explored', 0):10d} "
                f"{lv.get('unique', 0):10d} "
                f"{lv.get('next_frontier', 0):10d} "
                f"{lv.get('rate', 0.0):10.1f}")

    # Per-device × per-level heatmap (mesh scope): only rendered when
    # the level records carry per_device lanes (sharded/swarm engines).
    # Rows start with 'd' — the throughput rows above are the only
    # digit-leading rows, which the golden test counts.
    heat_engines = [e for e in sorted(report["series"])
                    if any(lv.get("per_device")
                           for lv in report["series"][e])]
    if heat_engines:
        ramp = " .:-=+*#%@"
        out.append("")
        out.append("-- per-device skew (explored share per level) --")
        for eng in heat_engines:
            lvs = [lv for lv in report["series"][eng]
                   if lv.get("per_device")]
            n_dev = max(len(lv["per_device"].get("explored", ()))
                        for lv in lvs)
            out.append(f"[engine {eng}] devices 0..{n_dev - 1}; "
                       "each cell = device share of the level's "
                       "expanded states")
            for lv in lvs:
                lane = lv["per_device"].get("explored", [])
                mx = max(max(lane, default=0), 1)
                cells = "".join(
                    ramp[min(len(ramp) - 1,
                             int(round(v / mx * (len(ramp) - 1))))]
                    for v in lane)
                sk = (lv.get("skew") or {}).get("explored", {})
                out.append(
                    f"d{lv.get('depth', 0):4d} |{cells}| "
                    f"imb={sk.get('imbalance', 1.0):5.2f} "
                    f"cv={sk.get('cv', 0.0):5.2f}")
            hbms = [lv for lv in lvs if lv.get("hbm_peak")]
            if hbms:
                peak = hbms[-1]["hbm_peak"]
                out.append("hbm peak bytes/device: "
                           + " ".join(f"{b:.2e}" for b in peak))

    out.append("")
    out.append("-- recovery timeline --")
    if not report["timeline"]:
        out.append("(no retries, failovers, or events)")
    for r in report["timeline"][-40:]:
        if r.get("t") == "event":
            extra = {k: v for k, v in r.items()
                     if k not in ("t", "ts", "kind")}
            out.append(f"+{r.get('ts', 0.0):8.2f}s event "
                       f"{r['kind']} {extra}")
        else:
            out.append(f"+{r.get('ts', 0.0):8.2f}s span {r['tag']} "
                       f"i={r['i']} retries={r.get('retries', 0)} "
                       f"outcome={r.get('outcome')}")

    out.append("")
    out.append("-- spill / overflow / recovery counts --")
    if report["counts"]:
        out.append(" ".join(f"{k}={v}"
                            for k, v in sorted(report["counts"].items())))
    else:
        out.append("(all zero)")
    if report.get("capacity"):
        out.append("capacity: " + " ".join(
            f"{k}={v}" for k, v in sorted(report["capacity"].items())))
    if report.get("faults"):
        out.append("faults: " + " ".join(
            f"{k}={v}" for k, v in sorted(report["faults"].items())))
    if report.get("drain"):
        out.append("drain overlap: " + " ".join(
            f"{k}={v}" for k, v in sorted(report["drain"].items())))
    for o in report["outcomes"]:
        out.append(
            f"outcome: {o.get('end_condition')} engine="
            f"{o.get('engine')} depth={o.get('depth')} "
            f"unique={o.get('unique_states')} "
            f"explored={o.get('states_explored')} "
            f"elapsed={o.get('elapsed_secs')}s "
            f"compile={o.get('compile_secs')}s")

    if report.get("phases"):
        out.append("")
        out.append("-- phases by call --")
        out.append(f"{'call':>6s} {'phase':30s} {'under':22s} {'n':>5s} "
                   f"{'secs':>9s}")
        for key, rows in report["phases"].items():
            for name, row in rows.items():
                out.append(f"{key:>6s} {name:30s} "
                           f"{row['parent'] or '-':22s} {row['n']:5d} "
                           f"{row['secs']:9.3f}")

    if report["in_flight"] is not None:
        r = report["in_flight"]
        out.append("")
        out.append(f"!! in-flight at EOF: {r['tag']} i={r['i']} "
                   f"depth={r.get('depth')} — the run died or wedged "
                   "inside this dispatch")
    return "\n".join(out)


def render_sites(summary: dict) -> str:
    """The per-site latency table of a :meth:`Telemetry.summary` —
    the shared renderer the profiling tools (tools/profile_*.py) print
    instead of hand-rolled timing scaffolds.  Columns match the report
    CLI's dispatch-latency section."""
    out = [f"{'site':40s} {'n':>6s} {'p50ms':>9s} {'p90ms':>9s} "
           f"{'maxms':>9s} {'total_s':>9s}"]
    for tag in sorted(summary.get("sites", {})):
        s = summary["sites"][tag]
        out.append(f"{tag:40s} {s['count']:6d} {s['p50']*1e3:9.2f} "
                   f"{s['p90']*1e3:9.2f} {s['max']*1e3:9.2f} "
                   f"{s['total']:9.3f}")
    return "\n".join(out)


# ----------------------------------------------------- live run monitor

def _resolve_status(path: str) -> Optional[str]:
    """STATUS.json for a run dir (or a direct path): ``STATUS.json``
    first (the checkpoint run-dir convention), else the newest
    ``*.STATUS.json`` (a named phase log's, ``default_status_path``)."""
    if os.path.isdir(path):
        cand = os.path.join(path, "STATUS.json")
        if os.path.exists(cand):
            return cand
        stats = sorted(
            (os.path.join(path, f) for f in os.listdir(path)
             if f.endswith("STATUS.json")),
            key=lambda p: os.path.getmtime(p))
        return stats[-1] if stats else None
    return path if path.endswith(".json") else None


def load_status(path: Optional[str]) -> Optional[dict]:
    """Read a STATUS.json; never raises (the writer's atomic replace
    means a well-formed file or nothing, but the run dir may predate
    the monitor entirely)."""
    if not path:
        return None
    try:
        with open(path) as f:
            return json.loads(f.read())
    except (OSError, ValueError):
        return None


def watch_frame(path: str, now: Optional[float] = None) -> dict:
    """One machine-readable live-monitor frame (``watch --json``, the
    satellite's scripting hook): the STATUS snapshot, the staleness
    verdict (the same >15 s rule the human view flags), and the
    in-flight dispatch derived from the flight tail's begin markers.
    Torn/absent artifacts are never fatal — every field degrades to
    None."""
    from dslabs_tpu.tpu import tracing as tracing_mod

    now = time.time() if now is None else now
    st = load_status(_resolve_status(path))
    age = (now - float(st.get("updated", now))) if st else None
    open_d = None
    try:
        recs, _ = tracing_mod.read_flight_lax(_resolve_flight(path))
    except (OSError, ValueError, FileNotFoundError):
        recs = []
    segs = tracing_mod.segment_flight(recs)
    if segs:
        # Only the LAST segment's open dispatch is live state — an
        # earlier child's kill point belongs to the trace assembler.
        open_d = segs[-1]["in_flight"]
    return {
        "t": "watch", "source": path,
        "status": st,
        "age_secs": round(age, 1) if age is not None else None,
        "stale": bool(st) and age is not None and age > 15,
        "finished": bool(st and st.get("end_condition")),
        "in_flight": open_d,
        "trace_id": (st or {}).get("trace_id"),
    }


def render_watch(path: str, now: Optional[float] = None) -> str:
    """One frame of the live monitor, from the run dir ALONE: the
    atomic STATUS.json (depth / rate / skew / spill / rung) plus the
    flight log's tail (last span; the in-flight dispatch of a torn
    tail — a SIGKILLed run stays attributable)."""
    now = time.time() if now is None else now
    out: List[str] = [f"== dslabs live monitor: {path} =="]
    st = load_status(_resolve_status(path))
    if st is None:
        out.append("(no STATUS.json yet — run predates the monitor, "
                   "or died before its first level)")
    else:
        age = now - float(st.get("updated", now))
        stale = " !! STALE (run dead or wedged?)" if age > 15 else ""
        out.append(f"status: pid {st.get('pid')} "
                   f"hint={st.get('hint')} "
                   f"updated {age:.1f}s ago{stale}")
        rate = st.get("rate_per_min")
        win = st.get("rate_per_min_window")
        out.append(
            f"engine {st.get('engine', '?')}  "
            f"depth {st.get('depth', 0)}  "
            f"unique {st.get('unique', 0)}  "
            f"explored {st.get('explored', 0)}  "
            f"rate {rate if rate is not None else '?'} states/min "
            f"(window {win if win is not None else '?'})")
        if st.get("trace_id"):
            out.append(f"trace: {st['trace_id']} "
                       f"(parent span {st.get('parent_span') or '-'})")
        if st.get("mesh_width"):
            out.append(f"mesh width: {st['mesh_width']} device(s)")
        if st.get("resilience"):
            out.append("resilience: " + " ".join(
                f"{k}={v}" for k, v in sorted(st["resilience"].items())))
        sk = st.get("skew") or {}
        if sk:
            parts = [f"{lane} imb={m.get('imbalance', 1.0):.2f} "
                     f"cv={m.get('cv', 0.0):.2f}"
                     for lane, m in sorted(sk.items())]
            out.append("skew: " + " | ".join(parts))
        if st.get("skew_agg"):
            out.append("skew agg: " + " ".join(
                f"{k}={v}" for k, v in sorted(st["skew_agg"].items())))
        pd = st.get("per_device") or {}
        if pd.get("frontier") is not None:
            out.append("per-device frontier: "
                       + " ".join(str(v) for v in pd["frontier"]))
        if st.get("load_factor") is not None:
            out.append(f"visited load factor: {st['load_factor']}")
        if st.get("spill"):
            out.append("spill: " + " ".join(
                f"{k}={v}" for k, v in sorted(st["spill"].items())))
        if st.get("drain"):
            out.append("drain: " + " ".join(
                f"{k}={v}" for k, v in sorted(st["drain"].items())))
        if st.get("capacity"):
            out.append("capacity: " + " ".join(
                f"{k}={v}" for k, v in sorted(st["capacity"].items())))
        if st.get("faults"):
            out.append("faults: " + " ".join(
                f"{k}={v}" for k, v in sorted(st["faults"].items())))
        if st.get("rung"):
            out.append("rung: " + " ".join(
                f"{k}={v}" for k, v in sorted(st["rung"].items())))
        if st.get("lane"):
            out.append("lane: " + " ".join(
                f"{k}={v}" for k, v in sorted(st["lane"].items())))
        if st.get("lanes"):
            # A lane-batch child (tpu/lanes.py): one line per resident
            # lane — the batched equivalent of the per-device lanes.
            for lrec in st["lanes"]:
                out.append(
                    f"job lane {lrec.get('lane')}: "
                    f"{lrec.get('job_id')} depth {lrec.get('depth')} "
                    f"unique {lrec.get('unique')} "
                    f"explored {lrec.get('explored')} "
                    f"frontier {lrec.get('frontier')}")
        ls = st.get("last_span")
        if ls:
            out.append(f"last span: {ls.get('tag')} i={ls.get('i')} "
                       f"depth={ls.get('depth')} "
                       f"{ls.get('outcome')} {ls.get('wall', 0.0)}s")
        if st.get("end_condition"):
            out.append(f"end: {st['end_condition']}")
    # The flight tail is the authority on an unclosed dispatch: the
    # STATUS snapshot may predate the wedge, but the begin marker
    # cannot (it is written BEFORE the device call).
    try:
        recs = read_flight(_resolve_flight(path))
    except (OSError, ValueError):
        recs = []
    if recs:
        done = {(s["tag"], s["i"]) for s in recs
                if s.get("t") == "span"}
        open_d = None
        for r in recs:
            if (r.get("t") == "dispatch"
                    and (r["tag"], r["i"]) not in done):
                open_d = r
        if open_d is not None:
            out.append(f"!! in-flight: {open_d['tag']} "
                       f"i={open_d['i']} depth={open_d.get('depth')} "
                       "— the run is inside (or died inside) this "
                       "dispatch")
    return "\n".join(out)


# ------------------------------------------------------------------ CLI

_USAGE = """usage: python -m dslabs_tpu.tpu.telemetry <command> ...

  report  <run-dir-or-flight-log> [--json]   render a run report
  watch   <run-dir> [--interval S] [--once] [--json]
                                             live monitor of any run
  trace   <run-dir|server-dir> [--job ID] [--json] [--perfetto F]
                                             assemble the causal trace
"""


def main(argv: Optional[List[str]] = None) -> int:
    import sys

    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) < 2 or argv[0] not in ("report", "watch", "trace"):
        print(_USAGE, file=sys.stderr)
        return 2
    cmd, path = argv[0], argv[1]
    flags = argv[2:]

    if cmd == "trace":
        # The causal-trace assembler (ISSUE 13) lives in tpu/tracing.py
        # — journal + SERVER_STATUS + per-job flight logs, from disk
        # alone, rendered or exported as Perfetto trace-event JSON.
        from dslabs_tpu.tpu import tracing as tracing_mod

        return tracing_mod.main([path] + flags)

    if cmd == "report":
        flight = _resolve_flight(path)
        report = build_report(read_flight(flight))
        if "--json" in flags:
            # The machine-readable schema (pinned by test): the same
            # sections the renderer draws, one structure shared with
            # grading scripts.
            print(json.dumps(dict(report, source=flight)))
        else:
            print(render_report(report, source=flight))
        return 0

    # watch: redraw until interrupted (--once = one frame, for smoke
    # tests and scripts; --json = one machine-readable frame with the
    # staleness verdict, the satellite's scripting hook).  Reads only
    # the run dir — the run itself can be any process, a warden child
    # included.
    if "--json" in flags:
        print(json.dumps(watch_frame(path)))
        return 0
    interval = 2.0
    if "--interval" in flags:
        interval = float(flags[flags.index("--interval") + 1])
    once = "--once" in flags
    try:
        while True:
            frame = render_watch(path)
            if not once:
                print("\x1b[2J\x1b[H", end="")
            print(frame, flush=True)
            if once:
                return 0
            time.sleep(interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    raise SystemExit(main())
