"""Process-isolated dispatch warden: hang-proof failover supervision.

The in-process supervisor (tpu/supervisor.py) retries, watchdogs, and
fails over — but a truly wedged XLA runtime cannot be interrupted from
Python: the watchdog can only ABANDON the dispatch by leaking a blocked
daemon thread, and a hard runtime wedge takes the whole process down
with it (raw tracebacks, rc=124 with no JSON, a pre-flight that hangs
for its whole budget).  This
module is the layer that makes every in-process resilience feature hold
against those failures, the same way elastic-training supervisors
restart a worker stuck in a hung collective:

* **Spawned child per rung.**  :class:`Warden` runs the
  accelerator-facing search loop in a child process
  (``python -m dslabs_tpu.tpu.warden``), supervised over a pipe.  The
  child rebuilds the protocol from a ``"module:callable"`` factory spec
  (live protocol objects hold closures that cannot cross a spawn
  boundary) and runs a single-rung :class:`SearchSupervisor` — the
  in-child retry/backoff/fault machinery is unchanged.
* **Heartbeats from the dispatch seam.**  The child installs a dispatch
  observer at the existing ``TensorSearch._dispatch`` boundary and
  emits one JSON line per dispatch attempt: tag, dispatch index, live
  BFS depth, and the last DURABLE checkpoint depth
  (``checkpoint.peek_depth``).  Every heartbeat announces its own
  silence budget (``grace``): compile-inclusive for the first dispatch
  at a tag, deadline-scale-stretched for fused supersteps, idle-sized
  between dispatches.
* **SIGKILL, not abandonment.**  A child silent past its announced
  grace (+ slack) is SIGKILLed and REAPED — no leaked thread, no
  zombie, no runtime state left racing device work.  The death is
  classified from the exit code + last heartbeat
  (:func:`classify_death`): ``wedge`` (warden kill after silence),
  ``oom`` (unprompted SIGKILL — the kernel OOM killer / an external
  kill), ``crash`` (other signal or abrupt exit), ``failed`` (the child
  reported a classified in-child failure and exited cleanly).
* **Failover + durable resume.**  After a death the warden spawns the
  next rung's child (``sharded -> device -> host``), which resumes from
  the unified PR-2 checkpoint (tpu/checkpoint.py) — now torn-write-safe
  via content checksums and ``.prev`` rotation, so even a SIGKILL that
  lands mid-dump costs one checkpoint interval, never the run.  The
  LAST rung's child is forced onto the CPU runtime
  (``JAX_PLATFORMS=cpu`` in the child env) so a verdict lands even
  when the accelerator runtime itself is the thing that is broken —
  and the outcome names the platform it was computed on
  (``SearchOutcome.platform`` / ``device_kind``), so a last-rung CPU
  verdict is never mistaken for a chip verdict.
* **Identical verdict semantics.**  ``SearchSupervisor(
  process_isolation=True)`` rides this class; outcomes keep the full
  recovery accounting (``retries`` / ``failovers`` /
  ``resumed_from_depth``) plus ``child_restarts`` and
  ``killed_dispatches``.

:class:`LineWatch` is the shared child-stream monitor: the warden's
children and the lane runner's (tpu/lanes.py) ride it, so a wedged
child is killed at heartbeat silence (seconds) instead of at its full
budget (minutes).

Exercised by the deterministic kill/hang/crash matrix in
tests/test_warden.py (``make fault-smoke``) — injected via the
``fault`` spec field, on CPU, no broken hardware required.
"""

from __future__ import annotations

import dataclasses
import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from dslabs_tpu.tpu import checkpoint as ckpt_mod
from dslabs_tpu.tpu.supervisor import (CHILD_RC_FAILED, EngineFailure,
                                       RetryPolicy, SupervisorExhausted,
                                       classify_child_death)

__all__ = ["Warden", "LineWatch", "classify_death", "outcome_to_dict",
           "outcome_from_dict", "CHILD_RC_FAILED"]

# The repo root (…/dslabs_tpu/tpu/warden.py -> three levels up): child
# processes get it on PYTHONPATH so ``-m dslabs_tpu.tpu.warden``
# resolves regardless of the parent's cwd.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def classify_death(exitcode: Optional[int],
                   killed_by_warden: bool,
                   stderr_markers=()) -> str:
    """The exit-code taxonomy (pinned by tests/test_warden.py and the
    table-driven test in tests/test_service.py) — a thin alias of the
    SHARED :func:`~dslabs_tpu.tpu.supervisor.classify_child_death`, so
    the warden's failover, the elastic ladder's ``classify_oom``, and
    the service scheduler's retry policy agree on one vocabulary:

    * ``wedge``  — the warden SIGKILLed the child after heartbeat
      silence (a hung dispatch / wedged runtime);
    * ``oom``    — an UNPROMPTED SIGKILL (kernel OOM killer / external
      ``kill -9``), or an abrupt death whose stderr tail carries an
      OOM marker (MemoryError traceback, RESOURCE_EXHAUSTED, …);
    * ``failed`` — the child exited :data:`CHILD_RC_FAILED` after
      reporting a classified in-child failure over the pipe;
    * ``crash``  — anything else: another signal (SIGSEGV, SIGBUS, …)
      or an abrupt nonzero exit with no report.
    """
    return classify_child_death(exitcode, killed_by_warden,
                                stderr_markers)


# ---------------------------------------------------------- serialization

_SCALAR_FIELDS = (
    "end_condition", "states_explored", "unique_states", "depth",
    "elapsed_secs", "predicate_name", "exception_code", "trace",
    "dropped", "samples", "visited_overflow", "retries", "failovers",
    "resumed_from_depth", "engine", "platform", "device_kind",
    "levels", "compile_secs",
    "child_restarts", "killed_dispatches", "abandoned_threads",
    "mesh_width", "mesh_shrinks", "knob_retries", "trace_id",
    "lane", "lane_width", "lane_share",
    "fault_events", "partition_events", "crash_events",
    "drop_events", "dup_events")


def outcome_to_dict(out) -> dict:
    """``SearchOutcome`` -> a JSON-serialisable dict (the pipe format).
    Batch-1 terminal states become nested int lists; everything else in
    the outcome is already plain data."""
    import numpy as np

    def _state(s):
        if s is None:
            return None
        return {k: np.asarray(v).tolist() for k, v in s.items()}

    d = {f: getattr(out, f) for f in _SCALAR_FIELDS}
    d["violating_state"] = _state(out.violating_state)
    d["goal_state"] = _state(out.goal_state)
    return d


def outcome_from_dict(d: dict):
    """Inverse of :func:`outcome_to_dict` (parent side of the pipe)."""
    import numpy as np

    from dslabs_tpu.tpu.engine import SearchOutcome

    def _state(s):
        if s is None:
            return None
        return {k: np.asarray(v, np.int32) for k, v in s.items()}

    out = SearchOutcome(
        end_condition=d["end_condition"],
        states_explored=d["states_explored"],
        unique_states=d["unique_states"],
        depth=d["depth"], elapsed_secs=d["elapsed_secs"])
    for f in _SCALAR_FIELDS:
        setattr(out, f, d.get(f, getattr(out, f)))
    out.violating_state = _state(d.get("violating_state"))
    out.goal_state = _state(d.get("goal_state"))
    return out


# ------------------------------------------------------------- line watch

class LineWatch:
    """Watch a child process's text stream line by line, tracking
    last-activity time, so a caller can enforce BOTH a total budget and
    a heartbeat-silence budget.  The reader thread forwards each line to
    ``on_line`` and keeps a short tail for attributable errors."""

    def __init__(self, proc: subprocess.Popen, stream, on_line=None):
        self.proc = proc
        self.last_activity = time.time()
        self.tail: List[str] = []
        self._on_line = on_line
        self._thread = threading.Thread(target=self._drain,
                                        args=(stream,), daemon=True)
        self._thread.start()

    def _drain(self, stream) -> None:
        for line in stream:
            self.last_activity = time.time()
            self.tail.append(line.rstrip()[:300])
            del self.tail[:-5]
            if self._on_line is not None:
                self._on_line(line)

    def silence(self) -> float:
        return time.time() - self.last_activity

    def kill(self) -> None:
        try:
            self.proc.kill()
        except OSError:
            pass
        self.proc.wait()

    def wait(self, timeout: float,
             silence: Optional[float] = None) -> Tuple[str, Optional[int]]:
        """Wait for exit within ``timeout`` total seconds, killing the
        child if its stream goes quiet for ``silence`` seconds.
        Returns ``("ok", returncode)``, ``("silence", None)``, or
        ``("total", None)`` — the child is dead in every case."""
        deadline = time.time() + timeout
        while True:
            try:
                rc = self.proc.wait(timeout=0.25)
                self._thread.join(timeout=5.0)
                return "ok", rc
            except subprocess.TimeoutExpired:
                pass
            if time.time() >= deadline:
                self.kill()
                return "total", None
            if silence is not None and self.silence() > silence:
                self.kill()
                return "silence", None


# ----------------------------------------------------------------- warden

@dataclasses.dataclass
class ChildDeath:
    """One reaped child: what rung died, how, and what it last said."""

    rung: str
    kind: str                   # classify_death vocabulary
    exitcode: Optional[int]
    detail: str
    last_hb: Optional[dict] = None


class Warden:
    """Parent half of the process-isolation layer: spawn one child per
    failover rung, enforce heartbeat deadlines with SIGKILL, classify
    deaths, and resume the next rung from the durable checkpoint.

    ``fault`` injects a deterministic child-side fault for the CI
    matrix: ``{"kind": "hang"|"die"|"exit"|"raise", "at": k}`` fires at
    dispatch index ``k`` of the FIRST rung it matches (optional
    ``"engine"`` restricts the rung; optional ``"spawns": [0, 1]``
    targets spawn indices instead — how the elastic SIGKILL matrix
    kills the 8-wide and 4-wide children but spares the 2-wide one) —
    a hang blocks the dispatch (the
    warden must kill), ``die`` is SIGKILL-self (an external/OOM kill),
    ``exit`` is an abrupt ``os._exit``, ``raise`` a fatal in-child
    error reported over the pipe."""

    def __init__(self, factory: str,
                 factory_kwargs: Optional[dict] = None,
                 transform: Optional[str] = None,
                 ladder: Tuple[str, ...] = ("sharded", "device", "host"),
                 policy: Optional[RetryPolicy] = None,
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
                 boot_grace: float = 240.0,
                 first_grace: Optional[float] = None,
                 steady_grace: float = 120.0,
                 idle_grace: float = 300.0,
                 grace_slack: float = 5.0,
                 fault: Optional[dict] = None,
                 env: Optional[dict] = None,
                 extra_sys_path: Optional[List[str]] = None,
                 telemetry=None,
                 elastic: bool = False,
                 trace_id: Optional[str] = None,
                 parent_span: Optional[str] = None):
        # Unified telemetry (tpu/telemetry.py): child heartbeats from
        # the pipe protocol are re-emitted as parent-side telemetry
        # events, so the flight log shows the child's dispatch-level
        # liveness even though the child is a separate process.
        self.telemetry = telemetry
        self.factory = factory
        self.factory_kwargs = factory_kwargs or {}
        self.transform = transform
        self.ladder = tuple(ladder)
        self.policy = policy or RetryPolicy()
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.strict = strict
        self.max_depth = max_depth
        self.max_secs = max_secs
        self.chunk = chunk
        self.frontier_cap = frontier_cap
        self.visited_cap = visited_cap
        self.ev_budget = ev_budget
        self.aot_warmup = aot_warmup
        # Grace ladder: boot (spawn + imports + jax init), first
        # dispatch per tag (XLA compile), steady dispatch, idle (host
        # work between dispatches).  The CHILD announces the applicable
        # grace on every heartbeat; the parent enforces announced grace
        # + slack, so policy lives in one place.
        self.boot_grace = boot_grace
        self.first_grace = (boot_grace if first_grace is None
                            else first_grace)
        self.steady_grace = steady_grace
        self.idle_grace = idle_grace
        self.grace_slack = grace_slack
        self.fault = fault
        self.env = env or {}
        self.extra_sys_path = list(extra_sys_path or [])
        # Elastic degraded-mesh ladder (ISSUE 9): expand the "sharded"
        # rung into width rungs sharded(D) -> ... -> sharded(2); each
        # width runs in its own child on a rebuilt smaller mesh,
        # resuming the unified checkpoint re-sharded to the new owner
        # map (tpu/supervisor.py expand_ladder — one expansion rule for
        # both modes).
        self.elastic = bool(elastic)
        # Causal-trace propagation (ISSUE 13, tpu/tracing.py): every
        # child gets DSLABS_TRACE_ID/DSLABS_PARENT_SPAN in its env, so
        # its run-dir telemetry recorder stamps the whole flight log
        # into the submitting trace's causal tree.  Defaults inherit
        # this process's own trace context — a warden inside a traced
        # service forwards the trace with no extra plumbing.
        from dslabs_tpu.tpu import tracing as tracing_mod

        env_trace, env_parent = tracing_mod.current_trace()
        self.trace_id = trace_id or env_trace
        self.parent_span = parent_span or env_parent
        self.mesh_shrinks = 0
        self.failures: List[EngineFailure] = []
        self.deaths: List[ChildDeath] = []
        self.killed_dispatches = 0
        # Platform the winning child actually ran on (the host rung's
        # forced-CPU contract is asserted against this).
        self.last_platform: Optional[str] = None

    # ------------------------------------------------------------- child io

    def _spec(self, rung: str, resume: bool,
              width: Optional[int] = None) -> dict:
        return {
            # Degraded-mesh rung width (None = the child's full device
            # set): the child builds make_mesh(width) for its sharded
            # supervisor.
            "mesh_width": width,
            "factory": self.factory,
            "factory_kwargs": self.factory_kwargs,
            "transform": self.transform,
            "rung": rung,
            "resume": resume,
            "strict": self.strict,
            "max_depth": self.max_depth,
            "max_secs": self.max_secs,
            "chunk": self.chunk,
            "frontier_cap": self.frontier_cap,
            "visited_cap": self.visited_cap,
            "ev_budget": (list(self.ev_budget)
                          if isinstance(self.ev_budget, tuple)
                          else self.ev_budget),
            "aot_warmup": self.aot_warmup,
            "checkpoint_path": self.checkpoint_path,
            "checkpoint_every": self.checkpoint_every,
            "policy": dataclasses.asdict(self.policy),
            "grace": {"boot": self.boot_grace, "first": self.first_grace,
                      "steady": self.steady_grace,
                      "idle": self.idle_grace},
            # The last rung runs with the CPU runtime forced: when the
            # accelerator runtime itself is the broken part, the final
            # rung must not touch it.
            "force_cpu": rung == self.ladder[-1],
            "fault": self.fault,
            "spawn_index": len(self.deaths),
        }

    def _child_env(self, spec: dict) -> dict:
        env = dict(os.environ)
        paths = [_REPO_ROOT] + self.extra_sys_path
        if env.get("PYTHONPATH"):
            paths.append(env["PYTHONPATH"])
        env["PYTHONPATH"] = os.pathsep.join(paths)
        env["DSLABS_WARDEN_CHILD"] = "1"
        if spec["force_cpu"]:
            env["JAX_PLATFORMS"] = "cpu"
        env.update(self.env)
        # Trace propagation AFTER self.env so explicit warden-level
        # trace identity wins over whatever a caller's env carried.
        from dslabs_tpu.tpu import tracing as tracing_mod

        env.update(tracing_mod.child_trace_env(self.trace_id,
                                               self.parent_span))
        return env

    def _run_child(self, rung: str, resume: bool,
                   width: Optional[int] = None) -> dict:
        """Spawn + supervise ONE rung child.  Returns the child's
        ``result`` message, or a death dict
        ``{"t": "death", "kind", "detail", "exitcode", "last_hb"}``."""
        spec = self._spec(rung, resume, width)
        proc = subprocess.Popen(
            [sys.executable, "-m", "dslabs_tpu.tpu.warden"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
            env=self._child_env(spec))

        def _tee(line):
            # stderr passes straight through (live heartbeats in the
            # driver tail) while LineWatch keeps the last lines — the
            # tail feeds the UNIFIED death taxonomy so an abrupt exit
            # with a MemoryError traceback classifies "oom", not
            # "crash" (supervisor.classify_child_death).
            sys.stderr.write(line)
            sys.stderr.flush()

        err_watch = LineWatch(proc, proc.stderr, on_line=_tee)
        try:
            proc.stdin.write(json.dumps(spec))
            proc.stdin.close()
        except BrokenPipeError:
            pass

        msgs: "queue.Queue[dict]" = queue.Queue()

        def _read():
            for line in proc.stdout:
                try:
                    msgs.put(json.loads(line))
                except ValueError:
                    continue          # stray child output, not protocol
            msgs.put({"t": "eof"})

        threading.Thread(target=_read, daemon=True).start()

        grace = self.boot_grace
        last_hb: Optional[dict] = None
        while True:
            try:
                msg = msgs.get(timeout=grace + self.grace_slack)
            except queue.Empty:
                # Heartbeat silence past the announced grace: the child
                # is wedged.  SIGKILL — the one interruption a hung XLA
                # runtime cannot ignore — and reap.
                try:
                    proc.kill()
                except OSError:
                    pass
                proc.wait()
                in_dispatch = (last_hb is not None
                               and last_hb.get("phase") == "start")
                if in_dispatch:
                    self.killed_dispatches += 1
                where = (f"dispatch {last_hb.get('tag')!r} "
                         f"(index {last_hb.get('n')}, depth "
                         f"{last_hb.get('depth')})" if in_dispatch
                         else "boot/idle")
                return {"t": "death", "kind": "wedge",
                        "exitcode": proc.returncode, "last_hb": last_hb,
                        "detail": (f"child silent > {grace:.1f}s in "
                                   f"{where}; SIGKILLed and reaped")}
            t = msg.get("t")
            if t == "hb":
                last_hb = msg
                grace = float(msg.get("grace", self.steady_grace))
                if self.telemetry is not None:
                    self.telemetry.event(
                        "heartbeat", rung=rung,
                        phase=msg.get("phase"), tag=msg.get("tag"),
                        n=msg.get("n"), depth=msg.get("depth"),
                        ckpt_depth=msg.get("ckpt_depth"),
                        grace=msg.get("grace"))
                continue
            if t == "result":
                proc.wait()
                return msg
            if t == "err":
                # The child reported a classified failure and will exit
                # CHILD_RC_FAILED; give it a moment, then reap.
                try:
                    rc = proc.wait(timeout=30.0)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    rc = proc.wait()
                return {"t": "death",
                        "kind": classify_death(rc, False,
                                               err_watch.tail),
                        "exitcode": rc, "last_hb": last_hb,
                        "detail": msg.get("error", "child failure")}
            if t == "eof":
                rc = proc.wait()
                kind = classify_death(rc, False, err_watch.tail)
                return {"t": "death", "kind": kind, "exitcode": rc,
                        "last_hb": last_hb,
                        "detail": (f"child exited rc={rc} without a "
                                   f"result (classified {kind}; last "
                                   f"heartbeat: {last_hb}; stderr "
                                   f"tail: {err_watch.tail[-2:]})")}

    # ----------------------------------------------------------------- run

    def run(self, resume: bool = False):
        """Run the ladder to a verdict, one supervised child per rung.
        Failover rungs always resume from the durable checkpoint when a
        matching dump exists (the in-child supervisor verifies the
        fingerprint).  Raises :class:`SupervisorExhausted` with the
        per-rung failure chain when every rung's child dies."""
        self.failures = []
        self.deaths = []
        self.killed_dispatches = 0
        self.mesh_shrinks = 0
        if self.elastic:
            import jax

            from dslabs_tpu.tpu.supervisor import expand_ladder

            specs = expand_ladder(self.ladder, len(jax.devices()), True)
            full_width = len(jax.devices())
        else:
            specs = [(r, None) for r in self.ladder]
            full_width = None
        spawned = 0
        prev_width = None
        for i, (rung, width) in enumerate(specs):
            eff = None
            if rung == "sharded" and self.elastic:
                eff = width or full_width
                if prev_width is not None and eff < prev_width:
                    self.mesh_shrinks += 1
                    if self.telemetry is not None:
                        self.telemetry.event("mesh_shrunk",
                                             from_width=prev_width,
                                             to_width=eff)
                prev_width = eff
            res = self._run_child(rung, resume=(resume or i > 0),
                                  width=eff)
            spawned += 1
            if res.get("t") == "result":
                out = outcome_from_dict(res["outcome"])
                self.last_platform = res.get("platform")
                out.engine = rung
                out.failovers = len(self.failures)
                out.child_restarts = spawned - 1
                out.killed_dispatches = self.killed_dispatches
                out.mesh_shrinks = self.mesh_shrinks
                if out.mesh_width is None and eff is not None:
                    out.mesh_width = eff
                return out
            death = ChildDeath(rung=rung, kind=res["kind"],
                               exitcode=res.get("exitcode"),
                               detail=res["detail"],
                               last_hb=res.get("last_hb"))
            self.deaths.append(death)
            if self.telemetry is not None:
                self.telemetry.event(
                    "child_death", rung=rung, kind=death.kind,
                    exitcode=death.exitcode,
                    detail=death.detail[:200])
            self.failures.append(EngineFailure(
                rung, death.kind, RuntimeError(death.detail)))
        raise SupervisorExhausted(self.failures)


# ------------------------------------------------------------ child half

def _resolve(ref: str):
    """``"module:callable"`` -> the callable (child-side import)."""
    import importlib

    mod, _, name = ref.partition(":")
    obj = importlib.import_module(mod)
    for part in name.split("."):
        obj = getattr(obj, part)
    return obj


def _send(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _child_main() -> int:
    spec = json.load(sys.stdin)
    g = spec.get("grace") or {}
    boot_g = float(g.get("boot", 240.0))
    first_g = float(g.get("first", boot_g))
    steady_g = float(g.get("steady", 120.0))
    idle_g = float(g.get("idle", 300.0))
    _send({"t": "hb", "phase": "boot", "stage": "spawned",
           "grace": boot_g})
    if spec.get("force_cpu"):
        os.environ["JAX_PLATFORMS"] = "cpu"     # before jax loads
    from dslabs_tpu.tpu.supervisor import (RetryPolicy, SearchSupervisor,
                                           SupervisorExhausted)

    proto = _resolve(spec["factory"])(**(spec.get("factory_kwargs")
                                         or {}))
    if spec.get("transform"):
        proto = _resolve(spec["transform"])(proto)
    _send({"t": "hb", "phase": "boot", "stage": "protocol",
           "grace": boot_g})

    policy = RetryPolicy(**(spec.get("policy") or {}))
    ev = spec.get("ev_budget")
    if isinstance(ev, list):
        ev = tuple(ev)
    ckpt_path = spec.get("checkpoint_path")
    fault = spec.get("fault")
    rung = spec["rung"]
    if fault is not None:
        if fault.get("spawns") is not None:
            # Explicit spawn targeting (the elastic SIGKILL matrix:
            # kill the 8-wide AND the 4-wide child, let the 2-wide
            # finish) — overrides the engine/first-child scoping, which
            # cannot distinguish same-named width rungs.
            if int(spec.get("spawn_index", 0)) not in fault["spawns"]:
                fault = None
        elif fault.get("engine") is not None:
            if fault["engine"] != rung:
                fault = None
        elif int(spec.get("spawn_index", 0)) > 0:
            # Un-scoped faults fire on the FIRST child only — otherwise
            # the same injected death would chase the run down every
            # rung of the ladder.
            fault = None
    seen_tags = set()
    st = {"ckpt_depth": None}
    sup_ref: Dict[str, object] = {}

    def observer(phase, tag, idx, depth):
        if phase == "start":
            first = tag not in seen_tags
            seen_tags.add(tag)
            scale = 1.0
            b = sup_ref.get("sup") and sup_ref["sup"].boundary
            if b is not None:
                scale = b._deadline_scale(tag)
            grace = first_g if first else steady_g * max(scale, 1.0)
            _send({"t": "hb", "phase": "start", "tag": tag, "n": idx,
                   "depth": depth, "ckpt_depth": st["ckpt_depth"],
                   "grace": grace})
            if fault is not None:
                kind = fault.get("kind")
                at = int(fault.get("at", 0))
                # Process-death kinds arm at index ``at`` and fire on
                # the first armed dispatch; with ``after_ckpt`` they
                # additionally wait until a DURABLE checkpoint has been
                # observed on disk (peek_depth above), so resume-parity
                # tests are deterministic instead of racing the async
                # dump drain.  ``raise`` keeps exact-index semantics (a
                # repeated raise would just exhaust retries).
                due = (idx >= at if kind in ("die", "exit", "hang")
                       else idx == at)
                if due and fault.get("after_ckpt") and (
                        st["ckpt_depth"] is None):
                    due = False
                if due:
                    if kind == "die":
                        os.kill(os.getpid(), signal.SIGKILL)
                    elif kind == "exit":
                        os._exit(int(fault.get("rc", 86)))
                    elif kind == "hang":
                        # An UNINTERRUPTIBLE block, as a wedged runtime
                        # would be — only the parent's SIGKILL ends it.
                        time.sleep(float(fault.get("secs", 3600.0)))
                    elif kind == "raise":
                        raise RuntimeError(
                            f"injected warden child fault [{tag} "
                            f"dispatch {idx}]")
        else:
            if ckpt_path and tag.rsplit(".", 1)[-1] in ("promote",
                                                        "expand"):
                d = ckpt_mod.peek_depth(ckpt_path)
                if d is not None:
                    st["ckpt_depth"] = d
            _send({"t": "hb", "phase": "done", "tag": tag, "n": idx,
                   "depth": depth, "ckpt_depth": st["ckpt_depth"],
                   "grace": idle_g})

    # A checkpointed child gets a run-dir telemetry recorder of its
    # own (flight.jsonl + STATUS.json beside the dump): `telemetry
    # watch <run-dir>` then renders the CHILD's live depth/rate/skew
    # from the directory alone — the parent's heartbeat re-emission
    # covers liveness, this covers progress.  Never fatal: a child on
    # a read-only dir just runs unrecorded.
    child_tel = None
    if ckpt_path:
        try:
            from dslabs_tpu.tpu.telemetry import Telemetry

            child_tel = Telemetry.for_checkpoint(
                ckpt_path, engine_hint=f"warden-child:{rung}")
        except Exception:  # noqa: BLE001 — observability is optional
            child_tel = None
    # Degraded-mesh rung: the child rebuilds the SMALLER mesh and its
    # in-child supervisor resumes the unified checkpoint re-sharded to
    # the new owner map (tpu/checkpoint.py carries everything needed).
    mesh = None
    width = spec.get("mesh_width")
    if width and rung == "sharded":
        from dslabs_tpu.tpu.sharded import make_mesh

        mesh = make_mesh(int(width))
    sup = SearchSupervisor(
        proto, ladder=(rung,), policy=policy, mesh=mesh,
        checkpoint_path=ckpt_path,
        checkpoint_every=spec.get("checkpoint_every", 0),
        strict=spec.get("strict", True),
        max_depth=spec.get("max_depth"),
        max_secs=spec.get("max_secs"),
        chunk=spec.get("chunk", 1 << 10),
        frontier_cap=spec.get("frontier_cap", 1 << 14),
        visited_cap=spec.get("visited_cap", 1 << 20),
        ev_budget=ev, aot_warmup=spec.get("aot_warmup", False),
        dispatch_observer=observer, telemetry=child_tel)
    sup_ref["sup"] = sup
    try:
        out = sup.run(resume=bool(spec.get("resume")))
    except BaseException as e:  # noqa: BLE001 — reported over the pipe
        kind = "failed"
        if isinstance(e, SupervisorExhausted) and e.failures:
            kind = e.failures[-1].kind
        _send({"t": "err", "kind": kind,
               "error": f"{type(e).__name__}: {e}"[:500]})
        return CHILD_RC_FAILED
    finally:
        if child_tel is not None:
            child_tel.close()
    import jax

    _send({"t": "result", "outcome": outcome_to_dict(out),
           "platform": jax.devices()[0].platform})
    return 0


if __name__ == "__main__":
    sys.exit(_child_main())
