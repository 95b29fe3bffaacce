"""Checking-as-a-service: the resident multi-tenant search server
(ISSUE 11 tentpole).

The composition layer ROADMAP #2 asked for: every prerequisite landed
in earlier PRs and this module only WIRES them —

* **Admission gate** (PR 10): an untrusted (factory spec, predicate)
  submission is linted by ``analysis.conformance`` in a **CPU-pinned
  subprocess** (the spec's own code runs there, never in the server,
  and never near the accelerator) BEFORE any twin is compiled.
  Unsound protocols are rejected with structured ``SpecError``-derived
  verdicts (rule code + location + message); a hung or crashing
  admission child is itself a rejection, never a server stall.
* **One fault domain per job** (PR 4): accepted jobs run as warden
  children with their own run dir
  (``<root>/jobs/<job_id>/`` — checkpoint, flight.jsonl, STATUS.json:
  tpu/checkpoint.py ``run_dir_layout``), heartbeat-
  reaped, so one tenant's OOM/hang/crash is a SIGKILL + classified
  death in ITS domain — a neighbor's verdict stays bit-exact (proven
  by the chaos soak in tests/test_service.py).
* **Fairness-preserving degradation** (PR 9 + service/scheduler.py):
  deaths classify through the unified taxonomy and buy strictly
  lighter retries (oom -> knob-shrink re-level, wedge -> rung-step),
  resumed from the job's durable checkpoint; a reported deterministic
  failure lands a structured failure verdict — never a silent partial
  one, and never an unbounded retry loop burning the queue.
* **Bounded backpressure** (service/queue.py): a full queue answers
  submission with a structured retry-after rejection instead of
  blocking the front end.

``SERVER_STATUS.json`` (atomic tmp+replace, same discipline as the
per-run STATUS.json) aggregates what ``telemetry watch`` shows per
job: queue depth/cap, backpressure state, per-tenant
pending/running/completed/failed/rejected, and the live fairness
index.  Knobs: the ``DSLABS_SERVICE_*`` table in docs/service.md.

CLI: ``python -m dslabs_tpu.service {submit,status,drain}``
(service/__main__.py).  Running THIS module as ``__main__`` is the
admission child half, mirroring tpu/warden.py's parent/child split.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from dslabs_tpu.service import memo as memo_mod
from dslabs_tpu.service.queue import Job, ServiceQueue
from dslabs_tpu.service.scheduler import (AttemptPlan, DeficitRoundRobin,
                                          RetrySpec, degrade,
                                          fairness_index)
from dslabs_tpu.tpu import tracing

__all__ = ["CheckServer", "SERVER_STATUS_NAME", "admission_check"]

SERVER_STATUS_NAME = "SERVER_STATUS.json"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _zero_stats() -> dict:
    return {"submitted": 0, "completed": 0, "failed": 0, "rejected": 0,
            "verdicts": 0, "budget_spent": 0.0}


# -------------------------------------------------------------- admission

def admission_check(factory: str, factory_kwargs: Optional[dict],
                    transform: Optional[str],
                    extra_sys_path: Optional[List[str]] = None,
                    env: Optional[dict] = None,
                    timeout: Optional[float] = None) -> List[dict]:
    """Run the conformance gate over one factory spec in a CPU-pinned
    subprocess.  Returns the finding dicts (``analysis.core.Finding``
    shape, waivers applied); an empty list means admissible.  A child
    that hangs past ``timeout`` (DSLABS_SERVICE_ADMIT_SECS, default
    120) or dies abruptly IS a finding — a hostile spec must not be
    able to wedge or crash its way past the gate."""
    if timeout is None:
        timeout = _env_float("DSLABS_SERVICE_ADMIT_SECS", 120.0)
    child_env = dict(os.environ)
    child_env["JAX_PLATFORMS"] = "cpu"
    paths = [_REPO_ROOT] + list(extra_sys_path or [])
    if child_env.get("PYTHONPATH"):
        paths.append(child_env["PYTHONPATH"])
    child_env["PYTHONPATH"] = os.pathsep.join(paths)
    child_env.update(env or {})
    spec = {"factory": factory, "factory_kwargs": factory_kwargs or {},
            "transform": transform}

    def _gate_error(message: str) -> List[dict]:
        return [{"code": "C4", "leg": "conformance", "path": factory,
                 "obj": "<admission>", "line": 0, "waived": False,
                 "waiver": "", "message": message}]

    try:
        proc = subprocess.run(
            [sys.executable, "-m", "dslabs_tpu.service.server"],
            input=json.dumps(spec), capture_output=True, text=True,
            env=child_env, timeout=timeout)
    except subprocess.TimeoutExpired:
        return _gate_error(
            f"admission child exceeded {timeout:.0f}s (hung import or "
            "hostile spec); rejected")
    except OSError as e:
        return _gate_error(f"admission child failed to spawn: {e}")
    if proc.returncode != 0 or not proc.stdout.strip():
        tail = (proc.stderr or "").strip().splitlines()[-1:][:1]
        return _gate_error(
            f"admission child died rc={proc.returncode} "
            f"(stderr tail: {tail}); rejected")
    try:
        return json.loads(
            proc.stdout.strip().splitlines()[-1]).get("findings", [])
    except ValueError:
        return _gate_error("admission child produced unparsable output")


# ------------------------------------------------------------------ server

class CheckServer:
    """The resident server: bounded persistent queue + admission gate
    + DRR scheduler + per-job warden fault domains.  Thread-safe;
    ``drain`` runs the backlog on ``workers`` worker threads (each job
    is its own child process tree, so workers only pay coordination;
    default ONE — each child takes the chip — raise it on a host with
    N chips).
    """

    def __init__(self, root: str,
                 queue_cap: Optional[int] = None,
                 quota: Optional[int] = None,
                 quotas: Optional[Dict[str, int]] = None,
                 workers: Optional[int] = None,
                 admission: Optional[bool] = None,
                 retry: Optional[RetrySpec] = None,
                 warden_kwargs: Optional[dict] = None,
                 env: Optional[dict] = None,
                 extra_sys_path: Optional[List[str]] = None,
                 elastic: bool = True,
                 keep: Optional[int] = None,
                 lanes: Optional[int] = None,
                 lane_swap: Optional[bool] = None,
                 telemetry=None,
                 memo: Optional[bool] = None,
                 memo_path: Optional[str] = None):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.queue = ServiceQueue(self.root, cap=queue_cap)
        # Per-tenant cost ledger (ISSUE 13, tpu/tracing.py): every
        # finished job appends one COSTS.jsonl record built from its
        # verdict counters + its run dir's flight log — zero added
        # device work; a restarted server replays the ledger.
        self.costs = tracing.CostMeter(
            os.path.join(self.root, tracing.COSTS_NAME))
        # Run-dir retention (ISSUE 13 satellite): service roots used to
        # grow without bound — at scheduler idle the oldest FINISHED
        # jobs' run dirs are pruned down to `keep` (DSLABS_SERVICE_KEEP,
        # default 64); running/queued jobs are never touched.
        self.keep = (keep if keep is not None
                     else _env_int("DSLABS_SERVICE_KEEP", 64))
        # Optional parent-side telemetry recorder: retention prunes and
        # scheduler-level events become flight-log events when one is
        # attached.
        self.telemetry = telemetry
        # ONE job child at a time by default: every job child takes
        # the chip, and a chip belongs to one process at a time.
        # ``workers=N`` / ``--workers N`` is for hosts with N chips.
        self.workers = (workers if workers is not None
                        else _env_int("DSLABS_SERVICE_WORKERS", 1))
        if admission is None:
            admission = os.environ.get(
                "DSLABS_SERVICE_ADMISSION", "1").strip().lower() not in (
                    "0", "off", "false", "no")
        self.admission = bool(admission)
        self.retry = retry or RetrySpec.from_env()
        self.warden_kwargs = dict(warden_kwargs or {})
        self.env = dict(env or {})
        self.extra_sys_path = list(extra_sys_path or [])
        self.elastic = bool(elastic)
        self.sched = DeficitRoundRobin(
            quota=(quota if quota is not None
                   else _env_int("DSLABS_SERVICE_QUOTA", 1)),
            quotas=quotas)
        # Batched job lanes (ISSUE 14, tpu/lanes.py): with lanes >= 2
        # the scheduler packs compatible queued jobs (same lane
        # signature, quotas preserved) into ONE lane-batch child — N
        # searches advanced by one compiled program, dispatch cost
        # amortised across tenants.  Default OFF (DSLABS_LANES=0): the
        # solo path stays byte-identical for existing callers.
        from dslabs_tpu.tpu import lanes as lanes_mod

        self.lanes = (int(lanes) if lanes is not None
                      else lanes_mod.lanes_enabled())
        self.lane_swap = (bool(lane_swap) if lane_swap is not None
                          else lanes_mod.lane_swap_enabled())
        self.lane_stats = {
            "batches": 0, "jobs": 0, "swaps": 0, "evicted": 0,
            "occupancy_sum": 0.0, "by_signature": {}}
        self._lane_seq = 0
        # Cross-job memoization (ISSUE 16, service/memo.py): ON by
        # default in the service path (DSLABS_MEMO) — an identical
        # resubmit returns its cached verdict with zero device
        # dispatches, a budget-grown resubmit warm-starts from the
        # signature's deepest checkpoint, and a one-handler edit
        # re-checks incrementally from its divergence bound.  OFF
        # leaves every existing path byte-identical (no memo dir, no
        # memo events, no introspection children).
        if memo is None:
            memo = memo_mod.memo_enabled()
        self.memo: Optional[memo_mod.MemoStore] = None
        if memo:
            self.memo = memo_mod.MemoStore(
                memo_path or memo_mod.memo_dir(self.root))
        self._intro_cache: Dict[tuple, dict] = {}
        self.status_path = os.path.join(self.root, SERVER_STATUS_NAME)
        self._lock = threading.Lock()
        self._running: Dict[str, int] = {}
        self._active = 0
        self.stats: Dict[str, dict] = {}
        self._admission_cache: Dict[tuple, List[dict]] = {}
        self.results: List[dict] = []
        # Crash recovery: the queue replays its journal on open; every
        # still-pending job re-enters the scheduler (and will resume
        # its own run-dir checkpoint when it runs).
        for job in list(self.queue.pending):
            self.sched.push(job)
            self.stats.setdefault(job.tenant, _zero_stats())
        self._write_status()

    # ------------------------------------------------------------- submit

    def submit(self, factory: str, tenant: str = "default",
               factory_kwargs: Optional[dict] = None,
               transform: Optional[str] = None,
               strict: bool = True,
               max_depth: Optional[int] = None,
               max_secs: Optional[float] = None,
               budget_units: float = 1.0,
               chunk: int = 1 << 10,
               frontier_cap: int = 1 << 14,
               visited_cap: int = 1 << 20,
               ladder: Tuple[str, ...] = ("device", "host"),
               fault: Optional[dict] = None) -> dict:
        """The submission protocol (docs/service.md).  Returns one of
        three STRUCTURED results — never raises, never blocks:

        * ``{"accepted": True, "job_id", "queue_depth"}``
        * ``{"accepted": False, "reason": "unsound_spec",
          "findings": […]}``  (admission gate, before any compile)
        * ``{"accepted": False, "reason": "queue_full",
          "retry_after_secs", "queue_depth", "queue_cap"}``
        """
        with self._lock:
            st = self.stats.setdefault(tenant, _zero_stats())
        # One trace id per submission (ISSUE 13): minted HERE — the
        # journal persists it on the job record, every phase of the
        # job's life (admission, queue wait, each warden attempt,
        # every child's flight log) is stamped with it, and
        # `telemetry trace` reassembles the causal tree from disk.
        trace_id = tracing.mint_trace_id()
        # Memo introspection (ISSUE 16): runs FIRST so the admission
        # cache can key on the structural fingerprint (satellite:
        # admission and memoization must never disagree about spec
        # identity).  Same sandbox discipline as admission — a
        # CPU-pinned child builds the protocol; a failed introspection
        # is journaled and the job simply runs cold.
        intro = self._introspect(factory, factory_kwargs, transform)
        spec_fp = (intro or {}).get("spec_fp") \
            if (intro or {}).get("ok") else None
        if self.admission:
            t_adm = time.time()
            findings, cached = self._admit(factory, factory_kwargs,
                                           transform, fp=spec_fp)
            unwaived = [f for f in findings if not f.get("waived")]
            self.queue.log_event(
                "admission", tenant=tenant, factory=factory,
                trace_id=trace_id, secs=round(time.time() - t_adm, 3),
                cached=cached, findings=len(unwaived))
            if unwaived:
                self.queue.mark_rejected(
                    tenant, "unsound_spec",
                    {"factory": factory, "trace_id": trace_id,
                     "findings": unwaived[:8]})
                with self._lock:
                    st["rejected"] += 1
                self._write_status()
                return {"accepted": False, "rejected": True,
                        "reason": "unsound_spec", "factory": factory,
                        "trace_id": trace_id, "findings": unwaived}
        else:
            # The gate-off path still lands an admission event so the
            # causal chain submit -> queue -> admission -> … is
            # unbroken in every configuration.
            self.queue.log_event("admission", tenant=tenant,
                                 factory=factory, trace_id=trace_id,
                                 secs=0.0, skipped=True, findings=0)
        job = Job(job_id=self.queue.next_id(tenant), tenant=tenant,
                  factory=factory, factory_kwargs=factory_kwargs,
                  transform=transform, strict=strict,
                  max_depth=max_depth, max_secs=max_secs,
                  budget_units=budget_units, chunk=chunk,
                  frontier_cap=frontier_cap, visited_cap=visited_cap,
                  ladder=tuple(ladder), fault=fault,
                  trace_id=trace_id)
        # Exact-key verdict cache (ISSUE 16 leg a): a structural +
        # budget + knob match returns the cached verdict with ZERO
        # device dispatches — journaled memo_hit, cached=true verdict,
        # near-zero COSTS charge (no flight log to bill).
        if (self.memo is not None and fault is None and spec_fp
                and intro.get("ok")):
            plan = self.memo.plan(
                intro, strict, chunk, frontier_cap, visited_cap,
                tuple(ladder), max_depth, max_secs,
                env=self._memo_env())
            if plan.mode == "hit":
                return self._complete_memo_hit(job, st, plan)
        res = self.queue.submit(job)
        if res.get("accepted"):
            res["trace_id"] = trace_id
            with self._lock:
                self.sched.push(job)
                st["submitted"] += 1
        else:
            self.queue.mark_rejected(tenant, "queue_full",
                                     {"trace_id": trace_id})
            with self._lock:
                st["rejected"] += 1
        self._write_status()
        return res

    def _admit(self, factory, factory_kwargs, transform,
               fp: Optional[str] = None) -> Tuple[List[dict], bool]:
        """The cached admission check; returns ``(findings, cached)``
        so the journal's admission event can tell a paid subprocess
        check from a cache hit (their latencies differ by ~1000x and
        the trace timeline should say which one a tenant waited on).

        With memoization on, the cache keys on the STRUCTURAL spec
        fingerprint (ISSUE 16 satellite) — the same identity the memo
        store uses, so a rename-only resubmit hits both caches and
        admission can never disagree with memoization about what a
        spec IS.  Without a fingerprint (memo off, introspection
        failed) the legacy source key applies."""
        key = (("fp", fp) if fp else
               (factory,
                json.dumps(factory_kwargs or {}, sort_keys=True),
                transform or ""))
        with self._lock:
            cached = self._admission_cache.get(key)
        if cached is not None:
            return cached, True
        findings = admission_check(factory, factory_kwargs, transform,
                                   extra_sys_path=self.extra_sys_path,
                                   env=self.env)
        with self._lock:
            self._admission_cache[key] = findings
        return findings, False

    # ---------------------------------------------------------- memo

    def _memo_env(self) -> dict:
        """The env the warden CHILD will actually see (os.environ
        overlaid with the server's env) — the memo key's pack/symmetry
        gates must be resolved exactly the way the engine will."""
        return {**os.environ, **self.env}

    def _introspect(self, factory, factory_kwargs,
                    transform) -> Optional[dict]:
        """Cached structural introspection (service/memo.py child).
        The cache key includes the factory MODULE FILE's content hash:
        a tenant editing the module in place gets a fresh child (fresh
        interpreter, no stale ``sys.modules``), so an edited spec can
        never ride a stale fingerprint into the verdict cache."""
        if self.memo is None:
            return None
        src = memo_mod.factory_source_hash(factory, self.extra_sys_path)
        key = (factory,
               json.dumps(factory_kwargs or {}, sort_keys=True,
                          default=repr),
               transform or "", src or "?")
        with self._lock:
            hit = self._intro_cache.get(key)
        if hit is not None:
            return hit
        intro = memo_mod.introspect_child(
            factory, factory_kwargs, transform,
            extra_sys_path=self.extra_sys_path, env=self.env)
        if not intro.get("ok"):
            self.queue.log_event(
                "memo", mode="introspect_failed", factory=factory,
                error=str(intro.get("error"))[:200])
        with self._lock:
            self._intro_cache[key] = intro
        return intro

    def _cached_verdict(self, job: Job, plan) -> dict:
        cached = plan.verdict or {}
        return {
            "job_id": job.job_id, "tenant": job.tenant,
            "trace_id": job.trace_id,
            "budget_units": job.budget_units,
            "status": "done",
            "end": cached.get("end"),
            "unique": cached.get("unique"),
            "explored": cached.get("explored"),
            "depth": cached.get("depth"),
            "engine": cached.get("engine"),
            "platform": cached.get("platform"),
            "device_kind": cached.get("device_kind"),
            "predicate": cached.get("predicate"),
            "witness": cached.get("witness"),
            "attempts": 0, "failovers": 0, "child_restarts": 0,
            "knob_shrinks": 0, "rung_steps": 0,
            "resumed_from_depth": 0, "degraded": False, "deaths": [],
            "cached": True, "run_dir": self.job_dir(job.job_id),
            "elapsed_secs": 0.0,
        }

    def _complete_memo_hit(self, job: Job, st: dict, plan) -> dict:
        """Land a verdict-cache hit: the job enters and leaves the
        journal in one motion (submit -> memo_hit -> done), the COSTS
        charge bills its exact counters against NO flight log (device
        seconds ~ 0), and no scheduler/warden work happens at all."""
        res = self.queue.submit(job)
        if not res.get("accepted"):
            self.queue.mark_rejected(job.tenant, "queue_full",
                                     {"trace_id": job.trace_id})
            with self._lock:
                st["rejected"] += 1
            self._write_status()
            return res
        res["trace_id"] = job.trace_id
        verdict = self._cached_verdict(job, plan)
        self.queue.log_event(
            "memo_hit", job_id=job.job_id, tenant=job.tenant,
            trace_id=job.trace_id, sig=plan.sig,
            device_secs_saved=round(plan.base_device_secs, 4))
        self.queue.mark_done(job.job_id, {
            "end": verdict["end"], "unique": verdict["unique"],
            "explored": verdict["explored"], "depth": verdict["depth"],
            "attempts": 0, "degraded": False, "cached": True})
        self._charge(verdict, self.job_dir(job.job_id))
        self.memo.bump("hits")
        self.memo.bump("device_secs_saved", plan.base_device_secs)
        with self._lock:
            st["submitted"] += 1
            st["completed"] += 1
            st["verdicts"] += 1
            self.results.append(verdict)
        self._write_status()
        res["verdict"] = verdict
        res["memo"] = "hit"
        return res

    def _memo_plan(self, job: Job):
        """(intro, plan) for one job at RUN time (restart replay safe:
        recomputes from the intro cache or a fresh child)."""
        if self.memo is None:
            return None, None
        intro = self._introspect(job.factory, job.factory_kwargs,
                                 job.transform)
        if not intro or not intro.get("ok"):
            return intro, None
        plan = self.memo.plan(
            intro, job.strict, job.chunk, job.frontier_cap,
            job.visited_cap, tuple(job.ladder), job.max_depth,
            job.max_secs, env=self._memo_env())
        if job.fault is not None and plan.mode == "hit":
            # Fault experiments always RUN (the injected condition is
            # the point); warm/incremental seeding still applies — the
            # seeded job survives its SIGKILL via the normal resume.
            return intro, None
        return intro, plan

    # ------------------------------------------------------------ run job

    def job_dir(self, job_id: str) -> str:
        return os.path.join(self.root, "jobs", job_id)

    def run_job(self, job: Job) -> dict:
        """Run ONE job to a verdict or a structured failure, applying
        the bounded degrade-and-retry policy (scheduler.degrade) across
        warden launches.  Every attempt resumes the job's own durable
        checkpoint; the fault domain is the warden child tree — nothing
        here can take the server down."""
        from dslabs_tpu.tpu.supervisor import SupervisorExhausted
        from dslabs_tpu.tpu.warden import Warden

        rd = self.job_dir(job.job_id)
        os.makedirs(rd, exist_ok=True)
        ckpt = os.path.join(rd, "ckpt.npz")
        intro, mplan = self._memo_plan(job)
        if mplan is not None and mplan.mode == "hit":
            # A sibling job archived this exact signature between
            # submit and run (drain ordering) — land it as a hit.
            verdict = self._cached_verdict(job, mplan)
            self.queue.log_event(
                "memo_hit", job_id=job.job_id, tenant=job.tenant,
                trace_id=job.trace_id, sig=mplan.sig,
                device_secs_saved=round(mplan.base_device_secs, 4))
            self.queue.mark_done(job.job_id, {
                "end": verdict["end"], "unique": verdict["unique"],
                "explored": verdict["explored"],
                "depth": verdict["depth"], "attempts": 0,
                "degraded": False, "cached": True})
            self._charge(verdict, rd)
            self.memo.bump("hits")
            self.memo.bump("device_secs_saved", mplan.base_device_secs)
            return verdict
        seeded = False
        if (mplan is not None and mplan.mode in ("warm", "incremental")
                and mplan.seed_ckpt and not os.path.exists(ckpt)):
            # Pre-seed the job's own durable checkpoint from the
            # archived signature state; the warden child resumes it
            # via the EXISTING checkpoint path — no new plumbing in
            # the engine, and a crash mid-run keeps the job's own
            # (deeper) checkpoint on later attempts.
            tmp = ckpt + ".seed"
            shutil.copyfile(mplan.seed_ckpt, tmp)
            os.replace(tmp, ckpt)
            seeded = True
            self.memo.bump("warm_starts" if mplan.mode == "warm"
                           else "incremental")
            if mplan.mode == "incremental":
                self.memo.bump("levels_skipped", mplan.levels_skipped)
            self.queue.log_event(
                "memo", mode=mplan.mode, job_id=job.job_id,
                tenant=job.tenant, trace_id=job.trace_id,
                sig=mplan.sig, seed_depth=mplan.seed_depth,
                levels_skipped=mplan.levels_skipped,
                reason=mplan.reason)
        elif self.memo is not None:
            self.memo.bump("misses")
        wenv = dict(self.env)
        if self.memo is not None:
            # Per-level archives for future incremental re-checks.
            wenv["DSLABS_MEMO_LEVELS"] = os.path.join(rd, "levels")
        plan = AttemptPlan(attempt=1, chunk=job.chunk,
                           ladder=tuple(job.ladder))
        deaths: List[dict] = []
        t0 = time.time()
        while True:
            self.queue.mark_started(job.job_id, plan.attempt)
            w = Warden(
                factory=job.factory,
                factory_kwargs=job.factory_kwargs,
                transform=job.transform,
                ladder=plan.ladder,
                checkpoint_path=ckpt, checkpoint_every=1,
                strict=job.strict, max_depth=job.max_depth,
                max_secs=job.max_secs, chunk=plan.chunk,
                frontier_cap=job.frontier_cap,
                visited_cap=job.visited_cap,
                # Injected faults model an environment condition of the
                # FIRST attempt; a scheduler-level retry runs clean.
                fault=(job.fault if plan.attempt == 1 else None),
                env=wenv,
                extra_sys_path=self.extra_sys_path,
                elastic=self.elastic,
                # Trace propagation (ISSUE 13): the warden forwards
                # both via DSLABS_TRACE_ID/DSLABS_PARENT_SPAN, and the
                # attempt span id is DERIVED from the journal's start
                # record, so the child's flight-log meta links back to
                # this exact attempt with no extra journal field.
                trace_id=job.trace_id,
                parent_span=plan.span_id(job.job_id),
                **self.warden_kwargs)
            try:
                out = w.run(resume=plan.attempt > 1 or seeded)
            except SupervisorExhausted:
                deaths += [{"rung": d.rung, "kind": d.kind,
                            "detail": d.detail[:200]} for d in w.deaths]
                kind = w.deaths[-1].kind if w.deaths else "failed"
                if seeded and any("Checkpoint" in d.get("detail", "")
                                  for d in deaths):
                    # A refused/torn memo seed must never fail the
                    # job: abandon the seed loudly and run cold.
                    for p in (ckpt, ckpt + ".prev"):
                        try:
                            os.remove(p)
                        except OSError:
                            pass
                    seeded = False
                    deaths = []
                    self.queue.log_event(
                        "memo", mode="seed_abandoned",
                        job_id=job.job_id, trace_id=job.trace_id,
                        detail=(w.deaths[-1].detail[:200]
                                if w.deaths else ""))
                    continue
                nxt = degrade(plan, kind, self.retry)
                if nxt is None:
                    failure = {
                        "job_id": job.job_id, "tenant": job.tenant,
                        "trace_id": job.trace_id,
                        "status": "failed", "kind": kind,
                        "attempts": plan.attempt,
                        "knob_shrinks": plan.knob_shrinks,
                        "rung_steps": plan.rung_steps,
                        "deaths": deaths,
                        "budget_units": job.budget_units,
                        "run_dir": rd,
                        "elapsed_secs": round(time.time() - t0, 2),
                    }
                    self.queue.mark_failed(job.job_id, {
                        "kind": kind, "attempts": plan.attempt,
                        "deaths": len(deaths)})
                    self._charge(failure, rd)
                    return failure
                time.sleep(self.retry.backoff(plan.attempt - 1))
                plan = nxt
                continue
            except BaseException as e:  # noqa: BLE001 — structured, never silent
                failure = {
                    "job_id": job.job_id, "tenant": job.tenant,
                    "trace_id": job.trace_id,
                    "status": "failed", "kind": "error",
                    "error": f"{type(e).__name__}: {e}"[:300],
                    "attempts": plan.attempt, "deaths": deaths,
                    "budget_units": job.budget_units,
                    "run_dir": rd,
                    "elapsed_secs": round(time.time() - t0, 2),
                }
                self.queue.mark_failed(job.job_id, {
                    "kind": "error",
                    "error": failure["error"][:200]})
                self._charge(failure, rd)
                return failure
            deaths += [{"rung": d.rung, "kind": d.kind,
                        "detail": d.detail[:200]} for d in w.deaths]
            verdict = {
                "job_id": job.job_id, "tenant": job.tenant,
                "trace_id": job.trace_id,
                "budget_units": job.budget_units,
                "status": "done",
                "end": out.end_condition,
                "unique": out.unique_states,
                "explored": out.states_explored,
                "depth": out.depth,
                "engine": out.engine,
                "platform": out.platform,
                "device_kind": out.device_kind,
                "predicate": out.predicate_name,
                "witness": memo_mod.witness_digest(
                    out.predicate_name, out.violating_state,
                    out.goal_state, out.trace),
                "attempts": plan.attempt,
                "failovers": out.failovers,
                "child_restarts": out.child_restarts,
                "knob_shrinks": plan.knob_shrinks,
                "rung_steps": plan.rung_steps,
                "resumed_from_depth": out.resumed_from_depth,
                "degraded": bool(deaths or plan.knob_shrinks
                                 or plan.rung_steps),
                "deaths": deaths,
                "run_dir": rd,
                "elapsed_secs": round(time.time() - t0, 2),
            }
            self.queue.mark_done(job.job_id, {
                "end": out.end_condition, "unique": out.unique_states,
                "explored": out.states_explored, "depth": out.depth,
                "attempts": plan.attempt,
                "degraded": verdict["degraded"]})
            self._charge(verdict, rd)
            if self.memo is not None and intro and intro.get("ok"):
                try:
                    dsecs = tracing.CostMeter.flight_costs(
                        os.path.join(rd, "flight.jsonl"))["device_secs"]
                except Exception:  # noqa: BLE001
                    dsecs = 0.0
                try:
                    fields = memo_mod.key_fields(
                        intro, job.strict, job.chunk, job.frontier_cap,
                        job.visited_cap, tuple(job.ladder),
                        env=self._memo_env())
                    self.memo.archive(intro, fields, verdict, rd, dsecs)
                    self.memo.record_verdict(fields, job.max_depth,
                                             job.max_secs, verdict,
                                             dsecs)
                except Exception as e:  # noqa: BLE001 — reuse is best-effort
                    self.queue.log_event(
                        "memo", mode="archive_failed",
                        job_id=job.job_id,
                        error=f"{type(e).__name__}: {e}"[:200])
                if seeded and mplan is not None:
                    self.memo.bump(
                        "device_secs_saved",
                        max(0.0, mplan.base_device_secs - dsecs))
            return verdict

    def run_job_batch(self, jobs: List["Job"]) -> List[dict]:
        """Run a lane-compatible job group as ONE lane-batch warden
        child (ISSUE 14, tpu/lanes.py): every job keeps its own run
        dir + checkpoint (SIGKILL mid-batch resumes each lane from its
        own dump), continuous batching refills drained lanes from the
        group, and a poisoned lane is EVICTED to a solo retry
        (re-queued with ``solo=True``) — it never burns a lane-mate's
        verdict.  Returns the verdicts/failures that LANDED; evicted
        jobs return to the scheduler instead."""
        from dslabs_tpu.tpu.lanes import LaneBatchWarden, job_signature

        with self._lock:
            self._lane_seq += 1
            batch_id = f"batch-{self._lane_seq:05d}"
        bdir = os.path.join(self.root, "lanes", batch_id)
        os.makedirs(bdir, exist_ok=True)
        first = jobs[0]
        lane_jobs = []
        for job in jobs:
            rd = self.job_dir(job.job_id)
            os.makedirs(rd, exist_ok=True)
            lane_jobs.append({
                "job_id": job.job_id,
                "max_depth": job.max_depth,
                "max_secs": job.max_secs,
                "checkpoint_path": os.path.join(rd, "ckpt.npz"),
                "checkpoint_every": 1,
                "trace_id": job.trace_id})
            self.queue.mark_started(job.job_id, 1)
        n_lanes = min(self.lanes, len(jobs))
        # The journal join the trace assembler + packing stats read:
        # which jobs shared which batch, and where its flight log is.
        self.queue.log_event(
            "lane_batch", batch=batch_id,
            jobs=[j.job_id for j in jobs], lanes=n_lanes,
            run_dir=bdir)
        t0 = time.time()
        w = LaneBatchWarden(
            factory=first.factory,
            factory_kwargs=first.factory_kwargs,
            transform=first.transform,
            jobs=lane_jobs, n_lanes=n_lanes,
            strict=first.strict, chunk=first.chunk,
            frontier_cap=first.frontier_cap,
            visited_cap=first.visited_cap,
            run_dir=bdir, swap=self.lane_swap,
            env=dict(self.env),
            extra_sys_path=self.extra_sys_path,
            telemetry=self.telemetry)
        try:
            res = w.run()
        except BaseException as e:  # noqa: BLE001 — structured, never silent
            from dslabs_tpu.tpu.lanes import LaneBatchResult

            res = LaneBatchResult(
                {}, {j.job_id: f"batch:error: {type(e).__name__}: "
                     f"{e}"[:300] for j in jobs})
        by_id = {j.job_id: j for j in jobs}
        elapsed = round(time.time() - t0, 2)
        bflight = os.path.join(bdir, "flight.jsonl")
        results: List[dict] = []
        for jid, out in res.outcomes.items():
            job = by_id[jid]
            verdict = {
                "job_id": jid, "tenant": job.tenant,
                "trace_id": job.trace_id,
                "budget_units": job.budget_units,
                "status": "done",
                "end": out.end_condition,
                "unique": out.unique_states,
                "explored": out.states_explored,
                "depth": out.depth,
                "engine": "lanes",
                "platform": out.platform,
                "device_kind": out.device_kind,
                "attempts": 1,
                "failovers": 0,
                "child_restarts": out.child_restarts,
                "knob_shrinks": 0, "rung_steps": 0,
                "resumed_from_depth": out.resumed_from_depth,
                "degraded": out.child_restarts > 0,
                "deaths": [{"rung": "lanes", "kind": d["kind"],
                            "detail": d["detail"][:200]}
                           for d in w.deaths],
                "run_dir": self.job_dir(jid),
                "lane_batch": batch_id,
                "lane": out.lane,
                "lanes": out.lane_width,
                "lane_share": out.lane_share,
                "elapsed_secs": elapsed,
            }
            self.queue.mark_done(jid, {
                "end": out.end_condition, "unique": out.unique_states,
                "explored": out.states_explored, "depth": out.depth,
                "attempts": 1, "degraded": verdict["degraded"],
                "lane_batch": batch_id})
            # The COSTS charge reads the BATCH flight log scaled by
            # the lane's share — shares sum to 1.0, so the shared
            # dispatch stream is billed exactly once.
            try:
                self.costs.charge(verdict, bflight)
            except Exception:  # noqa: BLE001 — accounting is best-effort
                pass
            results.append(verdict)
        requeued = []
        for jid, err in res.errors.items():
            job = by_id[jid]
            self.queue.log_event("lane_evicted", job_id=jid,
                                 batch=batch_id, error=err[:200])
            requeued.append(dataclasses.replace(job, solo=True))
        with self._lock:
            for j in requeued:
                self.sched.push(j)
            ls = self.lane_stats
            ls["batches"] += 1
            ls["jobs"] += len(jobs)
            ls["swaps"] += res.swaps
            ls["evicted"] += len(res.errors)
            ls["occupancy_sum"] += res.occupancy
            sig = job_signature(first) or "?"
            per = ls["by_signature"].setdefault(
                sig, {"batches": 0, "jobs": 0})
            per["batches"] += 1
            per["jobs"] += len(jobs)
        return results

    def _charge(self, verdict: dict, run_dir: str) -> None:
        """Feed the cost meter (never fatal — accounting must not take
        a verdict down): the verdict's exact counters + the run dir's
        flight log become one COSTS.jsonl record."""
        try:
            self.costs.charge(
                verdict, os.path.join(run_dir, "flight.jsonl"))
        except Exception:  # noqa: BLE001 — accounting is best-effort
            pass

    # ---------------------------------------------------------- retention

    def retention_sweep(self) -> List[str]:
        """Prune the oldest FINISHED jobs' run dirs down to
        ``self.keep`` (DSLABS_SERVICE_KEEP).  Called at scheduler idle
        (drain start/end) — never while that job could still run:
        running and queued jobs are excluded by construction, and a
        pruned job keeps its journal/ledger records (only the run dir
        — checkpoint, flight log, compile cache — goes).  Each prune
        is journaled and, when a recorder is attached, a telemetry
        event."""
        import shutil

        with self._lock:
            busy = {j.job_id
                    for q in self.sched._queues.values() for j in q}
            running = {t for t, n in self._running.items() if n > 0}
        def _seq(jid: str) -> int:
            try:
                return int(jid.rsplit("-", 1)[-1])
            except ValueError:
                return 0

        finished = []
        for jid, rec in sorted(self.queue.records.items(),
                               key=lambda kv: _seq(kv[0])):
            if rec.get("status") not in ("done", "failed"):
                continue
            if jid in busy or rec.get("tenant") in running:
                continue
            d = self.job_dir(jid)
            if os.path.isdir(d):
                finished.append(jid)
        pruned: List[str] = []
        if self.keep >= 0 and len(finished) > self.keep:
            for jid in finished[:len(finished) - self.keep]:
                try:
                    shutil.rmtree(self.job_dir(jid))
                except OSError:
                    continue
                pruned.append(jid)
                self.queue.log_event("prune", job_id=jid,
                                     keep=self.keep)
                if self.telemetry is not None:
                    self.telemetry.event("prune", job_id=jid,
                                         keep=self.keep)
        # Lane-batch run dirs (ISSUE 14) age out under the same knob:
        # the sweep runs at scheduler idle, so no batch child is live;
        # the journal's lane_batch events (the trace join) survive.
        lanes_root = os.path.join(self.root, "lanes")
        if self.keep >= 0 and os.path.isdir(lanes_root):
            try:
                batches = sorted(os.listdir(lanes_root))
            except OSError:
                batches = []
            for b in batches[:max(0, len(batches) - self.keep)]:
                try:
                    shutil.rmtree(os.path.join(lanes_root, b))
                except OSError:
                    continue
                pruned.append(b)
                self.queue.log_event("prune", batch=b, keep=self.keep)
        return pruned

    # -------------------------------------------------------------- drain

    def drain(self, max_secs: Optional[float] = None,
              workers: Optional[int] = None) -> dict:
        """Run the backlog to completion (or the deadline) and return
        the aggregate summary — per-tenant throughput, fairness index,
        queue state.  Each worker thread coordinates; the actual
        search work lives in per-job warden child processes."""
        n_workers = max(1, workers if workers is not None
                        else self.workers)
        deadline = (time.time() + max_secs) if max_secs else None
        t0 = time.time()

        from dslabs_tpu.tpu.lanes import job_signature

        def worker():
            while True:
                if deadline is not None and time.time() > deadline:
                    return
                picked: List = []
                with self._lock:
                    if self.lanes > 1:
                        # Lane packer (ISSUE 14): group lane-compatible
                        # queued jobs under the same DRR quota/deficit
                        # semantics; over-picking up to 2L feeds
                        # continuous batching's swap-ins.
                        picked = self.sched.pick_batch(
                            self._running, job_signature,
                            self.lanes * (2 if self.lane_swap else 1))
                    else:
                        job = self.sched.pick(self._running)
                        picked = [job] if job is not None else []
                    if not picked:
                        if self.sched.pending() == 0 and self._active == 0:
                            return
                    else:
                        for job in picked:
                            self.queue.pop(job.job_id)
                            self._running[job.tenant] = \
                                self._running.get(job.tenant, 0) + 1
                            self._active += 1
                            st = self.stats.setdefault(job.tenant,
                                                       _zero_stats())
                            st["budget_spent"] += job.budget_units
                if not picked:
                    time.sleep(0.05)
                    continue
                try:
                    if len(picked) == 1:
                        res_list = [self.run_job(picked[0])]
                    else:
                        res_list = self.run_job_batch(picked)
                finally:
                    with self._lock:
                        for job in picked:
                            self._running[job.tenant] -= 1
                            self._active -= 1
                with self._lock:
                    for res in res_list:
                        st = self.stats.setdefault(res["tenant"],
                                                   _zero_stats())
                        if res.get("status") == "done":
                            st["completed"] += 1
                            st["verdicts"] += 1
                        else:
                            st["failed"] += 1
                        self.results.append(res)
                self._write_status()

        threads = [threading.Thread(target=worker, daemon=True,
                                    name=f"dslabs-service-worker-{i}")
                   for i in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # The scheduler is idle here (workers drained or deadline hit):
        # the retention sweep runs now, never beside live jobs.
        self.retention_sweep()
        self._write_status(force=True)
        with self._lock:
            results = list(self.results)
            per_tenant = {t: dict(s) for t, s in self.stats.items()}
        done = [r for r in results if r.get("status") == "done"]
        failed = [r for r in results if r.get("status") != "done"]
        wall = max(time.time() - t0, 1e-9)
        for stats in per_tenant.values():
            stats["verdicts_per_min"] = round(
                stats["verdicts"] / wall * 60.0, 2)
        totals = self.costs.totals()
        return {
            "jobs": len(results),
            "completed": len(done),
            "failed": len(failed),
            "verdicts_per_min": round(len(done) / wall * 60.0, 2),
            "fairness_index": fairness_index(per_tenant),
            # Lane amortisation (ISSUE 14): packing decisions + the
            # mean dispatches billed per job (share-scaled across lane
            # batches).
            "lanes": self._lane_block(),
            # Cross-job reuse (ISSUE 16): hits / warm starts /
            # incremental re-checks and the device-seconds they saved.
            "memo": (self.memo.stats_block() if self.memo is not None
                     else {"enabled": False}),
            "dispatches_per_job": totals.get("dispatches_per_job"),
            "per_tenant": per_tenant,
            # The cost ledger's view (tpu/tracing.py CostMeter):
            # per-tenant device-seconds / dispatches / compile split /
            # cost-per-unique-state, and the aggregate headline.
            "costs": self.costs.tenant_summary(),
            "cost_per_unique": totals.get("cost_per_unique"),
            "device_secs": totals.get("device_secs"),
            "queue": self.queue.summary(),
            "wall_secs": round(wall, 2),
            "results": results,
        }

    # ------------------------------------------------------------- status

    def _lane_block(self) -> dict:
        """The ``lanes`` observability block (SERVER_STATUS.json +
        drain summary + ``service status``): batch width/swap config,
        packing decisions, occupancy, evictions, per-signature batch
        sizes."""
        with self._lock:
            ls = self.lane_stats
            return {
                "width": self.lanes,
                "swap": self.lane_swap,
                "batches": ls["batches"],
                "jobs_in_lanes": ls["jobs"],
                "swaps": ls["swaps"],
                "evicted": ls["evicted"],
                "mean_occupancy": (
                    round(ls["occupancy_sum"] / ls["batches"], 3)
                    if ls["batches"] else None),
                "by_signature": {s: dict(v) for s, v
                                 in ls["by_signature"].items()},
            }

    def server_status(self) -> dict:
        qs = self.queue.summary()
        cost_ledger = self.costs.tenant_summary()
        lane_block = self._lane_block()
        with self._lock:
            pending = self.sched.pending_by_tenant()
            tenants = {}
            for t in set(list(self.stats) + list(pending)
                         + list(self._running)):
                s = self.stats.get(t, _zero_stats())
                tenants[t] = {
                    "pending": pending.get(t, 0),
                    "running": self._running.get(t, 0),
                    "completed": s["completed"],
                    "failed": s["failed"],
                    "rejected": s["rejected"],
                    "budget_spent": round(s["budget_spent"], 3),
                    # The auditable per-tenant cost ledger (ISSUE 13):
                    # what the tenant's budget actually bought, from
                    # COSTS.jsonl — device seconds, dispatches, the
                    # compile-vs-search split, cost per unique state.
                    "costs": cost_ledger.get(t),
                }
            return {
                "t": "server_status",
                "updated": round(time.time(), 3),
                "pid": os.getpid(),
                "workers": self.workers,
                "queue_depth": qs["queue_depth"],
                "queue_cap": qs["queue_cap"],
                "backpressure": qs["backpressure"],
                "journal_error": qs["journal_error"],
                "tenants": tenants,
                "fairness_index": fairness_index(self.stats),
                # Batched-lane observability (ISSUE 14): occupancy,
                # packing decisions, per-signature batch sizes.
                "lanes": lane_block,
                # Cross-job memoization counters (ISSUE 16).
                "memo": (self.memo.stats_block()
                         if self.memo is not None
                         else {"enabled": False}),
            }

    def _write_status(self, force: bool = False) -> None:
        """Atomic SERVER_STATUS.json rewrite (tmp + ``os.replace``) —
        a reader or a SIGKILL never sees a torn file; an unwritable
        root disables the monitor, never the service."""
        st = self.server_status()
        tmp = self.status_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(json.dumps(st))
            os.replace(tmp, self.status_path)
        except OSError:
            self.status_path = None

    def close(self) -> None:
        self.queue.close()
        self.costs.close()


# ------------------------------------------------------- admission child

def _resolve(ref: str):
    import importlib

    mod, _, name = ref.partition(":")
    obj = importlib.import_module(mod)
    for part in name.split("."):
        obj = getattr(obj, part)
    return obj


def _admission_main() -> int:
    """The CPU-pinned admission child: read one factory spec from
    stdin, lint its module with the conformance linter, build the spec
    object (NEVER a twin/engine — no search is constructed here), run
    the live C4 introspection when it is a ProtocolSpec, and print the
    waiver-applied findings as one JSON line.  Any escape is the
    parent's "child died" rejection — a hostile spec cannot get past
    the gate by crashing it."""
    os.environ["JAX_PLATFORMS"] = "cpu"         # before jax loads
    spec = json.load(sys.stdin)
    factory = spec["factory"]
    mod_name = factory.partition(":")[0]

    from dslabs_tpu.analysis import conformance
    from dslabs_tpu.analysis.core import (Finding, apply_waivers,
                                          load_waivers, repo_root)

    findings: List[Finding] = []

    def _gate(message: str, code: str = "C4", line: int = 0) -> None:
        findings.append(Finding(
            code=code, leg="conformance", path=factory,
            obj="<admission>", line=line, message=message))

    mod = None
    try:
        import importlib

        mod = importlib.import_module(mod_name)
    except BaseException as e:  # noqa: BLE001 — import errors are findings
        _gate(f"factory import failed: {type(e).__name__}: {e}")
    if mod is not None and getattr(mod, "__file__", None):
        try:
            with open(mod.__file__) as f:
                src = f.read()
            rel = os.path.relpath(mod.__file__, repo_root())
            if rel.startswith(".."):
                rel = mod.__file__
            findings += conformance.lint_source(src, rel)
        except OSError as e:
            _gate(f"factory module unreadable: {e}")
        from dslabs_tpu.tpu.compiler import ProtocolSpec, SpecError

        try:
            proto = _resolve(factory)(**(spec.get("factory_kwargs")
                                         or {}))
            if spec.get("transform"):
                proto = _resolve(spec["transform"])(proto)
            if isinstance(proto, ProtocolSpec):
                findings += conformance.check_spec(
                    proto, origin=rel if mod else factory)
        except SpecError as e:
            _gate(str(e), code=e.code, line=e.line or 0)
        except BaseException as e:  # noqa: BLE001 — a raising factory is unsound
            _gate(f"factory raised {type(e).__name__}: {e}")
    try:
        apply_waivers(findings, load_waivers())
    except ValueError as e:
        _gate(f"waiver file malformed: {e}")
    sys.stdout.write(json.dumps(
        {"findings": [f.as_dict() for f in findings]}) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(_admission_main())
