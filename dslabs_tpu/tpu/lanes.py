"""Batched job lanes: N tenant searches as ONE compiled program.

The dispatch-amortisation half of ROADMAP #2 (ISSUE 14).  The service
stack made many small searches *cheap to host* — persistent compile
cache (PR 3), per-job fault domains (PR 4), the journal queue + DRR
scheduler (PR 11), causal tracing + cost metering (PR 13) — but every
job still paid its own dispatch stream: a small student submission is
dominated by per-level host->device round-trips, not by compute.  This
module applies the engine's own trick one level up: just as states are
vmapped into a frontier, whole JOBS are stacked along a leading lane
axis and advanced by one compiled program.

* **Lane-stacked carry.**  :class:`LaneSearch` stacks L compatible
  jobs' device carries (frontier SoA, per-lane visited tables,
  counters, verdict flags) with a leading ``[L, ...]`` axis and runs
  the EXISTING single-device step body (``TensorSearch._build_dev_step``
  — the exact program the solo engine dispatches) under ``jax.vmap``
  inside a ``lax.while_loop`` *lane superstep*: ONE device dispatch per
  level advances every lane through all of its chunks (event-window
  spill passes included), draining until no lane has work.  All carry
  arithmetic is int32/uint32, so the vmapped body is **bit-identical
  per lane to its solo run** — unique/explored/verdict parity is by
  construction, and pinned by tests/test_lanes.py.
* **Finished lanes are no-ops.**  A lane whose search ended has
  ``cur_n == 0``: the step body's validity masks make every subsequent
  wave a provable no-op on its counters (the same masking that makes
  the solo loop's speculative dispatch safe), so mixed-depth batches
  never corrupt a neighbor.
* **Continuous batching.**  At a level boundary a drained lane is
  refilled from the pending job list by ``lanes.inject`` — a jitted
  one-hot splice of a fresh root carry — with ZERO recompiles: the
  programs are keyed on (lane signature, L) and live in the persistent
  compile cache like every other engine program.
* **Per-lane fault domain inside one process.**  Each lane keeps its
  OWN run dir checkpoint (the engine-agnostic tpu/checkpoint.py dump,
  fingerprint-compatible with a solo resume); a SIGKILL mid-batch
  resumes every lane from its own dump, and a poisoned lane (capacity
  overflow, strict-table pressure) is EVICTED to a solo retry — its
  error never burns a lane-mate (the neighbors' carries are untouched
  by construction).
* **Cost splitting.**  Every shared dispatch's wall clock is divided
  evenly across the lanes resident at that level; a lane's
  ``lane_share`` (shares of a batch sum to 1.0) scales its COSTS.jsonl
  charge (tpu/tracing.py), so per-tenant bills DROP as batching
  improves instead of double-billing the shared program.

Process isolation mirrors tpu/warden.py: :class:`LaneBatchWarden`
spawns ``python -m dslabs_tpu.tpu.lanes`` as one supervised child per
lane batch (heartbeats from the dispatch seam, announced grace,
SIGKILL + classify + resume on silence), streaming per-lane results as
lanes finish so a late crash never loses an early verdict.

Knobs: ``DSLABS_LANES`` (service batch width, 0/1 = off),
``DSLABS_LANE_SWAP`` (continuous batching on/off, default on),
``DSLABS_LANE_RESTARTS`` (batch child respawns before solo eviction).
See docs/service.md "Batched job lanes" and docs/perf.md.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from dslabs_tpu.tpu import visited as visited_mod
from dslabs_tpu.tpu.engine import (SearchOutcome, TensorSearch,
                                   device_get, flatten_state)

__all__ = ["LaneSearch", "LaneJob", "LaneBatchResult", "LaneBatchWarden",
           "job_signature", "lanes_enabled", "lane_swap_enabled"]


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def lanes_enabled(default: int = 0) -> int:
    """The service-side batch width: DSLABS_LANES (<= 1 means off)."""
    return max(0, _env_int("DSLABS_LANES", default))


def lane_swap_enabled() -> bool:
    """Continuous batching (refill drained lanes from the pending
    list): DSLABS_LANE_SWAP, default ON whenever lanes are on."""
    return os.environ.get("DSLABS_LANE_SWAP", "1").strip().lower() \
        not in ("0", "off", "false", "no")


def job_signature(job) -> Optional[str]:
    """The scheduler-side lane packing key for a service
    :class:`~dslabs_tpu.service.queue.Job` — two jobs may share a lane
    batch iff this string matches (same factory spec -> same compiled
    twin; same engine knobs -> same program shapes; the engine-side
    twin of :meth:`TensorSearch.lane_signature`).  ``None`` = not
    lane-eligible: chaos-fault jobs, jobs already evicted to solo, and
    jobs whose ladder leads with a non-device rung run alone."""
    if getattr(job, "fault", None) or getattr(job, "solo", False):
        return None
    ladder = tuple(getattr(job, "ladder", ()) or ())
    if ladder and ladder[0] != "device":
        return None
    return json.dumps(
        [job.factory, job.factory_kwargs or {}, job.transform,
         bool(job.strict), int(job.chunk), int(job.frontier_cap),
         int(job.visited_cap)], sort_keys=True)


@dataclasses.dataclass
class LaneJob:
    """One job of a lane batch: identity + per-lane limits + the
    lane's own durable run dir.  The protocol itself is shared — lane
    compatibility (one factory spec, one knob set) is the CALLER's
    contract, enforced upstream by :func:`job_signature`."""

    job_id: str
    max_depth: Optional[int] = None
    max_secs: Optional[float] = None
    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 0
    trace_id: Optional[str] = None
    # Optional batch-1 state pytree to start from (staged searches);
    # host arrays, never crosses a spawn boundary.
    initial: Optional[dict] = None


@dataclasses.dataclass
class LaneBatchResult:
    """What one lane batch produced: per-job verdicts (bit-identical
    to solo runs), per-job eviction errors (poisoned lanes the caller
    retries solo), and the shared-dispatch accounting the cost meter
    splits."""

    outcomes: Dict[str, SearchOutcome]
    errors: Dict[str, str]
    swaps: int = 0
    levels: int = 0
    dispatches: float = 0.0
    device_secs: float = 0.0
    occupancy: float = 0.0          # mean resident lanes per level
    child_restarts: int = 0
    killed_dispatches: int = 0


class _Lane:
    """Host-side state of one resident lane."""

    __slots__ = ("idx", "job", "t0", "depth", "last", "active",
                 "device_secs", "dispatches", "prev_explored")

    def __init__(self, idx: int, job: LaneJob, t0: float,
                 depth: int = 0, last=(0, 1, 0)):
        self.idx = idx
        self.job = job
        self.t0 = t0
        self.depth = depth
        self.last = last            # (explored, unique, vis_over)
        self.active = True
        self.device_secs = 0.0
        self.dispatches = 0.0
        self.prev_explored = last[0]


class LaneSearch(TensorSearch):
    """The lane-stacked engine.  Construction mirrors
    :class:`TensorSearch` (one shared protocol + knob set = the lane
    signature); :meth:`run_lanes` drives a whole batch to per-lane
    verdicts.  Spill and trace recording are solo-only features — a
    job that needs them is not lane-eligible."""

    def __init__(self, protocol, n_lanes: int,
                 frontier_cap: int = 1 << 14,
                 chunk: int = 1 << 10,
                 max_secs: Optional[float] = None,
                 ev_budget=None,
                 visited_cap: int = 1 << 20,
                 strict: bool = True,
                 telemetry=None):
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        super().__init__(protocol, frontier_cap=frontier_cap,
                         chunk=chunk, max_secs=max_secs,
                         ev_budget=ev_budget, visited_cap=visited_cap,
                         strict=strict, spill=False,
                         telemetry=telemetry)
        self.L = int(n_lanes)
        # The solo loop grows its frontier buffer geometrically; lanes
        # run at the full user cap from the start — counters are
        # cap-independent below the overflow point, and a drop at the
        # user cap is the same CAPACITY_EXHAUSTED verdict the solo
        # run's final growth rung lands (parity-pinned).
        self._cap = -(-frontier_cap // chunk) * chunk
        self._lane_prog_cache: Optional[dict] = None
        self._maybe_sanitize()

    # ------------------------------------------------------------ plumbing

    def _dispatch(self, tag: str, fn, *args):
        # The probe/insert has no Pallas batching rule; pin the
        # bit-identical jnp oracle for anything traced under the lane
        # vmap (trace-time only — solo engines in the same process are
        # untouched, pinned by test).
        with visited_mod.force_jnp():
            return super()._dispatch(tag, fn, *args)

    def _lane_progs(self) -> dict:
        """The jitted lane programs, built once per engine (keyed by
        (lane signature, L) across processes via the persistent XLA
        compile cache — a resident server never recompiles for a new
        batch of the same shape)."""
        if self._lane_prog_cache is not None:
            return self._lane_prog_cache
        import jax
        import jax.numpy as jnp

        cap = self._cap
        C = self.chunk
        L = self.L
        step = self._build_dev_step(cap)
        promote = self._build_dev_promote(cap)
        build = self._build_dev_init(cap)

        def stats_of(carry):
            base = jnp.stack([
                carry["explored"][:, 0], carry["overflow"][:, 0],
                carry["vis_over"][:, 0], carry["f_drop"][:, 0],
                carry["vis_n"][:, 0], carry["nxt_n"][:, 0],
                carry["j"][:, 0]], axis=1)
            return jnp.concatenate(
                [base, carry["flag_cnt"]], axis=1).astype(jnp.int32)

        def superstep(carry, masks):
            # One dispatch = one whole LEVEL for every lane: drain
            # until no lane has an unstepped chunk.  A lane past its
            # own chunk count (or finished: cur_n == 0) no-ops — the
            # step body's validity masks freeze its counters exactly.
            def cond(c):
                return jnp.any(c["j"][:, 0] * C < c["cur_n"][:, 0])

            def body(c):
                c2, _ = jax.vmap(step, in_axes=(0, None))(c, masks)
                return c2

            out = jax.lax.while_loop(cond, body, carry)
            return out, stats_of(out)

        def promote_live(carry, live):
            out = jax.vmap(promote)(carry)
            # Retired lanes (verdict landed / poisoned / swapped out)
            # present an empty frontier from here on.
            out["cur_n"] = jnp.where(live[:, None], out["cur_n"], 0)
            return out

        def init_all(rows0, live):
            carry = jax.vmap(build)(rows0)
            carry["cur_n"] = jnp.where(live[:, None], carry["cur_n"], 0)
            return carry

        def _splice(carry, onehot, fresh):
            def mix(c, f):
                oh = onehot.reshape((L,) + (1,) * (c.ndim - 1))
                return jnp.where(oh, f[None], c)

            return jax.tree.map(mix, carry, fresh)

        def inject(carry, onehot, row0):
            # Continuous-batching swap-in: rebuild ONE lane from a
            # fresh root through the SAME init body solo uses (same
            # table insert, bit-identical lane state).
            return _splice(carry, onehot, build(row0))

        def restore(carry, onehot, lane_carry):
            # Resume splice: a host-rebuilt solo carry (from the
            # lane's own checkpoint) replaces lane ``onehot``.
            return _splice(carry, onehot, lane_carry)

        self._lane_prog_cache = {
            "superstep": jax.jit(superstep, donate_argnums=0),
            "promote": jax.jit(promote_live, donate_argnums=0),
            "init": jax.jit(init_all),
            "inject": jax.jit(inject, donate_argnums=0),
            "restore": jax.jit(restore, donate_argnums=0),
            "builders": {
                "superstep": lambda: jax.jit(superstep,
                                             donate_argnums=0),
                "promote": lambda: jax.jit(promote_live,
                                           donate_argnums=0),
                "init": lambda: jax.jit(init_all),
                "inject": lambda: jax.jit(inject, donate_argnums=0),
            },
        }
        return self._lane_prog_cache

    def dispatch_site_programs(self) -> Dict[str, dict]:
        """Sanitizer registry (ISSUE 10 contract): every lane program
        the batch loop dispatches, with abstract args — so ``analysis
        all`` audits the lane hot path (J1-J5) exactly like the solo
        engines' and a new lane site missing from
        ``telemetry.DISPATCH_SITES`` is a loud J0."""
        import jax
        import jax.numpy as jnp

        with visited_mod.force_jnp():
            progs = self._lane_progs()
            L, cap = self.L, self._cap
            rows_sds = jax.ShapeDtypeStruct((L, 1, self.lanes),
                                            jnp.int32)
            row_sds = jax.ShapeDtypeStruct((1, self.lanes), jnp.int32)
            live_sds = jax.ShapeDtypeStruct((L,), jnp.bool_)
            carry_sds = jax.eval_shape(progs["init"], rows_sds,
                                       live_sds)
            lane_sds = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                carry_sds)
        rt = getattr(self, "_rt_masks", None)
        b = progs["builders"]
        sites = {
            "lanes.init": dict(
                fn=progs["init"], args=(rows_sds, live_sds),
                donate=(), multi=False, builder=b["init"]),
            "lanes.superstep": dict(
                fn=progs["superstep"], args=(carry_sds, rt),
                donate=(0,), multi=False, builder=b["superstep"]),
            "lanes.promote": dict(
                fn=progs["promote"], args=(carry_sds, live_sds),
                donate=(0,), multi=False, builder=b["promote"]),
            "lanes.inject": dict(
                fn=progs["inject"], args=(carry_sds, live_sds, row_sds),
                donate=(0,), multi=False, builder=b["inject"]),
            "lanes.restore": dict(
                fn=progs["restore"], args=(carry_sds, live_sds,
                                           lane_sds),
                donate=(0,), multi=False, builder=None),
            "visited.insert": visited_mod.dispatch_site_program(
                self.visited_cap, self.chunk * self._num_events()),
        }
        return sites

    # ------------------------------------------------------------ helpers

    def _onehot(self, i: int):
        import jax.numpy as jnp

        return jnp.arange(self.L) == i

    def _lane_root(self, job: LaneJob):
        """(state pytree, [1, lanes] root row) for a fresh lane."""
        import jax
        import jax.numpy as jnp

        state = (jax.tree.map(jnp.asarray, job.initial)
                 if job.initial is not None else self.initial_state())
        return state, flatten_state(state)

    def _lane_seed(self, job: LaneJob, resume: bool):
        """How a lane starts: ``("done", outcome)`` (initial-state
        verdict / depth-0 exhaustion / finished checkpoint),
        ``("ckpt", solo_carry, ck)`` (resume splice), or
        ``("fresh", row0)``."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        t0 = time.time()
        if resume and job.checkpoint_path:
            fp = ckpt_mod.peek_fingerprint(job.checkpoint_path)
            if fp is not None and fp == self._ckpt_fingerprint():
                ck = ckpt_mod.load(job.checkpoint_path,
                                   self._ckpt_fingerprint())
                if ck is not None:
                    # Normalize the dump's frontier encoding to raw
                    # (loud cross-encoding conversion, ISSUE 15a) —
                    # _carry_from_ckpt then re-packs to native.
                    self._normalize_ckpt_frontier(ck)
                    if not len(ck.frontier):
                        out = SearchOutcome(
                            "SPACE_EXHAUSTED", ck.explored,
                            len(ck.visited_keys), ck.depth,
                            ck.elapsed, visited_overflow=ck.vis_over)
                        return ("done", out)
                    return ("ckpt", ck)
        state, row0 = self._lane_root(job)
        out = self._check_initial(state, t0)
        if out is not None:
            return ("done", out)
        if job.max_depth is not None and job.max_depth <= 0:
            return ("done", SearchOutcome(
                "DEPTH_EXHAUSTED", 0, 1, 0, time.time() - t0))
        return ("fresh", row0)

    def _lane_terminal(self, rows: np.ndarray, flag_counts,
                       explored: int, vis_n: int, depth: int,
                       elapsed: float, vis_over: int) -> SearchOutcome:
        """Per-lane twin of ``TensorSearch._dev_terminal`` (checkState
        order: exception -> invariant -> goal), over one lane's
        already-fetched flag rows."""
        import jax

        for fi, fname in enumerate(self._flag_names):
            if flag_counts[fi] <= 0:
                continue
            st = jax.tree.map(np.asarray,
                              self.unflatten_rows(rows[fi][None]))
            if fname == "exc":
                return SearchOutcome(
                    "EXCEPTION_THROWN", explored, vis_n, depth, elapsed,
                    violating_state=st, exception_code=int(st["exc"][0]),
                    visited_overflow=vis_over)
            kind, pname = fname.split(":", 1)
            if kind == "inv":
                return SearchOutcome(
                    "INVARIANT_VIOLATED", explored, vis_n, depth,
                    elapsed, violating_state=st, predicate_name=pname,
                    visited_overflow=vis_over)
            return SearchOutcome(
                "GOAL_FOUND", explored, vis_n, depth, elapsed,
                goal_state=st, predicate_name=pname,
                visited_overflow=vis_over)
        raise AssertionError("lane flag counts fired without a name")

    def _lane_ckpt(self, carry, ln: _Lane, nxt_n: int) -> None:
        """One lane's durable dump (post-promote: ``cur`` is the next
        level's frontier) — the engine-agnostic unified format, so a
        poisoned lane's SOLO retry resumes this exact dump."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        i = ln.idx
        if nxt_n:
            frontier = np.asarray(carry["cur"][i][:nxt_n])
        else:
            frontier = np.zeros((0, self.plane), np.int32)
        occ = visited_mod.host_occupied(np.asarray(carry["visited"][i]))
        extra = None
        if self._pk is not None:
            # Lane carries share the solo step body, so cur holds the
            # PACKED encoding (ISSUE 15a) — mark the dump for loud
            # cross-resume conversion like every other writer.
            extra = {"frontier_encoding": np.bytes_(
                self._frontier_encoding().encode())}
        ckpt_mod.save(ln.job.checkpoint_path, ckpt_mod.SearchCheckpoint(
            fingerprint=self._ckpt_fingerprint(), depth=ln.depth,
            explored=ln.last[0], elapsed=time.time() - ln.t0,
            frontier=frontier, visited_keys=occ, vis_over=ln.last[2],
            extra=extra))

    # ----------------------------------------------------------------- run

    def run_lanes(self, jobs: List[LaneJob], resume: bool = False,
                  swap: bool = True,
                  on_lane: Optional[Callable] = None) -> LaneBatchResult:
        """Drive every job to a verdict (or an eviction error).  The
        first L jobs seat immediately; the rest refill drained lanes
        at level boundaries when ``swap`` (continuous batching) is on
        — with it off, overflow jobs run in follow-on seatings of the
        same compiled programs.  ``on_lane(job_id, outcome_or_None,
        error_or_None, lane_secs)`` streams results as lanes retire
        (the batch child forwards them over the pipe, so a late crash
        never loses an early verdict)."""
        import jax.numpy as jnp

        if not jobs:
            return LaneBatchResult({}, {})
        progs = self._lane_progs()
        rt = getattr(self, "_rt_masks", None)
        L = self.L
        nf = len(self._flag_names)
        res = LaneBatchResult({}, {})
        pending = list(jobs)
        t_run = time.time()
        lane_secs: Dict[str, float] = {}

        def _finish(ln: Optional[_Lane], job: LaneJob,
                    out: Optional[SearchOutcome],
                    error: Optional[str]) -> None:
            if out is not None:
                out.engine = "lanes"
                self._stamp_device(out)
                out.lane = ln.idx if ln is not None else None
                out.lane_width = L
                if out.trace_id is None:
                    out.trace_id = job.trace_id
                lane_secs[job.job_id] = (ln.device_secs if ln is not None
                                         else 0.0)
                res.outcomes[job.job_id] = out
                tel = getattr(self, "_telemetry", None)
                if tel is not None:
                    tel.on_outcome(out, engine="lanes")
            else:
                res.errors[job.job_id] = error or "lane error"
                tel = getattr(self, "_telemetry", None)
                if tel is not None:
                    tel.event("lane_evicted", job_id=job.job_id,
                              error=(error or "")[:200])
            if on_lane is not None:
                on_lane(job.job_id, out, error,
                        lane_secs.get(job.job_id, 0.0))

        # ---- seat the initial lanes (one vmapped init dispatch; any
        # resumed lane is then spliced from its own checkpoint).
        lanes: List[Optional[_Lane]] = [None] * L
        splices: List[Tuple[int, object]] = []
        root_rows = np.zeros((L, 1, self.lanes), np.int32)
        live0 = np.zeros((L,), bool)
        i = 0
        while i < L and pending:
            job = pending.pop(0)
            kind, *rest = self._lane_seed(job, resume)
            if kind == "done":
                _finish(None, job, rest[0], None)
                continue
            ln = _Lane(i, job, time.time())
            if kind == "ckpt":
                ck = rest[0]
                ln.t0 = time.time() - ck.elapsed
                ln.depth = ck.depth
                ln.last = (ck.explored, len(ck.visited_keys),
                           ck.vis_over)
                ln.prev_explored = ck.explored
                splices.append((i, ck))
            else:
                root_rows[i] = np.asarray(rest[0])
            lanes[i] = ln
            live0[i] = True
            i += 1
        if not any(live0):
            return res
        carry = self._dispatch("lanes.init", progs["init"],
                               jnp.asarray(root_rows),
                               jnp.asarray(live0))
        res.dispatches += 1.0
        for idx, ck in splices:
            lane_carry = self._carry_from_ckpt(ck, self._cap)
            carry = self._dispatch("lanes.restore", progs["restore"],
                                   carry, self._onehot(idx), lane_carry)
            res.dispatches += 1.0
            lanes[idx].dispatches += 1.0

        def _swap_in(idx: int) -> bool:
            """Refill lane ``idx`` from the pending list; True when a
            job was seated."""
            while pending:
                job = pending.pop(0)
                kind, *rest = self._lane_seed(job, resume)
                if kind == "done":
                    _finish(None, job, rest[0], None)
                    continue
                ln = _Lane(idx, job, time.time())
                nonlocal carry
                if kind == "ckpt":
                    ck = rest[0]
                    ln.t0 = time.time() - ck.elapsed
                    ln.depth = ck.depth
                    ln.last = (ck.explored, len(ck.visited_keys),
                               ck.vis_over)
                    ln.prev_explored = ck.explored
                    lane_carry = self._carry_from_ckpt(ck, self._cap)
                    carry = self._dispatch(
                        "lanes.restore", progs["restore"], carry,
                        self._onehot(idx), lane_carry)
                else:
                    carry = self._dispatch(
                        "lanes.inject", progs["inject"], carry,
                        self._onehot(idx), rest[0])
                res.dispatches += 1.0
                ln.dispatches += 1.0
                lanes[idx] = ln
                res.swaps += 1
                tel = getattr(self, "_telemetry", None)
                if tel is not None:
                    tel.event("lane_swap_in", lane=idx,
                              job_id=job.job_id, depth_neighbors=[
                                  l.depth for l in lanes
                                  if l is not None and l.active])
                return True
            return False

        # ---- the level loop: superstep -> sync -> per-lane verdict
        # extraction -> masked promote -> per-lane checkpoints ->
        # swap-ins.  One superstep + one promote per LEVEL for the
        # whole batch — the dispatches-per-job amortisation
        # tests/test_lanes.py pins.
        tel = getattr(self, "_telemetry", None)
        while True:
            active = [ln for ln in lanes if ln is not None and ln.active]
            if not active:
                break
            self._current_depth = max(ln.depth for ln in active) + 1
            t_level = time.time()
            carry, sdev = self._dispatch("lanes.superstep",
                                         progs["superstep"], carry, rt)
            s = self._dispatch("lanes.sync", device_get, sdev)
            wall = time.time() - t_level
            share = wall / len(active)
            res.dispatches += 2.0
            res.device_secs += wall
            res.levels += 1
            res.occupancy += len(active)
            retiring: List[_Lane] = []
            lane_records = []
            for ln in active:
                ln.depth += 1
                ln.device_secs += share
                ln.dispatches += 2.0 / len(active)
                row = s[ln.idx]
                explored, overflow, vis_over, f_drop, vis_n, nxt_n = (
                    int(x) for x in row[:6])
                flag_counts = np.asarray(row[7:7 + nf])
                elapsed = time.time() - ln.t0
                job = ln.job
                p = self.p
                # The level record covers every lane RESIDENT during
                # this level — retiring lanes included (the monitor
                # must show the level that finished them).
                lane_records.append(
                    (ln, explored - ln.prev_explored, vis_n, nxt_n))
                ln.prev_explored = explored
                ln.last = (explored, vis_n, vis_over)
                if overflow:
                    # The solo contract raises CapacityOverflow; in a
                    # batch the lane is POISONED and evicted to a solo
                    # retry — lane-mates never see it.
                    _finish(ln, job, None,
                            f"CapacityOverflow: {p.name}: net_cap="
                            f"{p.net_cap}, timer_cap={p.timer_cap}, or "
                            f"max_live_sends={p.max_live_sends} "
                            f"overflowed at depth {ln.depth} "
                            f"({overflow} drops)")
                    retiring.append(ln)
                    continue
                if self.strict and (vis_over
                                    or vis_n > 3 * self.visited_cap // 4):
                    _finish(ln, job, None,
                            f"CapacityOverflow: {p.name}: visited "
                            f"table pressure at depth {ln.depth} "
                            f"({vis_n}/{self.visited_cap} occupied, "
                            f"{vis_over} unresolved); raise "
                            "visited_cap or retry solo with spill")
                    retiring.append(ln)
                    continue
                if flag_counts.any():
                    rows = self._dispatch("lanes.flags", device_get,
                                          carry["flag_rows"][ln.idx])
                    res.dispatches += 1.0
                    ln.dispatches += 1.0
                    out = self._lane_terminal(
                        rows, flag_counts, explored, vis_n, ln.depth,
                        elapsed, vis_over)
                    _finish(ln, job, out, None)
                    retiring.append(ln)
                    continue
                if f_drop:
                    out = SearchOutcome(
                        "CAPACITY_EXHAUSTED", explored, vis_n,
                        ln.depth, elapsed, visited_overflow=vis_over)
                    _finish(ln, job, out, None)
                    retiring.append(ln)
                    continue
                if nxt_n == 0:
                    out = SearchOutcome(
                        "SPACE_EXHAUSTED", explored, vis_n, ln.depth,
                        elapsed, visited_overflow=vis_over)
                    _finish(ln, job, out, None)
                    retiring.append(ln)
                    continue
                # Pre-NEXT-level limits, the solo loop's ordering: the
                # completed depth is checked before another level runs.
                if (job.max_depth is not None
                        and ln.depth >= job.max_depth):
                    out = SearchOutcome(
                        "DEPTH_EXHAUSTED", explored, vis_n, ln.depth,
                        elapsed, visited_overflow=vis_over)
                    _finish(ln, job, out, None)
                    retiring.append(ln)
                    continue
                if ((job.max_secs is not None and elapsed > job.max_secs)
                        or (self.max_secs is not None
                            and time.time() - t_run > self.max_secs)
                        or self._cancelled()):
                    out = SearchOutcome(
                        "TIME_EXHAUSTED", explored, vis_n, ln.depth,
                        elapsed, visited_overflow=vis_over,
                        cancelled=self._cancelled())
                    _finish(ln, job, out, None)
                    retiring.append(ln)
                    continue
            if tel is not None:
                from dslabs_tpu.tpu import telemetry as tel_mod

                deltas = [d for _, d, _, _ in lane_records] or [0]
                tel.on_level("lanes", {
                    "depth": max((ln.depth for ln in active)),
                    "wall": round(wall, 4),
                    "explored": sum(ln.last[0] for ln in active),
                    "unique": sum(ln.last[1] for ln in active),
                    "next_frontier": sum(n for _, _, _, n
                                         in lane_records),
                    "load_factor": round(
                        max((ln.last[1] for ln in active))
                        / self.visited_cap, 4),
                    "per_device": {
                        "explored": deltas,
                        "frontier": [n for _, _, _, n in lane_records]
                        or [0],
                        "load_factor": [round(v / self.visited_cap, 4)
                                        for _, _, v, _ in lane_records]
                        or [0.0],
                        "drops": [0] * max(len(lane_records), 1)},
                    "skew": {"explored": tel_mod.skew_metrics(deltas)},
                    # The batched-child monitor block (schema-pinned):
                    # per-lane job/depth/explored so `telemetry watch`
                    # renders every resident lane of one process.
                    "lanes": [{
                        "lane": ln.idx, "job_id": ln.job.job_id,
                        "depth": ln.depth, "explored": ln.last[0],
                        "unique": ln.last[1], "frontier": n}
                        for ln, _, _, n in lane_records],
                })
            for ln in retiring:
                ln.active = False
            live = np.array([ln is not None and ln.active
                             for ln in lanes], bool)
            carry = self._dispatch("lanes.promote", progs["promote"],
                                   carry, jnp.asarray(live))
            res.dispatches += 1.0
            still = [ln for ln in lanes if ln is not None and ln.active]
            for ln in still:
                ln.dispatches += 1.0 / len(still)
            # Post-promote: cur is the NEXT level's frontier — the
            # same boundary the solo device loop dumps at.
            for ln in still:
                if (ln.job.checkpoint_path and ln.job.checkpoint_every
                        and ln.depth % ln.job.checkpoint_every == 0):
                    nxt_n = int(s[ln.idx][5])
                    self._lane_ckpt(carry, ln, nxt_n)
            if swap and pending:
                for idx in range(L):
                    if lanes[idx] is None or not lanes[idx].active:
                        if not _swap_in(idx):
                            break
        # Follow-on seatings when continuous batching is off (same
        # compiled programs — the jobs queue behind the batch).
        if pending:
            tail = self.run_lanes(pending, resume=resume, swap=swap,
                                  on_lane=on_lane)
            res.outcomes.update(tail.outcomes)
            res.errors.update(tail.errors)
            res.swaps += tail.swaps
            res.levels += tail.levels
            res.dispatches += tail.dispatches
            res.device_secs += tail.device_secs
            res.occupancy += tail.occupancy * max(tail.levels, 1)
            for jid in tail.outcomes:
                lane_secs[jid] = (tail.outcomes[jid].lane_share or 0.0
                                  ) * max(tail.device_secs, 0.0)
        if res.levels:
            res.occupancy = round(res.occupancy / res.levels, 3)
        # Cost split: each lane's share of the batch's shared device
        # seconds — the shares of a batch sum to 1.0, so the cost
        # meter (tpu/tracing.py) never double-charges a dispatch.
        for jid, out in res.outcomes.items():
            out.lane_share = (
                round(lane_secs.get(jid, 0.0) / res.device_secs, 6)
                if res.device_secs > 0 else 0.0)
        return res


# --------------------------------------------------------- batch warden

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class LaneBatchWarden:
    """Parent half of the lane-batch fault domain (the tpu/warden.py
    pattern, one child per BATCH): spawn ``python -m
    dslabs_tpu.tpu.lanes``, enforce announced heartbeat grace with
    SIGKILL, collect per-lane results AS THEY STREAM (a late crash
    never loses an early verdict), and respawn with ``resume=True`` so
    every unfinished lane continues from its own checkpoint.  After
    ``max_restarts`` deaths the unfinished jobs come back as per-job
    errors — the caller (service/server.py) evicts them to solo
    retries, never burning finished lane-mates."""

    def __init__(self, factory: str, jobs: List[dict],
                 n_lanes: int,
                 factory_kwargs: Optional[dict] = None,
                 transform: Optional[str] = None,
                 strict: bool = True,
                 chunk: int = 1 << 10,
                 frontier_cap: int = 1 << 14,
                 visited_cap: int = 1 << 20,
                 ev_budget=None,
                 max_secs: Optional[float] = None,
                 run_dir: Optional[str] = None,
                 swap: bool = True,
                 env: Optional[dict] = None,
                 extra_sys_path: Optional[List[str]] = None,
                 boot_grace: float = 240.0,
                 first_grace: Optional[float] = None,
                 steady_grace: float = 120.0,
                 idle_grace: float = 300.0,
                 grace_slack: float = 5.0,
                 fault: Optional[dict] = None,
                 max_restarts: Optional[int] = None,
                 force_cpu: bool = False,
                 telemetry=None):
        self.factory = factory
        self.factory_kwargs = factory_kwargs or {}
        self.transform = transform
        self.jobs = list(jobs)
        self.n_lanes = int(n_lanes)
        self.strict = strict
        self.chunk = chunk
        self.frontier_cap = frontier_cap
        self.visited_cap = visited_cap
        self.ev_budget = ev_budget
        self.max_secs = max_secs
        self.run_dir = run_dir
        self.swap = bool(swap)
        self.env = dict(env or {})
        self.extra_sys_path = list(extra_sys_path or [])
        self.boot_grace = boot_grace
        self.first_grace = (boot_grace if first_grace is None
                            else first_grace)
        self.steady_grace = steady_grace
        self.idle_grace = idle_grace
        self.grace_slack = grace_slack
        self.fault = fault
        self.max_restarts = (max_restarts if max_restarts is not None
                             else _env_int("DSLABS_LANE_RESTARTS", 2))
        self.force_cpu = bool(force_cpu)
        self.telemetry = telemetry
        self.deaths: List[dict] = []
        self.killed_dispatches = 0

    def _spec(self, jobs: List[dict], resume: bool,
              spawn_index: int) -> dict:
        return {
            "factory": self.factory,
            "factory_kwargs": self.factory_kwargs,
            "transform": self.transform,
            "jobs": jobs,
            "n_lanes": min(self.n_lanes, max(len(jobs), 1)),
            "strict": self.strict,
            "chunk": self.chunk,
            "frontier_cap": self.frontier_cap,
            "visited_cap": self.visited_cap,
            "ev_budget": (list(self.ev_budget)
                          if isinstance(self.ev_budget, tuple)
                          else self.ev_budget),
            "max_secs": self.max_secs,
            "run_dir": self.run_dir,
            "swap": self.swap,
            "resume": resume,
            "force_cpu": self.force_cpu,
            "grace": {"boot": self.boot_grace,
                      "first": self.first_grace,
                      "steady": self.steady_grace,
                      "idle": self.idle_grace},
            "fault": self.fault,
            "spawn_index": spawn_index,
        }

    def _child_env(self) -> dict:
        env = dict(os.environ)
        paths = [_REPO_ROOT] + self.extra_sys_path
        if env.get("PYTHONPATH"):
            paths.append(env["PYTHONPATH"])
        env["PYTHONPATH"] = os.pathsep.join(paths)
        env["DSLABS_LANE_CHILD"] = "1"
        if self.force_cpu:
            env["JAX_PLATFORMS"] = "cpu"
        env.update(self.env)
        return env

    def run(self, resume: bool = False) -> LaneBatchResult:
        import queue as queue_mod

        from dslabs_tpu.tpu.supervisor import classify_child_death
        from dslabs_tpu.tpu.warden import LineWatch, outcome_from_dict

        res = LaneBatchResult({}, {})
        lane_secs: Dict[str, float] = {}
        remaining = {j["job_id"]: j for j in self.jobs}
        spawn = 0
        while remaining:
            spec = self._spec(list(remaining.values()),
                              resume or spawn > 0, spawn)
            proc = subprocess.Popen(
                [sys.executable, "-m", "dslabs_tpu.tpu.lanes"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
                env=self._child_env())

            def _tee(line):
                sys.stderr.write(line)
                sys.stderr.flush()

            err_watch = LineWatch(proc, proc.stderr, on_line=_tee)
            try:
                proc.stdin.write(json.dumps(spec))
                proc.stdin.close()
            except BrokenPipeError:
                pass
            msgs: "queue_mod.Queue[dict]" = queue_mod.Queue()

            def _read(stdout=proc.stdout):
                for line in stdout:
                    try:
                        msgs.put(json.loads(line))
                    except ValueError:
                        continue
                msgs.put({"t": "eof"})

            threading.Thread(target=_read, daemon=True).start()
            grace = self.boot_grace
            last_hb: Optional[dict] = None
            death: Optional[dict] = None
            finished = False
            while True:
                try:
                    msg = msgs.get(timeout=grace + self.grace_slack)
                except queue_mod.Empty:
                    try:
                        proc.kill()
                    except OSError:
                        pass
                    proc.wait()
                    in_dispatch = (last_hb is not None
                                   and last_hb.get("phase") == "start")
                    if in_dispatch:
                        self.killed_dispatches += 1
                    death = {"kind": "wedge",
                             "detail": (f"lane child silent > "
                                        f"{grace:.1f}s; SIGKILLed"),
                             "exitcode": proc.returncode,
                             "last_hb": last_hb}
                    break
                t = msg.get("t")
                if t == "hb":
                    last_hb = msg
                    grace = float(msg.get("grace", self.steady_grace))
                    if self.telemetry is not None:
                        self.telemetry.event(
                            "heartbeat", rung="lanes",
                            phase=msg.get("phase"), tag=msg.get("tag"),
                            n=msg.get("n"), depth=msg.get("depth"),
                            grace=msg.get("grace"))
                    continue
                if t == "lane_result":
                    jid = msg.get("job_id")
                    out = outcome_from_dict(msg["outcome"])
                    res.outcomes[jid] = out
                    lane_secs[jid] = float(msg.get("lane_secs", 0.0)
                                           or 0.0)
                    remaining.pop(jid, None)
                    continue
                if t == "lane_error":
                    jid = msg.get("job_id")
                    res.errors[jid] = msg.get("error", "lane error")
                    remaining.pop(jid, None)
                    continue
                if t == "result":
                    proc.wait()
                    res.swaps += int(msg.get("swaps", 0) or 0)
                    res.levels += int(msg.get("levels", 0) or 0)
                    res.dispatches += float(msg.get("dispatches", 0.0)
                                            or 0.0)
                    res.device_secs += float(msg.get("device_secs",
                                                     0.0) or 0.0)
                    res.occupancy = float(msg.get("occupancy", 0.0)
                                          or 0.0) or res.occupancy
                    finished = True
                    break
                if t == "err":
                    try:
                        rc = proc.wait(timeout=30.0)
                    except subprocess.TimeoutExpired:
                        proc.kill()
                        rc = proc.wait()
                    death = {"kind": classify_child_death(
                                 rc, False, err_watch.tail),
                             "detail": msg.get("error", "lane child "
                                               "failure"),
                             "exitcode": rc, "last_hb": last_hb}
                    break
                if t == "eof":
                    rc = proc.wait()
                    kind = classify_child_death(rc, False,
                                                err_watch.tail)
                    death = {"kind": kind, "exitcode": rc,
                             "last_hb": last_hb,
                             "detail": (f"lane child exited rc={rc} "
                                        f"without a result "
                                        f"(classified {kind})")}
                    break
            if finished:
                break
            self.deaths.append(death)
            res.child_restarts += 1
            if self.telemetry is not None:
                self.telemetry.event(
                    "lane_child_death", kind=death["kind"],
                    exitcode=death.get("exitcode"),
                    detail=death["detail"][:200])
            # A reported deterministic in-child failure ("failed")
            # buys nothing on retry; deaths past the restart budget
            # stop the batch either way.
            if death["kind"] == "failed" or spawn >= self.max_restarts:
                for jid in list(remaining):
                    res.errors[jid] = (f"batch:{death['kind']}: "
                                       f"{death['detail'][:160]}")
                    remaining.pop(jid, None)
                break
            spawn += 1
        # Normalise the cost split over the WHOLE batch (restart
        # children included): shares sum to 1.0 of the accumulated
        # shared device seconds.
        for jid, out in res.outcomes.items():
            out.lane_share = (
                round(lane_secs.get(jid, 0.0) / res.device_secs, 6)
                if res.device_secs > 0 else 0.0)
            out.child_restarts = res.child_restarts
            out.killed_dispatches = self.killed_dispatches
        res.killed_dispatches = self.killed_dispatches
        return res


# ------------------------------------------------------------ child half

def _send(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _resolve(ref: str):
    import importlib

    mod, _, name = ref.partition(":")
    obj = importlib.import_module(mod)
    for part in name.split("."):
        obj = getattr(obj, part)
    return obj


def _child_main() -> int:
    from dslabs_tpu.tpu.warden import outcome_to_dict

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
    proto = _resolve(spec["factory"])(**(spec.get("factory_kwargs")
                                         or {}))
    if spec.get("transform"):
        proto = _resolve(spec["transform"])(proto)
    _send({"t": "hb", "phase": "boot", "stage": "protocol",
           "grace": boot_g})
    ev = spec.get("ev_budget")
    if isinstance(ev, list):
        ev = tuple(ev)
    fault = spec.get("fault")
    if fault is not None:
        if fault.get("spawns") is not None:
            if int(spec.get("spawn_index", 0)) not in fault["spawns"]:
                fault = None
        elif int(spec.get("spawn_index", 0)) > 0:
            fault = None

    # The batch run dir: ONE flight log + STATUS.json for the whole
    # batch (per-lane progress rides the level records' `lanes` block);
    # each lane keeps its own checkpoint in its own job dir.
    child_tel = None
    run_dir = spec.get("run_dir")
    if run_dir:
        try:
            from dslabs_tpu.tpu.telemetry import Telemetry

            os.makedirs(run_dir, exist_ok=True)
            child_tel = Telemetry.for_checkpoint(
                os.path.join(run_dir, "ckpt.npz"),
                engine_hint="lane-batch")
        except Exception:  # noqa: BLE001 — observability is optional
            child_tel = None
    jobs = [LaneJob(job_id=j["job_id"], max_depth=j.get("max_depth"),
                    max_secs=j.get("max_secs"),
                    checkpoint_path=j.get("checkpoint_path"),
                    checkpoint_every=int(j.get("checkpoint_every", 0)
                                         or 0),
                    trace_id=j.get("trace_id"))
            for j in spec.get("jobs", [])]
    if child_tel is not None:
        # Shared-span trace attribution (ISSUE 14): the batch flight
        # log names every resident job + trace id up front, so the
        # trace assembler can attribute each shared dispatch span to
        # every lane's causal tree from disk alone.
        child_tel.event("lane_batch", jobs=[
            {"job_id": j.job_id, "trace_id": j.trace_id}
            for j in jobs], n_lanes=spec.get("n_lanes"))
    search = LaneSearch(
        proto, n_lanes=int(spec.get("n_lanes", 1) or 1),
        frontier_cap=int(spec.get("frontier_cap", 1 << 14)),
        chunk=int(spec.get("chunk", 1 << 10)),
        max_secs=spec.get("max_secs"),
        ev_budget=ev,
        visited_cap=int(spec.get("visited_cap", 1 << 20)),
        strict=bool(spec.get("strict", True)),
        telemetry=child_tel)

    seen_tags = set()
    n_seen = {"n": 0}

    def hook(tag, fn, *args):
        idx = n_seen["n"]
        n_seen["n"] += 1
        first = tag not in seen_tags
        seen_tags.add(tag)
        depth = getattr(search, "_current_depth", 0)
        grace = first_g if first else steady_g
        _send({"t": "hb", "phase": "start", "tag": tag, "n": idx,
               "depth": depth, "grace": grace})
        if fault is not None:
            kind = fault.get("kind")
            at = int(fault.get("at", 0))
            due = (idx >= at if kind in ("die", "exit", "hang")
                   else idx == at)
            if due:
                if kind == "die":
                    os.kill(os.getpid(), signal.SIGKILL)
                elif kind == "exit":
                    os._exit(int(fault.get("rc", 86)))
                elif kind == "hang":
                    time.sleep(float(fault.get("secs", 3600.0)))
                elif kind == "raise":
                    raise RuntimeError(
                        f"injected lane child fault [{tag} "
                        f"dispatch {idx}]")
        out = fn(*args)
        _send({"t": "hb", "phase": "done", "tag": tag, "n": idx,
               "depth": depth, "grace": idle_g})
        return out

    search._dispatch_hook = hook

    def on_lane(job_id, out, error, secs):
        if out is not None:
            _send({"t": "lane_result", "job_id": job_id,
                   "lane_secs": round(secs, 6),
                   "outcome": outcome_to_dict(out)})
        else:
            _send({"t": "lane_error", "job_id": job_id,
                   "error": error})

    try:
        res = search.run_lanes(jobs, resume=bool(spec.get("resume")),
                               swap=bool(spec.get("swap", True)),
                               on_lane=on_lane)
    except BaseException as e:  # noqa: BLE001 — reported over the pipe
        from dslabs_tpu.tpu.supervisor import CHILD_RC_FAILED

        _send({"t": "err", "error": f"{type(e).__name__}: {e}"[:500]})
        return CHILD_RC_FAILED
    finally:
        if child_tel is not None:
            child_tel.close()
    import jax

    _send({"t": "result", "swaps": res.swaps, "levels": res.levels,
           "dispatches": res.dispatches,
           "device_secs": round(res.device_secs, 6),
           "occupancy": res.occupancy,
           "platform": jax.devices()[0].platform})
    return 0


if __name__ == "__main__":
    sys.exit(_child_main())
