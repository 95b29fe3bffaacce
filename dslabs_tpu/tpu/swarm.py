"""Device-sharded swarm explorer: diversified random-walk fleets.

The checking power of the reference comes from a BFS + RandomDFS
*portfolio* (SURVEY §2.4): BFS proves shallow exhaustiveness, random
deep probes hit the deep-narrow violations BFS cannot reach inside a
budget.  This module is the accelerator-native second half of that
portfolio, in the spirit of swarm verification (Holzmann & Joshi,
*Swarm Verification Techniques*): a fleet of DIVERSIFIED random walkers
runs as ONE ``shard_map`` program across the device mesh, and every
witness it produces is minimized and independently replay-verified
before the verdict is returned.

Architecture
============

* **One fused superstep per round.**  Each device owns a block of
  ``walkers_per_device`` walkers (state rows + depths + per-walker
  event histories).  A round is a single dispatched ``shard_map``
  program whose ``lax.while_loop`` runs up to ``steps_per_round`` walk
  steps — event-table build, one random event pick per walker, one
  vmapped transition, invariant/goal/exception flags, visited-table
  insert, restart resolution — and stops EARLY when any device raises a
  terminal flag (the first-hit stop is a ``psum``'d flag count in the
  loop condition, so the whole fleet halts within one step of the first
  hit).  Host involvement per round is one dispatch + one scalar stats
  readback, through the same ``_dispatch`` seam as the BFS drivers — so
  supervisor retry/watchdog/FaultPlan, warden process isolation, and
  the persistent compile cache all apply unchanged.

* **Diversification axes** (what makes a swarm beat N copies of one
  walker): every walker gets (1) its own PRNG stream (per-device key,
  per-walker categorical picks), (2) its own DEPTH BOUND from a
  schedule spanning ``[min_steps, max_steps]`` — short-leash walkers
  resample shallow prefixes while long-leash walkers commit deep, and
  (3) its own event-pick TEMPERATURE and message/timer affinity — cold
  walkers follow their kind bias almost deterministically, hot walkers
  pick uniformly, so the fleet covers timer-storm and message-storm
  schedules that a uniform picker visits exponentially rarely.

* **Shared dedup** through the one open-addressing table implementation
  (tpu/visited.py): every advanced successor inserts its 128-bit
  fingerprint into the device's table, so fleets do not re-count each
  other's states (``unique_states`` is fresh inserts, never the walked
  count) and BFS coverage can be pre-seeded (below).  An optional
  ``revisit_patience`` restarts a walker whose last N steps all landed
  on already-visited states — restart steering away from covered
  territory.  A full table degrades exactly like the BFS engines
  (visited.py contract): unresolved keys count as fresh, surfaced on
  ``SearchOutcome.visited_overflow`` (strict swarms raise).

* **Frontier seeding** (the BFS+swarm hybrid): ``frontier_seed`` names
  a mid-BFS unified checkpoint (tpu/checkpoint.py); walkers then
  restart from the dumped FRONTIER rows instead of the root, and the
  dump's visited keys pre-seed every device's table — the swarm probes
  strictly PAST the exhaustively-proven region.  Witness traces are
  recorded relative to the walker's seed state (the staged-search
  ``initial=`` contract; ``_trace_root`` is set per hit).

* **Witness pipeline.**  A violation's root-first event trace comes
  straight from the walker's recorded history (no re-derivation), then
  :func:`minimize_event_trace` shrinks it to a fixpoint (the
  TraceMinimizer.java:32-109 discipline, executed in tensor space with
  one fused replay program per candidate) and :func:`replay_events`
  re-applies the minimized trace from the seed state, asserting every
  event applies and the predicate result reproduces.  The verdict is
  returned only with a verified :class:`Witness` attached
  (``SearchOutcome.witness``) — never an unminimized or unreplayed
  trace.  The object-level double-check (search/minimize.py +
  search/replay.py on the replayed object twin) rides in the search
  backend (tpu/backend.py) where an object root exists.

* **Rounds checkpoint/resume** like BFS levels: the walker rows,
  depths, histories, PRNG keys, seed pool, and table keys dump into the
  unified checkpoint format (``SearchCheckpoint.extra``), so a killed
  swarm resumes mid-flight with an IDENTICAL continuation (the PRNG
  state is part of the dump) — supervisor failover semantics unchanged.

Env knobs (docs/swarm.md): DSLABS_SWARM_WALKERS, DSLABS_SWARM_STEPS,
DSLABS_SWARM_ROUND, DSLABS_SWARM_PATIENCE, DSLABS_SWARM_RESTART_WARN,
DSLABS_SWARM_OVERFLOW_WARN.
"""

from __future__ import annotations

import dataclasses
import os
import time
import warnings
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from dslabs_tpu.tpu import checkpoint as ckpt_mod
from dslabs_tpu.tpu import compile_cache
from dslabs_tpu.tpu import telemetry as tel_mod
from dslabs_tpu.tpu import visited as visited_mod
from dslabs_tpu.tpu.engine import (SENTINEL, CapacityOverflow,
                                   SearchOutcome, TensorProtocol,
                                   TensorSearch, device_get,
                                   flatten_state, row_fingerprints)

__all__ = ["SwarmSearch", "Witness", "minimize_event_trace",
           "replay_events"]

# Warn thresholds for the loud-degradation counters (satellite of
# ISSUE 5: the old rollout probe restarted capacity-truncated walkers
# SILENTLY).  Any overflow restart is worth a warning by default;
# ordinary restarts are the walkers' job, so that bar is high.
RESTART_WARN = int(os.environ.get("DSLABS_SWARM_RESTART_WARN",
                                  str(1 << 20)))
OVERFLOW_WARN = int(os.environ.get("DSLABS_SWARM_OVERFLOW_WARN", "0"))

_TERMINAL = ("INVARIANT_VIOLATED", "EXCEPTION_THROWN", "GOAL_FOUND")

# Fresh inserts are also counted by the walk depth they were made at,
# for depths 1..FRESH_DEPTHS (a state first seen at walk depth d lies at
# BFS depth <= d: the cumulative count is held to an exhaustive search's
# by benchmark/drivers/timeboxed_swarm.py and tests/test_swarm_probe.py).
FRESH_DEPTHS = 16
# The scalar counters of the carry, in the order of the stats vector's
# head (``stats[:len(COUNTERS)]``, then the steps the round ran).
COUNTERS = ("explored", "fresh", "revisit", "restarts", "over",
            "vis_over", "deepest")
# What follows the flag counts in the stats vector, before the
# per-device tail: events beyond the event window, finished probes
# (restarts by a prune, the depth bound or a dead end), the most
# network rows and timer slots any successor held, steps the twin
# refused for want of room in its own state (``capacity_exc``), and the
# fresh inserts by depth.
EXTRAS = ("ev_rem", "probes", "net_peak", "tmr_peak", "refused")
# The round program's name (``jit_swarm_round`` in a profile).
ROUND = "swarm_round"
# A fleet whose network operand in the walk step's transposed merge —
# walkers x net_cap x msg_width x 4 bytes — passes STEP_BLOCK_BYTES runs
# its handlers and merge (:meth:`SwarmSearch._step_rows`) over blocks of
# STEP_BLOCK_ROWS walkers: one tile of the 128 lanes the walkers ride in
# that merge, the one block width of 64 to 2,048 at which a v5e's
# compiler keeps the merge's per-send planes ``[1, net_cap, rows]`` in
# whole (8, 128) tiles near the cores (at 256 and up: one sublane of
# eight, in HBM).  Five servers' probe twin, 64 KiB of network a walker,
# us a walker step at once / in blocks of 128 (PERF.md section 6, PR 44):
# 8,192 walkers (512 MiB) 8.2 / 5.7 — and 11-36 in blocks of 256 to 2,048
# — but 1,024 walkers (64 MiB) 3.9 / 5.2 and 256 walkers 3.8 / 4.9: the
# byte constant is the largest fleet measured faster at once.
STEP_BLOCK_ROWS = 128
STEP_BLOCK_BYTES = 64 << 20
# What a ``swarm.round`` span is closed with, of :meth:`_stats_dict`.
_SPAN_FIELDS = ("explored", "unique", "revisits", "restarts",
                "overflow_restarts", "vis_over", "deepest", "probes",
                "refused")


# ------------------------------------------------------------- witnesses

@dataclasses.dataclass
class Witness:
    """A minimized, replay-verified counterexample (or goal trace).

    ``trace`` is the minimized root-first grid-event-id list (the
    tpu/trace.py contract, relative to the walk's seed state);
    ``raw_trace`` is the walker's original history.  ``replay_verified``
    is True iff re-applying ``trace`` from the seed state applied every
    event and reproduced the predicate result — swarm verdicts refuse
    to ship otherwise."""

    end_condition: str
    predicate_name: Optional[str]
    exception_code: int
    raw_trace: List[int]
    trace: List[int]
    minimized: bool
    replay_verified: bool
    minimize_passes: int = 0
    # Set by the search backend when the object-level pipeline
    # (search/minimize.py + search/replay.py) also confirmed the
    # witness on the replayed object twin.
    object_verified: Optional[bool] = None

    def __len__(self) -> int:
        return len(self.trace)


def _replay_prog(search: TensorSearch, length: int):
    """One fused replay program for padded event lists of ``length``:
    a ``lax.scan`` of ``_step_one`` where ``ev < 0`` rows are inert
    padding and the first inapplicable/overflowed event FREEZES the
    state (TraceMinimizer.java:95-108 ``applyEvents`` semantics — later
    events are not applied).  Returns ``(final_row, applied[L])``.
    Cached per padded length (lengths are padded to powers of two so
    the program count stays O(log L))."""
    cache = getattr(search, "_swarm_replay_progs", None)
    if cache is None:
        cache = search._swarm_replay_progs = {}
    fn = cache.get(length)
    if fn is not None:
        return fn

    def prog(row0, evs):
        def step(carry, ev):
            row, alive = carry
            do = alive & (ev >= 0)
            succ, ok, over = search._step_one(row, jnp.maximum(ev, 0))
            good = do & ok & (over == 0)
            row2 = jnp.where(good, succ, row)
            alive2 = jnp.where(ev >= 0, alive & good, alive)
            return (row2, alive2), good

        (row, _alive), applied = jax.lax.scan(
            step, (row0, jnp.bool_(True)), evs)
        return row, applied

    fn = cache[length] = jax.jit(prog)
    return fn


def _pad_len(n: int) -> int:
    length = 8
    while length < n:
        length <<= 1
    return length


def replay_events(search: TensorSearch, root_row: np.ndarray,
                  events: List[int]) -> Tuple[np.ndarray, int]:
    """Replay ``events`` (grid event ids, root-first) from ``root_row``
    ([lanes] int32).  Returns ``(final_row, n_applied)`` where
    ``n_applied`` counts the applied prefix — application stops at the
    first undeliverable/overflowed event, like the reference
    minimizer's ``applyEvents``.  Replay is UNMASKED by design: the
    reference minimizer replays under default settings (all delivery
    permitted, search/minimize.py module docstring), and runtime masks
    gate validity, never the transition."""
    L = _pad_len(max(len(events), 1))
    evs = np.full((L,), -1, np.int32)
    evs[:len(events)] = np.asarray(events, np.int32)
    row, applied = _replay_prog(search, L)(
        jnp.asarray(root_row, jnp.int32), jnp.asarray(evs))
    applied = np.asarray(applied)[:len(events)]
    n_applied = int(applied.sum()) if applied.all() else \
        int(np.argmin(applied))
    return np.asarray(row), n_applied


def _verdict_check(search: TensorSearch, end_condition: str,
                   predicate_name: Optional[str], exception_code: int):
    """-> fn(final_row) -> bool: does this state reproduce the verdict
    (same-truth-value / same-exception-code discipline of
    search/minimize.py)?"""
    p = search.p

    def check(row: np.ndarray) -> bool:
        st = search.unflatten_rows(jnp.asarray(row, jnp.int32)[None])
        if end_condition == "EXCEPTION_THROWN":
            return int(np.asarray(st["exc"])[0]) == exception_code
        preds = (p.invariants if end_condition == "INVARIANT_VIOLATED"
                 else p.goals)
        holds = bool(np.asarray(jax.vmap(preds[predicate_name])(st))[0])
        return (not holds if end_condition == "INVARIANT_VIOLATED"
                else holds)

    return check


def minimize_event_trace(search: TensorSearch, root_row: np.ndarray,
                         events: List[int], check,
                         max_passes: int = 6) -> Tuple[List[int], int]:
    """Shrink an event trace to a (bounded) fixpoint: for each event,
    try replaying the trace WITHOUT it; keep the deletion when the end
    state still reproduces the predicate result (``check``) — the
    TraceMinimizer.java:33-61 loop, executed in tensor space with one
    fused replay dispatch per candidate.  ``max_passes`` bounds the
    fixpoint (each pass is O(L) replays); random-walk traces converge
    in 2-3 passes in practice.  Returns ``(minimized, passes_run)``."""
    events = list(events)
    passes = 0
    changed = True
    while changed and passes < max_passes:
        changed = False
        passes += 1
        i = 0
        while i < len(events):
            cand = events[:i] + events[i + 1:]
            row, _n = replay_events(search, root_row, cand)
            if check(row):
                events = cand
                changed = True
            else:
                i += 1
    return events, passes


def build_witness(search: TensorSearch, root_row: np.ndarray,
                  raw_trace: List[int], end_condition: str,
                  predicate_name: Optional[str], exception_code: int,
                  minimize: bool = True,
                  verify: bool = True) -> Witness:
    """The swarm witness pipeline: minimize (optional) then
    replay-verify.  A failed verification is a LOUD RuntimeError — a
    swarm verdict never ships a trace that does not independently
    reproduce its predicate result."""
    check = _verdict_check(search, end_condition, predicate_name,
                           exception_code)
    trace, passes = (minimize_event_trace(search, root_row, raw_trace,
                                          check)
                     if minimize else (list(raw_trace), 0))
    verified = False
    if verify:
        row, n_applied = replay_events(search, root_row, trace)
        if n_applied < len(trace):
            # check() accepted a prefix mid-minimization; the dangling
            # suffix is dead weight — trim and re-verify.
            trace = trace[:n_applied]
            row, n_applied = replay_events(search, root_row, trace)
        verified = n_applied == len(trace) and check(row)
        if not verified:
            raise RuntimeError(
                f"swarm witness failed replay verification "
                f"({end_condition}, predicate={predicate_name!r}, "
                f"{n_applied}/{len(trace)} events applied) — walker "
                "history or transition replay is corrupt (engine bug)")
    return Witness(end_condition=end_condition,
                   predicate_name=predicate_name,
                   exception_code=exception_code,
                   raw_trace=list(raw_trace), trace=trace,
                   minimized=minimize, replay_verified=verified,
                   minimize_passes=passes)


# ------------------------------------------------------------ the swarm

def step_block_rows(walkers: int, net_cap: int, msg_width: int) -> int:
    """Walkers a block of :meth:`SwarmSearch._step_rows`:
    ``STEP_BLOCK_ROWS`` where the fleet's network operand passes
    ``STEP_BLOCK_BYTES`` — else, or where the block does not divide the
    fleet, the whole fleet (ONE block: the program without the loop)."""
    small = 4 * walkers * net_cap * msg_width <= STEP_BLOCK_BYTES
    return (walkers if small or walkers % STEP_BLOCK_ROWS
            else STEP_BLOCK_ROWS)


def _abstract(x):
    """The shape, dtype and sharding of a device array: what a program
    is lowered for."""
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=getattr(x, "sharding", None))



class SwarmSearch(TensorSearch):
    """Diversified random-walk fleets over a device mesh (module
    docstring).  ``run()`` returns the standard :class:`SearchOutcome`:
    INVARIANT_VIOLATED / EXCEPTION_THROWN / GOAL_FOUND with a verified
    :class:`Witness`, else TIME_EXHAUSTED with the fleet statistics on
    ``outcome.swarm`` — exhaustive verdicts remain BFS-only by design.
    """

    def __init__(self, protocol: TensorProtocol, mesh=None,
                 walkers_per_device: Optional[int] = None,
                 max_steps: Optional[int] = None,
                 min_steps: Optional[int] = None,
                 steps_per_round: Optional[int] = None,
                 max_rounds: Optional[int] = None,
                 max_secs: Optional[float] = None,
                 seed: int = 0,
                 temperature: Tuple[float, float] = (0.25, 4.0),
                 kind_affinity: float = 2.0,
                 revisit_patience: Optional[int] = None,
                 visited_cap: int = 1 << 18,
                 strict: bool = False,
                 ev_budget=None,
                 frontier_seed: Optional[str] = None,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 0,
                 minimize: bool = True,
                 replay_verify: bool = True):
        if mesh is None:
            from dslabs_tpu.tpu.sharded import make_mesh

            mesh = make_mesh(len(jax.devices()))
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_devices = int(mesh.devices.size)
        self.walkers = int(walkers_per_device
                           or os.environ.get("DSLABS_SWARM_WALKERS", 128))
        self.max_steps = int(max_steps
                             or os.environ.get("DSLABS_SWARM_STEPS", 96))
        self.min_steps = int(min_steps if min_steps is not None
                             else max(4, self.max_steps // 4))
        self.steps_per_round = int(
            steps_per_round or os.environ.get("DSLABS_SWARM_ROUND", 64))
        self.max_rounds = max_rounds
        self.seed = int(seed)
        self.temperature = (float(temperature[0]), float(temperature[1]))
        self.kind_affinity = float(kind_affinity)
        # Restart steering: a walker whose last ``patience`` steps all
        # landed on already-visited states restarts (it is re-treading
        # covered territory).  <= 0 disables — the safe default: from a
        # root INSIDE a large covered region, a small patience would
        # fence walkers below the fresh frontier.  Enable alongside
        # frontier seeding, where restarts land PAST the covered region.
        if revisit_patience is None:
            revisit_patience = int(os.environ.get(
                "DSLABS_SWARM_PATIENCE", "0"))
        self.revisit_patience = int(revisit_patience)
        self.frontier_seed = frontier_seed
        self.minimize = minimize
        self.replay_verify = replay_verify
        super().__init__(protocol, frontier_cap=max(self.walkers, 2),
                         chunk=self.walkers, max_secs=max_secs,
                         ev_budget=ev_budget, visited_cap=visited_cap,
                         strict=strict,
                         checkpoint_path=checkpoint_path,
                         checkpoint_every=checkpoint_every)
        self.block_rows = step_block_rows(
            self.walkers, self.p.net_cap, self.p.msg_width)
        self.step_blocks = self.walkers // self.block_rows   # 1 = idle
        self._round = jax.jit(self._build_round(), donate_argnums=0)
        self._round_exe = None          # :meth:`_load_round`
        self._init_progs = {}           # :meth:`_init_carry`
        self._final_carry = None        # :meth:`walker_snapshot`
        self.compile_secs = 0.0
        # Watchdog granularity (tpu/supervisor.py): one round dispatch
        # legitimately runs up to steps_per_round walk steps.
        self._dispatch_deadline_scales = {
            "round": float(max(1, self.steps_per_round))}
        # Soundness sanitizer (ISSUE 10): audit the fused round program
        # when DSLABS_SANITIZE is on (base __init__ skips subclasses).
        self._maybe_sanitize()

    def dispatch_site_programs(self):
        """Sanitizer site registry (ISSUE 10; base-class docstring):
        the ONE hot swarm program — the fused round superstep.  Unlike
        the BFS engines the round's carry shapes live on device (the
        init shard_map builds them), so this runs the real swarm.init
        once and abstracts its result; the audit itself still only
        lowers."""
        carry = self._init_carry(self.initial_state())
        carry_sds = jax.tree.map(_abstract, carry)
        b = jnp.asarray(self.steps_per_round, jnp.int32)
        rt = getattr(self, "_rt_masks", None)
        args = ((carry_sds, b, rt) if rt is not None
                else (carry_sds, b))
        return {
            "swarm.round": dict(
                fn=self._round, args=args, donate=(0,), multi=True,
                builder=lambda: jax.jit(self._build_round(),
                                        donate_argnums=0)),
        }

    # --------------------------------------------------- diversification

    def _schedules(self):
        """Host-built per-walker diversification arrays over the WHOLE
        fleet (D * K walkers): depth bounds, temperatures, kind
        affinities.  Deterministic functions of the config — never
        checkpointed, always regenerated."""
        n = self.n_devices * self.walkers
        bounds = np.linspace(self.min_steps, self.max_steps, n)
        bounds = np.ceil(bounds).astype(np.int32).clip(1, self.max_steps)
        t_lo, t_hi = self.temperature
        temps = np.geomspace(max(t_lo, 1e-3), max(t_hi, 1e-3),
                             n).astype(np.float32)
        # Affinity alternates sign across the fleet so half the walkers
        # chase timer-heavy schedules and half message-heavy ones, at
        # every temperature rung.
        affin = np.where(np.arange(n) % 2 == 0, 1.0, -1.0)
        affin = (affin * self.kind_affinity).astype(np.float32)
        return bounds, temps, affin

    def _dev_keys(self) -> np.ndarray:
        """[D, 2] uint32 per-device PRNG keys (fold_in by device)."""
        base = jax.random.PRNGKey(self.seed)
        return np.stack([np.asarray(jax.random.fold_in(base, d))
                         for d in range(self.n_devices)]).astype(
            np.uint32)

    # -------------------------------------------------------- seed pool

    def _seed_pool(self, state) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]:
        """-> (seeds [D, P, lanes], seeds_n [D], preseed_keys [M, 4]).

        Root mode: every device's pool is the one root row, and its
        key is pre-seeded: the root is a visited state, so a walk that
        comes back to it revisits, and ``fresh`` counts the states
        other than the seeds (one less than a BFS's count of the same
        region, which holds its root).  Frontier mode (``frontier_seed`` = a BFS
        checkpoint path): the dumped frontier rows split contiguously
        across devices (distinct seeds per device = another
        diversification axis) and the dump's visited keys pre-seed
        EVERY device's table (tables are device-local; replication
        maximizes sharing)."""
        root = np.asarray(flatten_state(state))[0]
        D = self.n_devices
        if not self.frontier_seed:
            seeds = np.broadcast_to(root, (D, 1, self.lanes)).copy()
            return seeds, np.ones((D,), np.int32), np.asarray(
                row_fingerprints(jnp.asarray(root[None])), np.uint32)
        ck = self._load_bfs_seed(self.frontier_seed)
        rows = ck.frontier
        if not len(rows):
            rows = root[None]
        per = max(1, -(-len(rows) // D))
        seeds = np.zeros((D, per, self.lanes), np.int32)
        seeds_n = np.zeros((D,), np.int32)
        for d in range(D):
            part = rows[d * per:(d + 1) * per]
            if not len(part):
                # A device with no frontier share falls back to the
                # root (never an empty pool).
                part = root[None]
            seeds[d, :len(part)] = part
            seeds_n[d] = len(part)
        return seeds, seeds_n, np.asarray(ck.visited_keys, np.uint32)

    def _load_bfs_seed(self, path: str):
        """Load a BFS dump for frontier seeding.  The dump may have
        been written by a strict or beam, trace-recording or plain
        search — any fingerprint whose PROTOCOL half matches ours is a
        sound seed (we only consume frontier rows + visited keys)."""
        last = None
        for strict in (True, False):
            for rt in (False, True):
                fp = ckpt_mod.config_fingerprint(self.p, strict, rt)
                try:
                    ck = ckpt_mod.load(path, fp)
                except ckpt_mod.CheckpointMismatch as e:
                    last = e
                    continue
                if ck is not None:
                    return ck
        if last is not None:
            raise last
        raise FileNotFoundError(
            f"frontier_seed: no BFS checkpoint at {path}")

    # ------------------------------------------------------ the programs

    def _carry_specs(self):
        ax = self.axis
        keys = ["rows", "depths", "hists", "streak", "seed_idx",
                "bounds", "temps", "affin", "key", "seeds", "seeds_n",
                "visited", *COUNTERS, *EXTRAS, "fresh_by_depth",
                "hit_cnt", "hit_rows", "hit_hist", "hit_depth",
                "hit_seed"]
        return {k: P(ax) for k in keys}

    def _zero_counters(self) -> dict:
        """The carry's counters at the start of a walk (per device)."""
        out = {k: jnp.zeros((1,), jnp.int32) for k in COUNTERS + EXTRAS}
        out["fresh_by_depth"] = jnp.zeros((FRESH_DEPTHS,), jnp.int32)
        return out

    def _step_rows(self, rows, ev):
        """K rows, one grid event id each -> (successor rows, valid,
        over): :meth:`_step_block` of the fleet at once or, where the
        fleet is wider than a block (``step_blocks`` > 1), of one block
        of ``block_rows`` consecutive walkers after another — a pure
        function of each row and its event, so the same values either
        way."""
        n, kb = self.step_blocks, self.block_rows
        if n == 1:
            return self._step_block(rows, ev)
        succ, ok, over = jax.lax.map(
            lambda block: self._step_block(*block),
            (rows.reshape(n, kb, -1), ev.reshape(n, kb)))
        return succ.reshape(n * kb, -1), ok.reshape(-1), over.reshape(-1)

    def _step_block(self, rows, ev):
        """What ``vmap(_step_one)`` gives for a block of rows, with only
        the HANDLER half under the vmap and the network merge as the
        engine's one batched, transposed tail (``_batched_tail`` at one
        pair a row: walkers ride the minor axis, as the BFS chunk's
        pairs do)."""
        p = self.p
        K = rows.shape[0]
        with tel_mod.device_scope("expand.handlers"):
            is_msg = ev < p.net_cap
            m = jax.vmap(self._msg_step_raw)(
                rows, jnp.minimum(ev, p.net_cap - 1))
            t = jax.vmap(self._tmr_step_raw)(
                rows, jnp.maximum(ev - p.net_cap, 0))
            nodes2, sends, timers2, exc, ok, t_over = jax.tree.map(
                lambda a, b: jnp.where(
                    is_msg.reshape((K,) + (1,) * (a.ndim - 1)), a, b),
                m, t)
        with tel_mod.device_scope("expand.canon"):
            succ, over = self._batched_tail(rows, K, 1, nodes2, sends,
                                            timers2, exc, ok, t_over)
            if p.fault is not None and self._ev_flt:
                tgrid = p.n_nodes * p.timer_cap
                is_flt = ev >= p.net_cap + tgrid
                f_rows, f_ok, f_over = jax.vmap(self._flt_step)(
                    rows, jnp.maximum(ev - p.net_cap - tgrid, 0))
                succ = jnp.where(is_flt[:, None], f_rows, succ)
                ok = jnp.where(is_flt, f_ok, ok)
                over = jnp.where(is_flt, f_over, over)
        return succ, ok, over

    def _build_walk_step(self):
        """One walk step for this device's K walkers (runs INSIDE the
        round's shard_map/while_loop).  Its stages are named in the
        HLO's metadata (tpu/telemetry.py DEVICE_SCOPES): the BFS chunk
        step's own names where the stage is the same work, ``walk.*``
        for what only a walker does."""
        p = self.p
        K = self.walkers
        S = self.max_steps
        patience = self.revisit_patience

        def walk(c, masks=None):
            rows, depths, hists = c["rows"], c["depths"], c["hists"]
            key, sub, sub2 = jax.random.split(c["key"][0], 3)
            with tel_mod.device_scope("expand.events"):
                msg_ids, tmr_ids, flt_ids, ev_rem = self._event_tables(
                    rows, jnp.ones((K,), bool), masks=masks)
            with tel_mod.device_scope("walk.pick"):
                segs = [msg_ids,
                        jnp.where(tmr_ids >= 0, tmr_ids + p.net_cap, -1)]
                if flt_ids is not None:
                    tgrid = p.n_nodes * p.timer_cap
                    segs.append(jnp.where(
                        flt_ids >= 0, flt_ids + p.net_cap + tgrid, -1))
                ids = jnp.concatenate(segs, axis=1)          # [K, B]
                ok = ids >= 0
                # Diversified pick: kind-affinity bias over valid
                # events, scaled by each walker's temperature (cold =
                # committed to its bias, hot = uniform), resolved by
                # one categorical draw per walker.
                is_tmr = (jnp.arange(ids.shape[1])
                          >= self._ev_msg)[None, :]           # [1, B]
                bias = (c["affin"][:, None]
                        * jnp.where(is_tmr, 1.0, -1.0)
                        / c["temps"][:, None])
                logits = jnp.where(ok, bias, -jnp.inf)
                pick = jax.random.categorical(sub, logits, axis=-1)
                ev = jnp.take_along_axis(ids, pick[:, None],
                                         axis=1)[:, 0]
                any_ok = ok.any(axis=1)
                ev = jnp.where(any_ok, ev, 0)
            succ, s_ok, s_over = self._step_rows(rows, ev)
            # A capacity-overflowed successor is TRUNCATED — checking
            # predicates on it would be unsound.  The walker restarts,
            # and the truncation is COUNTED (c["over"]) — the old
            # rollout probe's silent-restart bug, fixed.  So is a step
            # the twin refused for want of room in its own state (the
            # row's last lane, ``exc``, reads ``capacity_exc``): the
            # object system would have gone on (c["refused"]).
            over = any_ok & s_ok & (s_over != 0)
            refused = (any_ok & s_ok & ~over
                       & (succ[:, -1] == p.capacity_exc)
                       if p.capacity_exc else jnp.zeros((K,), bool))
            cut = over | refused
            advance = any_ok & s_ok & ~cut

            with tel_mod.device_scope("flags"):
                sstate = self.unflatten_rows(succ)
                # Terminal flags, checkState order (exception ->
                # invariant -> goal; shared _flag_names layout with the
                # BFS drivers).
                hit_list = [advance & (sstate["exc"] != 0)]
                for n in p.invariants:
                    hit_list.append(
                        advance & ~jax.vmap(p.invariants[n])(sstate))
                for n in p.goals:
                    hit_list.append(advance
                                    & jax.vmap(p.goals[n])(sstate))
                hits = jnp.stack(hit_list)                    # [nf, K]
                pruned = jnp.zeros((K,), bool)
                for fn in p.prunes.values():
                    pruned = pruned | jax.vmap(fn)(sstate)
                # How full the caps ran: the most network rows, and the
                # most slots of one node's timer queue, any successor
                # held (what the probe's caps are sized from).
                net_n = jnp.max(jnp.sum(
                    sstate["net"][:, :, 0] != SENTINEL, axis=1))
                tmr_n = jnp.max(jnp.sum(
                    sstate["timers"][:, :, :, 0] != SENTINEL, axis=2))

            # History records the event BEFORE restart resolution: a
            # violating successor's trace must include its final edge.
            with tel_mod.device_scope("walk.history"):
                hists2 = jnp.where(
                    (jnp.arange(S)[None, :] == depths[:, None])
                    & advance[:, None], ev[:, None], hists)
                depths2 = depths + advance.astype(jnp.int32)

            # Shared dedup: fingerprints of advanced successors insert
            # into this device's table (visited.py contract: unresolved
            # = table full = treated as fresh, counted).
            with tel_mod.device_scope("fingerprint"):
                fp = row_fingerprints(succ)
            with tel_mod.device_scope("visited_insert"):
                table, ins, unres = visited_mod.insert(
                    c["visited"], fp, advance)

            with tel_mod.device_scope("walk.restart"):
                revisit = advance & ~ins & ~unres
                streak2 = jnp.where(revisit, c["streak"] + 1,
                                    jnp.zeros_like(c["streak"]))
                if patience > 0:
                    rv_restart = streak2 >= patience
                else:
                    rv_restart = jnp.zeros((K,), bool)

                # First-hit capture per flag (one walker's full
                # history), taken from the PRE-restart arrays.
                cnts = jnp.sum(hits, axis=1).astype(jnp.int32)
                idxs = jnp.argmax(hits, axis=1)
                freshf = (c["hit_cnt"] == 0) & (cnts > 0)
                hit_rows = jnp.where(freshf[:, None], succ[idxs],
                                     c["hit_rows"])
                hit_hist = jnp.where(freshf[:, None], hists2[idxs],
                                     c["hit_hist"])
                hit_depth = jnp.where(freshf, depths2[idxs],
                                      c["hit_depth"])
                hit_seed = jnp.where(freshf, c["seed_idx"][idxs],
                                     c["hit_seed"])

                # Restarts: a probe FINISHED (dead end / prune / depth
                # bound), a truncated or refused step, or revisit
                # patience -> re-seed from the pool.
                finished = ((~advance & ~cut) | pruned
                            | (depths2 >= c["bounds"]))
                restart = finished | cut | rv_restart
                nsd = jnp.maximum(c["seeds_n"][0], 1)
                ridx = jax.random.randint(sub2, (K,), 0, nsd)
                new_rows = c["seeds"][ridx]
                rows2 = jnp.where(restart[:, None], new_rows, succ)
                depths3 = jnp.where(restart, 0, depths2)
                hists3 = jnp.where(restart[:, None], -1, hists2)
                streak3 = jnp.where(restart, 0, streak2)
                seed_idx2 = jnp.where(restart, ridx, c["seed_idx"])
                by_depth = jnp.sum(
                    ins[:, None] & (depths2[:, None] == jnp.arange(
                        1, FRESH_DEPTHS + 1)[None, :]),
                    axis=0).astype(jnp.int32)

            def bump(name, val):
                return c[name].at[0].add(val.astype(jnp.int32))

            def peak(name, val):
                return c[name].at[0].max(val.astype(jnp.int32))

            return {
                "rows": rows2, "depths": depths3, "hists": hists3,
                "streak": streak3, "seed_idx": seed_idx2,
                "bounds": c["bounds"], "temps": c["temps"],
                "affin": c["affin"], "key": key[None],
                "seeds": c["seeds"], "seeds_n": c["seeds_n"],
                "visited": table,
                "explored": bump("explored", jnp.sum(advance)),
                "fresh": bump("fresh", jnp.sum(ins)),
                "revisit": bump("revisit", jnp.sum(revisit)),
                "restarts": bump("restarts", jnp.sum(restart)),
                "over": bump("over", jnp.sum(over)),
                "vis_over": bump("vis_over", jnp.sum(unres)),
                "deepest": peak("deepest", jnp.max(depths2)),
                "ev_rem": bump("ev_rem", ev_rem),
                "probes": bump("probes", jnp.sum(finished)),
                "net_peak": peak("net_peak", net_n),
                "tmr_peak": peak("tmr_peak", tmr_n),
                "refused": bump("refused", jnp.sum(refused)),
                "fresh_by_depth": c["fresh_by_depth"] + by_depth,
                "hit_cnt": c["hit_cnt"] + cnts,
                "hit_rows": hit_rows, "hit_hist": hit_hist,
                "hit_depth": hit_depth, "hit_seed": hit_seed,
            }

        return walk

    def _build_round(self):
        """The fused ROUND superstep: up to ``budget`` walk steps in one
        ``lax.while_loop``, stopping early when ANY device's flag count
        goes nonzero (psum'd first-hit stop).  Returns (carry', stats)
        with the psum'd scalar stats in-program, so host involvement
        per round is one dispatch.  The function is named: the program
        is ``jit_swarm_round`` in a profile."""
        walk = self._build_walk_step()
        ax = self.axis

        def stats_local(c, k):
            def ps(x):
                return jax.lax.psum(x, ax)

            def pm(x):
                return jax.lax.pmax(x, ax)

            core = jnp.stack([
                ps(c["explored"][0]), ps(c["fresh"][0]),
                ps(c["revisit"][0]), ps(c["restarts"][0]),
                ps(c["over"][0]), ps(c["vis_over"][0]),
                pm(c["deepest"][0]), k,
            ]).astype(jnp.int32)
            extras = jnp.stack([
                ps(c["ev_rem"][0]), ps(c["probes"][0]),
                pm(c["net_peak"][0]), pm(c["tmr_peak"][0]),
                ps(c["refused"][0]),
            ]).astype(jnp.int32)
            # Per-device stats lanes (ISSUE 8): the pre-psum per-device
            # scalars ride the SAME readback, LAST so every absolute
            # index parse stays valid — [explored×D, fresh×D,
            # restarts×D, deepest×D], one all_gather in the fused round
            # program, zero extra dispatches or transfers.  Between the
            # flag counts and that tail: EXTRAS, then the fresh inserts
            # by walk depth (:meth:`_stats_dict`).
            per_dev = jnp.stack([c["explored"][0], c["fresh"][0],
                                 c["restarts"][0], c["deepest"][0]])
            return jnp.concatenate([
                core, ps(c["hit_cnt"]).astype(jnp.int32), extras,
                ps(c["fresh_by_depth"]).astype(jnp.int32),
                jax.lax.all_gather(per_dev, ax).T.reshape(-1)
                .astype(jnp.int32)])

        def round_local(carry, budget, masks=None):
            def cond(st):
                c, k = st
                hit = jnp.sum(c["hit_cnt"])
                return (k < budget) & (jax.lax.psum(hit, ax) == 0)

            def body(st):
                c, k = st
                return walk(c, masks), k + 1

            carry, k = jax.lax.while_loop(cond, body,
                                          (carry, jnp.int32(0)))
            return carry, stats_local(carry, k)

        spec = self._carry_specs()
        masked = (self.p.deliver_message_rt is not None
                  or self.p.deliver_timer_rt is not None)
        sm = shard_map(
            round_local, mesh=self.mesh,
            in_specs=(spec, P()) + (((P(), P()),) if masked else ()),
            out_specs=(spec, P()), check_vma=False)

        def swarm_round(*args):
            return sm(*args)

        return swarm_round

    def _round_call(self, carry, budget: int):
        """Dispatch one round through the supervisor seam; the
        dispatched callable blocks on the scalar stats readback so the
        watchdog bounds the fused round.  Inside the seam's
        ``dispatch.round`` the round is one ``swarm.round`` span, closed
        with the fleet's counters as that readback gives them."""
        b = jnp.asarray(budget, jnp.int32)
        rt = getattr(self, "_rt_masks", None)
        prog = self._round_exe or self._round
        rounds = int(getattr(self, "_current_depth", 0) or 0)

        def run(c, bb, *masks):
            with tel_mod.phase("swarm.round", round=rounds, steps=budget,
                               blocks=self.step_blocks,
                               block_rows=self.block_rows) as span:
                c2, stats = prog(c, bb, *masks)
                stats = device_get(stats)
                span.set(**{k: v for k, v in self._stats_dict(
                    stats, rounds, 0.0).items() if k in _SPAN_FIELDS})
            return c2, stats

        if rt is not None:
            return self._dispatch("swarm.round", run, carry, b, rt)
        return self._dispatch("swarm.round", run, carry, b)

    def _store_devices(self) -> list:
        return list(self.mesh.devices.flat)

    def _store_shape(self) -> tuple:
        """The base engine's, and what this constructor bakes into the
        round program: the mesh, the fleet's shape (walkers a device,
        history length), the patience, the depth bins of the fresh
        count.  Bounds, temperatures, affinities, the PRNG key and the
        steps a round runs are arguments, not shape.  The walk step's
        blocks are not listed: they follow from two constants of this
        module (in the key's hash of the package's source), ``walkers``
        and the protocol's ``net_cap`` and ``msg_width`` (its
        fingerprint)."""
        return super()._store_shape() + (
            self.mesh.axis_names, self.mesh.devices.shape,
            tuple(int(d.id) for d in self._store_devices()),
            self.walkers, self.max_steps, self.revisit_patience,
            FRESH_DEPTHS)

    def _load_round(self, carry) -> None:
        """The round program as an executable, before the first round
        is dispatched: LOADED from the executable store
        (tpu/compile_cache.py) where a process of this source has
        compiled it for this twin, caps, predicates, mesh and fleet
        shape, else traced, lowered and compiled here and kept there.
        Either way the rounds dispatch the executable, and it is
        registered for ``telemetry.program_scopes``.  An engine with no
        key (a weak fingerprint, a patched package) compiles the same
        way and stores nothing."""
        if self._round_exe is not None:
            return
        rt = getattr(self, "_rt_masks", None)
        args = (jax.tree.map(_abstract, carry),
                jax.ShapeDtypeStruct((), jnp.int32))
        if rt is not None:
            args += (jax.tree.map(_abstract, rt),)
        with tel_mod.phase("compile.aot.swarm_round"):
            self._round_exe = compile_cache.stored(
                compile_cache.program_key(self.store_key(), ROUND, args),
                ROUND, lambda: self._round.lower(*args).compile(),
                self._store_devices())
        tel_mod.register_program(ROUND, self._round_exe)

    # ------------------------------------------------------------- carry

    def _init_carry(self, state):
        """Build the fleet carry: host-side small arrays + one jitted
        shard_map finisher that builds each device's table (pre-seeded
        when frontier seeding is on) and places walkers round-robin
        over the seed pool."""
        D, K, S, V = (self.n_devices, self.walkers, self.max_steps,
                      self.visited_cap)
        lanes = self.lanes
        nf = len(self._flag_names)
        seeds, seeds_n, pre_keys = self._seed_pool(state)
        pool = seeds.shape[1]
        bounds, temps, affin = self._schedules()
        m = len(pre_keys)
        # Pre-seed keys replicate to every device's table.
        pk = np.zeros((D, max(m, 1), 4), np.uint32)
        pv = np.zeros((D, max(m, 1)), bool)
        if m:
            pk[:] = pre_keys[None]
            pv[:] = True
        shard = NamedSharding(self.mesh, P(self.axis))
        dev_in = {k: jax.device_put(v, shard) for k, v in {
            "seeds": seeds.reshape(D * pool, lanes),
            "seeds_n": seeds_n,
            "bounds": bounds, "temps": temps, "affin": affin,
            "key": self._dev_keys(),
            "pkeys": pk.reshape(-1, 4), "pval": pv.reshape(-1),
        }.items()}

        def local(s):
            table, ins, unres = visited_mod.insert(
                visited_mod.empty_table(V), s["pkeys"], s["pval"])
            nsd = jnp.maximum(s["seeds_n"][0], 1)
            idx0 = (jnp.arange(K, dtype=jnp.int32) % nsd)
            out = {
                "rows": s["seeds"][idx0],
                "depths": jnp.zeros((K,), jnp.int32),
                "hists": jnp.full((K, S), -1, jnp.int32),
                "streak": jnp.zeros((K,), jnp.int32),
                "seed_idx": idx0,
                "bounds": s["bounds"], "temps": s["temps"],
                "affin": s["affin"], "key": s["key"],
                "seeds": s["seeds"], "seeds_n": s["seeds_n"],
                "visited": table,
                **self._zero_counters(),
                "hit_cnt": jnp.zeros((nf,), jnp.int32),
                "hit_rows": jnp.zeros((nf, lanes), jnp.int32),
                "hit_hist": jnp.full((nf, S), -1, jnp.int32),
                "hit_depth": jnp.zeros((nf,), jnp.int32),
                "hit_seed": jnp.zeros((nf,), jnp.int32),
            }
            return out, jnp.sum(unres).astype(jnp.int32)[None]

        # One initialiser a pool and pre-seed shape an engine: a second
        # run() of this fleet compiles nothing.
        fn = self._init_progs.get((pool, m))
        if fn is None:
            ax = self.axis
            in_spec = {k: P(ax) for k in dev_in}
            fn = self._init_progs[(pool, m)] = jax.jit(shard_map(
                local, mesh=self.mesh, in_specs=(in_spec,),
                out_specs=(self._carry_specs(), P(ax)),
                check_vma=False))

        def build(inputs):
            carry, unres = fn(inputs)
            return carry, device_get(unres)

        carry, unres = self._dispatch("swarm.init", build, dev_in)
        n_unres = int(np.asarray(unres).sum())
        if n_unres:
            raise CapacityOverflow(
                f"{self.p.name}: visited_cap={V}/device too small to "
                f"pre-seed {m} BFS keys ({n_unres} unresolved); raise "
                "visited_cap")
        return carry

    def walker_snapshot(self, walkers) -> List[Tuple[np.ndarray, List[int]]]:
        """``(row, events)`` of each walker of ``walkers`` (indices into
        the whole fleet) as the last run left it: the state row it
        stands on and the grid event ids, seed-first, that took it
        there from its seed — its recorded history, which a replay from
        that seed must end on that row."""
        c = self._final_carry
        if c is None:
            raise RuntimeError("no finished run to read walkers from")
        idx = jnp.asarray(np.asarray(walkers, np.int32))
        rows, depths, hists = (device_get(c[k][idx])
                               for k in ("rows", "depths", "hists"))
        return [(rows[i], [int(e) for e in hists[i][:int(depths[i])]])
                for i in range(len(rows))]

    # ------------------------------------------------------- checkpoints

    def _ckpt_fingerprint(self) -> str:
        """Swarm dumps are their own config family: a BFS engine must
        never resume one (and vice versa).  The history length (S) and
        the PRNG seed are part of the identity, but the mesh width (D)
        and per-device walker count (K) are deliberately EXCLUDED
        (ISSUE 9 satellite — the old ``D/K`` pin made every swarm dump
        unresumable after any mesh-width change): on load the walker
        rows, histories, PRNG keys, and per-device table key groups
        REDISTRIBUTE across whatever fleet resumes them
        (:meth:`_redistribute_swarm`); an unchanged-width resume takes
        the bit-exact passthrough path.  ``CheckpointMismatch`` is
        reserved for genuine protocol/strictness/seed mismatches."""
        base = ckpt_mod.config_fingerprint(self.p, self.strict, False)
        return f"swarm:{base}:S{self.max_steps}:seed{self.seed}"

    def _save_swarm_ckpt(self, carry, rounds: int, elapsed: float
                         ) -> None:
        """Host copies at the round boundary (before the next round's
        dispatch donates the buffers), file write drained async — the
        engine checkpoint discipline."""
        D, K, S = self.n_devices, self.walkers, self.max_steps
        per_dev = [visited_mod.host_occupied(t) for t in
                   np.split(np.asarray(carry["visited"]), D)]
        vdev = np.asarray([len(k) for k in per_dev], np.int64)
        keys = np.concatenate(per_dev)
        extra = {
            "depths": np.asarray(carry["depths"]),
            "hists": np.asarray(carry["hists"]),
            "streak": np.asarray(carry["streak"]),
            "seed_idx": np.asarray(carry["seed_idx"]),
            "key": np.asarray(carry["key"]),
            "seeds": np.asarray(carry["seeds"]),
            "seeds_n": np.asarray(carry["seeds_n"]),
            "vdev": vdev,
            "counters": np.stack([
                np.asarray(carry[k]).reshape(-1) for k in COUNTERS]),
            "extras": np.stack([
                np.asarray(carry[k]).reshape(-1) for k in EXTRAS]),
            "fresh_by_depth": np.asarray(
                carry["fresh_by_depth"]).reshape(D, FRESH_DEPTHS),
        }
        ck = ckpt_mod.SearchCheckpoint(
            fingerprint=self._ckpt_fingerprint(), depth=rounds,
            explored=int(np.asarray(carry["explored"]).sum()),
            elapsed=elapsed,
            frontier=np.asarray(carry["rows"]),
            visited_keys=keys,
            vis_over=int(np.asarray(carry["vis_over"]).sum()),
            extra=extra)
        self._ckpt_writer.kick(
            lambda: ckpt_mod.save(self.checkpoint_path, ck))

    def _redistribute_swarm(self, ck, x):
        """Cross-mesh-width resume (ISSUE 9 satellite): rewrite a dump
        written by a (D', K') fleet into this search's (D, K) shapes.

        Walker rows / depths / histories / streaks tile (or truncate)
        onto the new fleet size; the seed pool's live rows re-split
        into contiguous per-device shares; per-device PRNG keys map
        ``new[d] = old[d % D']`` (fresh streams per device either way);
        per-device visited key groups merge round-robin (duplicate keys
        across old device-local tables resolve in the insert); counters
        re-aggregate onto device 0 (sums — max for ``deepest`` — so
        psum/pmax stats stay exact).  The continuation is sound, not
        bit-exact — bit-exactness is reserved for the unchanged-width
        passthrough path."""
        import warnings

        D, K = self.n_devices, self.walkers
        vdev_old = np.asarray(x["vdev"], np.int64)
        d_old = max(len(vdev_old), 1)
        rows_old = np.asarray(ck.frontier, np.int32)
        n_old = max(len(rows_old), 1)
        m = D * K
        if len(rows_old) != m:
            warnings.warn(
                f"{self.p.name}: swarm resume redistributes "
                f"{len(rows_old)} walkers from a {d_old}-device dump "
                f"onto {D}x{K}={m} walker slots "
                f"({'tiling' if m > len(rows_old) else 'truncating'})",
                RuntimeWarning, stacklevel=3)
        idx = np.arange(m) % n_old
        x = dict(x)
        x["depths"] = np.asarray(x["depths"], np.int32)[idx]
        x["hists"] = np.asarray(x["hists"], np.int32)[idx]
        x["streak"] = np.asarray(x["streak"], np.int32)[idx]
        # PRNG keys: one per device, reused round-robin.
        key_old = np.asarray(x["key"], np.uint32).reshape(d_old, -1)
        x["key"] = key_old[np.arange(D) % d_old]
        # Seed pool: gather every device's live prefix, ceil-split into
        # contiguous per-device shares (the _seed_pool discipline).
        seeds_old = np.asarray(x["seeds"], np.int32)
        sn_old = np.asarray(x["seeds_n"], np.int32).reshape(-1)
        p_old = max(seeds_old.shape[0] // d_old, 1)
        live = [seeds_old[d * p_old:d * p_old + int(sn_old[d])]
                for d in range(d_old) if int(sn_old[d]) > 0]
        live = (np.concatenate(live) if live else rows_old[:1])
        per = max(1, -(-len(live) // D))
        seeds = np.zeros((D, per, self.lanes), np.int32)
        seeds_n = np.zeros((D,), np.int32)
        for d in range(D):
            part = live[d * per:(d + 1) * per]
            if not len(part):
                part = live[:1]     # never an empty pool
            seeds[d, :len(part)] = part
            seeds_n[d] = len(part)
        x["seeds"] = seeds.reshape(D * per, self.lanes)
        x["seeds_n"] = seeds_n
        # seed_idx references the per-device pool — clamp each walker's
        # index into its new device's pool size.
        sidx = np.asarray(x["seed_idx"], np.int32)[idx]
        owner = np.arange(m) // K
        x["seed_idx"] = np.minimum(sidx, seeds_n[owner] - 1).clip(0)
        # Per-device key groups merge round-robin onto the new width.
        offs = np.concatenate([[0], np.cumsum(vdev_old)]).astype(int)
        groups = [ck.visited_keys[offs[d]:offs[d + 1]]
                  for d in range(len(vdev_old))]
        merged = [[] for _ in range(D)]
        for g, keys in enumerate(groups):
            merged[g % D].append(keys)
        new_groups = [(np.concatenate(gs) if gs
                       else np.zeros((0, 4), np.uint32))
                      for gs in merged]
        x["vdev"] = np.asarray([len(g) for g in new_groups], np.int64)
        visited_keys = (np.concatenate(new_groups) if len(ck.visited_keys)
                        else ck.visited_keys)
        # Counters: per-device partials re-aggregate onto device 0 —
        # the stats psum (pmax for deepest) reads identical totals.
        c_old = np.asarray(x["counters"], np.int64).reshape(7, d_old)
        totals = c_old.sum(axis=1)
        totals[6] = c_old[6].max(initial=0)
        c_new = np.zeros((7, D), np.int64)
        c_new[:, 0] = totals
        x["counters"] = c_new
        # EXTRAS likewise (sums; max for the two peaks), and the fresh
        # inserts by depth; a dump from before they existed has none.
        e_old = np.asarray(x.get("extras", np.zeros((len(EXTRAS), d_old))),
                           np.int64).reshape(len(EXTRAS), d_old)
        e_new = np.zeros((len(EXTRAS), D), np.int64)
        peaks = [k.endswith("_peak") for k in EXTRAS]
        e_new[:, 0] = np.where(peaks, e_old.max(axis=1, initial=0),
                               e_old.sum(axis=1))
        x["extras"] = e_new
        f_new = np.zeros((D, FRESH_DEPTHS), np.int64)
        f_new[0] = np.asarray(
            x.get("fresh_by_depth", np.zeros((d_old, FRESH_DEPTHS))),
            np.int64).reshape(d_old, FRESH_DEPTHS).sum(axis=0)
        x["fresh_by_depth"] = f_new
        import dataclasses as _dc

        return _dc.replace(ck, frontier=rows_old[idx],
                           visited_keys=visited_keys), x

    def _load_swarm_ckpt(self):
        """-> (carry, rounds, elapsed) or None.  Rebuilds the full
        fleet carry — walker rows/depths/histories, PRNG keys, seed
        pool, per-device tables re-inserted from the dumped key groups
        — so an unchanged-width continuation is bit-exact (the
        resume-parity test); a dump from a DIFFERENT mesh width or
        walker count redistributes first (:meth:`_redistribute_swarm`)."""
        ck = self._load_ckpt()
        if ck is None:
            return None
        if ck.extra is None:
            raise ckpt_mod.CheckpointCorrupt(
                f"{self.checkpoint_path}: swarm checkpoint has no "
                "extra__ walker arrays")
        D, K, S, V = (self.n_devices, self.walkers, self.max_steps,
                      self.visited_cap)
        lanes = self.lanes
        nf = len(self._flag_names)
        x = ck.extra
        if (len(np.asarray(x["vdev"]).reshape(-1)) != D
                or len(ck.frontier) != D * K):
            ck, x = self._redistribute_swarm(ck, x)
        vdev = np.asarray(x["vdev"], np.int64)
        kmax = int(max(vdev.max(initial=0), 1))
        kbuf = np.zeros((D, kmax, 4), np.uint32)
        kval = np.zeros((D, kmax), bool)
        off = 0
        for d in range(D):
            n = int(vdev[d])
            kbuf[d, :n] = ck.visited_keys[off:off + n]
            kval[d, :n] = True
            off += n
        counters = np.asarray(x["counters"], np.int32)
        extras = np.asarray(
            x.get("extras", np.zeros((len(EXTRAS), D))), np.int32)
        by_depth = np.asarray(
            x.get("fresh_by_depth", np.zeros((D, FRESH_DEPTHS))),
            np.int32).reshape(D * FRESH_DEPTHS)
        shard = NamedSharding(self.mesh, P(self.axis))
        bounds, temps, affin = self._schedules()
        dev_in = {k: jax.device_put(v, shard) for k, v in {
            "rows": np.asarray(ck.frontier, np.int32),
            "depths": np.asarray(x["depths"], np.int32),
            "hists": np.asarray(x["hists"], np.int32),
            "streak": np.asarray(x["streak"], np.int32),
            "seed_idx": np.asarray(x["seed_idx"], np.int32),
            "key": np.asarray(x["key"], np.uint32),
            "seeds": np.asarray(x["seeds"], np.int32),
            "seeds_n": np.asarray(x["seeds_n"], np.int32),
            "bounds": bounds, "temps": temps, "affin": affin,
            "pkeys": kbuf.reshape(-1, 4), "pval": kval.reshape(-1),
            "counters": counters.T.copy(),          # [D, 7]
            "extras": extras.T.copy(),              # [D, len(EXTRAS)]
            "fresh_by_depth": by_depth,
        }.items()}

        def local(s):
            table, ins, unres = visited_mod.insert(
                visited_mod.empty_table(V), s["pkeys"], s["pval"])
            cnt = s["counters"][0]
            ext = s["extras"][0]
            out = {
                "rows": s["rows"], "depths": s["depths"],
                "hists": s["hists"], "streak": s["streak"],
                "seed_idx": s["seed_idx"],
                "bounds": s["bounds"], "temps": s["temps"],
                "affin": s["affin"], "key": s["key"],
                "seeds": s["seeds"], "seeds_n": s["seeds_n"],
                "visited": table,
                **{k: cnt[i][None] for i, k in enumerate(COUNTERS)},
                **{k: ext[i][None] for i, k in enumerate(EXTRAS)},
                "fresh_by_depth": s["fresh_by_depth"],
                "hit_cnt": jnp.zeros((nf,), jnp.int32),
                "hit_rows": jnp.zeros((nf, lanes), jnp.int32),
                "hit_hist": jnp.full((nf, S), -1, jnp.int32),
                "hit_depth": jnp.zeros((nf,), jnp.int32),
                "hit_seed": jnp.zeros((nf,), jnp.int32),
            }
            return out, jnp.sum(unres).astype(jnp.int32)[None]

        ax = self.axis
        in_spec = {k: P(ax) for k in dev_in}
        fn = jax.jit(shard_map(local, mesh=self.mesh,
                               in_specs=(in_spec,),
                               out_specs=(self._carry_specs(), P(ax)),
                               check_vma=False))
        with self.mesh:
            carry, unres = fn(dev_in)
        if int(np.asarray(unres).sum()):
            raise CapacityOverflow(
                f"{self.p.name}: visited_cap={V}/device too small to "
                "rebuild the swarm checkpoint's table; raise "
                "visited_cap")
        return carry, ck.depth, ck.elapsed

    # --------------------------------------------------------------- run

    def run(self, check_initial: bool = True,
            initial: Optional[dict] = None,
            resume: bool = False) -> SearchOutcome:
        """Run the swarm to a verdict.  ``initial`` (a batch-1 state
        pytree) roots the walk at an arbitrary state (the staged-search
        contract); ``resume=True`` continues from ``checkpoint_path``
        bit-exactly.  Compile time is excluded from the wall budget
        (the reference charges neither JIT nor class loading to
        maxTime) and reported on ``outcome.compile_secs``."""
        state = (jax.tree.map(jnp.asarray, initial)
                 if initial is not None else self.initial_state())
        self._trace_root = jax.tree.map(np.asarray, state)
        self._final_carry = None        # the last run's buffers go
        t0 = time.time()
        if check_initial:
            out = self._check_initial(state, t0)
            if out is not None:
                return self._stamp_device(out)
        try:
            with self.mesh:
                return self._run_rounds(state, resume)
        finally:
            w = getattr(self, "_ckpt_writer_obj", None)
            if w is not None:
                w.join()

    def _run_rounds(self, state, resume: bool) -> SearchOutcome:
        resumed = (self._load_swarm_ckpt()
                   if resume and self.checkpoint_path else None)
        if resumed is not None:
            carry, rounds, prev_elapsed = resumed
            self._resumed_from_depth = rounds
        else:
            carry = self._init_carry(state)
            rounds, prev_elapsed = 0, 0.0
        # Warm-up: the round program is loaded from the executable
        # store, or compiled and kept there, and a zero-step round runs
        # it once, all OUTSIDE the wall budget.
        t_c = time.time()
        self._load_round(carry)
        # Live "depth" for supervision heartbeats and for the round's
        # span = round count.
        self._current_depth = rounds
        carry, _ = self._round_call(carry, 0)
        self.compile_secs += time.time() - t_c
        tel = getattr(self, "_telemetry", None)
        if tel is not None:
            # Compile as a first-class trace node (ISSUE 13) — an
            # event, not a span, so span/dispatch parity holds.
            tel.event("compile", engine="swarm",
                      secs=round(time.time() - t_c, 4), aot=True)
        t0 = time.time() - prev_elapsed
        stats = None
        self._pd_prev_explored = [0] * self.n_devices
        while True:
            cancelled = self._cancelled()
            timed_out = (self.max_secs is not None
                         and time.time() - t0 > self.max_secs)
            round_cap = (self.max_rounds is not None
                         and rounds >= self.max_rounds)
            if cancelled or timed_out or round_cap:
                self._final_carry = carry
                return self._exhaust_outcome(stats, rounds, t0,
                                             cancelled)
            rounds += 1
            self._current_depth = rounds
            t_round = time.time()
            carry, stats = self._round_call(carry,
                                            self.steps_per_round)
            stats = np.asarray(stats)
            tel = getattr(self, "_telemetry", None)
            if tel is not None:
                # Fed from the round's fused stats vector — the same
                # scalars this loop reads anyway (zero extra syncs).
                rec = {
                    "depth": rounds,
                    "wall": round(time.time() - t_round, 4),
                    "explored": int(stats[0]), "unique": int(stats[1]),
                    "next_frontier": 0, "deepest": int(stats[6]),
                    "restarts": int(stats[3])}
                # Per-device lanes off the SAME readback (the 4D tail
                # stats_local appends): walker-work share per device is
                # the per-round explored delta.
                D = self.n_devices
                pd = [int(x) for x in stats[len(stats) - 4 * D:]]
                prev = getattr(self, "_pd_prev_explored", [0] * D)
                delta = [e - p for e, p in zip(pd[:D], prev)]
                self._pd_prev_explored = pd[:D]
                rec["per_device"] = {
                    "explored": delta, "unique": pd[D:2 * D],
                    "restarts": pd[2 * D:3 * D],
                    "deepest": pd[3 * D:]}
                rec["skew"] = {
                    "explored": tel_mod.skew_metrics(delta),
                    "unique": tel_mod.skew_metrics(pd[D:2 * D])}
                hbm = tel_mod.device_memory_stats(
                    self.mesh.devices.flat)
                if hbm is not None:
                    rec["hbm_peak"] = hbm
                tel.on_level("swarm", rec)
            vis_over = int(stats[5])
            over = int(stats[4])
            # Early-warning instrumentation (ISSUE 6 satellite): the
            # swarm shares the BFS visited table, so operators must
            # see fill pressure BEFORE the overflow contract fires
            # (strict raise / treat-as-fresh revisit inflation).
            from dslabs_tpu.tpu.spill import visited_warn_threshold

            fill = int(stats[1]) / (self.n_devices * self.visited_cap)
            if (fill >= visited_warn_threshold()
                    and not getattr(self, "_warned_visited", False)):
                self._warned_visited = True
                warnings.warn(
                    f"{self.p.name}: swarm visited table ~{fill:.0%} "
                    f"full ({int(stats[1])} fresh inserts vs "
                    f"{self.n_devices}x{self.visited_cap} slots) at "
                    f"round {rounds} — capacity pressure; raise "
                    "visited_cap before overflow degrades dedup",
                    RuntimeWarning, stacklevel=2)
            # Terminal flags BEFORE the strict capacity guards: a
            # violation found this round is a valid verdict even if
            # the table filled alongside it (the _sync_checks order).
            nf = len(self._flag_names)
            if stats[8:8 + nf].any():
                return self._resolve_hit(carry, stats, rounds, t0)
            if self.strict and vis_over:
                raise CapacityOverflow(
                    f"{self.p.name}: swarm visited table full "
                    f"({vis_over} unresolved keys, cap "
                    f"{self.visited_cap}/device); raise visited_cap "
                    "or run strict=False")
            if self.strict and over:
                raise CapacityOverflow(
                    f"{self.p.name}: {over} walker steps truncated by "
                    "net/timer caps (strict swarm); raise the caps")
            refused = int(stats[8 + nf + EXTRAS.index("refused")])
            if self.strict and refused:
                raise CapacityOverflow(
                    f"{self.p.name}: {refused} walker steps refused by "
                    "the twin for want of room in its own state "
                    "(strict swarm); bind a wider twin")
            if (self.checkpoint_path and self.checkpoint_every
                    and rounds % self.checkpoint_every == 0):
                self._save_swarm_ckpt(carry, rounds, time.time() - t0)

    def _stats_dict(self, stats, rounds: int, elapsed: float) -> dict:
        (explored, fresh, revisit, restarts, over, vis_over,
         deepest, _steps) = (int(x) for x in stats[:8])
        el = max(elapsed, 1e-9)
        # After the flag counts: EXTRAS, then the fresh inserts by walk
        # depth 1..FRESH_DEPTHS (the per-device tail follows).
        at = 8 + len(self._flag_names)
        extras = {k: int(x) for k, x in zip(EXTRAS, stats[at:])}
        at += len(EXTRAS)
        return {
            "walkers": self.n_devices * self.walkers,
            "step_blocks": self.step_blocks,
            "rounds": rounds, "explored": explored, "unique": fresh,
            "revisits": revisit, "restarts": restarts,
            "overflow_restarts": over, "vis_over": vis_over,
            "deepest": deepest, **extras,
            "fresh_by_depth": [int(x)
                               for x in stats[at:at + FRESH_DEPTHS]],
            "walkers_per_sec": round(explored / el, 1),
            "unique_per_min": round(fresh / el * 60.0, 1),
        }

    def _finish_outcome(self, out: SearchOutcome,
                        sd: dict) -> SearchOutcome:
        out.swarm = sd
        out.walker_restarts = sd["restarts"]
        out.swarm_overflow = sd["overflow_restarts"]
        out.visited_overflow = sd["vis_over"]
        out.compile_secs = round(self.compile_secs, 3)
        self._stamp_device(out)
        out.resumed_from_depth = getattr(self, "_resumed_from_depth", 0)
        if out.swarm_overflow + sd["refused"] > OVERFLOW_WARN:
            warnings.warn(
                f"{self.p.name}: {out.swarm_overflow} walker steps "
                "were capacity-truncated and restarted (net/timer caps "
                f"too small for the walked region), {sd['refused']} "
                "refused by the twin for want of room in its own state "
                "— deep coverage is degraded; raise the caps or run a "
                "strict swarm",
                RuntimeWarning, stacklevel=3)
        if out.walker_restarts > RESTART_WARN:
            warnings.warn(
                f"{self.p.name}: {out.walker_restarts} walker restarts "
                "(> DSLABS_SWARM_RESTART_WARN) — walkers are churning; "
                "raise max_steps or seed from a deeper frontier",
                RuntimeWarning, stacklevel=3)
        tel = getattr(self, "_telemetry", None)
        if tel is not None:
            # Trace stamp at span emission (ISSUE 13): host-side only.
            if out.trace_id is None:
                out.trace_id = tel.trace_id
            tel.on_outcome(out, engine="swarm")
        return out

    def _exhaust_outcome(self, stats, rounds: int, t0,
                         cancelled: bool) -> SearchOutcome:
        elapsed = time.time() - t0
        if stats is None:
            stats = np.zeros((8 + len(self._flag_names) + len(EXTRAS)
                              + FRESH_DEPTHS,), np.int64)
        sd = self._stats_dict(stats, rounds, elapsed)
        out = SearchOutcome(
            "TIME_EXHAUSTED", sd["explored"], sd["unique"],
            sd["deepest"], elapsed, cancelled=cancelled)
        return self._finish_outcome(out, sd)

    def _resolve_hit(self, carry, stats, rounds: int,
                     t0) -> SearchOutcome:
        """First-hit resolution: ONE readback of the capture arrays,
        checkState flag order, then the witness pipeline (minimize +
        replay-verify) before the verdict is returned."""
        D, K, S = self.n_devices, self.walkers, self.max_steps
        nf = len(self._flag_names)
        data = self._dispatch(
            "swarm.flags", device_get_tree,
            {k: carry[k] for k in ("hit_cnt", "hit_rows", "hit_hist",
                                   "hit_depth", "hit_seed", "seeds",
                                   "seeds_n")})
        cnts = data["hit_cnt"].reshape(D, nf)
        rows = data["hit_rows"].reshape(D, nf, self.lanes)
        hist = data["hit_hist"].reshape(D, nf, S)
        depth = data["hit_depth"].reshape(D, nf)
        seed_i = data["hit_seed"].reshape(D, nf)
        pool = data["seeds"].reshape(D, -1, self.lanes)
        elapsed = time.time() - t0
        sd = self._stats_dict(stats, rounds, elapsed)
        for fi, fname in enumerate(self._flag_names):
            devs = np.nonzero(cnts[:, fi])[0]
            if not len(devs):
                continue
            d = int(devs[0])
            raw = [int(e) for e in hist[d, fi][:int(depth[d, fi])]]
            seed_row = pool[d, int(seed_i[d, fi])]
            # The walk root this witness replays from (tpu/trace.py
            # contract): the walker's seed state — the run root for
            # root-started fleets, a frontier row under seeding.
            self._trace_root = jax.tree.map(
                np.asarray, self.unflatten_rows(seed_row[None]))
            st = jax.tree.map(np.asarray,
                              self.unflatten_rows(rows[d, fi][None]))
            if fname == "exc":
                end, pname = "EXCEPTION_THROWN", None
                code = int(st["exc"][0])
            else:
                kind, pname = fname.split(":", 1)
                end = ("INVARIANT_VIOLATED" if kind == "inv"
                       else "GOAL_FOUND")
                code = 0
            wit = build_witness(self, seed_row, raw, end, pname, code,
                                minimize=self.minimize,
                                verify=self.replay_verify)
            out = SearchOutcome(
                end, sd["explored"], sd["unique"],
                int(depth[d, fi]), elapsed,
                violating_state=(st if end != "GOAL_FOUND" else None),
                goal_state=(st if end == "GOAL_FOUND" else None),
                predicate_name=pname, exception_code=code,
                trace=wit.trace, witness=wit)
            return self._finish_outcome(out, sd)
        raise AssertionError("swarm hit counts fired without a flag")


def device_get_tree(tree):
    """Readback funnel for pytrees (mirrors engine.device_get, which
    tests monkeypatch to audit transfer sizes)."""
    return jax.tree.map(device_get, tree)
