"""Multi-chip sharded BFS: the full device-resident search loop (SPMD).

Scaling design (SURVEY §2.10, §5): the frontier, the visited set, and the
next-frontier accumulator all live in device HBM, sharded over the
``search`` mesh axis.  Each BFS level is a sequence of chunk steps — every
device expands a chunk of its frontier shard with the same vmapped
transition the single-chip engine uses, then successor fingerprints
(16 bytes each) AND rows are exchanged by **key ownership**
(device = key_hi mod D) with ``lax.all_to_all`` over ICI, in the same
owner buckets.  Each owner deduplicates the keys it owns against its
**open-addressing hash table in HBM** — 8-slot buckets read as one
aligned 128-byte line, membership and insert in one bounded probe loop
(tpu/visited.py), claim conflicts serialised by a per-bucket min-index
reservation — and appends the fresh rows it received to its own
next-frontier shard, so the between-level promote is a local buffer
swap: no collective, no compaction.  This is the classic
hash-partitioned distributed BFS, mapped onto XLA collectives instead
of the reference's shared-memory ConcurrentHashMap
(Search.java:405-505); with a 1-device mesh the collectives are
identities, which is how a one-chip search runs.

Host involvement per level: ONE on-device **superstep** dispatch — a
``lax.while_loop`` of chunk steps inside a single ``shard_map`` program
that drains every device's own frontier shard (occupancy-driven trip
count read from the carry, not a host bound) and returns the fused
scalar stats vector — plus the between-level promote, so at most two
host dispatches per level.  No state rows cross the host boundary
until a terminal state must be reported; even the initial carry is
built on device.

Everything on device is int32/uint32 (TPU-native dtypes; no x64).  All
fixed-capacity structures (routing buckets, frontier shards, visited
shards) count their drops and the driver raises
:class:`~dslabs_tpu.tpu.engine.CapacityOverflow` — never a silent
undercount (round-1 advisor findings: validity rides an explicit mask
through the all_to_all, not a reserved fingerprint value).
"""

from __future__ import annotations

import gc
import math
import os
import re
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dslabs_tpu.tpu import compile_cache
from dslabs_tpu.tpu import telemetry as tel_mod
from dslabs_tpu.tpu import visited as visited_mod
from dslabs_tpu.tpu.engine import (CapacityOverflow, SearchOutcome,
                                   TensorProtocol, TensorSearch,
                                   device_get, flatten_state,
                                   row_fingerprints, state_fingerprints)
from dslabs_tpu.tpu.spill import (dropped_warn_threshold as
                                  _DROPPED_WARN,
                                  visited_warn_threshold as
                                  _VISITED_WARN)

__all__ = ["ShardedTensorSearch", "make_mesh",
           "CARRY_PARTITION_RULES", "match_partition_rules"]


def _env_on(name: str, default: bool = True) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("0", "", "off", "false", "no")

OVERFLOW_FACTOR = 2
# Chunk steps per superstep dispatch under a wall-clock budget: the
# host reads its clock between dispatches, so a time box ends at one of
# these boundaries (PERF.md §2).
SUPERSTEP_CHUNKS = 16
# The visited hash table itself lives in dslabs_tpu/tpu/visited.py — ONE
# implementation shared with the single-device engine's device-resident
# wave loop (engine.py _run_device).
MAXU32 = visited_mod.MAXU32
BKT = visited_mod.BKT


# ------------------------------------------------------- carry placement
#
# First-class NamedSharding/PartitionSpec placement of the search carry
# (ISSUE 12, following the SNIPPETS [1] regex-partition-rule pattern):
# ONE rule table maps carry leaf names to PartitionSpecs over the named
# mesh axis, and every placement consumer — the shard_map in/out specs,
# the hot programs' jit in/out shardings, the carry initialiser's
# out_shardings, the AOT ShapeDtypeStructs, and the resume/spill
# device_puts — derives from it.  Width-free by construction: the
# elastic ladder (tpu/supervisor.py) re-derives the identical layout on
# any narrower mesh, and XLA sees one consistent placement end to end
# instead of inferring (and defensively resharding) between dispatches.

CARRY_PARTITION_RULES = (
    # Wide SoA buffers: frontier shards, next-frontier accumulator,
    # per-row trace meta — row-sharded over the search axis.  Under
    # the packed wire format (ISSUE 18) cur/nxt hold PACKED words
    # (width = descriptor.words), same placement.
    (r"^(cur|nxt|tmeta)$", lambda ax: P(ax)),
    # The owner-sharded visited hash table (one visited.table_shape
    # block of rows per device; owner = key lane 0 mod D picks it).
    (r"^visited$", lambda ax: P(ax)),
    # Terminal-flag rows/meta/counters: one n_flags block per device.
    (r"^(flag_rows|flag_meta|flag_cnt)$", lambda ax: P(ax)),
    # Delta-encoding level bases (ISSUE 18 leg (b)): one [n_delta]
    # int32 vector per device, value-replicated by construction (the
    # chunk step pmin's them) but stored per-device so the carry stays
    # uniformly sharded and donation-friendly.  pb_peak: one int32 a
    # device, the level's largest ``value - base`` so far.
    (r"^(pb_cur|pb_nxt|pb_peak)$", lambda ax: P(ax)),
    # Per-device scalar lanes: occupancies, loop counters, stats.
    (r"^(cur_n|nxt_n|vis_n|j|evp|noapp|explored|overflow|vis_over"
     r"|drops|f_full)$", lambda ax: P(ax)),
)


def match_partition_rules(rules, names, axis):
    """SNIPPETS [1]'s regex-rules -> PartitionSpec mapping, applied to
    carry leaf NAMES: the first matching rule wins; an unmatched leaf
    is a loud error (a new carry entry must declare its placement, not
    inherit one by accident)."""
    out = {}
    for name in names:
        for pat, spec in rules:
            if re.search(pat, name):
                out[name] = spec(axis) if callable(spec) else spec
                break
        else:
            raise ValueError(
                f"no partition rule for carry leaf {name!r} — add it "
                "to CARRY_PARTITION_RULES")
    return out


def make_mesh(n_devices: int = None, axis: str = "search") -> Mesh:
    """A 1-D mesh over the DEFAULT backend's devices.  Asking for more
    devices than that backend has raises — a CPU mesh is built only
    when the default backend is the CPU (``JAX_PLATFORMS=cpu`` with
    ``--xla_force_host_platform_device_count``, the tests' and the
    dry runs' setting); it never stands in for missing accelerators."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, the default backend "
                f"({jax.default_backend()}) has {len(devs)}; a virtual "
                "CPU mesh needs JAX_PLATFORMS=cpu and XLA_FLAGS="
                "--xla_force_host_platform_device_count")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


class ShardedTensorSearch(TensorSearch):
    """BFS driver whose frontier, visited set, and expansion all live
    sharded on a device mesh; ``run()`` executes the full multi-level
    search with one superstep dispatch (stats readback included) and
    one promote per level.

    Per-device carry (global shapes have a leading D factor):
      cur      [(F+K)*plane] int32  current frontier shard (owned
                                  states) as a LOG of packed words: row
                                  r at words [r*plane, (r+1)*plane).
                                  Words past cur_n rows are UNSPECIFIED:
                                  every reader masks by cur_n
      cur_n    [1]        int32   occupancy of cur (rows)
      nxt      [(F+K)*plane]      next-frontier log, the same shape (the
                                  promote SWAPS the two), appended by
                                  contiguous block writes; the K =
                                  visited.block_width slack rows take a
                                  block that would cross row F
      nxt_n    [1]                occupancy of nxt (rows)
      visited  [32, V/8]  uint32  open-addressing hash table of 128-bit
                                  keys, one bucket a column
                                  (visited.table_shape); EMPTY = all-MAX
      vis_n    [1]                number of keys inserted
      counters: explored / overflow / routed-drop / frontier-drop
      flag_cnt [n_flags], flag_rows [n_flags, lanes]: terminal detection
        (exception -> invariant -> goal, checkState order
        Search.java:162-231) — first-hit successor row kept per flag.
    """

    def __init__(self, protocol: TensorProtocol, mesh: Mesh,
                 chunk_per_device: int = 1 << 10,
                 frontier_cap: int = 1 << 14,
                 visited_cap: int = 1 << 20,
                 max_depth: Optional[int] = None,
                 max_secs: Optional[float] = None,
                 strict: bool = True,
                 ev_budget: Optional[int] = None,
                 ev_spill: Optional[bool] = None,
                 record_trace: bool = False,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 0,
                 superstep_chunks: int = SUPERSTEP_CHUNKS,
                 aot_warmup: Optional[bool] = None,
                 spill=None,
                 telemetry=None,
                 symmetry: Optional[bool] = None,
                 mesh_pack: bool = True):
        # Frontier checkpointing (SURVEY §5 "dump SoA tensors"): every
        # ``checkpoint_every`` levels the live carry — the OCCUPIED
        # frontier prefix, the occupied visited-table lines, and the
        # counters; never the empty accumulators or f_cap padding — is
        # snapshotted into fresh device buffers and drained to
        # ``checkpoint_path`` (atomic .npz rename) by a background
        # thread while the next levels compute (see the checkpointing
        # section below).  The dump is the UNIFIED engine-agnostic
        # format (tpu/checkpoint.py) — the single-device and host
        # engines resume the same file, which is what makes supervisor
        # failover (tpu/supervisor.py) resumable.  ``run(resume=True)``
        # continues a killed search from the last dump with identical
        # final verdict and unique count.  0 = off.
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_devices = int(mesh.devices.size)
        # strict=True (search tests): ANY capacity drop is fatal — verdicts
        # must be exact.  strict=False (throughput benches): routing-bucket
        # and frontier-cap drops truncate expansion coverage beam-style and
        # are reported via SearchOutcome.dropped; semantic overflow
        # (net/timer caps) stays fatal either way.  A FULL visited table
        # degrades to treat-as-fresh (visited.py contract): fatal in
        # strict (unique counts must be exact), counted and reported via
        # SearchOutcome.visited_overflow in beam.
        # F must divide evenly by the chunk (chunk-loop slicing) AND the
        # device count (resume and spill re-inject split the frontier
        # into per-device shares); pad to the lcm so neither pad breaks
        # the other's invariant.
        quantum = math.lcm(chunk_per_device, self.n_devices)
        if frontier_cap % quantum:
            frontier_cap += quantum - frontier_cap % quantum
        if visited_cap & (visited_cap - 1):
            raise ValueError("visited_cap must be a power of two "
                             "(hash-table slot arithmetic)")
        self.f_cap = frontier_cap          # per device
        self.v_cap = visited_cap           # per device
        self.cpd = chunk_per_device
        # Event-window spill (round-4): when a chunk has valid events past
        # the ev_budget window, re-step it at the next window instead of
        # dropping (beam) / aborting (strict) — a finite budget then costs
        # extra passes on rare over-budget chunks, never coverage.
        # Default: on for strict (exactness), off for beam (the re-pass
        # of a whole chunk for a few tail events is the wrong throughput
        # trade; drops are counted as before).
        self.ev_spill = strict if ev_spill is None else ev_spill
        # The owner-side hash table is the dedup authority, so the
        # engine's in-chunk sort-unique prefilter is redundant work — but
        # without it, duplicate successors (all sharing one fingerprint,
        # hence one owner) can pile onto a single fixed-size routing
        # bucket.  On ONE device the bucket holds the entire successor
        # batch exactly (_bucket), so pileup cannot
        # overflow and even strict runs skip the prefilter (it measured
        # ~60% of a loaded chunk step).  Multi-device strict keeps it:
        # per-owner buckets have only 2x-mean headroom.
        # Packed wire format (ISSUE 18): the sharded carry — frontier
        # shards, routing buckets, the row-exchange payload — is
        # re-typed to the spec-derived bit-packed encoding, so the
        # owner-hashed all_to_all ships descriptor.words int32 words per
        # state instead of ``lanes``.  super() still gets packed=False:
        # the base engine's OWN packing paths (device wave loop, its
        # checkpoint writer) are not on the sharded hot path, and the
        # sharded descriptor is derived separately below WITH the
        # delta-lane extension (delta=True) so view-number-style fields
        # pack here even though the single-device engine keeps them
        # raw.  Symmetry DOES ride along: the canonicalize pass lives
        # in the shared _expand_chunk hash step, so the owner-hash keys
        # on canonical fingerprints and symmetric twins dedup on one
        # owner.
        super().__init__(protocol, frontier_cap=frontier_cap,
                         chunk=chunk_per_device, max_depth=max_depth,
                         max_secs=max_secs,
                         in_chunk_dedup=strict and self.n_devices > 1,
                         ev_budget=ev_budget, record_trace=record_trace,
                         visited_cap=visited_cap, strict=strict,
                         checkpoint_path=checkpoint_path,
                         checkpoint_every=checkpoint_every,
                         spill=spill, telemetry=telemetry,
                         packed=False, symmetry=symmetry)
        # Mesh wire codec: mesh_pack=False keeps the raw int32 exchange
        # (the codec's reference in the packed-vs-raw tests).  Identity
        # descriptors (hand twins without domain metadata) run the raw
        # wire too — loudly, via the run()-time telemetry event.
        self.mesh_pack = bool(mesh_pack)
        if self.mesh_pack:
            from dslabs_tpu.tpu.packing import derive_packing
            pk = derive_packing(protocol, self.lanes, delta=True)
            self._pk = None if pk.identity else pk
        else:
            self._pk = None
        self.plane = self._pk.words if self._pk is not None else self.lanes
        if self._log_words() >= 1 << 31:
            raise ValueError(
                f"frontier_cap={self.f_cap}/device x {self.plane} words a "
                "row passes the 2^31 words an int32 offset into the next "
                "frontier's log can address; lower frontier_cap")
        self._mesh_delta = (self._pk is not None and self._pk.has_delta)
        if self._mesh_delta:
            self._delta_lanes = np.asarray(self._pk.delta_lanes, np.int32)
        # Host-RAM spill tier (tpu/spill.py, docs/capacity.md): the
        # carry gains an ``f_full`` abort-code lane, the chunk step
        # aborts-and-reverts GLOBALLY (a psum'd decision — owner-side
        # inserts for a retried chunk must revert on every device) on
        # frontier/table exhaustion, and level boundaries refilter the
        # would-be frontier against the host tier.  All of it is
        # conditional on the knob so non-spill programs stay
        # byte-identical (warm compile caches keep hitting).
        self._spill_on = self._spill is not None
        # Trace mode: each level spills (child_fp, parent_fp, event_id)
        # for every appended successor; reconstruction walks fingerprints
        # back to the root on the HOST (fps are stable identities, so the
        # owner routing needs no permutation bookkeeping) and replays
        # the grid event ids on the object twin via tpu/trace.py.
        self._fp_map = {}                  # child fp bytes -> (parent, ev)
        # _flag_names is set by super().__init__ (shared with the
        # single-device device-resident loop).  Hot programs are jitted
        # with the rule-derived carry shardings pinned on BOTH sides
        # (in_shardings/out_shardings): placement is an explicit
        # contract, not an inference XLA re-derives per dispatch.
        self._finish_level = self._sharded_jit(self._build_finish())
        if self._mesh_delta:
            self._finish_rebase = self._sharded_jit(
                self._build_finish(rebase=True))
        self._superstep = self._superstep_jit()
        # Chunk-step budget per superstep dispatch when a wall-clock
        # budget is active: bounds device work between host clock checks
        # so mid-level TIME_EXHAUSTED keeps chunk granularity.  The
        # supervisor's adaptive OOM backoff halves it per knob-shrink
        # re-level (docs/resilience.md "knob-shrink ladder").
        self._superstep_chunks = int(superstep_chunks)

        # Explicit AOT warm-up (ISSUE 3): .lower().compile() the hot
        # programs at construction so compile wall-time is measured
        # separately from search wall-time (SearchOutcome.compile_secs)
        # and — with the persistent compile cache wired — a second run of
        # the same config pays near-zero compile.
        self.compile_secs = 0.0
        if (_env_on("DSLABS_AOT_WARMUP", False)
                if aot_warmup is None else bool(aot_warmup)):
            self.aot_warmup()
        # Soundness sanitizer (ISSUE 10): audit the freshly-built
        # superstep/promote/init programs when DSLABS_SANITIZE is on.
        self._maybe_sanitize()

    # ------------------------------------------------- placement helpers

    def _carry_names(self) -> list:
        keys = ["cur", "cur_n", "j", "evp", "noapp", "nxt", "nxt_n",
                "visited", "vis_n", "explored", "overflow", "vis_over",
                "drops", "flag_cnt", "flag_rows"]
        if self.record_trace:
            keys += ["tmeta", "flag_meta"]
        if self._spill_on:
            keys += ["f_full"]
        if self._mesh_delta:
            keys += ["pb_cur", "pb_nxt", "pb_peak"]
        return keys

    def _bucket(self) -> int:
        """Rows of ONE owner bucket of a chunk step's exchange.  On one
        device every successor routes to the sole owner, so the bucket
        can hold the whole batch exactly (no overflow headroom needed)
        — halving the rows the probe loop and the append touch.
        Multi-device buckets keep 2x-mean headroom for skew."""
        D, n = self.n_devices, self.cpd * self._num_events()
        return n if D == 1 else (n // D + 1) * OVERFLOW_FACTOR

    def _log_words(self) -> int:
        """int32 words of one device's frontier log (``cur`` and ``nxt``
        alike): ``f_cap`` rows and the slack of one append block (``K``
        rows of the batch a chunk step receives), so that a block that
        would cross row ``f_cap`` still lies inside the buffer —
        ``dynamic_update_slice`` CLAMPS a start that overruns, and a
        clamped start would overwrite good rows."""
        K = visited_mod.block_width(self.n_devices * self._bucket())
        return (self.f_cap + K) * self.plane

    # Delta-lane level bases (ISSUE 18 leg (b)).  pb_cur/pb_nxt are
    # [n_delta] int32 per device: the per-lane minimum over the live
    # frontier / accumulating next frontier of every ("delta", bits)
    # lane.  The chunk step pmin's candidate minima across devices, so
    # the per-device copies are value-identical by construction and the
    # promote's re-encode needs no collective.  pb_peak [1] is the
    # largest ``value - pb_cur`` any live successor's delta lane held in
    # this level's chunk steps on this device: how much of the lanes'
    # window the level used (the level record's ``delta_peak``).
    _PB_EMPTY = np.int32(2 ** 31 - 1)

    def _base_vec(self, pb):
        """[n_delta] per-device base -> [lanes] base vector for the
        codec (non-delta lanes read their static lo; the scatter value
        for them is ignored by LanePacking)."""
        didx = jnp.asarray(self._delta_lanes)
        return (jnp.zeros((self.lanes,), jnp.int32)
                .at[didx].set(pb.astype(jnp.int32)))

    def _carry_shardings(self) -> dict:
        """Rule-derived NamedSharding per carry leaf — the ONE
        placement authority (CARRY_PARTITION_RULES) every consumer
        shares; rebuilt per mesh so the elastic ladder's narrower
        rungs get the identical layout."""
        return {k: NamedSharding(self.mesh, s)
                for k, s in self._carry_specs().items()}

    def _sharded_jit(self, fn, extra_in=(), extra_out=None):
        """jit a carry-first program with the rule-derived placement
        pinned on both sides and the carry donated.  ``extra_in`` /
        ``extra_out`` list the shardings of any non-carry operands
        (replicated scalars/masks) after the carry."""
        cs = self._carry_shardings()
        ins = (cs,) + tuple(extra_in)
        outs = cs if extra_out is None else (cs,) + tuple(extra_out)
        return jax.jit(fn, donate_argnums=0, in_shardings=ins,
                       out_shardings=outs)

    def _replicated(self):
        return NamedSharding(self.mesh, P())

    def _superstep_jit(self):
        rep = self._replicated()
        extra = ((rep, (rep, rep)) if self._has_rt_masks()
                 else (rep,))
        return self._sharded_jit(self._build_superstep(),
                                 extra_in=extra, extra_out=(rep,))

    # --------------------------------------------------------- level chunk

    def _make_local_step(self):
        """The per-device chunk-step body (runs INSIDE shard_map, as
        the body of the superstep's ``lax.while_loop``): one chunk
        expand + owner routing of keys and rows + owner dedup +
        frontier append.  Returns the carry and the step's counts
        ``[write blocks scattered (table and append:
        visited.block_width), bucket columns the probe gathered, event
        kinds the expand skipped (0-2)]``."""
        p = self.p
        D = self.n_devices
        C = self.cpd
        F = self.f_cap
        ax = self.axis
        # Packed wire format (ISSUE 18): frontier shards and the
        # row-exchange payload hold PACKED words; owners decode
        # in-register at expand time (unpack below), producers encode
        # each successor batch ONCE and both the wire and the nxt store
        # reuse the same packed rows.  plane == lanes when the codec is
        # identity / disabled — every shape below degenerates to the
        # raw layout.
        pk = self._pk
        plane = self.plane
        delta = self._mesh_delta
        bucket = self._bucket()
        # Spill mode (tpu/spill.py): frontier/table exhaustion ABORTS
        # the chunk step GLOBALLY — the decision is psum'd and every
        # device reverts its whole update (owner-side inserts included:
        # a producer may have kept rows whose keys live only in another
        # device's reverted table, so all-or-nothing is the only sound
        # retry unit) — and an abort code lands on the carry's f_full
        # lane (bit 0 frontier full, bit 1 table full) for the host to
        # answer with a drain/evict before re-dispatching.
        spill_on = self._spill is not None

        def local(carry, masks=None):
            # The chunk index lives IN the carry (device-resident,
            # self-incrementing): passing it as a per-call jnp scalar cost
            # a fresh host->device transfer per chunk step — the same
            # latency class as a readback.
            cur, cur_n = carry["cur"], carry["cur_n"][0]
            j = carry["j"][0]
            start = j * C
            # Stage names for a profile (tpu/telemetry.py
            # DEVICE_SCOPES): HLO metadata only.
            with tel_mod.device_scope("pack"):
                rows_chunk = jax.lax.dynamic_slice(
                    cur, (start * plane,), (C * plane,)).reshape(C, plane)
                base_cur = (self._base_vec(carry["pb_cur"]) if delta
                            else None)
                if pk is not None:
                    # In-register decode at expand time: the frontier
                    # shard stores packed words, the expansion grid
                    # wants lanes.
                    rows_chunk = pk.unpack_jnp(rows_chunk, base_cur)
            valid = (start + jnp.arange(C)) < cur_n
            ev_pass = carry["evp"][0]
            (rows, valids, fp, unique, overflow, ev_rem, event_ids,
             flags, kind_skips) = self._expand_chunk(
                 rows_chunk, valid, ev_pass, masks)
            # Spill: valid events past this pass's window mean the SAME
            # chunk must re-step at the next window before j advances
            # (run() re-dispatches until every device's j reaches its
            # chunk count).  Without spill, the remainder is a counted
            # beam-style drop exactly as in round 3.
            if self.ev_spill:
                spill = ev_rem > 0
                j_next = carry["j"] + jnp.where(spill, 0, 1)
                evp_next = jnp.where(spill, carry["evp"] + 1, 0)
                ev_drops = jnp.int32(0)
            else:
                j_next = carry["j"] + 1
                evp_next = carry["evp"]
                ev_drops = ev_rem
            if self.record_trace:
                # [C*B, 9] uint32 trace meta: child fp, parent fp, grid
                # event id — spilled to host per level for fp-chain
                # reconstruction (the sharded analog of the base
                # engine's per-level (parent, event) spill).
                with tel_mod.device_scope("trace_meta"):
                    fp_par = row_fingerprints(rows_chunk)      # [C, 4]
                    ne_slots = self._num_events()
                    meta = jnp.concatenate([
                        fp,
                        jnp.repeat(fp_par, ne_slots, axis=0),
                        event_ids.reshape(-1, 1).astype(jnp.uint32),
                    ], axis=1)                                 # [C*B, 9]

            with tel_mod.device_scope("flags"):
                # ---- terminal flags, checkState order (exception first)
                hit_list = [valids & (rows[:, -1] != 0)]
                for n in p.invariants:
                    hit_list.append(valids & ~flags[f"inv:{n}"])
                for n in p.goals:
                    hit_list.append(flags[f"goal:{n}"])
                hits = jnp.stack(hit_list)                       # [nf, C*E]
                cnts = jnp.sum(hits, axis=1).astype(jnp.int32)
                idxs = jnp.argmax(hits, axis=1)
                new_rows_f = rows[idxs]                          # [nf, lanes]
                fresh_flag = (carry["flag_cnt"] == 0) & (cnts > 0)
                flag_rows = jnp.where(fresh_flag[:, None], new_rows_f,
                                      carry["flag_rows"])
                flag_cnt = carry["flag_cnt"] + cnts
                if self.record_trace:
                    flag_meta = jnp.where(fresh_flag[:, None], meta[idxs],
                                          carry["flag_meta"])

                pruned = rows[:, -1] != 0
                for n in p.prunes:
                    pruned = pruned | flags[f"prune:{n}"]

            with tel_mod.device_scope("pack"):
                # ---- encode the successor batch ONCE: the same packed rows
                # ride the owner-hashed all_to_all (the ~pack_ratio x ICI
                # cut) AND the nxt store.  Out-of-domain values (a wrong
                # Field bound, or a delta value past its window) are counted
                # on LIVE rows only and folded into the semantic-overflow
                # counter — _sync_checks raises the loud CapacityOverflow.
                pack_bad = jnp.int32(0)
                if pk is not None:
                    rows_store, bad = pk.pack_jnp(rows, base_cur,
                                                  count_bad=True)
                    pack_bad = jnp.sum(
                        jnp.where(valids, bad, 0)).astype(jnp.int32)
                else:
                    rows_store = rows
                if delta:
                    # Candidate next-level base: per-lane min of the live
                    # successors' delta values, pmin'd across the mesh so
                    # every device carries the identical base and the
                    # promote re-encode needs no collective.  The min over
                    # ALL live successors (pruned included) is a lower
                    # bound of the stored subset — a valid (just possibly
                    # looser) base.
                    dvals = rows[:, jnp.asarray(self._delta_lanes)]
                    pb_peak = jnp.maximum(carry["pb_peak"], jnp.max(
                        jnp.where(valids[:, None],
                                  dvals - carry["pb_cur"][None, :], 0)))
                    dvals = jnp.where(valids[:, None], dvals,
                                      jnp.int32(self._PB_EMPTY))
                    cand = jnp.min(dvals, axis=0).astype(jnp.int32)
                    pb_nxt = jax.lax.pmin(
                        jnp.minimum(carry["pb_nxt"], cand), ax)

            with tel_mod.device_scope("route"):
                # ---- ownership routing: successors sorted by owner form
                # contiguous segments, so the [D, bucket] key buckets are
                # narrow gathers at segment offsets.
                owner = (fp[:, 0] % jnp.uint32(D)).astype(jnp.int32)
                owner = jnp.where(unique, owner, D)     # non-unique -> nowhere
                order = jnp.argsort(owner, stable=True)
                owner_s = owner[order]
                dev = jnp.arange(D)
                starts = jnp.searchsorted(owner_s, dev, side="left")
                ends = jnp.searchsorted(owner_s, dev, side="right")
                src = (starts[:, None]
                       + jnp.arange(bucket)[None, :])       # [D, bkt]
                send_valid = src < ends[:, None]
                # [D, bkt] row idx
                gidx = order[src.clip(0, owner.shape[0] - 1)]
                send_keys = fp[gidx.reshape(-1)].reshape(D, bucket, 4)
                counts = ends - starts
                route_drop = jnp.sum(jnp.maximum(counts - bucket, 0)).astype(
                    jnp.int32)
                # The successor ROW, its pruned flag, and (in trace
                # mode) its meta ride the SAME owner buckets as the
                # keys — one more all_to_all per chunk lands every
                # fresh state on its OWNER's frontier shard as it is
                # produced, so the level promote is a local buffer swap
                # (_build_finish).
                parts = [rows_store, pruned[:, None].astype(jnp.int32)]
                if self.record_trace:
                    parts.append(jax.lax.bitcast_convert_type(
                        meta, jnp.int32))
                payload = jnp.concatenate(parts, axis=1)
                send_rows = payload[gidx.reshape(-1)].reshape(
                    D, bucket, payload.shape[1])

            with tel_mod.device_scope("exchange"):
                # ---- the exchange: every device receives the key bucket
                # destined to it from every other device (ICI all_to_all)
                recv_keys = jax.lax.all_to_all(send_keys, ax, 0, 0)
                recv_valid = jax.lax.all_to_all(send_valid, ax, 0, 0)
                rb = D * bucket
                recv_keys = jnp.where(recv_valid.reshape(rb, 1),
                                      recv_keys.reshape(rb, 4), MAXU32)
                recv_valid = recv_valid.reshape(rb)
                recv_rows = jax.lax.all_to_all(
                    send_rows, ax, 0, 0).reshape(rb, -1)

            with tel_mod.device_scope("visited_insert"):
                # ---- owner-side dedup via the SHARED open-addressing hash
                # table (dslabs_tpu/tpu/visited.py — one implementation for
                # this driver and the single-device device-resident loop).
                # The recv batch may hold the same key several times (from
                # different producers, or in-chunk duplicates when the
                # prefilter is off); the table's per-bucket reservation
                # guarantees exactly one copy ever inserts.  Bucket index
                # comes from lane 2 (b_hi), NOT lane 0: ownership routing
                # already fixed lane0 ≡ device (mod D), so a lane0-derived
                # home bucket would cluster every owned key into 1/D of the
                # table (visited.py keys buckets by lane 2 for this reason).
                #
                # Probe exhaustion (table effectively full) leaves keys
                # UNRESOLVED: per the visited.py contract they are treated
                # as FRESH (sound — re-explored, never silently dropped) and
                # counted into the vis_over flag, which _sync_checks raises
                # on in strict mode and reports via
                # SearchOutcome.visited_overflow in beam mode.
                (new_visited, ins_s, unres_s, blocks,
                 probe_cols) = visited_mod.insert(
                     carry["visited"], recv_keys, recv_valid,
                     count_blocks=True, count_cols=True)
                fresh_s = ins_s | unres_s
                vis_over = jnp.sum(unres_s).astype(jnp.int32)
                n_fresh = jnp.sum(ins_s).astype(jnp.int32)

            # Owner-side append: the received rows ARE this device's
            # share of the next frontier (owner-hashed placement — the
            # distribution the per-device skew lanes judge).
            with tel_mod.device_scope("append"):
                app_rows = recv_rows[:, :plane]
                app_pruned = recv_rows[:, plane] != 0
                if self.record_trace:
                    app_meta = jax.lax.bitcast_convert_type(
                        recv_rows[:, plane + 1:], jnp.uint32)
                # ---- append fresh, un-pruned successors, in owner-
                # received order (BFS level semantics are order-free), to
                # the local next frontier.
                # noapp (set by run() for the FINAL depth-limited level):
                # fresh states still count into vis_n/flags — discovered,
                # checked, never expanded — but skip the frontier append, so
                # a last level D times larger than frontier_cap needs no
                # frontier memory (the depth limit ends the search exactly as
                # DEPTH_EXHAUSTED would; the reference's BFS likewise never
                # queues states at the cutoff depth).
                noapp = carry["noapp"][0] == 1
                sel_would = fresh_s & ~app_pruned   # fresh implies valid
                # Spill mode appends pruned-but-fresh rows too: every fresh
                # insert must reach the host refilter (the drain recomputes
                # the prune/exception mask before anything re-expands), or
                # a post-eviction re-discovery of a pruned state would
                # double-count.  noapp counting stays on sel_would — the
                # DEPTH-vs-SPACE decision is about expandable successors.
                sel = (fresh_s if spill_on else sel_would) & ~noapp
                nxt_n = carry["nxt_n"][0]
                # Write narrow, as the table's scatter does
                # (visited.write_in_blocks): most received rows are not
                # selected.  The selected rows, in owner-received order, go
                # out a block at a time to rows nxt_n, nxt_n + 1, ... of the
                # log: ONE contiguous write of the block's K rows.  What a
                # block holds past its last selected row is garbage that
                # the next block (it starts at nxt_n + n_sel) overwrites or
                # that lies past the final nxt_n; what would land at row F
                # or beyond falls in the slack (_log_words) and is dropped.
                # (A [rows, plane] buffer written at a dynamic ROW made the
                # compiler carry it lane-padded through the loop and copy
                # the whole frontier in and out of that layout every
                # dispatch: PERF.md, PR 45.  A 1-D array has one layout.)
                bufs = (carry["nxt"],)
                if self.record_trace:
                    # Trace meta rides the SAME append blocks as the rows,
                    # scattered by row into its [F + 1, 9] buffer: what a
                    # block holds past the last selected row, and what would
                    # land past F, goes to the dump row F.
                    bufs += (carry["tmeta"],)

                def append_block(bufs, first, at, live):
                    row = nxt_n + first
                    out = (jax.lax.dynamic_update_slice(
                        bufs[0], app_rows[at].reshape(-1),
                        (jnp.minimum(row, F) * plane,)),)
                    if self.record_trace:
                        dst = row + jnp.arange(at.shape[0], dtype=jnp.int32)
                        dst = jnp.where(live & (dst < F), dst, F)
                        out += (bufs[1].at[dst].set(app_meta[at]),)
                    return out

                bufs, n_sel, app_blocks = visited_mod.write_in_blocks(
                    sel, append_block, bufs)
                nxt = bufs[0]
                frontier_drop = jnp.maximum(nxt_n + n_sel - F, 0)
                # Occupancy counts only rows that actually landed (<= F), else
                # the next level's chunk loop would re-expand the tail.
                n_sel = n_sel - frontier_drop

                out = {
                    "cur": cur, "cur_n": carry["cur_n"],
                    "j": j_next, "evp": evp_next, "noapp": carry["noapp"],
                    # On a noapp level nxt_n counts the WOULD-BE appends
                    # (rows themselves are skipped, no frontier-cap drops):
                    # run() reads it to tell DEPTH_EXHAUSTED (successors
                    # remained) from SPACE_EXHAUSTED (space ended exactly at
                    # the depth limit) — the base engine's verdict for the
                    # same boundary (engine.py run(): not lvl_keys).
                    "nxt": nxt, "nxt_n": carry["nxt_n"].at[0].add(
                        jnp.where(noapp,
                                  jnp.sum(sel_would).astype(jnp.int32),
                                  n_sel)),
                    "visited": new_visited,
                    "vis_n": carry["vis_n"].at[0].add(n_fresh),
                    "explored": carry["explored"].at[0].add(
                        jnp.sum(valids).astype(jnp.int32)),
                    # Semantic overflow (net/timer caps) corrupts state
                    # contents — always fatal.  Capacity drops (routing
                    # bucket, frontier cap) only truncate *expansion
                    # coverage* (beam-style) and are tolerable when the
                    # caller opts in (bench throughput runs).  A full
                    # visited table is its own flag (vis_over): sound
                    # treat-as-fresh degradation, fatal only in strict.
                    "overflow": carry["overflow"].at[0].add(
                        overflow + pack_bad),
                    "vis_over": carry["vis_over"].at[0].add(vis_over),
                    # ev_drops (valid events past the ev_budget) truncate
                    # expansion coverage like a routing/frontier drop: fatal
                    # in strict mode (via _sync_checks), beam-tolerable else.
                    "drops": carry["drops"].at[0].add(
                        route_drop + frontier_drop + ev_drops),
                    "flag_cnt": flag_cnt, "flag_rows": flag_rows,
                }
                if self.record_trace:
                    out["tmeta"] = bufs[1]
                    out["flag_meta"] = flag_meta
                if delta:
                    out["pb_cur"] = carry["pb_cur"]
                    out["pb_nxt"] = pb_nxt
                    out["pb_peak"] = pb_peak
                if spill_on:
                    front_full = (nxt_n + jnp.sum(sel).astype(jnp.int32)
                                  ) > F
                    tbl_full = jnp.any(unres_s)
                    fa = jax.lax.psum(front_full.astype(jnp.int32), ax) > 0
                    tb = jax.lax.psum(tbl_full.astype(jnp.int32), ax) > 0
                    abort = fa | tb
                    code = fa.astype(jnp.int32) + 2 * tb.astype(jnp.int32)
                    # (nxt itself needs no revert: nxt_n's takes the
                    # aborted step's rows out of the log.)
                    revert = ["j", "evp", "nxt_n", "visited",
                              "vis_n", "explored", "overflow", "vis_over",
                              "drops", "flag_cnt", "flag_rows"]
                    if delta:
                        revert += ["pb_nxt", "pb_peak"]
                    for k in revert:
                        out[k] = jnp.where(abort, carry[k], out[k])
                    out["f_full"] = jnp.where(abort, code,
                                              jnp.int32(0))[None]
                return out, jnp.stack([blocks + app_blocks, probe_cols,
                                       kind_skips])

        return local

    def _has_rt_masks(self) -> bool:
        return (self.p.deliver_message_rt is not None
                or self.p.deliver_timer_rt is not None)

    # ---------------------------------------------------- level superstep

    def _build_superstep(self):
        """The fused LEVEL superstep: one shard_map program whose
        ``lax.while_loop`` iterates chunk steps until every device's OWN
        frontier shard is drained (including event-window spill passes —
        a spilled chunk holds its ``j`` back, so the drain condition
        covers re-passes), bounded by a replicated ``budget`` scalar so
        a host wall-clock budget keeps mid-level granularity.

        The trip count is occupancy-driven FROM THE CARRY: device d runs
        ``ceil(cur_n_d / C)`` chunk steps (its own share), and the loop
        condition is the psum of the per-device "still draining" flags —
        every device executes the same trip count (the body contains
        collectives), the max of the ACTUAL needs.

        Returns ``(carry', stats)`` where ``stats`` is the fused scalar
        vector _sync_checks parses: 8 scalars, the n_flags counts,
        ``[remaining_devices, steps_taken, write_blocks, probe_cols,
        kind_skips]``,
        the spill abort code when the host tier is wired, under a delta
        descriptor ``[base moved, delta_peak]``, and the per-device lanes.
        Computing the stats in-program (psum/pmax over the mesh axis)
        folds the level sync into the same dispatch: host involvement
        per level is superstep + promote."""
        local = self._make_local_step()
        C = self.cpd
        ax = self.axis

        def _psum(x):
            return jax.lax.psum(x, ax)

        spill_on = self._spill is not None
        delta = self._mesh_delta

        def stats_local(c, steps, counts):
            core = jnp.stack([
                _psum(c["overflow"][0]),
                _psum(c["drops"][0]),
                _psum(c["vis_over"][0]),
                _psum(c["explored"][0]),
                jax.lax.pmax(c["vis_n"][0], ax),
                _psum(c["vis_n"][0]),
                jax.lax.pmax(c["nxt_n"][0], ax),
                # The slowest device's chunk count: a diagnostic slot,
                # _sync_checks does not read it.
                jax.lax.pmin(c["j"][0], ax),
            ]).astype(jnp.int32)
            flags = _psum(c["flag_cnt"]).astype(jnp.int32)
            remaining = _psum(
                (c["j"][0] * C < c["cur_n"][0]).astype(jnp.int32))
            # Write blocks scattered (table + append), bucket columns
            # the probe gathered and event kinds the expand skipped,
            # each summed over this dispatch's chunk steps, of the
            # device that counts most.
            tail = jnp.concatenate([jnp.stack([remaining, steps]),
                                    jax.lax.pmax(counts, ax)]
                                   ).astype(jnp.int32)
            parts = [core, flags, tail]
            if spill_on:
                # The abort is global, so any device's copy is the
                # fleet's (pmax for robustness).
                parts.append(jax.lax.pmax(
                    c["f_full"], ax).astype(jnp.int32))
            if delta:
                # What the promote will do with this level's rows, and
                # how much of the delta window the level used — known
                # here, so the host picks the promote's program and
                # writes the level's record from the readback it makes
                # anyway.  pb_nxt is pmin'd every chunk step: any
                # device's copy is the mesh's.
                moved = jnp.any((c["pb_nxt"] != jnp.int32(self._PB_EMPTY))
                                & (c["pb_nxt"] != c["pb_cur"]))
                parts.append(jnp.stack([
                    moved.astype(jnp.int32),
                    jax.lax.pmax(c["pb_peak"][0], ax)]).astype(jnp.int32))
            # Per-device stats lanes (ISSUE 8), LAST so all absolute
            # index parses above stay valid: one all_gather inside the
            # SAME fused program — the replicated stats vector simply
            # grows by 4D int32s, never an extra dispatch or readback.
            per_dev = jnp.stack([c["explored"][0], c["vis_n"][0],
                                 c["nxt_n"][0], c["drops"][0]])
            parts.append(jax.lax.all_gather(
                per_dev, ax).T.reshape(-1).astype(jnp.int32))
            return jnp.concatenate(parts)

        def super_local(carry, budget, masks=None):
            def cond(st):
                c, k, _ = st
                own = c["j"][0] * C < c["cur_n"][0]
                keep = (jax.lax.psum(own.astype(jnp.int32), ax) > 0) & (
                    k < budget)
                if spill_on:
                    # A spill abort (frontier/table full) suspends the
                    # drain loop: the host must evict/spool before the
                    # held-back chunk can be re-stepped.
                    keep = keep & (c["f_full"][0] == 0)
                return keep

            def body(st):
                c, k, counts = st
                c, step_counts = local(c, masks)
                return c, k + 1, counts + step_counts

            carry, k, counts = jax.lax.while_loop(
                cond, body,
                (carry, jnp.int32(0), jnp.zeros((3,), jnp.int32)))
            with tel_mod.device_scope("level_sync"):
                return carry, stats_local(carry, k, counts)

        spec = self._carry_specs()
        # The function's name is the program's in a profile
        # (``jit_superstep``).
        if self._has_rt_masks():
            def superstep(c, b, m):
                return super_local(c, b, m)

            return shard_map(
                superstep, mesh=self.mesh,
                in_specs=(spec, P(), (P(), P())),
                out_specs=(spec, P()), check_vma=False)

        def superstep(c, b):
            return super_local(c, b)

        return shard_map(
            superstep, mesh=self.mesh,
            in_specs=(spec, P()), out_specs=(spec, P()),
            check_vma=False)

    def _superstep_call(self, carry, budget: int):
        """Dispatch one superstep through the supervisor boundary.  The
        dispatched callable BLOCKS on the stats readback (the tiny
        replicated vector, never rows), so the watchdog bounds the whole
        fused level step and the per-level host transfers stay scalar."""
        if budget >= (1 << 30):
            b = getattr(self, "_budget_full", None)
            if b is None:
                b = self._budget_full = jnp.asarray(1 << 30, jnp.int32)
        else:
            b = jnp.asarray(budget, jnp.int32)
        rt = getattr(self, "_rt_masks", None)

        prog = self._prog("superstep", self._superstep)

        def run(c, bb, *masks):
            c2, stats = (prog(c, bb, masks[0]) if masks
                         else prog(c, bb))
            return c2, device_get(stats)

        if rt is not None:
            return self._dispatch("sharded.superstep", run, carry, b, rt)
        return self._dispatch("sharded.superstep", run, carry, b)

    def _build_finish(self, rebase: bool = False):
        """Promote nxt -> cur between levels.  Successors already landed
        on their owner's shard inside the superstep, so the promote is a
        LOCAL buffer SWAP — no collective, no compaction, no word moved:
        the log the level appended becomes ``cur``, the drained ``cur``
        becomes the empty log (``nxt_n`` = 0; its words are not
        re-zeroed, nothing reads a log past its occupancy).  The swap
        itself is the HOST's (:meth:`_promote_call`: two names changing
        place — inside the program the donated carry pairs each input
        with the output of its own name, and a swap would be three
        frontier-sized copies); this program, handed the swapped carry,
        moves the counters.

        Under a delta descriptor the promoted rows were packed against
        the OLD level base.  Where the next level's base is that same
        base — the superstep's stats say so before the promote is
        dispatched — this program is all there is.  Where it moved, the
        host dispatches the ``rebase`` program instead: the counters, and
        :meth:`_rebase_rows` over the occupied prefix of the log."""
        F = self.f_cap
        delta = self._mesh_delta

        def finish(carry):
            with tel_mod.device_scope("promote"):
                carry = dict(carry)
                carry["cur_n"] = carry["nxt_n"]
                if delta:
                    # A lane whose pb_nxt never saw a successor (empty
                    # next frontier) keeps the old base.  pb_nxt is a
                    # global pmin computed inside the chunk steps —
                    # value-identical on every device, so the promote
                    # keeps ZERO collectives.
                    pb_old = carry["pb_cur"]
                    if rebase:
                        pb_new = jnp.where(
                            carry["pb_nxt"] == jnp.int32(self._PB_EMPTY),
                            pb_old, carry["pb_nxt"])
                        with tel_mod.device_scope("promote.rebase"):
                            carry["cur"] = self._rebase_rows(
                                carry["cur"], carry["cur_n"][0], pb_old,
                                pb_new)
                        carry["pb_cur"] = pb_new
                    carry["pb_nxt"] = jnp.full_like(
                        pb_old, jnp.int32(self._PB_EMPTY))
                    carry["pb_peak"] = jnp.zeros((1,), jnp.int32)
                carry["nxt_n"] = jnp.zeros((1,), jnp.int32)
                carry["j"] = jnp.zeros((1,), jnp.int32)
                carry["evp"] = jnp.zeros((1,), jnp.int32)
                if self.record_trace:
                    # The level's meta was spilled to host before this runs.
                    carry["tmeta"] = jnp.zeros((F + 1, 9), jnp.uint32)
                return carry

        # The function's name is the program's in a profile
        # (``jit_promote``, ``jit_promote_rebase``).
        finish.__name__ = "promote_rebase" if rebase else "promote"
        spec = self._carry_specs()
        return shard_map(finish, mesh=self.mesh,
                         in_specs=(spec,), out_specs=spec,
                         check_vma=False)

    def _rebase_rows(self, log, n, pb_old, pb_new):
        """Re-encode the first ``n`` rows of a frontier log from the
        level base ``pb_old`` to ``pb_new`` (both [n_delta]).

        A delta lane stores ``value - base`` in its bit field, so a new
        base changes the field by ``pb_old - pb_new`` and nothing else
        of the row: ONE add a word (``LanePacking.rebase_words``: the
        shifted differences of a word's delta lanes, summed).  The add
        is exact while every field stays inside its window, and it does:
        the chunk steps packed every live successor against ``pb_old``
        and counted what fell outside (``pack_bad``, raised at the level
        sync, before any promote), and ``pb_new`` is the minimum over
        those very successors, so ``0 <= value - pb_new <= value -
        pb_old``.  No row is unpacked: the program holds no
        ``[rows, lanes]`` intermediate and no column of a row.

        Only the OCCUPIED prefix is touched, a block of the append's
        ``K`` rows at a time (the log's slack, :meth:`_log_words`, is
        one such block, so the last block lies inside the buffer; what
        it re-encodes past row ``n`` is words nothing reads)."""
        plane = self.plane
        K = visited_mod.block_width(self.n_devices * self._bucket())
        step = jnp.tile(self._pk.rebase_words(
            self._base_vec(pb_old), self._base_vec(pb_new)), K)

        def block(i, log):
            at = i * (K * plane)
            rows = jax.lax.dynamic_slice(log, (at,), (K * plane,))
            return jax.lax.dynamic_update_slice(log, rows + step, (at,))

        return jax.lax.fori_loop(0, (n + K - 1) // K, block, log)

    def _promote_call(self, carry, rebase: bool = False):
        """Promote through the supervisor boundary: swap the two logs
        (same shape, same placement) and dispatch the level's reset —
        with the re-base where the level's stats said the base moved."""
        carry = dict(carry, cur=carry["nxt"], nxt=carry["cur"])
        if rebase:
            return self._dispatch(
                "sharded.promote_rebase",
                self._prog("promote_rebase", self._finish_rebase), carry)
        return self._dispatch(
            "sharded.promote",
            self._prog("promote", self._finish_level), carry)

    def _carry_specs(self):
        """shard_map in/out specs for the carry — derived from the
        partition-rule table (CARRY_PARTITION_RULES), not hand-listed,
        so shard_map conventions and NamedSharding placement cannot
        drift apart."""
        return match_partition_rules(CARRY_PARTITION_RULES,
                                     self._carry_names(), self.axis)

    # ----------------------------------------------------------------- run

    def _root(self, state, hits: bool = True):
        """All a run asks of its root, by ONE launch of the engine's
        root program and one readback: host ``(row0 [1, lanes], fp0
        [1, 4])`` and, with ``hits``, the invariants' and the goals'
        verdicts at the root after them.  The key comes through the
        same canonicalize-then-hash step the expand programs use
        (symmetry reduction, ISSUE 15b)."""
        out = self._root_program()(state)
        return jax.device_get(out if hits else out[:2])

    def _root_ids(self, row0, fp0):
        """From what ``_root`` read back: the carry initialiser's
        arguments (the root's row and its sanitized key), and the owner
        device and home slot that pick the initialiser — shared by
        ``_root_carry``, the AOT warm-up and the dispatch-site table."""
        owner = int(fp0[0, 0]) % self.n_devices
        key0 = visited_mod.host_sanitize_key(fp0[0])
        # The root key sits in slot 0 of its home BUCKET — addressing
        # mirrored from visited.py (bucket keyed by lane 2).
        home = visited_mod.host_home_slot(key0, self.v_cap)
        return (row0[0], key0), owner, home

    def _init_carry(self, state) -> dict:
        """The sharded carry of a search that starts at ``state``."""
        return self._root_carry(*self._root(state, hits=False))

    def _root_carry(self, row0, fp0) -> dict:
        """Build the sharded carry ON DEVICE: the big buffers (frontier,
        next-frontier, visited table — hundreds of MB) are jnp
        allocations inside a jitted initializer, with only the root row
        and its key crossing the host boundary.  A host-numpy build +
        device_put would ship the whole carry (~750 MB at the bench
        caps) host->device on every run() — inside the bench's measured
        window."""
        args, owner, home = self._root_ids(row0, fp0)
        init = self._prog(("init", owner, home),
                          self._init_prog(owner, home))
        return self._dispatch("sharded.init", init, *args)

    def _init_prog(self, owner: int, home: int):
        """The jitted carry initializer for a given root owner/home slot
        (both are baked into the traced program).  Cached so the AOT
        warm-up's compiled program is the one run() actually uses."""
        cache = getattr(self, "_init_progs", None)
        if cache is None:
            cache = self._init_progs = {}
        fn = cache.get((owner, home))
        if fn is not None:
            return fn
        D, F, V, lanes = self.n_devices, self.f_cap, self.v_cap, self.lanes
        plane, pk, delta = self.plane, self._pk, self._mesh_delta
        nf, W = len(self._flag_names), self._log_words()

        def init_carry(row0, k0):
            onehot_d = jnp.arange(D) == owner
            if delta:
                # Level-0 base = the root row's own delta values (the
                # min over a one-row frontier).
                pb0 = row0[jnp.asarray(self._delta_lanes)].astype(
                    jnp.int32)
                row0s = pk.pack_jnp(row0[None], self._base_vec(pb0))[0]
            elif pk is not None:
                row0s = pk.pack_jnp(row0[None])[0]
            else:
                row0s = row0
            out = {
                "cur": jnp.zeros((D * W,), jnp.int32).at[
                    owner * W:owner * W + plane].set(row0s),
                "cur_n": onehot_d.astype(jnp.int32),
                "j": jnp.zeros((D,), jnp.int32),
                "evp": jnp.zeros((D,), jnp.int32),
                "noapp": jnp.zeros((D,), jnp.int32),
                "nxt": jnp.zeros((D * W,), jnp.int32),
                "nxt_n": jnp.zeros((D,), jnp.int32),
                "visited": visited_mod.with_root(
                    visited_mod.empty_table(V, D), k0, home, owner),
                "vis_n": onehot_d.astype(jnp.int32),
                "explored": jnp.zeros((D,), jnp.int32),
                "overflow": jnp.zeros((D,), jnp.int32),
                "vis_over": jnp.zeros((D,), jnp.int32),
                "drops": jnp.zeros((D,), jnp.int32),
                "flag_cnt": jnp.zeros((D * nf,), jnp.int32),
                "flag_rows": jnp.zeros((D * nf, lanes), jnp.int32),
            }
            if self.record_trace:
                out["tmeta"] = jnp.zeros((D * (F + 1), 9), jnp.uint32)
                out["flag_meta"] = jnp.zeros((D * nf, 9), jnp.uint32)
            if self._spill_on:
                out["f_full"] = jnp.zeros((D,), jnp.int32)
            if delta:
                out["pb_cur"] = jnp.tile(pb0, D)
                out["pb_nxt"] = jnp.full(
                    (D * pb0.shape[0],), jnp.int32(self._PB_EMPTY))
                out["pb_peak"] = jnp.zeros((D,), jnp.int32)
            return out

        fn = jax.jit(init_carry, out_shardings=self._carry_shardings())
        cache[(owner, home)] = fn
        return fn

    # ------------------------------------------------------- AOT warm-up

    def _carry_sds(self):
        """Abstract (ShapeDtypeStruct + NamedSharding) carry pytree for
        AOT lowering — shapes mirror _init_prog's builds, shardings come
        from the SAME partition-rule table every dispatch uses."""
        D, F, V, lanes = self.n_devices, self.f_cap, self.v_cap, self.lanes
        nf, W = len(self._flag_names), self._log_words()
        shards = self._carry_shardings()

        def sd(name, shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=shards[name])

        out = {
            "cur": sd("cur", (D * W,)),
            "cur_n": sd("cur_n", (D,)),
            "j": sd("j", (D,)), "evp": sd("evp", (D,)),
            "noapp": sd("noapp", (D,)),
            "nxt": sd("nxt", (D * W,)),
            "nxt_n": sd("nxt_n", (D,)),
            "visited": sd("visited", visited_mod.table_shape(V, D),
                          jnp.uint32),
            "vis_n": sd("vis_n", (D,)),
            "explored": sd("explored", (D,)),
            "overflow": sd("overflow", (D,)),
            "vis_over": sd("vis_over", (D,)),
            "drops": sd("drops", (D,)),
            "flag_cnt": sd("flag_cnt", (D * nf,)),
            "flag_rows": sd("flag_rows", (D * nf, lanes)),
        }
        if self.record_trace:
            out["tmeta"] = sd("tmeta", (D * (F + 1), 9), jnp.uint32)
            out["flag_meta"] = sd("flag_meta", (D * nf, 9), jnp.uint32)
        if self._spill_on:
            out["f_full"] = sd("f_full", (D,))
        if self._mesh_delta:
            nd = len(self._delta_lanes)
            out["pb_cur"] = sd("pb_cur", (D * nd,))
            out["pb_nxt"] = sd("pb_nxt", (D * nd,))
            out["pb_peak"] = sd("pb_peak", (D,))
        return out

    def aot_warmup(self) -> float:
        """Ahead-of-time compile the hot programs (the superstep, the
        level promote, and the default root's carry initializer) via
        ``.lower().compile()``, so compile cost
        is paid — and MEASURED — at construction instead of inside the
        first run's search window.  With the persistent compile cache
        (tpu/compile_cache.py) the second
        construction of any config hits the cache and this drops to
        near-zero.  Returns the wall seconds spent; also accumulated on
        ``self.compile_secs`` and surfaced as
        ``SearchOutcome.compile_secs``."""
        t0 = time.time()
        exes = self._aot_exes = getattr(self, "_aot_exes", {})
        sds = self._carry_sds()
        rt = getattr(self, "_rt_masks", None)
        if self._has_rt_masks() and rt is None:
            raise RuntimeError(
                "runtime-mask protocol: call set_runtime_masks() "
                "before aot_warmup()")
        mask_args = (rt,) if rt is not None else ()
        b = jnp.asarray(1 << 30, jnp.int32)
        # The compiled executables are KEPT and invoked directly by
        # the dispatch paths (_prog): jit.__call__ does not reuse
        # .lower().compile() results in this JAX, so calling the jit
        # again would re-trace and re-compile (the persistent cache
        # would absorb the XLA half, but not the tracing).  A compile
        # error propagates: a program the backend refuses is a fault
        # to report, not a warm-up to skip.
        base = self.store_key()
        devices = self._store_devices()

        def compile_(key, name, jitted, *args, baked=()):
            # ``name`` is the program's in a profile (``jit_<name>``);
            # the executable is also registered under that name for
            # telemetry.program_scopes (a set insert).  The store
            # (tpu/compile_cache.py) is asked before anything is traced.
            with tel_mod.phase("compile.aot." + name):
                exes[key] = compile_cache.stored(
                    compile_cache.program_key(base, name, args, *baked),
                    name, lambda: jitted.lower(*args).compile(), devices)
            tel_mod.register_program(name, exes[key])

        with tel_mod.phase("compile.aot"):
            compile_("superstep", "superstep", self._superstep,
                     sds, b, *mask_args)
            compile_("promote", "promote", self._finish_level, sds)
            if self._mesh_delta:
                compile_("promote_rebase", "promote_rebase",
                         self._finish_rebase, sds)
            # The root program compiles where it is first called: here,
            # on the twin's own root, so that run() finds it compiled.
            args, owner, home = self._root_ids(
                *self._root(self.initial_state(), hits=False))
            compile_(("init", owner, home), "init_carry",
                     self._init_prog(owner, home), *args,
                     baked=(owner, home))
        # Tracing leaves a heap (0.7 M objects for lab 4's multi-server
        # twin) whose next full collection is a second or more of host
        # time, due whenever the allocator's counters say: collected
        # here, it is set-up's and never a search window's.
        gc.collect()
        secs = time.time() - t0
        self.compile_secs = getattr(self, "compile_secs", 0.0) + secs
        tel = getattr(self, "_telemetry", None)
        if tel is not None:
            # The explicit AOT warm-up as a first-class trace node
            # (ISSUE 13): the causal timeline shows compile as its own
            # phase instead of folding it into the first dispatch.
            # An event, not a span — span counts stay equal to
            # dispatch counts (the obs-suite parity pin).
            tel.event("compile", engine="sharded",
                      secs=round(secs, 4), aot=True)
        return secs

    def _store_devices(self) -> list:
        return list(self.mesh.devices.flat)

    def _store_shape(self) -> tuple:
        """The base engine's, and what this constructor adds to the
        programs' shape: the mesh (axes, extent, the devices in their
        order), the per-device caps and the exchange's options."""
        return super()._store_shape() + (
            self.mesh.axis_names, self.mesh.devices.shape,
            tuple(int(d.id) for d in self._store_devices()),
            self.cpd, self.f_cap, self.v_cap, self.ev_spill,
            self.mesh_pack, self._mesh_delta)

    def _prog(self, name, default):
        """The AOT-compiled executable for a program when the warm-up
        built one (invoked directly — zero retrace), else the lazy jit."""
        return getattr(self, "_aot_exes", {}).get(name) or default

    def lane_signature(self):
        """Sharded searches are NOT lane-packable (ISSUE 14,
        tpu/lanes.py): the superstep is already one whole-mesh program
        whose dispatch cost is amortised across devices, and stacking
        a lane axis on top of shard_map would multiply the carry's HBM
        footprint by L on every chip.  The service's lane packer reads
        ``None`` as "run solo" — a mesh-sized job keeps its own
        dispatch stream."""
        return None

    def dispatch_site_programs(self):
        """Sanitizer site registry (ISSUE 10; see the base-class
        docstring): the superstep, the level promote, the root carry
        initializer, and the spill reset/evict shard_map programs when
        the host tier is wired.  Args are the same abstract carry
        (ShapeDtypeStruct + NamedSharding) the AOT warm-up lowers, so
        the audit sees byte-identical programs to the ones dispatched."""
        sds = self._carry_sds()
        rt = getattr(self, "_rt_masks", None)
        if self._has_rt_masks() and rt is None:
            raise RuntimeError(
                "runtime-mask protocol: call set_runtime_masks() "
                "before dispatch_site_programs()")
        mask_args = (rt,) if rt is not None else ()
        b = jnp.asarray(1 << 30, jnp.int32)
        sites = {"sharded.superstep": dict(
            fn=self._superstep, args=(sds, b, *mask_args),
            donate=(0,), multi=True,
            builder=self._superstep_jit)}
        sites["sharded.promote"] = dict(
            fn=self._finish_level, args=(sds,), donate=(0,),
            multi=True,
            builder=lambda: self._sharded_jit(self._build_finish()))
        if self._mesh_delta:
            sites["sharded.promote_rebase"] = dict(
                fn=self._finish_rebase, args=(sds,), donate=(0,),
                multi=True,
                builder=lambda: self._sharded_jit(
                    self._build_finish(rebase=True)))
        # The bucket-probe kernel (ISSUE 12): the ACTIVE visited.insert
        # variant (Pallas or jnp per DSLABS_VISITED_PALLAS) as a
        # standalone single-device program over one owner-side dedup
        # batch — the profiler's hot-site table and the J1/J2/J4 audit
        # cover the kernel itself, not just the superstep it inlines
        # into.
        sites["visited.insert"] = visited_mod.dispatch_site_program(
            self.v_cap, self.n_devices * self._bucket())
        args, owner, home = self._root_ids(
            *self._root(self.initial_state(), hits=False))
        sites["sharded.init"] = dict(
            fn=self._init_prog(owner, home), args=args, donate=(),
            multi=True, builder=None)
        if self._spill_on:
            progs = self._sh_spill_progs()
            sites["sharded.spill_drain"] = dict(
                fn=progs["reset"], args=(sds,), donate=(0,),
                multi=True, builder=None)
            sites["sharded.spill_evict"] = dict(
                fn=progs["evict"], args=(sds,), donate=(0,),
                multi=True, builder=None)
        # Packed-wire codec lowerings (ISSUE 18): the sharded engine's
        # own pack/decode over one chunk batch, so J1-J5 cover the
        # codec the superstep inlines (delta descriptors take the base
        # vector argument).
        if self._pk is not None:
            pk = self._pk
            rows_sds = jax.ShapeDtypeStruct((self.cpd, self.lanes),
                                            jnp.int32)
            packed_sds = jax.ShapeDtypeStruct((self.cpd, self.plane),
                                              jnp.int32)
            if pk.has_delta:
                base_sds = jax.ShapeDtypeStruct((self.lanes,),
                                                jnp.int32)
                def pack_delta(r, b):
                    return pk.pack_jnp(r, b)

                def unpack_delta(r, b):
                    return pk.unpack_jnp(r, b)

                mk_p = lambda: jax.jit(pack_delta)      # noqa: E731
                mk_u = lambda: jax.jit(unpack_delta)    # noqa: E731
                sites["packing.pack"] = dict(
                    fn=mk_p(), args=(rows_sds, base_sds), donate=(),
                    multi=False, builder=mk_p)
                sites["packing.unpack"] = dict(
                    fn=mk_u(), args=(packed_sds, base_sds), donate=(),
                    multi=False, builder=mk_u)
            else:
                sites["packing.pack"] = dict(
                    fn=jax.jit(pk.pack_jnp), args=(rows_sds,),
                    donate=(), multi=False,
                    builder=lambda: jax.jit(pk.pack_jnp))
                sites["packing.unpack"] = dict(
                    fn=jax.jit(pk.unpack_jnp), args=(packed_sds,),
                    donate=(), multi=False,
                    builder=lambda: jax.jit(pk.unpack_jnp))
        return sites

    def _terminal_from_flags(self, carry, explored, vis_total, depth, t0):
        """Resolve the first terminal flag (checkState order) from the
        per-device counters; returns a SearchOutcome or None."""
        nf = len(self._flag_names)
        cnts = np.asarray(carry["flag_cnt"]).reshape(self.n_devices, nf)
        if not cnts.any():
            return None
        rows = np.asarray(carry["flag_rows"]).reshape(
            self.n_devices, nf, self.lanes)
        metas = (np.asarray(carry["flag_meta"]).reshape(
            self.n_devices, nf, 9) if self.record_trace else None)
        for fi, fname in enumerate(self._flag_names):
            devs = np.nonzero(cnts[:, fi])[0]
            if not len(devs):
                continue
            row = rows[devs[0], fi]
            st = jax.tree.map(np.asarray,
                              self.unflatten_rows(row[None]))
            trace = None
            if metas is not None:
                m = metas[devs[0], fi]
                trace = self._walk_fp_chain(
                    tuple(int(x) for x in m[4:8]), int(m[8]))
            elapsed = time.time() - t0
            if fname == "exc":
                return SearchOutcome(
                    "EXCEPTION_THROWN", explored, vis_total, depth, elapsed,
                    violating_state=st, exception_code=int(st["exc"][0]),
                    trace=trace)
            kind, pname = fname.split(":", 1)
            if kind == "inv":
                return SearchOutcome(
                    "INVARIANT_VIOLATED", explored, vis_total, depth,
                    elapsed, violating_state=st, predicate_name=pname,
                    trace=trace)
            return SearchOutcome(
                "GOAL_FOUND", explored, vis_total, depth, elapsed,
                goal_state=st, predicate_name=pname, trace=trace)
        return None

    # ------------------------------------------------------- checkpointing
    #
    # A synchronous full-carry readback stalls the search for the whole
    # transfer of a GB-scale carry (not measured on this machine).  So
    # the dump (a) slices only the LIVE state — the occupied frontier prefix
    # (bounded by the level sync's max_n, not f_cap) + the visited table
    # + counters; the empty nxt, the f_cap padding, and tmeta are never
    # read back — and (b) runs ASYNChronously: device-side slices are
    # snapshotted into fresh buffers in the level gap, then a background
    # thread drains them host-side and writes the atomic .npz while the
    # next levels compute.  A snapshot still in flight skips the next
    # checkpoint tick (never queues).  Kill mid-write leaves the previous
    # complete dump (tmp + rename).

    def _snapshot_checkpoint(self, carry, max_n: int):
        """Device-side snapshot (fresh buffers — the live carry is
        donated to the next chunk step, so the dump thread must never
        alias it)."""
        # max_n (the level sync's nxt_max) is the exact per-device
        # occupancy bound.  Rounded UP to a power of two so the
        # per-shape jitted snapshot programs number O(log f_cap), not
        # one per frontier size (each is a synchronous shard_map
        # compile in the level gap).
        need = min(max_n, self.f_cap)
        m = self.cpd
        while m < need:
            m <<= 1
        m = max(min(m, self.f_cap), 1)
        plane = self.plane
        cache = getattr(self, "_snap_fns", None)
        if cache is None:
            cache = self._snap_fns = {}
        if m in cache:
            with self.mesh:
                return cache[m](carry)

        def checkpoint_snapshot(c):
            out = {
                "cur": jax.lax.dynamic_slice(
                    c["cur"], (0,), (m * plane,)),
                "cur_n": c["cur_n"] + 0,
                "visited": c["visited"] + jnp.uint32(0),
                "vis_n": c["vis_n"] + 0,
                "explored": c["explored"] + 0,
                "overflow": c["overflow"] + 0,
                "vis_over": c["vis_over"] + 0,
                "drops": c["drops"] + 0,
                "flag_cnt": c["flag_cnt"] + 0,
                "flag_rows": c["flag_rows"] + 0,
            }
            if self._mesh_delta:
                out["pb_cur"] = c["pb_cur"] + 0
            return out

        spec = self._carry_specs()
        keys = ["cur", "cur_n", "visited", "vis_n", "explored",
                "overflow", "vis_over", "drops", "flag_cnt", "flag_rows"]
        if self._mesh_delta:
            keys.append("pb_cur")
        snap_spec = {k: spec[k] for k in keys}
        fn = jax.jit(shard_map(checkpoint_snapshot, mesh=self.mesh,
                               in_specs=(spec,),
                               out_specs=snap_spec, check_vma=False))
        cache[m] = fn
        with self.mesh:
            return fn(carry)

    def _write_checkpoint(self, snap, depth: int, elapsed: float) -> None:
        """Background-thread half: host readback + conversion to the
        UNIFIED engine-agnostic format (tpu/checkpoint.py) + atomic npz
        write.  The dump stores the semantic search state — live
        frontier rows (all shards concatenated) and the occupied
        visited-table lines — not this engine's carry layout, so any
        ladder rung can resume it."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        D = self.n_devices
        cur = np.asarray(snap["cur"]).reshape(D, -1, self.plane)
        cur_n = np.asarray(snap["cur_n"]).reshape(-1)
        parts = [cur[d, :cur_n[d]] for d in range(D)]
        frontier = (np.concatenate(parts) if cur_n.sum()
                    else np.zeros((0, self.plane), np.int32))
        fp_map = None
        if self.record_trace and self._fp_map:
            fp_map = np.asarray(
                [(k + v[0] + (v[1],)) for k, v in self._fp_map.items()],
                dtype=np.int64)
        # Frontier rows ride in the mesh engine's NATIVE encoding
        # (packed when the descriptor is non-identity) with the marker
        # — and, for delta descriptors, the level base — so any ladder
        # rung converts on resume (engine.py _normalize_ckpt_frontier;
        # loud, never silent).
        extra = None
        if self._pk is not None:
            extra = {"frontier_encoding": np.bytes_(
                self._pk.signature().encode())}
            if self._mesh_delta:
                pb = np.asarray(snap["pb_cur"]).reshape(
                    D, -1)[0].astype(np.int32)
                base = np.zeros((self.lanes,), np.int32)
                base[self._delta_lanes] = pb
                extra["pack_base"] = base
        ckpt_mod.save(self.checkpoint_path, ckpt_mod.SearchCheckpoint(
            fingerprint=self._ckpt_fingerprint(), depth=depth,
            explored=int(np.asarray(snap["explored"]).sum()),
            elapsed=elapsed, frontier=frontier,
            visited_keys=visited_mod.host_occupied(snap["visited"]),
            vis_over=int(np.asarray(snap["vis_over"]).sum()),
            dropped=int(np.asarray(snap["drops"]).sum()),
            fp_map=fp_map, extra=extra))

    def _save_checkpoint(self, carry, depth: int, elapsed: float,
                         max_n: int = None) -> None:
        """Kick an async checkpoint; skipped (not queued) while a prior
        dump is still draining (checkpoint.AsyncCheckpointWriter)."""
        if self._ckpt_writer.busy():
            return
        snap = self._snapshot_checkpoint(
            carry, max_n if max_n is not None else self.f_cap)
        self._ckpt_writer.kick(
            lambda: self._write_checkpoint(snap, depth, elapsed))

    def _join_checkpoint(self) -> None:
        self._ckpt_writer.join()

    def _load_checkpoint(self):
        """-> (carry on device, depth, elapsed) or None (no dump).  A
        dump from a DIFFERENT protocol/capacity configuration raises a
        loud :class:`~dslabs_tpu.tpu.checkpoint.CheckpointMismatch`
        naming both fingerprints — never resumed (or skipped) silently.
        Rebuilds the full sharded carry from the unified dump: frontier
        rows re-split into contiguous per-device shares, visited keys
        RE-INSERTED into each owner's shard table (owner = key lane 0
        mod D — the same routing the chunk step uses), and the
        never-dumped parts (nxt, loop counters, trace meta) rebuilt
        empty — exactly their state at a level boundary."""
        ck = self._load_ckpt()
        if ck is None:
            return None
        if ck.fp_map is not None:
            self._fp_map = {tuple(r[:4]): (tuple(r[4:8]), int(r[8]))
                            for r in ck.fp_map.tolist()}
        if self._spill_on:
            # Spill-mode resume: every dumped key loads into the host
            # tier and the device tables restart empty (a fresh epoch
            # — the refilter makes that exact); the dumped frontier
            # spools in mesh-sized segments, the first injected via
            # the normal resume path.
            import dataclasses as _dc

            sp = self._spill
            sp.restore(ck.visited_keys, ck.extra)
            # ck.frontier was normalized to RAW lanes by the loader;
            # the spool's steady-state encoding is packed for
            # non-delta descriptors — re-encode the deferred segments
            # to match (_sh_spill_drain's contract), keep raw for
            # delta (re-based per inject) and identity codecs.
            rows = np.asarray(ck.frontier, np.int32)
            spool_rows = rows
            if self._pk is not None and not self._mesh_delta:
                spool_rows = self._pk.pack_np(rows)
            segcap = self.n_devices * self.f_cap
            for i in range(segcap, len(rows), segcap):
                sp.spool_cur.push(spool_rows[i:i + segcap])
            ck = _dc.replace(ck, frontier=rows[:segcap],
                             visited_keys=np.zeros((0, 4), np.uint32))
        return self._resume_carry(ck), ck.depth, ck.elapsed

    def _resume_carry(self, ck):
        D, F, V, lanes = self.n_devices, self.f_cap, self.v_cap, self.lanes
        plane = self.plane
        nf = len(self._flag_names)
        n = len(ck.frontier)
        if -(-n // D) > F:
            raise CapacityOverflow(
                f"{self.p.name}: frontier_cap {F}/device too small to "
                f"resume {n} checkpointed frontier rows on {D} devices")
        # The loader normalized the dump's frontier to RAW lanes
        # (engine.py _normalize_ckpt_frontier) — re-encode to this
        # engine's native packed storage here, with a fresh level base
        # (the per-lane min over the resumed rows) when the descriptor
        # has delta lanes.
        frontier = np.asarray(ck.frontier, np.int32).reshape(-1, lanes)
        pb0 = None
        if self._mesh_delta:
            didx = self._delta_lanes
            pb0 = (frontier[:, didx].min(axis=0).astype(np.int32)
                   if n else np.zeros((len(didx),), np.int32))
            base = np.zeros((lanes,), np.int32)
            base[didx] = pb0
            spans = frontier[:, didx].astype(np.int64) - pb0
            # Max in-window span: the lane mask, minus the reserved
            # all-ones sentinel code where one exists.
            win = ((1 << self._pk.width[didx].astype(np.int64)) - 1
                   - self._pk.sent[didx].astype(np.int64))
            if n and (spans > win[None, :]).any():
                raise CapacityOverflow(
                    f"{self.p.name}: resumed frontier spans a delta "
                    "window wider than the declared Field(delta=) "
                    "bits — raise the delta bits on the offending "
                    "field")
            frontier = self._pk.pack_np(frontier, base)
        elif self._pk is not None:
            frontier = self._pk.pack_np(frontier)
        per = max(1, -(-n // D))
        cur = np.zeros((D, per, plane), np.int32)
        cur_n = np.zeros((D,), np.int32)
        for d in range(D):
            rows = frontier[d * per:(d + 1) * per]
            cur[d, :len(rows)] = rows
            cur_n[d] = len(rows)
        keys = ck.visited_keys
        owner = (keys[:, 0].astype(np.uint64)
                 % np.uint64(D)).astype(np.int64)
        groups = [keys[owner == d] for d in range(D)]
        kmax = max([len(g) for g in groups] + [1])
        kbuf = np.zeros((D, kmax, 4), np.uint32)
        kval = np.zeros((D, kmax), bool)
        for d, g in enumerate(groups):
            kbuf[d, :len(g)] = g
            kval[d, :len(g)] = True

        def spread0(v):
            a = np.zeros((D,), np.int32)
            a[0] = v
            return a

        shard = NamedSharding(self.mesh, P(self.axis))
        dev_in = {k: jax.device_put(v, shard) for k, v in {
            "cur0": cur.reshape(D * per, plane),
            "cur_n": cur_n,
            "keys": kbuf.reshape(D * kmax, 4),
            "kval": kval.reshape(D * kmax),
            "explored": spread0(ck.explored),
            "vis_over": spread0(ck.vis_over),
            "drops": spread0(ck.dropped),
        }.items()}

        W = self._log_words()

        def resume_carry(s):
            table, ins, unres = visited_mod.insert(
                visited_mod.empty_table(V), s["keys"], s["kval"])
            out = {
                "cur": jnp.zeros((W,), jnp.int32).at[
                    :per * plane].set(s["cur0"].reshape(-1)),
                "cur_n": s["cur_n"],
                "j": jnp.zeros((1,), jnp.int32),
                "evp": jnp.zeros((1,), jnp.int32),
                "noapp": jnp.zeros((1,), jnp.int32),
                "nxt": jnp.zeros((W,), jnp.int32),
                "nxt_n": jnp.zeros((1,), jnp.int32),
                "visited": table,
                "vis_n": jnp.sum(ins).astype(jnp.int32)[None],
                "explored": s["explored"],
                "overflow": jnp.zeros((1,), jnp.int32),
                "vis_over": s["vis_over"],
                "drops": s["drops"],
                "flag_cnt": jnp.zeros((nf,), jnp.int32),
                "flag_rows": jnp.zeros((nf, lanes), jnp.int32),
            }
            if self.record_trace:
                out["tmeta"] = jnp.zeros((F + 1, 9), jnp.uint32)
                out["flag_meta"] = jnp.zeros((nf, 9), jnp.uint32)
            if self._spill_on:
                out["f_full"] = jnp.zeros((1,), jnp.int32)
            if self._mesh_delta:
                out["pb_cur"] = jnp.asarray(pb0, jnp.int32)
                out["pb_nxt"] = jnp.full((len(pb0),), jnp.int32(
                    self._PB_EMPTY))
                out["pb_peak"] = jnp.zeros((1,), jnp.int32)
            return out, jnp.sum(unres).astype(jnp.int32)[None]

        ax = self.axis
        in_spec = {k: P(ax) for k in dev_in}
        fn = jax.jit(shard_map(
            resume_carry, mesh=self.mesh, in_specs=(in_spec,),
            out_specs=(self._carry_specs(), P(ax)), check_vma=False))
        with self.mesh:
            carry, unres = fn(dev_in)
        n_unres = int(np.asarray(unres).sum())
        if n_unres:
            raise CapacityOverflow(
                f"{self.p.name}: visited_cap={V}/device too small to "
                f"rebuild the checkpoint's visited set ({n_unres} keys "
                "unresolved); raise visited_cap")
        return carry

    # ------------------------------------------- host-RAM spill tier
    #
    # The sharded half of tpu/spill.py (docs/capacity.md): same
    # drain/evict/refilter/reinject protocol as the single-device
    # engine, with the carry sharded over the mesh — readbacks gather
    # all shards, injections re-split into contiguous per-device
    # shares (the same discipline as _resume_carry).  Everything rides
    # the _dispatch seam (sharded.spill_* tags) so supervisor retry/
    # watchdog/FaultPlan and warden heartbeats cover the spill path.

    def _sh_spill_progs(self) -> dict:
        progs = getattr(self, "_sh_spill_prog_cache", None)
        if progs is not None:
            return progs
        V = self.v_cap
        spec = self._carry_specs()

        def spill_reset(c):
            out = dict(c)
            out["nxt_n"] = jnp.zeros((1,), jnp.int32)
            out["f_full"] = jnp.zeros((1,), jnp.int32)
            return out

        def spill_evict(c):
            out = dict(c)
            out["visited"] = visited_mod.empty_table(V)
            out["vis_n"] = jnp.zeros((1,), jnp.int32)
            out["f_full"] = jnp.zeros((1,), jnp.int32)
            return out

        progs = self._sh_spill_prog_cache = {
            "reset": self._sharded_jit(shard_map(
                spill_reset, mesh=self.mesh, in_specs=(spec,),
                out_specs=spec, check_vma=False)),
            "evict": self._sharded_jit(shard_map(
                spill_evict, mesh=self.mesh, in_specs=(spec,),
                out_specs=spec, check_vma=False)),
            "inject": {},
        }
        return progs

    def _sh_spill_drain(self, carry):
        """Gather every device's occupied nxt prefix (ONE batched
        readback), refilter against the host tier, drop exception/
        pruned rows, spool the keepers, and reset nxt on device.

        Spool encoding (ISSUE 18): PACKED rows when the descriptor has
        no delta lanes (the host tier holds pack_ratio x more states at
        fixed RAM — keys/refilter masks come from a host-side unpack);
        RAW rows under a delta descriptor (the level base changes at
        each re-inject, so a fixed-encoding spool would go stale)."""
        sp = self._spill
        D, F = self.n_devices, self.f_cap
        pk, plane = self._pk, self.plane
        spool_packed = pk is not None and not self._mesh_delta

        def fetch():
            nxt = np.asarray(carry["nxt"]).reshape(D, -1, plane)
            counts = np.asarray(carry["nxt_n"]).reshape(-1)
            if counts.sum():
                rows = np.concatenate(
                    [nxt[d, :counts[d]] for d in range(D)])
            else:
                rows = np.zeros((0, plane), np.int32)
            if pk is None:
                raw = rows
            elif self._mesh_delta:
                pb = np.asarray(carry["pb_cur"]).reshape(D, -1)[0]
                base = np.zeros((self.lanes,), np.int32)
                base[self._delta_lanes] = pb
                rows = raw = pk.unpack_np(rows, base)
            else:
                raw = pk.unpack_np(rows)
            return rows, raw, self._spill_keys_of(raw, F)

        rows, raw, keys = self._dispatch("sharded.spill_drain", fetch)
        if len(rows):
            # Async drain (ISSUE 15c): the host half rides the ordered
            # worker while the mesh re-dispatches — see engine.py
            # _spill_drain for the exactness argument.
            def host_half():
                kept = sp.refilter(rows, keys)
                if len(kept):
                    ku = pk.unpack_np(kept) if spool_packed else kept
                    kept = kept[self._spill_keep_mask(ku, F)]
                sp.spool(kept)

            sp.submit_drain(host_half)
        return self._dispatch("sharded.spill_drain",
                              self._sh_spill_progs()["reset"], carry)

    def _sh_spill_evict(self, carry):
        """Bulk eviction: every shard's occupied table lines -> the
        (global) host tier; all tables restart empty."""
        sp = self._spill

        def fetch():
            return visited_mod.host_occupied(carry["visited"])

        occ = self._dispatch("sharded.spill_evict", fetch)
        sp.submit_drain(lambda: sp.evict(occ), evict=True)
        self._last_vis_max = 0
        return self._dispatch("sharded.spill_evict",
                              self._sh_spill_progs()["evict"], carry)

    def _sh_spill_inject(self, carry, rows: np.ndarray):
        """(Re-)inject a host frontier segment: contiguous per-device
        shares (ceil split), zero-padded to a pow2 per-device width so
        the jitted set programs stay O(log f_cap).  Returns
        ``(carry, per_device_max)`` — the chunk-grid bound."""
        D, F, lanes = self.n_devices, self.f_cap, self.lanes
        plane = self.plane
        n = len(rows)
        per = max(1, -(-n // D))
        if per > F:
            raise CapacityOverflow(
                f"{self.p.name}: spool segment of {n} rows exceeds "
                f"frontier_cap {F}/device on {D} devices")
        if self._mesh_delta and n:
            # Delta spools hold RAW rows (_sh_spill_drain): re-encode
            # the segment against the CURRENT level base — pb_cur only
            # moves at promote, and the level's nxt rows all pack
            # against one base, so this stays consistent with what the
            # chunk step decodes.  A value outside the window from the
            # current base is the declared-bits contract being
            # exceeded: loud, with the fix named.
            rows = np.asarray(rows, np.int32).reshape(-1, lanes)
            pb = np.asarray(carry["pb_cur"]).reshape(D, -1)[0]
            base = np.zeros((lanes,), np.int32)
            base[self._delta_lanes] = pb
            spans = (rows[:, self._delta_lanes].astype(np.int64)
                     - pb.astype(np.int64))
            win = ((1 << self._pk.width[self._delta_lanes].astype(
                np.int64)) - 1
                - self._pk.sent[self._delta_lanes].astype(np.int64))
            if (spans < 0).any() or (spans > win[None, :]).any():
                raise CapacityOverflow(
                    f"{self.p.name}: spill re-inject found delta-lane "
                    "values outside the window from the current level "
                    "base — raise the Field(delta=) bits (spill defers "
                    "re-basing, so deep spilled runs need wider "
                    "windows)")
            rows = self._pk.pack_np(rows, base)
        m = self.cpd
        while m < per:
            m <<= 1
        m = max(min(m, F), 1)
        progs = self._sh_spill_progs()
        fn = progs["inject"].get(m)
        if fn is None:
            spec = self._carry_specs()
            ax = self.axis

            def spill_inject(c, seg, nn):
                out = dict(c)
                out["cur"] = jnp.zeros((self._log_words(),), jnp.int32).at[
                    :m * plane].set(seg.reshape(-1))
                out["cur_n"] = nn
                out["j"] = jnp.zeros((1,), jnp.int32)
                out["evp"] = jnp.zeros((1,), jnp.int32)
                out["f_full"] = jnp.zeros((1,), jnp.int32)
                return out

            seg_shard = NamedSharding(self.mesh, P(ax))
            fn = progs["inject"][m] = self._sharded_jit(shard_map(
                spill_inject, mesh=self.mesh,
                in_specs=(spec, P(ax), P(ax)), out_specs=spec,
                check_vma=False), extra_in=(seg_shard, seg_shard))
        buf = np.zeros((D, m, plane), np.int32)
        counts = np.zeros((D,), np.int32)
        for d in range(D):
            part = rows[d * per:(d + 1) * per]
            buf[d, :len(part)] = part
            counts[d] = len(part)
        shard = NamedSharding(self.mesh, P(self.axis))
        seg = jax.device_put(buf.reshape(D * m, plane), shard)
        nn = jax.device_put(counts, shard)
        carry = self._dispatch("sharded.spill_reinject", fn, carry,
                               seg, nn)
        return carry, int(counts.max())

    def _sh_spill_ckpt(self, carry, depth: int, explored: int,
                       elapsed: float) -> None:
        """Synchronous spill-mode unified dump: visited_keys = all
        shard tables ∪ host tier (exact-deduped), frontier = the
        spooled next level, counters on extra__spill_stats.  Any rung
        — spill or not, sharded or not — resumes it (docs/capacity.md)."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        sp = self._spill
        occ = visited_mod.host_occupied(carry["visited"])
        # The spool holds packed rows for non-delta descriptors
        # (_sh_spill_drain) — the dump then carries the encoding
        # marker; delta spools are raw, so their dump is raw too.
        spool_packed = self._pk is not None and not self._mesh_delta
        extra = sp.checkpoint_extra() or {}
        if spool_packed:
            extra["frontier_encoding"] = np.bytes_(
                self._pk.signature().encode())
        ckpt_mod.save(self.checkpoint_path, ckpt_mod.SearchCheckpoint(
            fingerprint=self._ckpt_fingerprint(), depth=depth,
            explored=explored, elapsed=elapsed,
            frontier=sp.spool_cur.concat(
                self.plane if spool_packed else self.lanes),
            visited_keys=sp.checkpoint_keys(occ),
            extra=extra or None))

    def run(self, check_initial: bool = True,
            initial: Optional[dict] = None,
            resume: bool = False) -> SearchOutcome:
        """Run the sharded BFS.  ``initial`` (a batch-1 state pytree,
        e.g. a prior outcome's ``goal_state``) starts from an arbitrary
        state — the staged-search pattern (PaxosTest.java:886-1096),
        same contract as the single-device engine.  ``resume=True``
        continues from ``checkpoint_path`` if a dump exists (a killed
        search restarts at its last checkpointed level with identical
        final verdict and unique count)."""
        t0 = time.time()
        # The host's work before the first level (telemetry.PHASES;
        # ``search.carry`` in _run_levels is its second half).
        with tel_mod.phase("search.start"):
            # One launch, one readback (``_root``): row, key and the
            # initial check's verdicts; what follows is host work.
            root = self._root(
                initial if initial is not None else self.initial_state(),
                hits=check_initial)
            # Root of this run's trace (tpu/trace.py replays from here):
            # host views of the row read back.
            self._trace_root = self.unflatten_rows(root[0])
            self._fp_map = {}
            self._deep_samples = None
            # Structured per-level throughput records (depth, chunks,
            # write_blocks, probe_cols, kind_skips, wall, explored, unique,
            # next_frontier) — attached
            # to the outcome as SearchOutcome.levels; the ``search.level``
            # phase carries the same counters into a profile.
            self._level_records: List[dict] = []
            self._pd_prev_explored = [0] * self.n_devices
            self._root_fp = tuple(root[1][0].tolist())
            if check_initial:
                out = self._initial_verdict(*root[2:], self._trace_root,
                                            t0)
                if out is not None:
                    return self._stamp_device(out)

        tel = getattr(self, "_telemetry", None)
        if tel is not None and self._spill is not None:
            self._spill.telemetry = tel
        try:
            out = self._run_levels(t0, root, resume)
            out.levels = self._level_records or None
            out.compile_secs = round(getattr(self, "compile_secs", 0.0), 3)
            self._stamp_device(out)
            self._stamp_capacity(out)
            if self._spill_on:
                self._spill.attach(out)
            if tel is not None:
                # Trace stamp at span emission (ISSUE 13): host string
                # copy off the recorder's context, zero device work.
                if out.trace_id is None:
                    out.trace_id = tel.trace_id
                tel.on_outcome(out, engine="sharded")
                if self.n_devices > 1 and self._pk is None:
                    # Identity-codec fallback on a real mesh (ISSUE 18
                    # satellite): the exchange shipped RAW lanes — hand
                    # twins without domain declarations, or
                    # mesh_pack=False.  Loud until ROADMAP #1 deletes
                    # the hand twins.
                    tel.event(
                        "mesh_unpacked", engine="sharded",
                        protocol=self.p.name,
                        mesh_width=self.n_devices,
                        reason=("knob" if not self.mesh_pack
                                else "identity descriptor"),
                        wire_lanes=self.lanes)
            if out.dropped and out.dropped >= _DROPPED_WARN():
                # Millions of beam drops with one flag to show for
                # them (an early chip run's shape) must be LOUD.
                import warnings

                warnings.warn(
                    f"{self.p.name}: beam truncation dropped "
                    f"{out.dropped} states (>= DSLABS_DROPPED_WARN="
                    f"{_DROPPED_WARN()}); the verdict covers a "
                    "narrowed space — raise frontier_cap or enable "
                    "the spill tier for zero-drop coverage",
                    RuntimeWarning, stacklevel=2)
            return out
        finally:
            # An async checkpoint still draining must complete before the
            # caller sees the outcome (kill-resume tests depend on the
            # dump landing; the thread holds device snapshots alive).
            self._join_checkpoint()

    def _run_levels(self, t0, root, resume) -> SearchOutcome:
        with self.mesh:
            with tel_mod.phase("search.carry"):
                resumed = self._load_checkpoint() if resume else None
                if resumed is not None:
                    carry, depth, prev_elapsed = resumed
                    t0 = time.time() - prev_elapsed
                    max_n = int(np.asarray(carry["cur_n"]).max())
                    # Pre-loop totals: a checkpoint saved after the FINAL
                    # level has an empty frontier, so the while body
                    # (which normally binds these) never runs.
                    explored = int(np.asarray(carry["explored"]).sum())
                    vis_total = int(np.asarray(carry["vis_n"]).sum())
                    if self._spill_on:
                        vis_total = self._spill.unique(vis_total)
                    drops = int(np.asarray(carry["drops"]).sum())
                else:
                    if self._spill_on:
                        # Fresh start: run N must not refilter against
                        # run N-1's tier (engine-reuse pattern; the
                        # resumed branch restores the tier from the dump
                        # instead).
                        self._spill.reset_run()
                    carry = self._root_carry(*root[:2])
                    depth = 0
                    max_n = 1
                    explored, vis_total, drops = 0, 1, 0  # the root state
            while max_n > 0:
                if self.max_depth is not None and depth >= self.max_depth:
                    return self._limit_outcome("DEPTH_EXHAUSTED", carry,
                                               depth, t0)
                if (self.max_secs is not None
                        and time.time() - t0 > self.max_secs) \
                        or self._cancelled():
                    out = self._limit_outcome("TIME_EXHAUSTED", carry,
                                              depth, t0)
                    out.cancelled = self._cancelled()
                    return out
                depth += 1
                # Live depth for supervision heartbeats (tpu/warden.py).
                self._current_depth = depth
                with tel_mod.phase("search.level", depth=depth,
                                   explored0=int(explored),
                                   frontier0=int(max_n)) as lvl:
                    t_lvl = time.time()
                    # Final depth-limited level: count/check fresh successors
                    # without building the next frontier (it would never be
                    # expanded — and at bench scale it would not even FIT:
                    # the depth-10 strict probe's last level is ~4x the
                    # frontier cap).  The explicit DEPTH_EXHAUSTED return
                    # below replaces the loop-top check for this level.
                    noapp_level = (self.max_depth is not None
                                   and depth >= self.max_depth)
                    if noapp_level and not self._spill_on:
                        # Spill mode keeps appends ON for the final level:
                        # the host spool absorbs an over-cap last level
                        # (noapp's reason to exist), and every fresh insert
                        # must reach the boundary refilter or a tier
                        # re-discovery would double-count (exact unique
                        # parity is the whole point of the tier).
                        shard = NamedSharding(self.mesh, P(self.axis))
                        carry["noapp"] = jax.device_put(
                            np.ones(self.n_devices, np.int32), shard)
                    (carry, out, explored, vis_total, drops, max_n,
                     chunks, counts) = self._level_superstep(
                         carry, depth, t0, max_n)
                    if out is not None:
                        return out
                    if self._spill_on:
                        # Deferred re-expansion waves: spooled segments of
                        # THIS level (frontier rows that outgrew the device
                        # buffer, or a resumed dump's tail) run at the same
                        # depth before the level closes — depth accounting,
                        # and therefore DEPTH_EXHAUSTED soundness, is
                        # preserved exactly.
                        while True:
                            seg = self._spill.pop_current()
                            if seg is None:
                                break
                            carry, per = self._sh_spill_inject(carry, seg)
                            (carry, out, explored, vis_total, drops, max_n,
                             ch2, cn2) = self._level_superstep(
                                 carry, depth, t0, per)
                            chunks += ch2
                            counts += cn2
                            if out is not None:
                                return out
                    rec = {
                        "depth": depth, "chunks": int(chunks),
                        # Scatter blocks the level's chunk steps wrote
                        # (table + append; on a mesh, of the device that
                        # wrote most): above one a probe iteration and one
                        # an append, visited.block_width is too narrow
                        # for the traffic.
                        "write_blocks": int(counts[0]),
                        # Bucket columns the level's probes gathered
                        # (indices handed to the table's gather, of the
                        # device that gathered most): a step's live
                        # blocks of visited.block_width, not its batch.
                        "probe_cols": int(counts[1]),
                        # (chunk step, event kind) pairs the expand did
                        # not compute because the pass's table held no
                        # event of the kind (engine._expand_chunk): of
                        # 2 x chunks, of the device that skipped most —
                        # on a mesh a device whose shard ran out skips
                        # both kinds while it waits, so the count says
                        # what was saved on one device only.
                        "kind_skips": int(counts[2]),
                        "wall": round(time.time() - t_lvl, 4),
                        "explored": int(explored), "unique": int(vis_total),
                        "next_frontier": int(max_n),
                        # Per-level visited-table load factor (ISSUE 6
                        # satellite): pressure is visible in bench JSON
                        # before the overflow contract can fire.
                        "load_factor": round(
                            getattr(self, "_last_load", 0.0), 4),
                        # Wire/storage codec this level ran under (ISSUE
                        # 18): 1.0 = raw exchange — the identity-fallback
                        # gap the run()-level telemetry event makes loud.
                        "pack_ratio": (round(self._pk.pack_ratio, 3)
                                       if self._pk is not None else 1.0)}
                    rebase = False
                    if self._mesh_delta:
                        # Delta lanes (the stats of the level's last
                        # superstep): ``rebased`` 1 where the next
                        # level's base differs from this one's, so that
                        # the promote below re-encodes the
                        # ``rebase_rows`` rows the level appended (over
                        # the mesh); ``delta_peak``
                        # the largest value - base a live successor's
                        # delta lane held, of a window of 2^bits - 1.
                        moved, peak = self._last_delta
                        # Not with the spill tier wired: whether this
                        # level ends in the promote or in a drain and a
                        # re-inject is decided below, after this record
                        # is written, and the re-inject packs a spooled
                        # segment against pb_cur as it stands.  Not
                        # re-basing is always sound; the window is not
                        # re-centred, and a lane past it raises.
                        rebase = bool(moved) and not (
                            noapp_level or self._spill_on)
                        delta_rec = dict(
                            rebased=int(rebase), delta_peak=peak,
                            rebase_rows=(sum(self._last_per_device[
                                "frontier"]) if rebase else 0))
                        rec.update(delta_rec)
                        lvl.set(**delta_rec)
                    # Mesh-scope lanes (ISSUE 8): the pre-psum per-device
                    # scalars the fused stats vector already carried, plus
                    # skew metrics — what the owner-hashed all_to_all
                    # design is decided on (ROADMAP #1).  Explored is
                    # cumulative per device, so the level's work share is
                    # the delta against the previous level sync.
                    pdev = getattr(self, "_last_per_device", None)
                    if pdev is not None:
                        prev = getattr(self, "_pd_prev_explored",
                                       [0] * self.n_devices)
                        delta = [e - p for e, p in zip(pdev["explored"],
                                                       prev)]
                        self._pd_prev_explored = list(pdev["explored"])
                        rec["per_device"] = {
                            "explored": delta,
                            "frontier": pdev["frontier"],
                            "load_factor": [round(v / self.v_cap, 4)
                                            for v in pdev["vis_n"]],
                            "drops": pdev["drops"]}
                        rec["skew"] = {
                            "explored": tel_mod.skew_metrics(delta),
                            "frontier": tel_mod.skew_metrics(
                                pdev["frontier"])}
                    tel = getattr(self, "_telemetry", None)
                    if tel is not None:
                        # Host-side HBM high-water per device, polled via
                        # the runtime's memory stats at level boundaries
                        # ONLY (a host syscall — never a device dispatch
                        # or readback; CPU meshes report nothing and the
                        # lane is omitted).
                        hbm = tel_mod.device_memory_stats(
                            self.mesh.devices.flat)
                        if hbm is not None:
                            rec["hbm_peak"] = hbm
                    self._level_records.append(rec)
                    lvl.set(explored=int(explored), unique=int(vis_total),
                            chunks=int(chunks),
                            write_blocks=rec["write_blocks"],
                            probe_cols=rec["probe_cols"],
                            kind_skips=rec["kind_skips"],
                            next_frontier=int(max_n))
                    if self.record_trace and not noapp_level:
                        # A final depth-limited level returns below:
                        # nothing it appended is ever expanded.
                        self._spill_tmeta(carry)
                if tel is not None:
                    # The SAME host scalars the fused stats readback
                    # already delivered — telemetry adds no transfers.
                    tel.on_level("sharded", self._level_records[-1])
                if noapp_level and self._spill_on:
                    # Final level, spill mode: drain through the
                    # refilter for the exact dedup accounting, then
                    # decide DEPTH vs SPACE on the refiltered,
                    # prune-filtered remainder — the same "expandable
                    # successors remained" question noapp's would-be
                    # count answers in the uncapped run.
                    carry = self._sh_spill_drain(carry)
                    vis_total = self._spill.unique(
                        int(np.asarray(carry["vis_n"]).sum()))
                    remained = self._spill.spool_next.rows()
                    out = SearchOutcome(
                        "DEPTH_EXHAUSTED" if remained > 0
                        else "SPACE_EXHAUSTED",
                        explored, vis_total, depth,
                        time.time() - t0, dropped=drops,
                        samples=getattr(self, "_deep_samples", None))
                    return out
                if noapp_level:
                    # max_n counted the final level's would-be appends:
                    # zero means the space ended exactly at the depth
                    # limit — SPACE_EXHAUSTED, matching the base engine
                    # and the pre-noapp loop's verdict at this boundary.
                    return SearchOutcome(
                        "DEPTH_EXHAUSTED" if max_n > 0
                        else "SPACE_EXHAUSTED",
                        explored, vis_total, depth,
                        time.time() - t0, dropped=drops,
                        samples=getattr(self, "_deep_samples", None),
                        visited_overflow=getattr(self, "_vis_over", 0))
                sp = self._spill
                if self._spill_on and (sp.active or sp.should_evict(
                        getattr(self, "_last_vis_max", 0), self.v_cap)):
                    # Spill boundary: drain nxt through the refilter
                    # (the corrected promote mask — one batched
                    # readback against the PRE-eviction tier), evict at
                    # high water, swap spools, re-inject the next
                    # level's first segment.  Replaces the on-device
                    # promote until the pressure clears.
                    carry = self._sh_spill_drain(carry)
                    if sp.should_evict(
                            getattr(self, "_last_vis_max", 0),
                            self.v_cap):
                        carry = self._sh_spill_evict(carry)
                    vis_total = sp.unique(
                        int(np.asarray(carry["vis_n"]).sum()))
                    sp.advance_level()
                    if not sp.spool_cur.segments:
                        return SearchOutcome(
                            "SPACE_EXHAUSTED", explored, vis_total,
                            depth, time.time() - t0, dropped=drops,
                            samples=getattr(self, "_deep_samples",
                                            None))
                    if (self.checkpoint_every and self.checkpoint_path
                            and depth % self.checkpoint_every == 0):
                        self._sh_spill_ckpt(carry, depth, explored,
                                            time.time() - t0)
                    seg = sp.spool_cur.pop()
                    carry, max_n = self._sh_spill_inject(carry, seg)
                    continue
                carry = self._promote_call(carry, rebase)
                if (self.checkpoint_every and self.checkpoint_path
                        and depth % self.checkpoint_every == 0):
                    self._save_checkpoint(carry, depth, time.time() - t0,
                                          max_n=max_n)

            return SearchOutcome(
                "SPACE_EXHAUSTED", explored, vis_total, depth,
                time.time() - t0, dropped=drops,
                samples=getattr(self, "_deep_samples", None),
                visited_overflow=getattr(self, "_vis_over", 0))

    def _level_superstep(self, carry, depth, t0, max_n):
        """One BFS level via the fused on-device superstep: each
        dispatch drains up to ``budget`` chunk steps (unbounded when no
        wall-clock budget is set — the whole level in ONE dispatch) and
        returns the fused stats in the same program.  Returns
        ``(carry, outcome_or_none, explored, vis_total, drops, nxt_max,
        chunk_steps_run, [write_blocks_run, probe_cols_run,
        kind_skips_run])``."""
        budget = ((1 << 30) if self.max_secs is None
                  else max(1, self._superstep_chunks))
        # Watchdog granularity (tpu/supervisor.py): a superstep
        # legitimately runs a whole level's chunk work in one dispatch,
        # so the per-dispatch deadline scales by the expected trip count
        # (2x for event-window spill re-passes).
        est = -(-max_n // self.cpd)
        self._dispatch_deadline_scales = {
            "superstep": float(max(1, min(budget, 2 * est)))}
        nf = len(self._flag_names)
        chunks, counts = 0, np.zeros(3, np.int64)
        while True:
            carry, stats = self._superstep_call(carry, budget)
            chunks += int(stats[9 + nf])
            counts += stats[10 + nf:13 + nf]
            # The checks run BEFORE any time-budget return: a violation
            # or capacity loss in the chunks already completed is never
            # masked by TIME_EXHAUSTED.
            (out, explored, vis_total, drops,
             nxt_max) = self._sync_checks(carry, depth, t0, stats)
            if out is not None:
                return (carry, out, explored, vis_total, drops, nxt_max,
                        chunks, counts)
            if self._spill_on and int(stats[13 + nf]):
                # Spill abort: the superstep suspended on a frontier-
                # full (bit 0) / table-full (bit 1) chunk, reverted
                # wholesale.  Drain nxt through the refilter to the
                # host spool, evict the tables if they were the wall,
                # and re-enter the drain loop — the held-back chunk
                # re-steps against recovered capacity.
                code = int(stats[13 + nf])
                if (code & 1) and nxt_max == 0:
                    raise CapacityOverflow(
                        f"{self.p.name}: one chunk's fresh successors "
                        f"exceed frontier_cap={self.f_cap}/device even "
                        f"with spill; lower chunk_per_device "
                        f"({self.cpd}) or raise frontier_cap")
                if (code & 2) and int(stats[4]) == 0:
                    raise CapacityOverflow(
                        f"{self.p.name}: one chunk's unique successors "
                        f"exceed visited_cap={self.v_cap}/device even "
                        f"from empty tables; lower chunk_per_device "
                        f"({self.cpd}) or raise visited_cap")
                carry = self._sh_spill_drain(carry)
                if code & 2:
                    carry = self._sh_spill_evict(carry)
                continue
            if int(stats[8 + nf]) == 0:     # every device's shard drained
                return (carry, None, explored, vis_total, drops, nxt_max,
                        chunks, counts)
            if (self.max_secs is not None
                    and time.time() - t0 > self.max_secs) \
                    or self._cancelled():
                out = self._limit_outcome("TIME_EXHAUSTED", carry,
                                          depth, t0)
                out.cancelled = self._cancelled()
                return (carry, out, explored, vis_total, drops, nxt_max,
                        chunks, counts)

    def _spill_tmeta(self, carry) -> None:
        """Fold this level's appended (child_fp, parent_fp, event) rows
        into the host-side fingerprint chain map (trace mode only).
        Vectorised: a per-row Python loop at frontier scale would dwarf
        the device time per level."""
        F = self.f_cap
        with tel_mod.phase("level.trace_meta") as span:
            meta = np.asarray(carry["tmeta"]).reshape(
                self.n_devices, F + 1, 9)
            counts = np.asarray(carry["nxt_n"]).reshape(-1)
            rows = np.concatenate([meta[d, :counts[d]]
                                   for d in range(self.n_devices)])
            span.set(rows=len(rows), bytes=meta.nbytes + counts.nbytes)
            if not len(rows):
                return
            children = list(map(tuple, rows[:, :4].tolist()))
            parents = list(map(tuple, rows[:, 4:8].tolist()))
            events = rows[:, 8].tolist()
            # Keep FIRST occurrence (BFS parent) both within the level's
            # batch (reversed zip: earlier rows overwrite later
            # duplicates — today owner-side dedup already makes
            # within-level children unique, but first-wins must not
            # depend on that) and across levels (existing entries win
            # via the update order below).
            new = dict(zip(reversed(children),
                           zip(reversed(parents), reversed(events))))
            new.update(self._fp_map)
            self._fp_map = new
            # Sample a few of this level's children (spread across the
            # batch) and keep their root-first traces; at an exhaust
            # verdict these are the deepest states available for the
            # object-side value-invariant re-check (ADVICE r4).  The rows
            # are already on the host — only K short chain walks per
            # level.
            k = min(3, len(rows))
            picks = {0, len(rows) // 2, len(rows) - 1}
            samples = []
            for i in sorted(picks)[:k]:
                tr = self._walk_fp_chain(parents[i], int(events[i]))
                if tr is not None:
                    samples.append(tr)
            if samples:
                self._deep_samples = samples

    def _walk_fp_chain(self, parent_fp, event_id) -> Optional[list]:
        """flag_meta (parent fp, event) -> grid event ids root-first, by
        walking the host fp map back to the run's root state."""
        events = [event_id]
        fp = parent_fp
        seen = 0
        while fp != self._root_fp:
            ent = self._fp_map.get(fp)
            if ent is None:
                return None     # chain broken (shouldn't happen)
            fp, ev = ent
            events.append(ev)
            seen += 1
            if seen > 10 ** 6:
                return None
        events.reverse()
        return events

    def _sync_checks(self, carry, depth, t0, stats):
        """The per-sync check pipeline: semantic overflow (raise) ->
        strict-mode drops (raise) -> terminal flags (checkState order) ->
        visited load factor (raise), over the fused ``stats`` vector the
        superstep returned in-program — no readback of its own; the
        expensive flag-row readback happens only when a terminal flag
        actually fired.  Returns (outcome_or_none, explored, vis_total,
        drops, nxt_max)."""
        s = np.asarray(stats)
        nf = len(self._flag_names)
        (overflow, drops, vis_over, explored, vis_max, vis_total,
         nxt_max) = (int(x) for x in s[:7])
        flag_counts = s[8:8 + nf]
        # Per-device stats lanes: the LAST 4D slots of the vector —
        # stashed for the level record's skew derivation, same readback
        # as everything above.
        D = self.n_devices
        pd = [int(x) for x in s[len(s) - 4 * D:]]
        self._last_per_device = {
            "explored": pd[:D], "vis_n": pd[D:2 * D],
            "frontier": pd[2 * D:3 * D], "drops": pd[3 * D:]}
        if self._mesh_delta:
            # [base moved, delta_peak], just before the per-device lanes.
            self._last_delta = (int(s[-4 * D - 2]), int(s[-4 * D - 1]))
        # Running total for outcome plumbing (SearchOutcome
        # .visited_overflow): keys the full table degraded to
        # treat-as-fresh — sound, but unique counts may over-report.
        self._vis_over = vis_over
        # Early-warning instrumentation (ISSUE 6 satellite): surface
        # table pressure BEFORE the overflow contract fires.  The
        # effective ceiling is the strict 75% guard when it applies,
        # the raw capacity otherwise; load_factor also lands on the
        # per-level records (SearchOutcome.levels).
        limit = (3 * self.v_cap // 4
                 if self.strict and not self._spill_on else self.v_cap)
        self._last_load = vis_max / self.v_cap
        self._last_vis_max = vis_max
        if (vis_max >= int(_VISITED_WARN() * limit)
                and not getattr(self, "_warned_visited", False)):
            self._warned_visited = True
            import warnings

            warnings.warn(
                f"{self.p.name}: visited table at {vis_max}/"
                f"{self.v_cap} per device (load "
                f"{self._last_load:.0%}) at depth {depth} — capacity "
                "pressure; "
                + ("the spill tier will evict to host RAM"
                   if self._spill_on else
                   "raise visited_cap or enable the spill tier "
                   "(spill=True / DSLABS_SPILL=1) before this "
                   "becomes CapacityOverflow"),
                RuntimeWarning, stacklevel=2)
        if self._spill_on:
            # Exact unique count across tiers (tpu/spill.py): the
            # device total is one epoch's inserts; the host tier holds
            # the evicted epochs, minus refilter-corrected duplicates.
            vis_total = self._spill.unique(vis_total)
        if overflow:
            raise CapacityOverflow(
                f"{self.p.name}: {overflow} semantic drops at depth "
                f"{depth} (net_cap/timer_cap overflowed; raise the caps"
                + ("; or a delta lane's value left the window above its "
                   "level base: raise the Field(delta=) bits"
                   if self._mesh_delta else "") + ")")
        if drops and self.strict:
            raise CapacityOverflow(
                f"{self.p.name}: {drops} capacity drops at depth "
                f"{depth} (routing bucket or frontier cap "
                f"{self.f_cap}/device; raise caps or run "
                f"strict=False for beam-style truncation)")
        # Terminal flags before the table guards: a violation/goal found
        # this level is a valid verdict even if the table is full.
        if flag_counts.any():
            out = self._terminal_from_flags(carry, explored, vis_total,
                                            depth, t0)
            if out is not None:
                out.dropped = drops
                out.visited_overflow = vis_over
                return out, explored, vis_total, drops, nxt_max
        if self._spill_on:
            # The abort protocol reverts any chunk that would leave
            # keys unresolved, and eviction replaces the 75% guard.
            if vis_over:
                raise AssertionError(
                    "spill mode committed unresolved keys (abort "
                    "contract violated)")
            return None, explored, vis_total, drops, nxt_max
        if vis_over and self.strict:
            raise CapacityOverflow(
                f"{self.p.name}: visited hash table full at depth "
                f"{depth} ({vis_over} unresolved keys, cap "
                f"{self.v_cap}/device); raise visited_cap or run "
                "strict=False for sound treat-as-fresh degradation")
        if self.strict and vis_max > 3 * self.v_cap // 4:
            raise CapacityOverflow(
                f"{self.p.name}: visited hash table > 75% full "
                f"({vis_max}/{self.v_cap} per device) "
                f"at depth {depth}; raise visited_cap")
        return None, explored, vis_total, drops, nxt_max

    def _limit_outcome(self, cond, carry, depth, t0):
        unique = int(np.asarray(carry["vis_n"]).sum())
        if self._spill_on:
            unique = self._spill.unique(unique)
        return SearchOutcome(
            cond,
            int(np.asarray(carry["explored"]).sum()),
            unique,
            depth, time.time() - t0,
            dropped=int(np.asarray(carry["drops"]).sum()),
            samples=getattr(self, "_deep_samples", None),
            visited_overflow=int(np.asarray(carry["vis_over"]).sum()))
