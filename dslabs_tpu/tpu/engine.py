"""TPU tensor-search engine: vmapped BFS over a frontier of packed states.

This is the component the whole rebuild points at (SURVEY §0, §8,
BASELINE.json): the reference's explicit-state model checker
(framework/tst/.../search/Search.java:405-505 — one thread pops one state,
clones one node, runs one reflective handler) becomes a data-parallel XLA
program:

  frontier [N, ...]  --(enumerate events x vmapped transition)-->
  successors [N*E, ...] --(canonicalise + 128-bit fingerprint)-->
  dedup (device sort-unique prefilter + device-resident visited hash
  table, dslabs_tpu/tpu/visited.py) --> frontier'

The whole wave cycle — expand, in-chunk sort-unique, visited-table
insert, frontier compaction — stays on device: the carry (visited table
+ frontier) rides ``jax.jit(..., donate_argnums=0)`` so the table is
updated in place, per-wave host transfers are SCALARS only (counters +
flag counts; never ``[N, 4]`` fingerprint pulls), and the loop is
double-buffered (wave k+1 dispatches before wave k's scalars are read).
The original host-side ``sorted_member`` loop survives as
:meth:`TensorSearch.run_host` — the parity oracle for tests and the
trace-recording path (per-level event spills are host-side by nature).

Checker semantics reproduced exactly (SURVEY §7):
  * the network is a SET of fixed-width message records, kept in canonical
    sorted order (Java hashes unordered sets; canonical order makes equal
    states hash equal — SURVEY §8.1 "canonicalization matters");
    delivery never removes a message (SearchState.java:300);
  * per-node timer queues keep insertion order; a timer is deliverable iff
    no earlier-queued timer t' has t.min >= t'.max (TimerQueue.java:66-105),
    computed as a vectorised prefix-min; firing removes the timer;
  * dedup happens on successor generation, pre-check (Search.java:485);
    equivalence keys on (node lanes, network set, timer queues, exception
    lane) via a 128-bit fingerprint (hash compaction; collision odds
    ~n^2 / 2^128);
  * guard failures in a tensor twin set a terminal per-state exception code
    that participates in equivalence (SearchState.java:594-596, SURVEY
    §8.4.7) and ends the search with EXCEPTION_THROWN (checkState order:
    exception strictly first, Search.java:162-231).

All device arithmetic is int32/uint32 — TPUs have no native int64 and the
round-1 bench crashed inside the x64-emulated fingerprint path.  The two
64-bit fingerprints live on device as paired uint32 lanes `[N, 4]`
(a_hi, a_lo, b_hi, b_lo); only host-side NumPy packs them into uint64 for
the sorted visited set.  Capacity overflow (network set or timer queue) is
counted on device and surfaced as a loud ``CapacityOverflow`` error rather
than silently corrupting state counts (SURVEY §8.4.2).

The engine is protocol-agnostic: a :class:`TensorProtocol` supplies packed
node-state lanes and a pure ``step(state, event)`` transition; the engine
owns event enumeration, network-set insertion, canonicalisation,
fingerprinting, dedup, predicate checks, and frontier compaction.  Multi-
chip scaling shards the frontier over a mesh and exchanges successor
fingerprints by hash ownership (see ``dslabs_tpu/tpu/sharded.py``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dslabs_tpu.tpu import telemetry as tel_mod
from dslabs_tpu.tpu import visited as visited_mod

__all__ = ["TensorProtocol", "TensorState", "TensorSearch", "SearchOutcome",
           "CapacityOverflow", "SENTINEL", "drop_pending_messages",
           "device_get"]


def _visited_warn() -> float:
    from dslabs_tpu.tpu.spill import visited_warn_threshold

    return visited_warn_threshold()


def device_get(x) -> np.ndarray:
    """The device->host readback funnel for the device-resident run loop.

    Every transfer the wave loop performs goes through here so tests can
    instrument it (monkeypatch) and assert the per-wave transfer
    contract: scalars/short stat vectors only — never state rows or
    ``[N, 4]`` fingerprint batches."""
    return np.asarray(x)

# Empty slots in the network / timer arrays hold SENTINEL in every lane, so
# they sort after every real record and hash consistently.
SENTINEL = np.int32(2 ** 31 - 1)


def drop_pending_messages(state: dict) -> dict:
    """The staged-search ``dropPendingMessages`` analog
    (SearchState.java:534-561): a copy of the state with an empty network
    (timers survive, so retry timers re-drive the protocol)."""
    return {**state, "net": jnp.full_like(jnp.asarray(state["net"]),
                                          SENTINEL)}


class CapacityOverflow(RuntimeError):
    """A fixed-capacity structure (network set / timer queue) overflowed.

    The reference's structures are unbounded; the tensor twin's are sized
    per protocol.  Overflow would silently corrupt verdicts and state
    counts, so the engine counts drops on device and aborts loudly
    (SURVEY §8.4.2 "fail loudly on bound overflow")."""


# --------------------------------------------------------------------- state

class TensorState(Dict[str, jnp.ndarray]):
    """A batch of packed search states (struct-of-arrays pytree):

    nodes  [N, NW]            int32 — all nodes' packed protocol fields
    net    [N, NET_CAP, MW]   int32 — canonical-sorted message set
    timers [N, NN, T_CAP, TW] int32 — per-node timer queues, insertion order
                                      (lane 0 = tag, lane 1 = min, lane 2 =
                                      max, rest payload)
    exc    [N]                int32 — terminal exception code (0 = none)
    """


@dataclasses.dataclass(frozen=True)
class TensorProtocol:
    """Contract a tensorised protocol twin fulfils.

    The transition functions operate on ONE state (the engine vmaps them):

    ``step_message(nodes, msg) -> (nodes', sends, new_timers[, exc])``
    ``step_timer(nodes, node_idx, timer) -> (nodes', sends, new_timers[, exc])``

    where ``sends`` is ``[MAX_SENDS, MW]`` with invalid rows = SENTINEL,
    ``new_timers`` is ``[MAX_SETS, 1 + TW]`` (leading lane = target node
    index, SENTINEL rows invalid), and the optional trailing ``exc`` is an
    int32 exception code (0 = none) — the tensor analog of a handler
    throwing (SearchState.java:218-222).
    """

    name: str
    n_nodes: int
    node_width: int
    msg_width: int
    timer_width: int
    net_cap: int
    timer_cap: int
    max_sends: int
    max_sets: int
    init_nodes: Callable[[], np.ndarray]
    init_messages: Callable[[], np.ndarray]   # [k, MW] initial network
    init_timers: Callable[[], np.ndarray]     # [k, 1 + TW] initial timer sets
    step_message: Callable
    step_timer: Callable
    # message -> destination node index (for delivery gating); jax fn
    msg_dest: Callable
    # state-level predicates: dict name -> vmapped-able fn(state_slice)->bool
    invariants: Dict[str, Callable] = dataclasses.field(default_factory=dict)
    goals: Dict[str, Callable] = dataclasses.field(default_factory=dict)
    prunes: Dict[str, Callable] = dataclasses.field(default_factory=dict)
    # optional masks: deliver_message(msg)->bool, deliver_timer(node)->bool
    deliver_message: Optional[Callable] = None
    deliver_timer: Optional[Callable] = None
    # RUNTIME-mask variants: fn(msg, marr)->bool / fn(node, tarr)->bool
    # where marr/tarr are device arrays passed per run (TensorSearch
    # .set_runtime_masks), NOT trace-time constants.  The harness search
    # backend uses these so every staged phase of a lab test (different
    # partitions/timer gating, same protocol shape) shares ONE compiled
    # expand program instead of recompiling per mask (settings gate
    # events, never shapes — SURVEY §7.7).  Applied in _event_tables
    # (the single validity source for the expand pipeline).
    deliver_message_rt: Optional[Callable] = None
    deliver_timer_rt: Optional[Callable] = None
    # Max SIMULTANEOUS valid send rows any single transition can emit.
    # ``max_sends`` is the static row budget summed over all (mutually
    # exclusive) handler branches; the live count is far smaller (lab3:
    # 29 rows budgeted, <= ~12 ever valid at once).  When set, the engine
    # compacts sends to this width before the set-insert merge — the
    # merge is O(S x CAP) so this directly shrinks the hot loop.  Too
    # small a value is a loud CapacityOverflow, never silent truncation.
    max_live_sends: Optional[int] = None
    # optional object-twin decoders for trace reconstruction
    # (tpu/trace.py): decode_message(np_record) -> (from_addr, to_addr,
    # Message); decode_timer(node_idx, np_record) -> (to_addr, Timer,
    # min_ms, max_ms).  Addresses follow the twin's parity-test naming.
    decode_message: Optional[Callable] = None
    decode_timer: Optional[Callable] = None
    # Declared per-lane value domains (ISSUE 15, tpu/packing.py):
    # {"nodes": [...], "msg": [...], "timer": [...], "exc": (lo, hi)}
    # with (lo, hi) or None per lane — the input to the bit-packed
    # frontier encoding.  None (hand twins) derives the identity
    # descriptor: the packed path is a traced no-op.
    lane_domains: Optional[dict] = None
    # Symmetry groups (ISSUE 15, tpu/symmetry.py SymmetrySpec):
    # permutation tables over node ids/lanes for the opt-in
    # canonicalize-before-fingerprint pass.  None = no groups.
    symmetry: Optional[object] = None
    # Checkable fault scenarios (ISSUE 19, tpu/faults.py FaultLanes):
    # the compiled fault-model descriptor — partition/crash/drop/dup
    # event segment layout, controller lane offsets, deliverability
    # tables.  None = no fault model; every engine addition is gated
    # at trace time on this, so fault-free specs lower to the
    # byte-identical pre-fault program.
    fault: Optional[object] = None
    # The ``exc`` code (0 = none) by which a handler says that the
    # TWIN's own bounded state ran out where the object's has no end (a
    # log slot past the last: specs_lab3 ``loud_refusal``).  A step that
    # ends on it is truncated like one past net_cap / timer_cap, not a
    # handler that threw: the swarm restarts the walker and counts it
    # (``refused``), a strict fleet raises.
    capacity_exc: int = 0


@dataclasses.dataclass
class SearchOutcome:
    end_condition: str               # GOAL_FOUND / INVARIANT_VIOLATED /
                                     # EXCEPTION_THROWN / SPACE_EXHAUSTED /
                                     # CAPACITY_EXHAUSTED / DEPTH_EXHAUSTED /
                                     # TIME_EXHAUSTED
    states_explored: int
    unique_states: int
    depth: int
    elapsed_secs: float
    violating_state: Optional[dict] = None
    goal_state: Optional[dict] = None
    predicate_name: Optional[str] = None
    exception_code: int = 0
    trace: Optional[list] = None     # [(parent event id, ...)] — see trace.py
    dropped: int = 0                 # beam-truncation drops (strict=False)
    # Trace-mode exhaust verdicts carry a few deepest-state traces so the
    # caller can re-check value-level invariants (which collapse to
    # constant-true lane predicates on the twin) on replayed OBJECT
    # states before trusting the exhaustion (ADVICE r4).
    samples: Optional[list] = None   # [root-first event-id list, ...]
    # Visited-table overflow: keys whose probe exhausted (table
    # effectively full) were treated as FRESH — sound (the state may be
    # re-explored; nothing is ever silently dropped) but the unique
    # count can then over-report re-explorations.  Strict engines raise
    # instead; beam runs report the count here (ISSUE 1 contract).
    visited_overflow: int = 0
    # Recovery accounting (tpu/supervisor.py, docs/resilience.md): every
    # degradation the supervisor absorbed on the way to this verdict is
    # visible here — never a silent partial verdict.
    retries: int = 0                 # transient-dispatch retries absorbed
    failovers: int = 0               # ladder rungs abandoned before this one
    resumed_from_depth: int = 0      # checkpoint depth resumed from (0=root)
    engine: Optional[str] = None     # ladder rung that produced the verdict
    # The device the verdict was COMPUTED on (``jax.Device.platform`` /
    # ``.device_kind``), stamped by the engine that ran and carried
    # through the supervisor, the warden/lane pipes and the service's
    # verdict records — so a last-rung CPU verdict can always be told
    # from a chip verdict.
    platform: Optional[str] = None
    device_kind: Optional[str] = None
    # Process-isolation accounting (tpu/warden.py): children the warden
    # spawned beyond the first on the way to this verdict, and
    # dispatches SIGKILLed mid-flight after heartbeat silence.  Zero in
    # in-process mode.
    child_restarts: int = 0
    killed_dispatches: int = 0
    # In-process watchdog leak accounting: watchdog-abandoned daemon
    # threads STILL BLOCKED when the verdict landed (each one pins a
    # wedged XLA dispatch; process isolation is the leak-free mode).
    abandoned_threads: int = 0
    # Structured per-level throughput records from the sharded driver
    # (dicts of depth / chunks / wall / explored / unique /
    # next_frontier) — the bench emits them as its throughput series;
    # the ``search.level`` phases carry the same counters in a profile.
    levels: Optional[list] = None
    # Wall seconds spent in explicit AOT compilation (the construction-
    # time .lower().compile() warm-up) — reported SEPARATELY from
    # elapsed_secs so compile cost never pollutes states/min, and so a
    # warm persistent compile cache (tpu/compile_cache.py) is visible
    # as this number dropping to near-zero on the second run.
    compile_secs: float = 0.0
    # Swarm-explorer accounting (tpu/swarm.py, docs/swarm.md).  A
    # random walker RESTARTS (root/frontier re-seed) on dead ends,
    # prunes, its depth bound, or — the loud bugfix of the old silent
    # rollout behaviour — a capacity-truncated step; the truncated-step
    # count is swarm_overflow (strict swarms raise instead, matching
    # the visited-overflow contract), and the total restart count is
    # walker_restarts.  ``swarm`` carries the fleet's throughput stats
    # (walkers/sec, unique-states/min, deepest depth) for the bench.
    walker_restarts: int = 0
    swarm_overflow: int = 0
    swarm: Optional[dict] = None
    # The verified counterexample (tpu/swarm.py ``Witness``): minimized
    # event trace + replay-verification flags.  Populated by swarm /
    # rollout violations before the verdict is returned — no tensor
    # verdict ships an unminimized or unreplayed trace.
    witness: Optional[object] = None
    # Portfolio-mode cancellation marker (tpu/supervisor.py): this
    # outcome was cut short because the OTHER portfolio lane already
    # landed a terminal verdict — never a standalone verdict.
    cancelled: bool = False
    # Host-RAM spill-tier accounting (tpu/spill.py, docs/capacity.md):
    # keys evicted from the device visited table to the host tier,
    # re-discoveries the level-boundary refilter removed (each one a
    # corrected duplicate count), and frontier rows that took the
    # host-spool detour instead of being dropped.  All zero when the
    # spill tier never engaged.
    spilled_keys: int = 0
    host_tier_hits: int = 0
    respilled_frontier: int = 0
    # Elastic-mesh resilience accounting (ISSUE 9, tpu/supervisor.py,
    # docs/resilience.md): the mesh width (device count) of the rung
    # that produced this verdict, how many times the degraded-mesh
    # ladder halved the mesh (``mesh_shrunk`` events), and how many
    # in-place knob-shrink re-levels OOM-classified failures were
    # answered with (``knobs_shrunk`` events) instead of burning a
    # rung.  None/0 outside the supervisor.
    mesh_width: Optional[int] = None
    mesh_shrinks: int = 0
    knob_retries: int = 0
    # Causal-trace identity (ISSUE 13, tpu/tracing.py): the trace this
    # verdict belongs to, stamped from the attached telemetry
    # recorder's context at span emission — how a service verdict, its
    # COSTS.jsonl record, and its flight log stay joinable after the
    # run dir is pruned.  None outside any trace.
    trace_id: Optional[str] = None
    # Batched job lanes (ISSUE 14, tpu/lanes.py): the lane index this
    # verdict ran in, the batch width (L), and this lane's fraction of
    # the batch's shared device-seconds (every dispatch's wall split
    # evenly across the lanes resident at that level — the shares of a
    # batch sum to 1.0, so lane billing never double-charges a
    # dispatch).  None/unset outside a lane batch.
    lane: Optional[int] = None
    lane_width: Optional[int] = None
    lane_share: Optional[float] = None
    # Capacity round 2 (ISSUE 15, tpu/packing.py / tpu/symmetry.py):
    # HBM bytes per stored frontier row under the engine's encoding
    # (packed when the spec declares domains), the unpacked reference,
    # their ratio (the capacity multiplier at fixed HBM), and the
    # symmetry-quotient accounting — the canonicalize pass's
    # permutation count (0 = reduction off; unique_states is then the
    # CANONICAL orbit count, strictly <= the raw count).
    bytes_per_state: Optional[int] = None
    bytes_per_state_unpacked: Optional[int] = None
    pack_ratio: Optional[float] = None
    symmetry_perms: int = 0
    # Async spill drain (ISSUE 15c, tpu/spill.py): host ms inside
    # drain jobs vs ms the driver actually blocked waiting for them —
    # the gap is drain work overlapped with device compute.
    spill_drain_ms: int = 0
    spill_wait_ms: int = 0
    # Checkable fault scenarios (ISSUE 19, tpu/faults.py): valid fault
    # events EXPLORED (counted over successor states, like
    # states_explored) split by family — partition cut/heal, crash +
    # restart, message drops, dup tags — and their total.  All zero
    # when the spec declares no fault model.
    fault_events: int = 0
    partition_events: int = 0
    crash_events: int = 0
    drop_events: int = 0
    dup_events: int = 0

    @property
    def dropped_states(self) -> int:
        """Beam-truncation drop COUNT under its roadmap name (ISSUE 6
        satellite: surfaced everywhere, never a boolean) — the same
        number as ``dropped``; the alias exists so bench JSON, docs,
        and the DSLABS_DROPPED_WARN threshold all speak one name."""
        return self.dropped


# ----------------------------------------------------------------- hashing

def _mix32(x: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Add-shift-xor mixer over int32 lanes (vectorised, uint32 only).

    Jenkins one-at-a-time-style avalanche: NO per-element integer
    multiplies — uint32 multiplies at (pairs x lanes) scale measured ~6x
    slower than shift/add/xor lanes on the TPU VPU (round-2 profile).
    The only multiply is on the [1, L] positional seed row."""
    x = x.astype(jnp.uint32) ^ (seed.astype(jnp.uint32)
                                * jnp.uint32(0x9E3779B9))
    x = x + (x << 10)
    x = x ^ (x >> 6)
    x = x + (x << 3)
    x = x ^ (x >> 11)
    x = x + (x << 15)
    x = x ^ (x >> 7)
    return x


def _fingerprint32(flat: jnp.ndarray, seed: int,
                   sum_fn=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """64-bit fingerprint of [N, L] int32 rows as a (hi, lo) uint32 pair.

    Sequential-free: each lane is mixed with its position and a seed, then
    lanes are combined with addition and a final avalanche (order within the
    row still matters via the positional term).  No int64 anywhere — TPU
    native dtypes only.

    ``sum_fn`` overrides the uint32 lane reduction: the Pallas kernel
    (tpu/kernels.py) passes a bit-identical int32-bitcast sum because
    Mosaic cannot reduce over unsigned ints — keeping the mixing sequence
    and constants defined in exactly one place."""
    if sum_fn is None:
        def sum_fn(x):
            return jnp.sum(x, axis=1, dtype=jnp.uint32)
    _, l = flat.shape
    pos = jnp.arange(l, dtype=jnp.uint32)[None, :] + jnp.uint32(seed * 0x1000193)
    h = _mix32(flat, pos)
    lo = sum_fn(h)
    hi = sum_fn(_mix32(h, pos + jnp.uint32(0x27D4EB2F)))
    return hi, lo


def row_fingerprints(flat: jnp.ndarray) -> jnp.ndarray:
    """[N, L] int32 rows -> [N, 4] uint32 (a_hi, a_lo, b_hi, b_lo): two
    independent 64-bit fingerprints = one 128-bit equivalence key."""
    a_hi, a_lo = _fingerprint32(flat, 1)
    b_hi, b_lo = _fingerprint32(flat, 2)
    return jnp.stack([a_hi, a_lo, b_hi, b_lo], axis=1)


def flatten_state(state: dict) -> jnp.ndarray:
    """[N]-batch state pytree -> [N, L] int32 rows (the hash preimage).
    The exception lane participates — exception states are equivalence-
    distinct from normal ones (SearchState.java:594-596)."""
    n = state["nodes"].shape[0]
    return jnp.concatenate([
        state["nodes"].reshape(n, -1),
        state["net"].reshape(n, -1),
        state["timers"].reshape(n, -1),
        state["exc"].reshape(n, 1),
    ], axis=1)


def state_fingerprints(state: dict) -> jnp.ndarray:
    """[N]-batch -> [N, 4] uint32 128-bit equivalence keys.  Defaults to
    the jnp path, which XLA fuses into the expand program (measured ~2x
    faster end-to-end than the VMEM-tiled Pallas kernel in
    tpu/kernels.py, which is opt-in via DSLABS_PALLAS_FP=1)."""
    from dslabs_tpu.tpu.kernels import fingerprint_rows

    return fingerprint_rows(flatten_state(state))


def host_keys(fp: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """[N, 4] uint32 device fingerprints -> host (h1, h2) uint64 arrays.
    x64 lives only here, in host NumPy (TPUs emulate int64; round 1's
    global ``jax_enable_x64`` crashed the TPU worker)."""
    fp = np.asarray(fp, dtype=np.uint64)
    h1 = (fp[:, 0] << np.uint64(32)) | fp[:, 1]
    h2 = (fp[:, 2] << np.uint64(32)) | fp[:, 3]
    return h1, h2


def _keys_to_rows(visited: Tuple[np.ndarray, np.ndarray]) -> np.ndarray:
    """Inverse of :func:`host_keys`: host (h1, h2) uint64 arrays ->
    [K, 4] uint32 device-format key rows (the unified checkpoint's
    visited_keys layout, tpu/checkpoint.py)."""
    h1, h2 = visited
    rows = np.empty((len(h1), 4), np.uint32)
    rows[:, 0] = (h1 >> np.uint64(32)).astype(np.uint32)
    rows[:, 1] = (h1 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    rows[:, 2] = (h2 >> np.uint64(32)).astype(np.uint32)
    rows[:, 3] = (h2 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return rows


def sorted_member(vh1: np.ndarray, vh2: np.ndarray,
                  h1: np.ndarray, h2: np.ndarray) -> np.ndarray:
    """Membership of query keys (h1, h2) in a visited set sorted by
    (h1, h2).  Scans forward over the full run of equal h1 (not a fixed
    2-slot probe), so >=3-way 64-bit collisions cannot cause re-exploration
    (round-1 advisor finding)."""
    seen = np.zeros(len(h1), dtype=bool)
    if not len(vh1):
        return seen
    pos = np.searchsorted(vh1, h1, side="left")
    off = 0
    while True:
        q = pos + off
        inb = q < len(vh1)
        qc = np.where(inb, q, 0)
        eq1 = inb & (vh1[qc] == h1)
        if not eq1.any():
            return seen
        seen |= eq1 & (vh2[qc] == h2)
        off += 1


# ------------------------------------------------------------ net/timer ops

def _row_less(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Lexicographic ``a < b`` over the trailing lane axis (broadcasts).
    Pure compare/select lanes — no integer multiplies: uint32-multiply
    hashing at (state x event x row) scale measured ~6x slower than these
    raw-lane compares on TPU (round-2 bisection)."""
    eq = a == b
    # first_diff[l] = lanes 0..l-1 all equal and lane l differs
    prefix_eq = jnp.cumprod(eq, axis=-1, dtype=jnp.int32).astype(bool)
    prefix_excl = jnp.concatenate([
        jnp.ones_like(prefix_eq[..., :1]), prefix_eq[..., :-1]], axis=-1)
    return jnp.any(~eq & prefix_excl & (a < b), axis=-1)


def canonicalize_net(net: jnp.ndarray) -> jnp.ndarray:
    """Sort the message set into canonical (raw-lane lexicographic) order
    and collapse duplicates.

    [CAP, MW] -> [CAP, MW]; empty rows are all-SENTINEL and sort last
    (SENTINEL is int32 max and occupied rows always have lane 0 !=
    SENTINEL).  Cold path: used for batch-1 initial states only — the hot
    loop's set-insertion (:func:`insert_messages`) is a sort-free merge
    that preserves this order."""
    cap = net.shape[0]
    empty = net[:, 0] == SENTINEL
    # lexsort: LAST key is primary — empty rows always sort to the back.
    keys = tuple(net[:, lane] for lane in range(net.shape[1] - 1, -1, -1))
    order = jnp.lexsort(keys + (empty,))
    net_s = net[order]
    empty_s = empty[order]
    dup = jnp.zeros(cap, dtype=bool).at[1:].set(
        jnp.all(net_s[1:] == net_s[:-1], axis=1) & ~empty_s[1:])
    keep = ~dup & ~empty_s
    pos = jnp.cumsum(keep) - 1
    out = jnp.full((cap + 1, net.shape[1]), SENTINEL, net.dtype)
    out = out.at[jnp.where(keep, pos, cap)].set(net_s)
    return out[:cap]


def compact_rows(rows: jnp.ndarray,
                 budget: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compact occupied rows (lane 0 != SENTINEL) of [R, W] into the first
    ``budget`` slots of a [budget, W] output, preserving order; returns
    ``(out, overflow)`` where overflow counts occupied rows beyond the
    budget (callers treat nonzero as fatal — a dropped row would corrupt
    the successor state, never a beam-style truncation).

    One-hot select-reduce over the [budget, R] grid — static indexing
    only (a pos-indexed scatter per pair is the slow dynamic path)."""
    occ = rows[:, 0] != SENTINEL
    pos = jnp.cumsum(occ) - 1
    hit = occ[None, :] & (pos[None, :] == jnp.arange(budget)[:, None])
    out = jnp.sum(jnp.where(hit[:, :, None], rows[None, :, :], 0), axis=1)
    out = jnp.where(jnp.any(hit, axis=1)[:, None], out, SENTINEL)
    overflow = jnp.sum(occ & (pos >= budget)).astype(jnp.int32)
    return out, overflow


def insert_messages(net: jnp.ndarray,
                    sends: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Set-insert up to S records into the canonical network.

    Sort-free merge: ``net`` is always in canonical form (occupied rows
    first, raw-lane ascending — every state enters the engine through
    :func:`canonicalize_net` or this function), so inserting S small
    ``sends`` needs only O(S x CAP) lexicographic comparisons to compute
    each row's merged rank.  The round-2 profile showed a
    sort-per-(state,event) version was 82% of the whole expand program;
    round 3 replaced the remaining O(CAP^2) one-hot placement of net rows
    with S+1 STATIC shifted slices: net row j lands at j + shift_j where
    shift_j = #valid sends below it <= S, so out[k] selects among
    net[k-c] for c in 0..S — an O(CAP x S) select chain with no dynamic
    indexing.  Callers compact ``sends`` to the protocol's
    ``max_live_sends`` first, which is what makes S genuinely small.

    Returns ``(net', overflow)`` where overflow counts distinct occupied
    records that did not fit back into capacity — the caller surfaces any
    nonzero count as a CapacityOverflow (never a silent truncation)."""
    cap = net.shape[0]
    s = sends.shape[0]
    w = net.shape[1]
    net_occ = net[:, 0] != SENTINEL                       # [cap]
    send_occ = sends[:, 0] != SENTINEL                    # [s]
    sn_less = _row_less(sends[:, None, :], net[None, :, :])  # send_i < net_j
    sn_eq = jnp.all(sends[:, None, :] == net[None, :, :], axis=-1)
    dup_net = jnp.any(sn_eq & net_occ[None, :], axis=1)   # [s]
    ss_eq = jnp.all(sends[:, None, :] == sends[None, :, :], axis=-1)
    earlier = jnp.tril(jnp.ones((s, s), bool), k=-1)      # j < i
    earlier_dup = jnp.any(ss_eq & earlier & send_occ[None, :], axis=1)
    valid = send_occ & ~dup_net & ~earlier_dup            # [s]

    # Merged rank of each valid send: occupied net rows strictly below it
    # plus valid sends strictly below it (ties impossible after dedup —
    # tie-break among equal-key sends never fires, but keep the j<i term
    # for full determinism anyway).
    net_below = jnp.sum((~sn_less & ~sn_eq) & net_occ[None, :], axis=1)
    ss_less = _row_less(sends[:, None, :], sends[None, :, :])  # [s,s] i<j?
    sends_below = jnp.sum(
        (ss_less.T | (ss_eq & earlier)) & valid[None, :], axis=1)
    dst_send = net_below + sends_below                    # [s]

    # Net row j lands at j + shift_j (valid sends below push it right);
    # place via S+1 static shifted slices: out[k] = net[k-c] when
    # shift[k-c] == c and net[k-c] occupied.
    shift = jnp.sum(sn_less & valid[:, None], axis=0)      # [cap]
    pad_rows = jnp.full((s, w), SENTINEL, net.dtype)
    pnet = jnp.concatenate([pad_rows, net])                # [s+cap, w]
    pshift = jnp.concatenate([jnp.full((s,), -1, shift.dtype), shift])
    pocc = jnp.concatenate([jnp.zeros((s,), bool), net_occ])
    out = jnp.zeros((cap, w), net.dtype)
    any_hit = jnp.zeros((cap,), bool)
    for c in range(s + 1):
        lo = s - c
        hit = (pshift[lo:lo + cap] == c) & pocc[lo:lo + cap]
        out = out + jnp.where(hit[:, None], pnet[lo:lo + cap], 0)
        any_hit = any_hit | hit
    # Send placement: [cap, s] one-hot select-reduce (S is small).
    k = jnp.arange(cap)
    hit_send = valid[None, :] & (dst_send[None, :] == k[:, None])  # [cap,s]
    # Masked select-reduce, not an int32 einsum: integer-multiply
    # dot_general lowers to slow VPU loops, while where+sum fuses.
    out = out + jnp.sum(
        jnp.where(hit_send[:, :, None], sends[None, :, :], 0), axis=1)
    any_hit = any_hit | jnp.any(hit_send, axis=1)
    out = jnp.where(any_hit[:, None], out, SENTINEL)
    total = (jnp.sum(net_occ) + jnp.sum(valid)).astype(jnp.int32)
    overflow = jnp.maximum(total - cap, 0).astype(jnp.int32)
    return out, overflow


def compact_rows_batched(rowsT: jnp.ndarray,
                         budget: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched, TRANSPOSED :func:`compact_rows`: ``rowsT`` is
    [R, W, P] (pairs on the MINOR axis — full 128-lane VPU utilisation;
    the per-pair vmapped form left 7/8 of every vector op idle, the
    round-3 measured pathology) -> ([budget, W, P], overflow [P])."""
    r, w, pp = rowsT.shape
    occ = rowsT[:, 0, :] != SENTINEL                 # [R, P]
    pos = jnp.cumsum(occ, axis=0) - 1                # [R, P]
    outs = []
    hits = []
    for b in range(budget):
        hit = occ & (pos == b)                       # [R, P]
        outs.append(jnp.sum(jnp.where(hit[:, None, :], rowsT, 0), axis=0))
        hits.append(jnp.any(hit, axis=0))
    out = jnp.stack(outs)                            # [budget, W, P]
    has = jnp.stack(hits)                            # [budget, P]
    out = jnp.where(has[:, None, :], out, SENTINEL)
    overflow = jnp.sum(occ & (pos >= budget), axis=0).astype(jnp.int32)
    return out, overflow


def insert_messages_batched(netT: jnp.ndarray, sendsT: jnp.ndarray
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched, TRANSPOSED :func:`insert_messages`: ``netT`` [CAP, MW, P]
    (canonical per pair), ``sendsT`` [S, MW, P] (compacted) ->
    (merged [CAP, MW, P], overflow [P]).

    Same math as the per-pair form — lexicographic ranks, S+1 static
    shifted slices for net placement, one-hot send placement — but every
    op is [CAP, P] or [S, S, P] with pairs riding the minor (lane) axis.
    Measured on the v5e: the per-pair form's [S, CAP, MW]-shaped compare
    ran ~30x slower than this layout purely from lane waste (MW = 8 of
    128 lanes)."""
    cap, mw, pp = netT.shape
    s = sendsT.shape[0]
    net_occ = netT[:, 0, :] != SENTINEL              # [CAP, P]
    send_occ = sendsT[:, 0, :] != SENTINEL           # [S, P]

    # send_i vs net_j lexicographic, one send at a time: [CAP, P] lanes.
    sn_less_l, sn_eq_l = [], []
    for si in range(s):
        lt = jnp.zeros((cap, pp), bool)
        eqp = jnp.ones((cap, pp), bool)
        for l in range(mw):
            nv = netT[:, l, :]
            sv = sendsT[si, l, :][None, :]
            lt = lt | (eqp & (sv < nv))
            eqp = eqp & (sv == nv)
        sn_less_l.append(lt)
        sn_eq_l.append(eqp)
    sn_less = jnp.stack(sn_less_l)                   # [S, CAP, P]
    sn_eq = jnp.stack(sn_eq_l)
    dup_net = jnp.any(sn_eq & net_occ[None], axis=1)  # [S, P]

    # send_i vs send_j lexicographic: [S, S, P].
    lt = jnp.zeros((s, s, pp), bool)
    eqp = jnp.ones((s, s, pp), bool)
    for l in range(mw):
        a = sendsT[:, None, l, :]
        b = sendsT[None, :, l, :]
        lt = lt | (eqp & (a < b))
        eqp = eqp & (a == b)
    ss_less, ss_eq = lt, eqp
    earlier = jnp.tril(jnp.ones((s, s), bool), k=-1)[:, :, None]
    earlier_dup = jnp.any(ss_eq & earlier & send_occ[None, :, :], axis=1)
    valid = send_occ & ~dup_net & ~earlier_dup       # [S, P]

    net_below = jnp.sum(~sn_less & ~sn_eq & net_occ[None], axis=1)
    sends_below = jnp.sum(
        (jnp.swapaxes(ss_less, 0, 1) | (ss_eq & earlier))
        & valid[None, :, :], axis=1)
    dst_send = net_below + sends_below               # [S, P]
    shift = jnp.sum(sn_less & valid[:, None, :], axis=0)   # [CAP, P]

    pad_rows = jnp.full((s, mw, pp), SENTINEL, netT.dtype)
    pnet = jnp.concatenate([pad_rows, netT])         # [S+CAP, MW, P]
    pshift = jnp.concatenate([jnp.full((s, pp), -1, shift.dtype), shift])
    pocc = jnp.concatenate([jnp.zeros((s, pp), bool), net_occ])
    out = jnp.zeros((cap, mw, pp), netT.dtype)
    any_hit = jnp.zeros((cap, pp), bool)
    for c in range(s + 1):
        lo = s - c
        hit = (pshift[lo:lo + cap] == c) & pocc[lo:lo + cap]   # [CAP, P]
        out = out + jnp.where(hit[:, None, :], pnet[lo:lo + cap], 0)
        any_hit = any_hit | hit
    k = jnp.arange(cap)[:, None]
    for si in range(s):
        hit = valid[si][None, :] & (dst_send[si][None, :] == k)
        out = out + jnp.where(hit[:, None, :], sendsT[si][None], 0)
        any_hit = any_hit | hit
    out = jnp.where(any_hit[:, None, :], out, SENTINEL)
    total = (jnp.sum(net_occ, axis=0) + jnp.sum(valid, axis=0)
             ).astype(jnp.int32)
    overflow = jnp.maximum(total - cap, 0).astype(jnp.int32)
    return out, overflow


def timer_deliverable_mask(queue: jnp.ndarray) -> jnp.ndarray:
    """[T_CAP, TW] -> [T_CAP] bool: the TimerQueue partial order
    (TimerQueue.java:66-105).  Lane 1 = min, lane 2 = max; empty rows are
    SENTINEL.  deliverable[i] = occupied[i] and min[i] < min(max[j] for
    occupied j < i) (strictly: NOT exists earlier t' with t.min >= t'.max)."""
    occupied = queue[:, 0] != SENTINEL
    maxes = jnp.where(occupied, queue[:, 2], SENTINEL)
    prefix_min = jnp.concatenate([
        jnp.array([SENTINEL], dtype=maxes.dtype),
        jax.lax.cummin(maxes)[:-1]])
    return occupied & (queue[:, 1] < prefix_min)


def remove_timer(queue: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Remove the timer at position idx, shifting later entries left
    (insertion order is semantic — it drives the partial order).
    Static shift-select: the shifted copy is a constant-offset slice, the
    blend a positional mask — no dynamic gather."""
    cap = queue.shape[0]
    pos = jnp.arange(cap)
    shifted = jnp.concatenate([
        queue[1:], jnp.full((1, queue.shape[1]), SENTINEL, queue.dtype)])
    return jnp.where((pos >= idx)[:, None], shifted, queue)


def append_timers(timers: jnp.ndarray,
                  new_timers: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Append [MAX_SETS, 1+TW] records (lane 0 = node idx) to the per-node
    queues [NN, T_CAP, TW], preserving insertion order.  Returns
    ``(timers', dropped)`` — a full queue drops the append (insertion order
    is semantic, clobbering would corrupt the partial order) and the drop
    count is surfaced loudly by the engine.

    Occupied rows form a prefix of each queue (appends land at the count,
    removals shift left), so every append's slot is computable up front:
    queue occupancy + number of earlier appends to the same node.  The
    writes land via a one-hot 0/1 einsum over the (node, slot) grid —
    static indexing only (dynamic scatters under the engine's flat vmap
    lowered to ~1 GB/s code on TPU, the round-2 bottleneck; distinct
    records land on distinct slots, so the products sum exactly)."""
    nn, cap, tw = timers.shape
    s = new_timers.shape[0]
    node = new_timers[:, 0]
    valid = node != SENTINEL
    node_c = jnp.where(valid, node, 0).astype(jnp.int32).clip(0, nn - 1)
    counts = jnp.sum(timers[:, :, 0] != SENTINEL, axis=1)   # [NN]
    earlier_same = (jnp.tril(jnp.ones((s, s), bool), k=-1)
                    & (node[None, :] == node[:, None]) & valid[None, :])
    offset = jnp.sum(earlier_same, axis=1)
    # counts[node_c] as a one-hot sum (static): [s, nn] @ [nn]
    node_oh = jnp.arange(nn)[None, :] == node_c[:, None]    # [s, nn]
    slot = jnp.sum(node_oh * counts[None, :], axis=1) + offset
    ok = valid & (slot < cap)
    dropped = jnp.sum(valid & ~ok).astype(jnp.int32)
    slot_oh = jnp.arange(cap)[None, :] == slot[:, None]     # [s, cap]
    write = (node_oh[:, :, None] & slot_oh[:, None, :]
             & ok[:, None, None])                           # [s, nn, cap]
    # Masked select-reduce, not an int32 einsum (see insert_messages).
    contrib = jnp.sum(
        jnp.where(write[:, :, :, None], new_timers[:, None, None, 1:], 0),
        axis=0)                                             # [nn, cap, tw]
    hit = jnp.any(write, axis=0)                            # [nn, cap]
    return jnp.where(hit[:, :, None], contrib, timers), dropped


def _normalize_step(out):
    """Protocol step fns may return 3-tuple (no exception lane) or 4-tuple
    with a trailing int32 exception code."""
    if len(out) == 3:
        nodes2, sends, new_t = out
        return nodes2, sends, new_t, jnp.int32(0)
    nodes2, sends, new_t, exc = out
    return nodes2, sends, new_t, jnp.asarray(exc, jnp.int32)


# ------------------------------------------------------------------- engine

class TensorSearch:
    """Single-device BFS driver.  One jitted program expands a frontier
    chunk into successors (vmapped transition + canonicalisation +
    128-bit fingerprints + in-chunk sort-unique + predicate flags); the
    host loop handles level accounting, visited merging, and termination."""

    def __init__(self, protocol: TensorProtocol,
                 frontier_cap: int = 1 << 16,
                 chunk: int = 1 << 12,
                 max_depth: Optional[int] = None,
                 max_secs: Optional[float] = None,
                 record_trace: bool = False,
                 in_chunk_dedup: bool = True,
                 ev_budget: Optional[int] = None,
                 visited_cap: int = 1 << 20,
                 strict: bool = True,
                 use_host_visited: bool = False,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 0,
                 spill=None,
                 telemetry=None,
                 packed: Optional[bool] = None,
                 symmetry: Optional[bool] = None):
        self.p = protocol
        # Unified telemetry (tpu/telemetry.py): when attached — here or
        # via ``Telemetry.attach(search)`` — every ``_dispatch`` call
        # becomes a flight-recorder span and the per-level fused-stats
        # scalars feed the metrics registry.  Strictly host-side: zero
        # extra device dispatches or transfers (the overhead-guard
        # test pins this).
        self._telemetry = telemetry
        # Host-RAM spill tier (tpu/spill.py, docs/capacity.md): when
        # enabled, a full visited table EVICTS to a host fingerprint
        # set (and would-be frontier drops take a host spool detour)
        # instead of raising CapacityOverflow — strict searches stay
        # exact, just slower.  ``spill`` is False/None (off; env
        # DSLABS_SPILL=1 flips the default), True, or a
        # spill.SpillConfig.  Off by default: the overflow contract
        # (strict raises) is load-bearing for existing callers; the
        # supervisor's capacity ladder opts in on their behalf.
        from dslabs_tpu.tpu import spill as spill_mod

        if spill is None:
            spill = spill_mod.spill_env_default()
        if isinstance(spill, spill_mod.SpillConfig):
            self._spill = spill_mod.SpillManager(spill)
        elif spill:
            self._spill = spill_mod.SpillManager()
        else:
            self._spill = None
        if self._spill is not None and record_trace:
            raise ValueError(
                "spill + record_trace is unsupported (trace spills are "
                "host-side already; run the trace pass uncapped)")
        # Unified checkpoint/resume (tpu/checkpoint.py): every
        # ``checkpoint_every`` completed waves the live search state —
        # occupied frontier rows + occupied visited-table lines +
        # counters + depth — is snapshotted host-side and drained to
        # ``checkpoint_path`` (atomic .npz) by a background thread;
        # ``run(resume=True)`` continues a killed search from the last
        # dump with identical verdict and unique count.  The dump format
        # is ENGINE-AGNOSTIC — the device-resident wave loop, the host
        # parity loop, and the sharded driver all read the same file
        # (the supervisor's failover ladder depends on that).  0 = off.
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self._resumed_from_depth = 0
        # Persistent XLA compile cache (tpu/compile_cache.py): one
        # fixed place (JAX_COMPILATION_CACHE_DIR, else
        # <checkout>/.jax_cache), so the second construction of any
        # config — in this process or the next — pays near-zero compile.
        from dslabs_tpu.tpu import compile_cache

        compile_cache.setup()
        self.frontier_cap = frontier_cap
        self.chunk = chunk
        self.max_depth = max_depth
        self.max_secs = max_secs
        self.record_trace = record_trace
        # Device-resident dedup (run()): capacity of the open-addressing
        # visited table (power of two; ~16 bytes/slot) and the overflow
        # policy — strict raises on a table-full (unique counts must be
        # exact), non-strict degrades to treat-as-fresh and reports the
        # count via SearchOutcome.visited_overflow.  use_host_visited
        # forces the legacy host sorted_member loop (the parity oracle).
        visited_mod.check_cap(visited_cap)
        self.visited_cap = visited_cap
        self.strict = strict
        self.use_host_visited = use_host_visited
        # Occupancy-compacted event enumeration: expand only each state's
        # VALID events (occupied messages + deliverable timers), packed
        # into per-KIND pair-slot tables — message pairs run only the
        # message machinery and timer pairs only the timer machinery (the
        # round-2 select-both design computed BOTH branches for every
        # pair).  ``ev_budget``: None = full grid per kind (always safe);
        # int b = message slots capped at b, timer slots full; tuple
        # (bm, bt) caps both — the benchmark's deep cells run (40, 8):
        # sized on Paxos' 64 + 30 grid, which it never overflows; lab
        # 4's three-server groups hold 13 live timers at the root and
        # overflow its 8 timer slots in every chunk.  A state with more
        # valid events of a kind than the kind's budget is never cut
        # short where the driver spills (device loop, sharded
        # ``ev_spill``): its chunk is stepped again at the next window —
        # a whole chunk step more (tables, fingerprints, pack, route,
        # insert at the full successor shape), less the handlers and
        # merge of a kind whose window is then empty (_expand_chunk
        # branches on each kind's table: _kinds_live).  Without spill it
        # overflows LOUDLY (host loop: CapacityOverflow; sharded strict:
        # same; sharded beam: counted in SearchOutcome.dropped —
        # coverage truncation, same class as a frontier-cap drop).
        tgrid = protocol.n_nodes * protocol.timer_cap
        if ev_budget is None:
            bm, bt = protocol.net_cap, tgrid
        elif isinstance(ev_budget, tuple):
            bm, bt = ev_budget
        else:
            bm, bt = ev_budget, tgrid
        self._ev_msg = min(bm, protocol.net_cap)
        self._ev_tmr = min(bt, tgrid)
        # Fault event segment (ISSUE 19, tpu/faults.py): always the FULL
        # fault grid — fault grids are small (2 + 2*crashable + the
        # drop/dup slots), never budget-windowed, so re-step spill
        # passes (ev_pass > 0) see an empty fault table via the
        # _compact_ids offset logic rather than a shifted window.
        self._ev_flt = (protocol.fault.n_events
                        if protocol.fault is not None else 0)
        self._ev_slots = self._ev_msg + self._ev_tmr + self._ev_flt
        # When False, _expand_chunk marks every valid successor unique and
        # dedup is entirely the caller's job — only meaningful for drivers
        # with their own dedup authority (the sharded engine's owner-side
        # hash table); the base run() loop REQUIRES the prefilter.
        self._in_chunk_dedup = in_chunk_dedup
        # Flat-row layout: states travel as [*, lanes] int32 rows (nodes
        # ++ net ++ timers ++ exc) everywhere past initial_state() — the
        # round-3 bisect showed the expand is HBM-bound, and the old
        # dict-of-pieces representation materialised every successor
        # twice (once as the pytree, once flattened for hashing).
        p = protocol
        self._off = (p.node_width,
                     p.node_width + p.net_cap * p.msg_width,
                     p.node_width + p.net_cap * p.msg_width
                     + p.n_nodes * p.timer_cap * p.timer_width)
        self.lanes = self._off[2] + 1
        # Bit-packed frontier encoding (ISSUE 15a, tpu/packing.py): ON
        # by default — protocols with no declared domains derive the
        # IDENTITY descriptor (self._pk stays None, traced programs
        # unchanged), so only spec-compiled twins with bounds actually
        # pack.  The device wave loop stores cur/nxt (and the spill
        # spool + checkpoints) at ``self.plane`` words/row; handlers,
        # predicates, and fingerprints always see the unpacked int32
        # view, decoded in-register at expand time — bit-identical
        # unique/explored/verdict to the unpacked path by construction.
        from dslabs_tpu.tpu import packing as packing_mod

        if packed is None:
            packed = os.environ.get(
                "DSLABS_PACKED", "1").strip().lower() not in (
                "0", "off", "false", "no")
        pk = (packing_mod.derive_packing(protocol, self.lanes)
              if packed else None)
        self._pk = None if (pk is None or pk.identity) else pk
        self.plane = (self._pk.words if self._pk is not None
                      else self.lanes)
        # Symmetry reduction (ISSUE 15b, tpu/symmetry.py): OPT-IN and
        # default OFF — canonical unique counts differ from raw counts
        # by design, so the pinned lab counts stay untouched unless a
        # caller asks.  When on, every fingerprint site (expand, root,
        # spill keys — and through _expand_chunk, the sharded
        # owner-hash) hashes the canonical orbit representative.
        if symmetry is None:
            symmetry = os.environ.get(
                "DSLABS_SYMMETRY", "").strip().lower() in (
                "1", "on", "true", "yes")
        if symmetry:
            if protocol.symmetry is None:
                raise ValueError(
                    f"{protocol.name}: symmetry=True but the protocol "
                    "declares no symmetry groups (ProtocolSpec("
                    "symmetry=...))")
            from dslabs_tpu.tpu.symmetry import build_canonicalizer

            self._canon = build_canonicalizer(protocol, self._off)
        else:
            self._canon = None
        # Per-level (parent row, event id) spill for trace reconstruction
        # (SURVEY §8.1; SearchState.java:361-474). Populated by run() when
        # record_trace is set; consumed by tpu/trace.py.
        self._levels: List[dict] = []
        # Fault-event counters accumulated per run (ISSUE 19): numpy
        # [4] = partition / crash / drop / dup valid successor events
        # (counted like states_explored); stamped onto the outcome by
        # _stamp_faults.  Always zeros when protocol.fault is None.
        self._fault_counts = np.zeros((4,), np.int64)
        self._expand = jax.jit(self._expand_chunk)
        # Terminal-flag order = checkState order (Search.java:162-231):
        # exception strictly first, then invariants, then goals.  Shared
        # by the device-resident wave loop and the sharded driver.
        self._flag_names = (["exc"]
                            + [f"inv:{n}" for n in protocol.invariants]
                            + [f"goal:{n}" for n in protocol.goals])
        # Jitted device-loop programs, keyed by frontier-buffer capacity
        # (the buffer grows geometrically on overflow — see _run_device).
        self._dev_progs: Dict[int, tuple] = {}
        # Soundness sanitizer (ISSUE 10): DSLABS_SANITIZE=1 statically
        # audits this engine's dispatch-site programs at build time.
        # Subclasses call _maybe_sanitize at the END of their own
        # __init__ (their programs are not built yet here).
        if type(self) is TensorSearch:
            self._maybe_sanitize()

    # ------------------------------------------------------------- plumbing

    def _maybe_sanitize(self) -> None:
        """DSLABS_SANITIZE build-time hook (dslabs_tpu/analysis): off
        means off — one env read, zero imports, zero dispatches (the
        overhead-guard test pins it).  On, the jaxpr auditor lowers
        every site program and records findings as telemetry events."""
        if os.environ.get("DSLABS_SANITIZE", "").strip().lower() in (
                "", "0", "off", "false", "no"):
            return
        from dslabs_tpu.analysis.jaxpr_audit import sanitize_engine

        sanitize_engine(self)

    def _store_shape(self) -> tuple:
        """What this engine's constructor arguments contribute to the
        shape of the programs it traces (subclasses add theirs): the
        engine's part of an executable-store key."""
        return (type(self).__qualname__, self.chunk, self.frontier_cap,
                self.visited_cap, self.lanes, self.plane, self._off,
                self._ev_msg, self._ev_tmr, self._ev_flt, self.strict,
                self.record_trace, self._in_chunk_dedup,
                self.use_host_visited, self._canon is not None,
                repr(self._spill.config) if self._spill is not None
                else None, tuple(self._flag_names))

    def _store_devices(self) -> list:
        """The devices this engine's programs are compiled for, in
        their order: where the store loads an executable onto."""
        return jax.devices()[:1]

    def store_key(self) -> Optional[str]:
        """The key under which the executable store
        (tpu/compile_cache.py) keeps this engine's programs, less each
        program's own part (``compile_cache.program_key`` adds its name
        and abstract arguments): a digest of everything they are traced
        from — the process's environment (``environment_key``) with
        every ``DSLABS_*`` variable (the constructors read several), the
        protocol's structural fingerprint (service/memo.py
        ``program_fingerprint``) and :meth:`_store_shape`.  None — the
        engine then traces as it always did, and nothing is stored —
        where the fingerprint is weak (a closure hashed by type), where
        a handler's source is no file, or where building the key fails
        at all."""
        from dslabs_tpu.tpu import compile_cache

        with tel_mod.phase("compile.store.key"):
            try:
                from dslabs_tpu.service.memo import program_fingerprint

                fp = program_fingerprint(self.p)
                env = (None if fp["weak"] else
                       compile_cache.environment_key(
                           self._store_devices(), fp["files"]))
                if env is None:
                    return None
                knobs = tuple(sorted(
                    kv for kv in os.environ.items()
                    if kv[0].startswith("DSLABS_")))
                return hashlib.sha256(repr(
                    (env, knobs, fp["fp"], self._store_shape())
                ).encode()).hexdigest()
            except Exception:  # noqa: BLE001 — no key, no store
                return None

    def dispatch_site_programs(self) -> Dict[str, dict]:
        """The site-program registry for the sanitizer's jaxpr auditor
        (ISSUE 10): every lowered program this engine dispatches
        through :meth:`_dispatch`, keyed by its dispatch tag (the same
        tags telemetry.DISPATCH_SITES enumerates), with example
        abstract args, the declared donation, and a ``builder`` that
        re-derives the program for the retrace-hazard check.  Pure
        host work: programs are jit-wrapped (already cached) and args
        are ShapeDtypeStructs — nothing here traces, compiles, or
        touches a device."""
        C = self.chunk
        cap = -(-self.frontier_cap // C) * C        # run()'s user_cap
        step, promote, init = self._dev_programs(cap)
        row_sds = jax.ShapeDtypeStruct((1, self.lanes), jnp.int32)
        carry_sds = jax.eval_shape(init, row_sds)
        rt = getattr(self, "_rt_masks", None)
        sites = {
            "device.init": dict(
                fn=init, args=(row_sds,), donate=(), multi=False,
                builder=lambda: jax.jit(self._build_dev_init(cap))),
            "device.step": dict(
                fn=step, args=(carry_sds, rt), donate=(0,),
                multi=False,
                builder=lambda: jax.jit(self._build_dev_step(cap),
                                        donate_argnums=0)),
            "device.promote": dict(
                fn=promote, args=(carry_sds,), donate=(0,),
                multi=False,
                builder=lambda: jax.jit(self._build_dev_promote(cap),
                                        donate_argnums=0)),
        }
        if self._spill is not None:
            progs = self._spill_progs(cap)
            sites["device.spill_drain"] = dict(
                fn=progs["reset"], args=(carry_sds,), donate=(0,),
                multi=False, builder=None)
            sites["device.spill_evict"] = dict(
                fn=progs["evict"], args=(carry_sds,), donate=(0,),
                multi=False, builder=None)
        # The bucket-probe kernel (ISSUE 12): the ACTIVE
        # visited.insert variant (Pallas/jnp per DSLABS_VISITED_PALLAS)
        # standalone over one wave's successor batch, so the auditor
        # and profiler cover the kernel itself.
        sites["visited.insert"] = visited_mod.dispatch_site_program(
            self.visited_cap, C * self._num_events())
        # Capacity round 2 (ISSUE 15): the pack/unpack codecs and the
        # symmetry canonicalize pass are fused INTO the step programs
        # above, but register standalone too (like visited.insert) so
        # the jaxpr auditor (J0-J5) and the profiler's hot-site table
        # cover the codec lowerings themselves.
        if self._pk is not None:
            pk = self._pk
            rows_sds = jax.ShapeDtypeStruct((C, self.lanes), jnp.int32)
            packed_sds = jax.ShapeDtypeStruct((C, self.plane),
                                              jnp.int32)
            sites["packing.pack"] = dict(
                fn=jax.jit(pk.pack_jnp), args=(rows_sds,), donate=(),
                multi=False, builder=lambda: jax.jit(pk.pack_jnp))
            sites["packing.unpack"] = dict(
                fn=jax.jit(pk.unpack_jnp), args=(packed_sds,),
                donate=(), multi=False,
                builder=lambda: jax.jit(pk.unpack_jnp))
        if self._canon is not None:
            rows_sds = jax.ShapeDtypeStruct((C, self.lanes), jnp.int32)
            sites["symmetry.canonicalize"] = dict(
                fn=jax.jit(self._canon), args=(rows_sds,), donate=(),
                multi=False, builder=lambda: jax.jit(self._canon))
        return sites

    def _dispatch(self, tag: str, fn, *args):
        """THE device-dispatch boundary: every hot-loop dispatch and
        blocking readback in this engine (and the sharded subclass)
        funnels through here.  With no hook installed it is a plain
        call; the search supervisor (tpu/supervisor.py) installs its
        retry/watchdog/fault-injection boundary as ``_dispatch_hook``.
        Tags are ``"<engine>.<site>"`` — the engine half keys the
        supervisor's fault plan and per-rung counters.  An attached
        telemetry recorder (tpu/telemetry.py) wraps the WHOLE chain —
        hook included — so every dispatch becomes one structured span
        with zero extra device work.  Recorder or not, the dispatch is
        a ``dslabs:dispatch.<site>`` annotation in any profile being
        taken (tpu/telemetry.py ``annotate``: nothing otherwise)."""
        hook = getattr(self, "_dispatch_hook", None)
        tel = getattr(self, "_telemetry", None)
        with self._dispatch_note(tag):
            if tel is not None:
                return tel.record_dispatch(self, tag, hook, fn, *args)
            if hook is None:
                return fn(*args)
            return hook(tag, fn, *args)

    def _dispatch_note(self, tag: str):
        """The ``dslabs:dispatch.<site>`` annotation of this search's
        next dispatch: which one it is, and the level it belongs to."""
        i = self._dispatch_i = getattr(self, "_dispatch_i", -1) + 1
        return tel_mod.annotate(
            "dispatch." + tag.partition(".")[2], i=i,
            depth=int(getattr(self, "_current_depth", 0) or 0))

    def lane_signature(self) -> Optional[str]:
        """The batched-lane packing key (ISSUE 14, tpu/lanes.py): two
        searches may share a lane-stacked program iff this string
        matches — the checkpoint config fingerprint (protocol lane
        widths + strict) plus every knob that shapes the compiled
        step/promote programs.  ``None`` means the engine is not
        lane-packable (the sharded subclass opts out — its superstep
        is already a whole-mesh program)."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        return "|".join([
            ckpt_mod.config_fingerprint(self.p, self.strict,
                                        self.record_trace),
            f"chunk={self.chunk}", f"fcap={self.frontier_cap}",
            f"vcap={self.visited_cap}",
            f"ev={self._ev_msg},{self._ev_tmr}",
            f"enc={self._frontier_encoding()}",
            f"sym={self.p.symmetry.n_perms if self._canon is not None else 0}"])

    def _cancelled(self) -> bool:
        """Portfolio-lane cancellation (tpu/supervisor.py portfolio
        mode): when the OTHER lane lands a terminal verdict first, the
        supervisor sets this event and every run loop returns a
        TIME_EXHAUSTED-shaped outcome (marked ``cancelled``) at its
        next boundary instead of burning the rest of its budget."""
        ev = getattr(self, "_cancel_event", None)
        return ev is not None and ev.is_set()

    # -------------------------------------------------------- checkpointing

    def _ckpt_fingerprint(self) -> str:
        """The config identity a dump must share to be resumable here
        (engine-agnostic by design — see tpu/checkpoint.py).  The
        symmetry-reduction flag participates: canonical unique counts
        describe the QUOTIENT space, so a reduced dump must never
        silently resume an unreduced search (or vice versa)."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        return ckpt_mod.config_fingerprint(
            self.p, self.strict, self.record_trace,
            symmetry=(self.p.symmetry.n_perms
                      if self._canon is not None else 0))

    def has_resumable_checkpoint(self) -> bool:
        """Existence + fingerprint check WITHOUT loading the arrays."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        if not self.checkpoint_path:
            return False
        fp = ckpt_mod.peek_fingerprint(self.checkpoint_path)
        return fp is not None and fp == self._ckpt_fingerprint()

    def _load_ckpt(self):
        """Load + verify the dump; ``None`` when no file exists, a loud
        CheckpointMismatch when it belongs to a different config.  The
        returned checkpoint's frontier is ALWAYS normalized to raw
        (unpacked) rows — packed dumps decode here (loudly when the
        live engine itself is unpacked), so every consumer (device
        carry, host loop, spill spool, lanes) converts from one
        canonical form."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        if not self.checkpoint_path:
            return None
        ck = ckpt_mod.load(self.checkpoint_path, self._ckpt_fingerprint())
        if ck is not None:
            self._resumed_from_depth = ck.depth
            self._normalize_ckpt_frontier(ck)
        return ck

    def _normalize_ckpt_frontier(self, ck) -> None:
        """Decode a dump's frontier rows to raw int32 lanes per its
        ``frontier_encoding`` marker (ISSUE 15a).  Cross-encoding
        resume is a LOUD conversion; an encoding this protocol cannot
        derive (foreign domain declarations) is a loud refusal —
        never a silent reinterpretation of packed bytes as lanes."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod
        from dslabs_tpu.tpu import packing as packing_mod

        enc = "raw"
        if ck.extra and "frontier_encoding" in ck.extra:
            enc = np.asarray(ck.extra["frontier_encoding"]
                             ).item()
            if isinstance(enc, bytes):
                enc = enc.decode()
            ck.extra = {k: v for k, v in ck.extra.items()
                        if k != "frontier_encoding"} or None
        if enc == "raw":
            if len(ck.frontier) and ck.frontier.shape[1] != self.lanes:
                raise ckpt_mod.CheckpointMismatch(
                    f"checkpoint frontier rows are "
                    f"{ck.frontier.shape[1]} lanes wide, this "
                    f"protocol's are {self.lanes} — foreign dump")
            return
        pk = self._pk or packing_mod.derive_packing(self.p, self.lanes)
        if pk.identity or pk.signature() != enc:
            raise ckpt_mod.CheckpointMismatch(
                f"refusing to resume packed checkpoint: frontier "
                f"encoding {enc!r} does not match this protocol's "
                f"derived descriptor "
                f"{pk.signature() if not pk.identity else 'raw'!r} "
                "(domain declarations changed, or the dump belongs to "
                "a different spec) — delete the file or restore the "
                "declarations")
        if self._pk is None:
            import warnings

            warnings.warn(
                f"{self.p.name}: resuming a PACKED checkpoint "
                f"({enc}) on an unpacked engine — converting the "
                f"frontier rows (loud by contract, never silent)",
                RuntimeWarning, stacklevel=3)
        # Delta-lane dumps (ISSUE 18 leg (b)) carry the level base the
        # rows were packed against; a delta descriptor without one is
        # a corrupt/foreign dump, refused loudly.
        base = None
        if ck.extra and "pack_base" in ck.extra:
            base = np.asarray(ck.extra["pack_base"],
                              np.int32).reshape(-1)
            ck.extra = {k: v for k, v in ck.extra.items()
                        if k != "pack_base"} or None
        if pk.has_delta and base is None:
            raise ckpt_mod.CheckpointMismatch(
                f"packed checkpoint {enc!r} uses delta lanes but "
                "carries no pack_base vector — corrupt or foreign "
                "dump, refusing to guess a bias")
        ck.frontier = pk.unpack_np(ck.frontier, base) \
            if len(ck.frontier) \
            else np.zeros((0, self.lanes), np.int32)

    @property
    def _ckpt_writer(self):
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        w = getattr(self, "_ckpt_writer_obj", None)
        if w is None:
            w = self._ckpt_writer_obj = ckpt_mod.AsyncCheckpointWriter()
        return w

    def _kick_ckpt(self, frontier: np.ndarray, visited_keys: np.ndarray,
                   depth: int, explored: int, elapsed: float,
                   vis_over: int = 0) -> None:
        """Queue one async atomic dump (skip-if-busy, never a queue);
        arrays must already be host copies.  Frontier rows are in the
        engine's NATIVE encoding (packed when the spec declares
        domains) — the marker rides the dump so any engine can
        convert on resume (loud, never silent)."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        extra = None
        if self._pk is not None:
            extra = {"frontier_encoding": np.bytes_(
                self._frontier_encoding().encode())}
        ck = ckpt_mod.SearchCheckpoint(
            fingerprint=self._ckpt_fingerprint(), depth=depth,
            explored=explored, elapsed=elapsed, frontier=frontier,
            visited_keys=visited_keys, vis_over=vis_over, extra=extra)
        self._ckpt_writer.kick(
            lambda: ckpt_mod.save(self.checkpoint_path, ck))

    def initial_state(self) -> dict:
        """The twin's initial state, a batch-1 pytree on the device.  It
        is a constant of the protocol the engine was built with, so it
        is built ONCE an engine (a ``CapacityOverflow`` of the initial
        timers raises on that first build) and handed out again."""
        state = getattr(self, "_initial", None)
        if state is None:
            state = self._initial = self._build_initial_state()
        return dict(state)

    def _build_initial_state(self) -> dict:
        p = self.p
        nodes = jnp.asarray(p.init_nodes(), jnp.int32)[None]
        net = jnp.full((1, p.net_cap, p.msg_width), SENTINEL, jnp.int32)
        init_msgs = np.asarray(p.init_messages(), np.int32).reshape(-1, p.msg_width)
        if init_msgs.shape[0]:
            pad = np.full((p.net_cap - init_msgs.shape[0], p.msg_width),
                          SENTINEL, np.int32)
            net = jnp.asarray(np.concatenate([init_msgs, pad]))[None]
            net = jax.vmap(canonicalize_net)(net)
        timers = jnp.full((1, p.n_nodes, p.timer_cap, p.timer_width),
                          SENTINEL, jnp.int32)
        init_tmrs = np.asarray(p.init_timers(), np.int32)
        if init_tmrs.size:
            timers, dropped = jax.vmap(append_timers)(
                timers, jnp.asarray(init_tmrs, jnp.int32)[None])
            if int(dropped.sum()):
                raise CapacityOverflow(
                    f"{self.p.name}: initial timers overflow timer_cap="
                    f"{p.timer_cap}")
        return {"nodes": nodes, "net": net, "timers": timers,
                "exc": jnp.zeros((1,), jnp.int32)}

    @staticmethod
    def _grid_events(p: TensorProtocol) -> int:
        return p.net_cap + p.n_nodes * p.timer_cap

    def unflatten_rows(self, rows) -> dict:
        """[N, lanes] rows -> batched state pytree (the inverse of
        :func:`flatten_state`); slices/reshapes only, no copies."""
        p = self.p
        o0, o1, o2 = self._off
        n = rows.shape[0]
        return {
            "nodes": rows[:, :o0],
            "net": rows[:, o0:o1].reshape(n, p.net_cap, p.msg_width),
            "timers": rows[:, o1:o2].reshape(
                n, p.n_nodes, p.timer_cap, p.timer_width),
            "exc": rows[:, o2],
        }

    def _slice_state(self, row) -> dict:
        """[lanes] row -> ONE unbatched state dict (views)."""
        p = self.p
        o0, o1, o2 = self._off
        return {
            "nodes": row[:o0],
            "net": row[o0:o1].reshape(p.net_cap, p.msg_width),
            "timers": row[o1:o2].reshape(
                p.n_nodes, p.timer_cap, p.timer_width),
            "exc": row[o2],
        }

    def _num_events(self) -> int:
        """Pair slots per state in the expand program (the successor-row
        stride): the compacted budget when ev_budget is set, else the full
        event grid."""
        return self._ev_slots

    # ------------------------------------------ packing / symmetry

    def _canon_rows(self, rows):
        """Symmetry hash-step hook: the canonical orbit representative
        of each row when the reduction is on, the rows themselves
        otherwise.  ONLY fingerprints flow through here — stored
        states stay the real reachable states."""
        return rows if self._canon is None else self._canon(rows)

    def _canonical_root_fp(self, state):
        """[1, 4] fingerprints of a batch-1 state pytree through the
        SAME canonicalize-then-hash step the expand programs use."""
        from dslabs_tpu.tpu.kernels import fingerprint_rows

        return fingerprint_rows(self._canon_rows(flatten_state(state)))

    def _root_program(self):
        """ONE compiled program for all a run asks of its root, built
        once an engine: a batch-1 state pytree (host numpy or device
        arrays alike) -> ``(row0 [1, lanes] int32, fp0 [1, 4] uint32,
        inv_hits [n_inv] bool, goal_hits [n_goal] bool)``, the hits in
        ``p.invariants`` / ``p.goals`` order.  Made of the functions the
        eager helpers call (``flatten_state``, ``_canon_rows``,
        ``fingerprint_rows``, the protocol's own predicates), so the
        symmetry reduction and the hash are the expand programs' own.
        A plain jitted helper, not a dispatch site: a profile names it
        ``jit_root_program``."""
        fn = getattr(self, "_root_prog", None)
        if fn is None:
            from dslabs_tpu.tpu.kernels import fingerprint_rows

            p = self.p

            def hits(preds, state):
                if not preds:
                    return jnp.zeros((0,), bool)
                return jnp.stack([jax.vmap(f)(state)[0].astype(bool)
                                  for f in preds.values()])

            def root_program(state):
                rows = flatten_state(state)
                return (rows, fingerprint_rows(self._canon_rows(rows)),
                        hits(p.invariants, state), hits(p.goals, state))

            fn = self._root_prog = jax.jit(root_program)
        return fn

    def _pack_rows(self, rows):
        """[N, lanes] -> [N, plane] native frontier-storage encoding."""
        return rows if self._pk is None else self._pk.pack_jnp(rows)

    def _unpack_rows(self, rows):
        return rows if self._pk is None else self._pk.unpack_jnp(rows)

    def _frontier_encoding(self) -> str:
        """The marker dumped with every checkpoint's frontier rows."""
        return "raw" if self._pk is None else self._pk.signature()

    def _stamp_device(self, out: "SearchOutcome") -> "SearchOutcome":
        """Name the device this engine's programs ran on (the sharded
        and swarm engines: their mesh's first device)."""
        mesh = getattr(self, "mesh", None)
        dev = (mesh.devices.flat[0] if mesh is not None
               else jax.devices()[0])
        out.platform, out.device_kind = dev.platform, dev.device_kind
        return out

    @property
    def bytes_per_state(self) -> int:
        """Bytes of one stored frontier row: packed where a packing is
        on.  A property of the engine, not of a run: the lab entry's
        higher ladder rungs bind a wider network, whose rows pack to
        more."""
        return (self._pk.bytes_per_state if self._pk is not None
                else self.lanes * 4)

    def _stamp_capacity(self, out: "SearchOutcome") -> "SearchOutcome":
        """Attach the capacity-round-2 accounting every verdict
        carries (STATUS.json renders it)."""
        out.bytes_per_state = self.bytes_per_state
        out.bytes_per_state_unpacked = self.lanes * 4
        out.pack_ratio = round(
            out.bytes_per_state_unpacked / max(out.bytes_per_state, 1),
            3)
        out.symmetry_perms = (self.p.symmetry.n_perms
                              if self._canon is not None else 0)
        return out

    # -------------------------------------------- fault plane (ISSUE 19)
    #
    # Every method below is reached only under a trace-time
    # ``p.fault is not None`` guard: a fault-free spec lowers to the
    # byte-identical pre-fault program.  All picks are one-hot /
    # static-index, matching the step kinds' discipline.

    def _fault_down_vec(self, nodes: jnp.ndarray) -> jnp.ndarray:
        """[NN] int32 down flags of ONE state's node vector (0 for
        non-crashable nodes) — a static gather over the controller's
        ``down_*`` lanes."""
        fl = self.p.fault
        z = jnp.zeros((), jnp.int32)
        return jnp.stack([nodes[int(off)] if int(off) >= 0 else z
                          for off in fl.down_off])

    def _fault_msg_ok(self, nodes: jnp.ndarray,
                      msg: jnp.ndarray) -> jnp.ndarray:
        """Deliverability of ONE message row under ONE state's fault
        lanes: blocked while a cut separates frm/to's partition blocks,
        or while the DESTINATION is down (in-flight messages from a
        node that later crashed stay deliverable — they already left).
        Blocked messages stay in the network set, deliverable again
        after HEAL/RESTART; only the DROP event removes them."""
        fl = self.p.fault
        ok = jnp.asarray(True)
        nid = jnp.arange(fl.n_nodes)
        oh_f = nid == msg[1]
        oh_t = nid == msg[2]
        if fl.has_partition:
            blk = jnp.asarray(fl.block_id)
            bf = jnp.sum(oh_f * blk)
            bt = jnp.sum(oh_t * blk)
            cross = (bf >= 0) & (bt >= 0) & (bf != bt)
            ok = ok & ~((nodes[fl.pcut_off] > 0) & cross)
        if fl.n_crashable:
            ok = ok & (jnp.sum(oh_t * self._fault_down_vec(nodes)) == 0)
        return ok

    def _flt_step(self, row: jnp.ndarray, f_idx: jnp.ndarray):
        """Expand ONE state row by ONE fault event (index into the
        fault segment of the grid) -> (successor row, valid, over).
        Fault steps run no handlers and send nothing — they flip
        controller lanes, wipe volatile fields (CRASH) or remove one
        network row (DROP); ``over`` is always 0."""
        p = self.p
        fl = p.fault
        s = self._slice_state(row)
        nodes, net = s["nodes"], s["net"]
        ok = jnp.asarray(False)
        nodes2 = nodes
        net2 = net
        if fl.has_partition:
            is_cut = f_idx == fl.seg_cut
            is_heal = f_idx == fl.seg_heal
            pcut, eras = nodes[fl.pcut_off], nodes[fl.eras_off]
            ok = ok | (is_cut & (pcut == 0)
                       & (eras < fl.model.partition.max_eras)) \
                    | (is_heal & (pcut > 0))
            nodes2 = nodes2.at[fl.pcut_off].set(
                jnp.where(is_cut, 1,
                          jnp.where(is_heal, 0, nodes2[fl.pcut_off])))
            nodes2 = nodes2.at[fl.eras_off].add(
                jnp.where(is_cut, 1, 0))
        for k in range(fl.n_crashable):
            n = int(fl.crash_nodes[k])
            off = int(fl.down_off[n])
            is_c = f_idx == fl.seg_crash + k
            is_r = f_idx == fl.seg_restart + k
            down_n = nodes[off]
            ok = ok | (is_c & (down_n == 0)
                       & (nodes[fl.crashes_off]
                          < fl.model.crash.max_crashes)) \
                    | (is_r & (down_n > 0))
            # Volatile wipe back to declared inits; durable lanes (and
            # every other node's lanes) keep their values.
            nodes2 = jnp.where(is_c & jnp.asarray(fl.wipe[k]),
                               jnp.asarray(fl.init_vec), nodes2)
            nodes2 = nodes2.at[off].set(
                jnp.where(is_c, 1, jnp.where(is_r, 0, nodes2[off])))
            nodes2 = nodes2.at[fl.crashes_off].add(
                jnp.where(is_c, 1, 0))
        if fl.model.max_drops > 0:
            in_drop = (f_idx >= fl.seg_drop) \
                & (f_idx < fl.seg_drop + p.net_cap)
            slot = (f_idx - fl.seg_drop).clip(0, p.net_cap - 1)
            s_oh = jnp.arange(p.net_cap) == slot
            occ = jnp.sum(s_oh * (net[:, 0] != SENTINEL)) > 0
            ok = ok | (in_drop & occ
                       & (nodes[fl.drops_off] < fl.model.max_drops))
            # Static shift-left removal keeps the network set's
            # canonical sorted prefix (same pattern as remove_timer).
            net2 = jnp.where(in_drop, remove_timer(net, slot), net2)
            nodes2 = nodes2.at[fl.drops_off].add(
                jnp.where(in_drop, 1, 0))
        if fl.model.max_dups > 0:
            in_dup = f_idx >= fl.seg_dup
            slot = (f_idx - fl.seg_dup).clip(0, p.net_cap - 1)
            s_oh = jnp.arange(p.net_cap) == slot
            occ = jnp.sum(s_oh * (net[:, 0] != SENTINEL)) > 0
            # Set-semantics delivery never consumes, so a duplicate is
            # behaviorally subsumed; the explicit event binds the dup
            # budget and names the slot in witness traces.
            ok = ok | (in_dup & occ
                       & (nodes[fl.dups_off] < fl.model.max_dups))
            nodes2 = nodes2.at[fl.dups_off].add(
                jnp.where(in_dup, 1, 0))
        row2 = jnp.concatenate([
            nodes2.astype(jnp.int32), net2.reshape(-1),
            s["timers"].reshape(-1), jnp.zeros((1,), jnp.int32)])
        return row2, ok, jnp.int32(0)

    def _fault_event_grid(self, chunk_state: dict) -> jnp.ndarray:
        """[C, n_fault_events] validity grid over the fault segment —
        the fault-side analog of the msg/timer tables in
        :meth:`_event_tables`; validity conditions mirror
        :meth:`_flt_step`'s ``ok`` exactly."""
        p = self.p
        fl = p.fault
        nodesC = chunk_state["nodes"]
        c = nodesC.shape[0]
        cols = []
        if fl.has_partition:
            pcut = nodesC[:, fl.pcut_off]
            eras = nodesC[:, fl.eras_off]
            cols.append(((pcut == 0)
                         & (eras < fl.model.partition.max_eras))[:, None])
            cols.append((pcut > 0)[:, None])
        if fl.n_crashable:
            downs = jnp.stack(
                [nodesC[:, int(fl.down_off[int(n)])] > 0
                 for n in fl.crash_nodes], axis=1)       # [C, nc]
            budget = (nodesC[:, fl.crashes_off]
                      < fl.model.crash.max_crashes)[:, None]
            cols.append(~downs & budget)
            cols.append(downs)
        occ = chunk_state["net"][:, :, 0] != SENTINEL    # [C, net_cap]
        if fl.model.max_drops > 0:
            cols.append(occ & (nodesC[:, fl.drops_off]
                               < fl.model.max_drops)[:, None])
        if fl.model.max_dups > 0:
            cols.append(occ & (nodesC[:, fl.dups_off]
                               < fl.model.max_dups)[:, None])
        return (jnp.concatenate(cols, axis=1) if cols
                else jnp.zeros((c, 0), bool))

    def _fault_chunk_counts(self, event_ids, valids) -> jnp.ndarray:
        """[4] int32 partition/crash/drop/dup VALID successor events in
        one expanded chunk (traced; the device wave loop sums it into
        the carry).  ``event_ids`` [C, B] grid ids, ``valids`` [C*B]."""
        fl = self.p.fault
        base = self.p.net_cap + self.p.n_nodes * self.p.timer_cap
        ev = event_ids.reshape(-1)
        ok = valids & (ev >= base)
        f = ev - base

        def cnt(m):
            return jnp.sum(ok & m).astype(jnp.int32)

        return jnp.stack([
            cnt(f < fl.seg_crash),
            cnt((f >= fl.seg_crash) & (f < fl.seg_drop)),
            cnt((f >= fl.seg_drop) & (f < fl.seg_dup)),
            cnt(f >= fl.seg_dup)])

    def _accum_fault_counts(self, event_ids, valids) -> None:
        """Host-loop twin of :meth:`_fault_chunk_counts`: accumulate
        one chunk's fault-family counts into ``self._fault_counts``
        (numpy, no device work)."""
        fl = self.p.fault
        base = self.p.net_cap + self.p.n_nodes * self.p.timer_cap
        ev = np.asarray(event_ids).reshape(-1)
        ok = np.asarray(valids).reshape(-1) & (ev >= base)
        f = ev - base
        self._fault_counts[0] += int(np.sum(ok & (f < fl.seg_crash)))
        self._fault_counts[1] += int(np.sum(
            ok & (f >= fl.seg_crash) & (f < fl.seg_drop)))
        self._fault_counts[2] += int(np.sum(
            ok & (f >= fl.seg_drop) & (f < fl.seg_dup)))
        self._fault_counts[3] += int(np.sum(ok & (f >= fl.seg_dup)))

    def _stamp_faults(self, out: "SearchOutcome") -> "SearchOutcome":
        """Stamp the run's accumulated fault-event counters onto the
        outcome (zeros when no fault model is declared)."""
        fc = self._fault_counts
        out.partition_events = int(fc[0])
        out.crash_events = int(fc[1])
        out.drop_events = int(fc[2])
        out.dup_events = int(fc[3])
        out.fault_events = int(fc.sum())
        return out

    def _fault_block(self) -> dict:
        """The schema-pinned ``faults`` telemetry block (STATUS.json /
        level records — docs/scenarios.md): cumulative fault-event
        counts by family for the current run."""
        fc = self._fault_counts
        return {"partition_events": int(fc[0]),
                "crash_events": int(fc[1]),
                "drop_events": int(fc[2]),
                "dup_events": int(fc[3]),
                "fault_events": int(fc.sum())}

    def _msg_step_raw(self, row: jnp.ndarray, net_slot: jnp.ndarray):
        """Handler half of a message step (no network merge): ONE state
        row + net slot -> (nodes', sends, timers', exc, ok, t_over).
        All event picks are one-hot 0/1 sums — static indexing only
        (per-pair dynamic gathers materialise at ~1 GB/s under the flat
        vmap on TPU)."""
        p = self.p
        s = self._slice_state(row)
        nodes, net, timers = s["nodes"], s["net"], s["timers"]
        moh = jnp.arange(p.net_cap) == net_slot.clip(0, p.net_cap - 1)
        msg = jnp.sum(moh[:, None] * net, axis=0)
        ok = msg[0] != SENTINEL
        if p.deliver_message is not None:
            ok = ok & p.deliver_message(msg)
        if p.fault is not None:
            ok = ok & self._fault_msg_ok(nodes, msg)
        nodes2, sends, new_t, exc = _normalize_step(
            p.step_message(nodes, msg))
        timers2, t_over = append_timers(timers, new_t)
        return nodes2, sends, timers2, exc, ok, t_over

    def _tmr_step_raw(self, row: jnp.ndarray, t_idx: jnp.ndarray):
        """Handler half of a timer step (no network merge): timer grid
        index t_idx = node * timer_cap + queue slot."""
        p = self.p
        s = self._slice_state(row)
        nodes, net, timers = s["nodes"], s["net"], s["timers"]
        t_node = t_idx // p.timer_cap
        t_slot = t_idx % p.timer_cap
        n_oh = jnp.arange(p.n_nodes) == t_node               # [NN]
        s_oh = jnp.arange(p.timer_cap) == t_slot             # [T_CAP]
        queue = jnp.sum(n_oh[:, None, None] * timers, axis=0)
        ok = jnp.sum(timer_deliverable_mask(queue) * s_oh) > 0
        if p.deliver_timer is not None:
            ok = ok & p.deliver_timer(t_node)
        if p.fault is not None and p.fault.n_crashable:
            # A down node's timers are masked, not cleared — they fire
            # only after restart (a recovered node's stale timers).
            ok = ok & (jnp.sum(n_oh * self._fault_down_vec(nodes)) == 0)
        timer = jnp.sum(s_oh[:, None] * queue, axis=0)
        nodes2, sends, new_t, exc = _normalize_step(
            p.step_timer(nodes, t_node, timer))
        # Firing consumes the timer (SearchState.java:357); the updated
        # queue lands via the node one-hot, never a dynamic scatter.
        fired_q = remove_timer(queue, t_slot)
        timers1 = jnp.where(n_oh[:, None, None], fired_q[None], timers)
        timers2, t_over = append_timers(timers1, new_t)
        return nodes2, sends, timers2, exc, ok, t_over

    def _finish_row(self, net, nodes2, sends, timers2, exc, ok, t_over):
        """Per-pair merge tail (the batched expand uses the TRANSPOSED
        tail in _batched_tail; this form remains for _step_one)."""
        p = self.p
        send_over = jnp.int32(0)
        if (p.max_live_sends is not None
                and p.max_live_sends < p.max_sends):
            sends, send_over = compact_rows(sends, p.max_live_sends)
        net2, net_over = insert_messages(net, sends)
        over = (net_over + t_over + send_over) * ok.astype(jnp.int32)
        row = jnp.concatenate([
            nodes2.astype(jnp.int32), net2.reshape(-1),
            timers2.reshape(-1),
            jnp.asarray(exc, jnp.int32).reshape(1)])
        return row, ok, over
        # An exception-state successor is frozen at the throwing
        # transition: sends/new timers from the faulting handler are
        # still applied (the reference captures the throwable after the
        # hooks ran, SearchState.java:218-222), but the state is terminal
        # (run() ends).

    def _msg_step(self, row: jnp.ndarray, net_slot: jnp.ndarray):
        """ONE state row x message slot -> (successor row, valid, over)."""
        s = self._slice_state(row)
        nodes2, sends, timers2, exc, ok, t_over = self._msg_step_raw(
            row, net_slot)
        return self._finish_row(s["net"], nodes2, sends, timers2, exc,
                                ok, t_over)

    def _tmr_step(self, row: jnp.ndarray, t_idx: jnp.ndarray):
        """ONE state row x timer grid index -> (successor row, valid,
        over)."""
        s = self._slice_state(row)
        nodes2, sends, timers2, exc, ok, t_over = self._tmr_step_raw(
            row, t_idx)
        return self._finish_row(s["net"], nodes2, sends, timers2, exc,
                                ok, t_over)

    def _batched_tail(self, chunk_rows, c, b, nodes2, sendsP, timersP,
                      excP, okP, toverP):
        """Batched TRANSPOSED merge tail: pairs ride the minor axis so
        the set-insert's compare/select ops use all 128 VPU lanes (the
        vmapped per-pair tail used MW = 8 of them — measured ~30x slower
        on the v5e).  The parent network is broadcast from the CHUNK
        rows ([CAP, MW, C] -> [CAP, MW, C*B]) instead of being
        materialised per pair."""
        p = self.p
        pp = c * b
        live = (p.max_live_sends
                if (p.max_live_sends is not None
                    and p.max_live_sends < p.max_sends) else None)
        sendsT = jnp.transpose(sendsP, (1, 2, 0))        # [S, MW, P]
        send_over = jnp.zeros((pp,), jnp.int32)
        if live is not None:
            sendsT, send_over = compact_rows_batched(sendsT, live)
        o0, o1, _ = self._off
        net_rows = chunk_rows[:, o0:o1].reshape(c, p.net_cap,
                                                p.msg_width)
        netT = jnp.transpose(net_rows, (1, 2, 0))        # [CAP, MW, C]
        netT = jnp.broadcast_to(
            netT[:, :, :, None],
            (p.net_cap, p.msg_width, c, b)).reshape(
            p.net_cap, p.msg_width, pp)
        outT, net_over = insert_messages_batched(netT, sendsT)
        net_flat = jnp.transpose(outT, (2, 0, 1)).reshape(pp, -1)
        rows = jnp.concatenate([
            nodes2.astype(jnp.int32), net_flat,
            timersP.reshape(pp, -1),
            excP.astype(jnp.int32).reshape(pp, 1)], axis=1)
        over = (net_over + send_over + toverP) * okP.astype(jnp.int32)
        return rows, over

    def _step_one(self, row: jnp.ndarray, event_idx: jnp.ndarray):
        """Expand ONE state row by ONE grid event id -> (successor row,
        valid, over).  Select-both compatibility wrapper over the split
        kinds — the expand pipeline uses the split grids; this remains
        for trace replay (tpu/trace.py) and external callers."""
        p = self.p
        is_msg = event_idx < p.net_cap
        m = self._msg_step(row, event_idx)
        t = self._tmr_step(row, jnp.maximum(event_idx - p.net_cap, 0))
        out = jax.tree.map(lambda a, b: jnp.where(is_msg, a, b), m, t)
        if p.fault is not None and self._ev_flt:
            tgrid = p.n_nodes * p.timer_cap
            is_flt = event_idx >= p.net_cap + tgrid
            f = self._flt_step(
                row, jnp.maximum(event_idx - p.net_cap - tgrid, 0))
            out = jax.tree.map(
                lambda a, b: jnp.where(is_flt, b, a), out, f)
        return out

    @staticmethod
    def _compact_ids(valid_ev: jnp.ndarray, budget: int, offset=0):
        """[C, G] validity grid -> ([C, budget] compacted indices into G
        (-1 = empty slot), remaining scalar).  One-hot select-reduce over
        the [C, budget, G] cube — static indexing; per-CHUNK, not
        per-pair.

        ``offset`` (static int or traced scalar) selects the event WINDOW
        [offset, offset + budget) by valid-event rank: the spill
        mechanism re-steps a chunk with the next window when
        ``remaining`` (valid events at rank >= offset + budget) is
        nonzero, so a budget smaller than the worst-case event count
        truncates nothing.  What it costs: every over-budget chunk is a
        whole chunk step more a window, whatever the window holds —
        half a search's steps where one kind overflows in every chunk
        (lab 4's three-server groups under (40, 8)) — of which
        _expand_chunk leaves out the handlers and merge of a kind whose
        table for the pass is all -1 (the round-3 drop-or-abort became
        round 4's count-then-respill)."""
        c, g = valid_ev.shape
        if budget >= g:
            # Window 0 covers every rank (remaining always 0) — but the
            # OTHER event kind may still spill the chunk, so later passes
            # must present an empty table here or the full-grid kind's
            # events would be re-expanded (and re-counted) every pass.
            ids = jnp.broadcast_to(jnp.arange(g, dtype=jnp.int32), (c, g))
            first = jnp.asarray(offset, jnp.int32) == 0
            return jnp.where(valid_ev & first, ids, -1), jnp.int32(0)
        pos = jnp.cumsum(valid_ev, axis=1) - 1
        hit = valid_ev[:, None, :] & (
            pos[:, None, :] == jnp.arange(budget)[None, :, None] + offset)
        ids = jnp.sum(jnp.where(hit, jnp.arange(g, dtype=jnp.int32)
                                [None, None, :], 0), axis=2)
        ids = jnp.where(jnp.any(hit, axis=2), ids, -1)
        remaining = jnp.sum(valid_ev
                            & (pos >= budget + offset)).astype(jnp.int32)
        return ids, remaining

    def set_runtime_masks(self, marr, tarr) -> None:
        """Install per-run delivery masks (device arrays consumed by the
        protocol's deliver_*_rt fns).  They ride the jitted programs as
        ARGUMENTS, so changing masks never recompiles."""
        import jax.numpy as jnp

        self._rt_masks = (jnp.asarray(marr), jnp.asarray(tarr))

    def _event_tables(self, chunk_rows: jnp.ndarray,
                      chunk_valid: jnp.ndarray, ev_pass=0, masks=None):
        """[C, lanes] chunk -> (msg_ids [C, Bm] net-slot indices, tmr_ids
        [C, Bt] timer grid indices, flt_ids [C, Bf] fault-segment
        indices (``None`` when no fault model), ev_remaining): each
        state's VALID events (occupied network rows + deliverable
        timers, masked by the protocol's deliver_* settings AND the
        fault deliverability mask — exactly the predicates the step
        kinds re-check — plus enabled fault events) packed into
        per-kind pair slots.  ``ev_pass`` selects the budget WINDOW
        (pass w covers valid-event ranks [w*budget, (w+1)*budget) of
        each kind); ``ev_remaining`` counts valid events past the
        current window — spill drivers re-step the chunk at the next
        window until it reaches zero, so a finite budget never
        truncates coverage.  The fault segment is never windowed
        (budget = its full grid), so pass 0 covers it entirely and
        later passes present an empty fault table."""
        p = self.p
        c = chunk_valid.shape[0]
        chunk_state = self.unflatten_rows(chunk_rows)
        msg_ok = chunk_state["net"][:, :, 0] != SENTINEL   # [C, net_cap]
        if p.deliver_message is not None:
            msg_ok = msg_ok & jax.vmap(jax.vmap(p.deliver_message))(
                chunk_state["net"])
        if p.deliver_message_rt is not None and masks is not None:
            marr = masks[0]
            msg_ok = msg_ok & jax.vmap(jax.vmap(
                lambda m: p.deliver_message_rt(m, marr)))(
                chunk_state["net"])
        tmask = jax.vmap(jax.vmap(timer_deliverable_mask))(
            chunk_state["timers"])                         # [C, NN, T_CAP]
        if p.deliver_timer is not None:
            dt = jax.vmap(p.deliver_timer)(jnp.arange(p.n_nodes))
            tmask = tmask & dt[None, :, None]
        if p.deliver_timer_rt is not None and masks is not None:
            tarr = masks[1]
            dt = jax.vmap(lambda nd: p.deliver_timer_rt(nd, tarr))(
                jnp.arange(p.n_nodes))
            tmask = tmask & dt[None, :, None]
        flt_ids = None
        if p.fault is not None:
            fl = p.fault
            nodesC = chunk_state["nodes"]
            nid = jnp.arange(fl.n_nodes)
            net = chunk_state["net"]
            if fl.has_partition:
                # Cross-block messages are blocked while the cut is up
                # (block ids resolved by one-hot over the static table;
                # -1 = unpartitioned node, never blocked).
                blk = jnp.asarray(fl.block_id)
                bf_ = jnp.sum((net[:, :, 1, None] == nid) * blk, axis=2)
                bt_ = jnp.sum((net[:, :, 2, None] == nid) * blk, axis=2)
                cross = (bf_ >= 0) & (bt_ >= 0) & (bf_ != bt_)
                pcut = nodesC[:, fl.pcut_off] > 0
                msg_ok = msg_ok & ~(pcut[:, None] & cross)
            if fl.n_crashable:
                z = jnp.zeros((c,), jnp.int32)
                down = jnp.stack(
                    [nodesC[:, int(off)] if int(off) >= 0 else z
                     for off in fl.down_off], axis=1)     # [C, NN]
                dest_down = jnp.sum(
                    (net[:, :, 2, None] == nid) * down[:, None, :],
                    axis=2)
                msg_ok = msg_ok & (dest_down == 0)
                tmask = tmask & (down == 0)[:, :, None]
            flt_ids, _f_rem = self._compact_ids(
                self._fault_event_grid(chunk_state)
                & chunk_valid[:, None], self._ev_flt,
                ev_pass * self._ev_flt)
        msg_ids, m_rem = self._compact_ids(
            msg_ok & chunk_valid[:, None], self._ev_msg,
            ev_pass * self._ev_msg)
        tmr_ids, t_rem = self._compact_ids(
            tmask.reshape(c, -1) & chunk_valid[:, None], self._ev_tmr,
            ev_pass * self._ev_tmr)
        return msg_ids, tmr_ids, flt_ids, m_rem + t_rem

    def _kinds_live(self, msg_ids: jnp.ndarray, tmr_ids: jnp.ndarray):
        """(messages, timers): whether a pass's table of the kind
        (:meth:`_event_tables`) holds an event at all — the two scalars
        :meth:`_expand_chunk` branches on, computed from the tables
        alone."""
        return jnp.any(msg_ids >= 0), jnp.any(tmr_ids >= 0)

    def _expand_chunk(self, chunk_rows: jnp.ndarray,
                      chunk_valid: jnp.ndarray, ev_pass=0, masks=None,
                      dedup: Optional[bool] = None):
        """[C, lanes] chunk rows -> successor rows + fingerprints + masks
        + flags.

        Returns (rows [C*B, lanes], valids [C*B], fp [C*B, 4] uint32,
        unique [C*B] in-chunk-first-occurrence mask, overflow scalar,
        ev_remaining scalar (valid events past this pass's window — see
        :meth:`_event_tables`), event_ids [C, B], flags dict, kind_skips
        scalar (the kinds, 0-2, whose table held no event in this pass
        and whose handlers and merge did not run)) — all device arrays;
        no host sync inside.  B = Bm + Bt, message pair
        slots first per state (successor row = chunk_row * B + slot, the
        arithmetic run()/_reconstruct and the sharded driver use)."""
        p = self.p
        bm, bt = self._ev_msg, self._ev_tmr
        bf = self._ev_flt
        has_flt = p.fault is not None and bf > 0
        c = chunk_valid.shape[0]
        # The stages below are named in the HLO's metadata
        # (``dslabs.<scope>``, tpu/telemetry.py DEVICE_SCOPES) so that a
        # profile can say which stage a device operation belongs to.
        with tel_mod.device_scope("expand.events"):
            msg_ids, tmr_ids, flt_ids, ev_drops = self._event_tables(
                chunk_rows, chunk_valid, ev_pass, masks)
            live_m, live_t = self._kinds_live(msg_ids, tmr_ids)
        # TWO flat vmaps — one per event kind, each running only its own
        # machinery (the round-2 select-both design ran BOTH handlers for
        # every pair).  Flat, not nested: a nested
        # vmap-over-events-inside-vmap-over-states compiles the protocol
        # twins' traced-index gathers/scatters into a pathologically slow
        # two-batch-dim scatter path on TPU (~100x); flattening keeps
        # every scatter on the fast single-batch-dim lowering.  The
        # per-state repeat is a broadcast (XLA fuses it into the reads).
        # Only the HANDLER half is vmapped; the network merge runs as
        # ONE batched transposed program per kind (_batched_tail).
        #
        # Each kind runs under a device BRANCH on its table: a kind with
        # no event in this pass (a re-step whose other kind spilled, a
        # search with every timer frozen) computes nothing and hands back
        # invalid rows of the same shape, so the successor block keeps
        # its layout.  No collective sits inside, so on a mesh each chip
        # branches for itself.
        def kind(step_raw, ids, b, live):
            def run(_):
                with tel_mod.device_scope("expand.handlers"):
                    rep = jnp.repeat(chunk_rows, b, axis=0)
                    (nodes2, sends, timers2, exc, ok,
                     tover) = jax.vmap(step_raw)(
                        rep, jnp.maximum(ids, 0).reshape(-1))
                with tel_mod.device_scope("expand.canon"):
                    rows, over = self._batched_tail(
                        chunk_rows, c, b, nodes2, sends, timers2, exc,
                        ok, tover)
                    return rows, ok & (ids >= 0).reshape(-1), over

            def skip(_):
                return (jnp.zeros((c * b, self.lanes), jnp.int32),
                        jnp.zeros((c * b,), bool),
                        jnp.zeros((c * b,), jnp.int32))

            return jax.lax.cond(live, run, skip, None)

        # The barrier does two things, both read off the text compiled
        # for a v5e (PERF.md section 6, PR 49) and pinned in
        # tests/test_chip_compile.py.  It keeps the compiler from moving
        # what follows INTO the branches (its conditional code motion
        # hoisted the interleave's pads, and in one placement the
        # fingerprints' converts, into each branch and handed a whole
        # padded block out of every one).  And, a side effect of the
        # compiler's own heuristics that the tree's speed now rests on:
        # behind it a branch assembles its kind's rows by ONE
        # concatenate, where without it (and before there was a branch)
        # they are written by one in-place dynamic-update-slice a lane
        # group, 42 of them in lab 4 — the cells that never skip a kind
        # gained 2-9 % by that, and without the barrier the branches
        # cost them 1-3 %.
        (rows_m, val_m, over_m, rows_t, val_t,
         over_t) = jax.lax.optimization_barrier(
            kind(self._msg_step_raw, msg_ids, bm, live_m)
            + kind(self._tmr_step_raw, tmr_ids, bt, live_t))
        with tel_mod.device_scope("expand.canon"):
            # Fault segment (ISSUE 19): no handlers, no sends —
            # _flt_step returns full successor rows directly, so the
            # pairs skip the batched merge tail entirely.
            if has_flt:
                rep_f = jnp.repeat(chunk_rows, bf, axis=0)
                rows_f, ok_f, over_f = jax.vmap(self._flt_step)(
                    rep_f, jnp.maximum(flt_ids, 0).reshape(-1))
                val_f = ok_f & (flt_ids >= 0).reshape(-1)

            widths = [bm, bt] + ([bf] if has_flt else [])

            def _inter(*parts):
                return jnp.concatenate(
                    [x.reshape((c, w) + x.shape[1:])
                     for x, w in zip(parts, widths)],
                    axis=1).reshape((c * sum(widths),)
                                    + parts[0].shape[1:])

            if has_flt:
                rows = _inter(rows_m, rows_t, rows_f)
                valids = _inter(val_m, val_t, val_f)
                overs = _inter(over_m, over_t, over_f)
            else:
                rows = _inter(rows_m, rows_t)
                valids = _inter(val_m, val_t)
                overs = _inter(over_m, over_t)
            # Grid event ids for trace spills: timer table entries are
            # net_cap + t_idx in the flat grid numbering; fault entries
            # follow at net_cap + NN*T_CAP + f_idx.
            ev_segs = [msg_ids,
                       jnp.where(tmr_ids >= 0, p.net_cap + tmr_ids, -1)]
            if has_flt:
                tgrid = p.n_nodes * p.timer_cap
                ev_segs.append(jnp.where(flt_ids >= 0,
                                         p.net_cap + tgrid + flt_ids, -1))
            event_ids = jnp.concatenate(ev_segs, axis=1)       # [C, B]
            overflow = jnp.sum(overs * valids.astype(jnp.int32))
            # The kinds this pass did not compute (0-2).
            kind_skips = (2 - live_m.astype(jnp.int32)
                          - live_t.astype(jnp.int32))
        # Symmetry hash step (ISSUE 15b): fingerprints — and through
        # them the sharded owner-hash — key on the canonical orbit
        # representative; the stored rows stay the real states.
        with tel_mod.device_scope("fingerprint"):
            fp = row_fingerprints(self._canon_rows(rows))

        if self._in_chunk_dedup if dedup is None else dedup:
            # In-chunk sort-unique on device: first occurrence of each
            # 128-bit key among valid rows (invalid rows sort last and are
            # never unique).  Cuts host dedup work before any readback.
            # Scoped with the fingerprints it sorts.
            with tel_mod.device_scope("fingerprint"):
                inv = ~valids
                order = jnp.lexsort((fp[:, 3], fp[:, 2], fp[:, 1],
                                     fp[:, 0], inv))
                fps = fp[order]
                vs = valids[order]
                first = jnp.ones(fps.shape[0], bool).at[1:].set(
                    jnp.any(fps[1:] != fps[:-1], axis=1))
                unique = jnp.zeros_like(vs).at[order].set(first & vs)
        else:
            # Sharded path: the owner-side hash table (and its in-batch
            # key sort) is the dedup authority — the prefilter sort here
            # is redundant work; routing buckets are sized for the full
            # successor count.
            unique = valids

        flags = {}
        with tel_mod.device_scope("flags"):
            succ_states = self.unflatten_rows(rows)  # views for predicates
            for kind, preds in (("inv", p.invariants), ("goal", p.goals),
                                ("prune", p.prunes)):
                for name, fn in preds.items():
                    flags[f"{kind}:{name}"] = (jax.vmap(fn)(succ_states)
                                               & valids)
        return (rows, valids, fp, unique, overflow, ev_drops, event_ids,
                flags, kind_skips)

    # ----------------------------------------------------------------- run

    def _check_initial(self, state, t0) -> Optional[SearchOutcome]:
        """The root's verdict by one eager launch a predicate, stopping
        at the first that decides (the engines no benchmark cell runs;
        the sharded engine reads ``_root_program``'s hits instead)."""
        def hits(preds):
            return (bool(jax.vmap(fn)(state)[0]) for fn in preds.values())

        return self._initial_verdict(hits(self.p.invariants),
                                     hits(self.p.goals), state, t0)

    def _initial_verdict(self, inv_hits, goal_hits, state,
                         t0) -> Optional[SearchOutcome]:
        """checkState at the root: the first invariant that does not
        hold, then the first goal that does; hits in ``p.invariants`` /
        ``p.goals`` order."""
        import time
        for name, hit in zip(self.p.invariants, inv_hits):
            if not hit:
                return SearchOutcome("INVARIANT_VIOLATED", 1, 1, 0,
                                     time.time() - t0,
                                     violating_state=state,
                                     predicate_name=name)
        for name, hit in zip(self.p.goals, goal_hits):
            if hit:
                return SearchOutcome("GOAL_FOUND", 1, 1, 0,
                                     time.time() - t0,
                                     goal_state=state,
                                     predicate_name=name)
        return None

    def _terminal_outcome(self, rows, np_valids, np_exc, flags,
                          explored, visited_n, depth, t0,
                          level_base_row: int = 0):
        """checkState order: exception -> invariant -> goal
        (Search.java:162-231).  Returns a SearchOutcome or None."""
        import time

        def slice_state(idx):
            return jax.tree.map(
                np.asarray,
                self.unflatten_rows(np.asarray(rows[idx:idx + 1])))

        exc_hit = np_valids & (np_exc != 0)
        if exc_hit.any():
            idx = int(np.nonzero(exc_hit)[0][0])
            return SearchOutcome(
                "EXCEPTION_THROWN", explored, visited_n, depth,
                time.time() - t0, violating_state=slice_state(idx),
                exception_code=int(np_exc[idx]),
                trace=self._reconstruct(level_base_row + idx))
        for kind in ("inv", "goal"):
            for name, f in flags.items():
                if not name.startswith(kind + ":"):
                    continue
                fa = np.asarray(f)
                pname = name.split(":", 1)[1]
                if kind == "inv" and not fa[np_valids].all():
                    idx = int(np.nonzero(np_valids & ~fa)[0][0])
                    return SearchOutcome(
                        "INVARIANT_VIOLATED", explored, visited_n, depth,
                        time.time() - t0, violating_state=slice_state(idx),
                        predicate_name=pname,
                        trace=self._reconstruct(level_base_row + idx))
                if kind == "goal" and fa[np_valids].any():
                    idx = int(np.nonzero(np_valids & fa)[0][0])
                    return SearchOutcome(
                        "GOAL_FOUND", explored, visited_n, depth,
                        time.time() - t0, goal_state=slice_state(idx),
                        predicate_name=pname,
                        trace=self._reconstruct(level_base_row + idx))
        return None

    def _reconstruct(self, row: int) -> Optional[list]:
        """Walk the per-level (parent, event) spill back from a successor
        row of the current level to the initial state -> [event ids] root
        first (SearchState.java:361-371's parent chain, tensorised)."""
        if not self.record_trace or not self._levels:
            return None
        ne = self._num_events()
        events = []
        for lvl in reversed(self._levels):
            parent_chunk_row = row // ne
            if isinstance(lvl["event_ids"], list):
                lvl["event_ids"] = np.concatenate(lvl["event_ids"], axis=0)
            # The pair slot is a compacted rank when ev_budget is set; the
            # level's spilled event table maps it back to the GRID event
            # id (what tpu/trace.py decodes).
            events.append(int(lvl["event_ids"][parent_chunk_row, row % ne]))
            # Map the in-level parent row back through the previous level's
            # kept-state compaction.
            row = int(lvl["parent_rows"][parent_chunk_row])
        events.reverse()
        return events

    def random_rollouts(self, n_walkers: int = 256,
                        n_steps: int = 64, seed: int = 0,
                        initial: Optional[dict] = None,
                        max_secs: Optional[float] = None) -> SearchOutcome:
        """RandomDFS-style DEEP probes: ``n_walkers`` parallel random
        walks of up to ``n_steps`` events each — a walker reaches depth
        d in O(d) steps where BFS must exhaust every shallower level
        first (RandomDFS.java via SURVEY §2.4).

        Since ISSUE 5 this is a thin single-device client of the swarm
        explorer (tpu/swarm.py ``SwarmSearch``) — ONE walker
        implementation, so the probe gains the swarm's shared-table
        dedup, loud overflow-restart accounting (the old loop restarted
        capacity-truncated walkers silently), and the witness pipeline:
        a violation's trace is minimized and replay-verified before the
        verdict returns (``SearchOutcome.witness``).  Verdict
        vocabulary is unchanged: INVARIANT_VIOLATED / EXCEPTION_THROWN
        with a root-first event trace (the tpu/trace.py contract), else
        TIME_EXHAUSTED — exhaustive verdicts stay BFS-only."""
        from dslabs_tpu.tpu.sharded import make_mesh
        from dslabs_tpu.tpu.swarm import SwarmSearch

        sw = SwarmSearch(
            self.p, mesh=make_mesh(1), walkers_per_device=n_walkers,
            max_steps=n_steps, seed=seed, max_secs=max_secs,
            visited_cap=min(self.visited_cap, 1 << 18),
            ev_budget=(self._ev_msg, self._ev_tmr))
        rt = getattr(self, "_rt_masks", None)
        if rt is not None:
            sw.set_runtime_masks(*rt)
        # The probe inherits this engine's supervision boundary (the
        # backend installs transient retry on the engine, and the probe
        # must ride the same seam).
        hook = getattr(self, "_dispatch_hook", None)
        if hook is not None:
            sw._dispatch_hook = hook
        tel = getattr(self, "_telemetry", None)
        if tel is not None:
            sw._telemetry = tel
        out = sw.run(initial=initial, check_initial=False)
        # Expose the walk root for tpu/trace.py replay on THIS engine
        # too (decode_trace reads search._trace_root off whichever
        # search object the caller holds).
        self._trace_root = sw._trace_root
        return out

    def run(self, check_initial: bool = True,
            initial: Optional[dict] = None,
            resume: bool = False) -> SearchOutcome:
        """Run the BFS.  ``initial`` (a batch-1 state pytree, e.g. a prior
        outcome's ``goal_state``) starts the search from an arbitrary
        state — the staged-search pattern (PaxosTest.java:886-1096):
        extract a goal state, change the settings masks
        (``dataclasses.replace(protocol, deliver_message=...)``), and
        search onward from it.  ``resume=True`` continues from
        ``checkpoint_path`` if a fingerprint-matching dump exists (a
        killed search restarts at its last checkpointed level with
        identical final verdict and unique count).

        Dispatch: the device-resident wave loop (:meth:`_run_device` —
        visited table + frontier as donated device buffers, scalar-only
        per-wave host transfers) unless trace recording or
        ``use_host_visited`` demand the legacy host-dedup loop
        (:meth:`run_host`, the parity oracle — trace mode spills
        per-level event tables to the host by design)."""
        tel = getattr(self, "_telemetry", None)
        if tel is not None and self._spill is not None:
            # Spill evict/reinject operations surface as telemetry
            # events (tpu/spill.py) — host bookkeeping only.
            self._spill.telemetry = tel
        if self.record_trace or self.use_host_visited:
            out = self.run_host(check_initial, initial, resume=resume)
            eng = "host"
        else:
            out = self._run_device(check_initial, initial,
                                   resume=resume)
            eng = "device"
        self._stamp_device(out)
        self._stamp_capacity(out)
        self._stamp_faults(out)
        if tel is not None:
            # Trace stamp at span emission (ISSUE 13): the verdict
            # carries the recorder's causal-trace identity — a host
            # string copy, never a device transfer.
            if out.trace_id is None:
                out.trace_id = tel.trace_id
            tel.on_outcome(out, engine=eng)
        return out

    def run_host(self, check_initial: bool = True,
                 initial: Optional[dict] = None,
                 resume: bool = False) -> SearchOutcome:
        """The legacy host-dedup BFS: device expand + in-chunk sort-unique,
        host ``sorted_member`` visited membership.  Kept as (a) the parity
        oracle the device-table loop is tested against and (b) the trace-
        recording path (per-level (parent, event) spills are host-side).
        Same contract as :meth:`run`."""
        import time
        t0 = time.time()
        state = (jax.tree.map(jnp.asarray, initial) if initial is not None
                 else self.initial_state())
        # The root this run's trace event-ids are relative to (staged
        # searches start from arbitrary states; tpu/trace.py replays from
        # here, not from the protocol's initial state).
        self._trace_root = jax.tree.map(np.asarray, state)
        ck = self._load_ckpt() if resume else None
        if ck is not None and self.record_trace:
            raise ValueError(
                "resume + record_trace is unsupported on the host loop "
                "(per-level trace spills cannot be rebuilt from a "
                "checkpoint); rerun without record_trace")
        self._levels = []
        self._host_prev_explored = 0
        self._fault_counts[:] = 0
        if ck is not None:
            # Resume at the checkpointed level boundary: the visited SET
            # comes back from the dumped 128-bit keys, the frontier from
            # the dumped live rows; clocks continue from the dump.
            t0 = time.time() - ck.elapsed
            h1, h2 = host_keys(ck.visited_keys)
            order = np.lexsort((h2, h1))
            visited = (h1[order], h2[order])
            self._host_visited = visited
            explored = ck.explored
            depth = ck.depth
            frontier = jnp.asarray(ck.frontier)
            frontier_n = len(ck.frontier)
            parent_rows = np.full(max(frontier_n, 1), -1, dtype=np.int64)
        else:
            fp0 = np.asarray(self._canonical_root_fp(state))
            visited = host_keys(fp0)
            # Diagnostic stash: the parity tests compare this loop's
            # exact visited SET against the device table's keys.
            self._host_visited = visited
            explored = 0
            depth = 0

            if check_initial:
                out = self._check_initial(state, t0)
                if out is not None:
                    return out

            frontier = flatten_state(state)          # [1, lanes] rows
            # parent_rows[i] = the global successor row (in the PREVIOUS
            # level's enumeration) that produced frontier state i; for
            # the root level it is -1.  Used by _reconstruct.
            parent_rows = np.array([-1], dtype=np.int64)
            frontier_n = 1
        while frontier_n > 0:
            if self.max_depth is not None and depth >= self.max_depth:
                return SearchOutcome("DEPTH_EXHAUSTED", explored,
                                     len(visited[0]), depth,
                                     time.time() - t0)
            if (self.max_secs is not None
                    and time.time() - t0 > self.max_secs) \
                    or self._cancelled():
                return SearchOutcome("TIME_EXHAUSTED", explored,
                                     len(visited[0]), depth,
                                     time.time() - t0,
                                     cancelled=self._cancelled())
            depth += 1
            # Live depth for supervision heartbeats (the dispatch
            # observer reads it — tpu/supervisor.py, tpu/warden.py).
            self._current_depth = depth
            with tel_mod.phase("search.level", depth=depth,
                               explored0=int(explored)) as lvl:
                t_lvl = time.time()
                if self.record_trace:
                    self._levels.append({"parent_rows": parent_rows,
                                         "event_ids": []})
                # ---- expand all chunks (device), collect level arrays (host)
                lvl_states: List[np.ndarray] = []
                lvl_keys: List[Tuple[np.ndarray, np.ndarray]] = []
                lvl_pruned: List[np.ndarray] = []
                lvl_rows: List[np.ndarray] = []
                ne = self._num_events()
                for start in range(0, frontier_n, self.chunk):
                    end = min(start + self.chunk, frontier_n)
                    c = end - start
                    pad = self.chunk - c
                    chunk_rows = (jnp.concatenate(
                        [frontier[start:end],
                         jnp.repeat(frontier[:1], pad, axis=0)], axis=0)
                        if pad else frontier[start:end])
                    chunk_valid = jnp.concatenate(
                        [jnp.ones(c, bool), jnp.zeros(pad, bool)])
                    rt = getattr(self, "_rt_masks", None)
                    (rows_d, valids, fp, unique, overflow, ev_drops, event_ids,
                     flags, _) = (
                        self._dispatch("host.expand", self._expand,
                                       chunk_rows, chunk_valid, 0, rt)
                        if rt is not None
                        else self._dispatch("host.expand", self._expand,
                                            chunk_rows, chunk_valid))
                    if int(overflow):
                        raise CapacityOverflow(
                            f"{self.p.name}: net_cap={self.p.net_cap}, "
                            f"timer_cap={self.p.timer_cap}, or max_live_sends="
                            f"{self.p.max_live_sends} overflowed at depth "
                            f"{depth} ({int(overflow)} drops); raise the caps")
                    if int(ev_drops):
                        raise CapacityOverflow(
                            f"{self.p.name}: ev_budget={self._ev_slots} < "
                            f"valid events of some state at depth {depth} "
                            f"({int(ev_drops)} skipped); raise the budget")
                    if self.record_trace:
                        self._levels[-1]["event_ids"].append(
                            np.asarray(event_ids))
                    np_valids = np.asarray(valids)
                    explored += int(np_valids.sum())
                    if self.p.fault is not None:
                        self._accum_fault_counts(event_ids, np_valids)
                    np_exc = np.asarray(rows_d[:, -1])
                    out = self._terminal_outcome(
                        rows_d, np_valids, np_exc, flags, explored,
                        len(visited[0]), depth, t0,
                        level_base_row=start * ne)
                    if out is not None:
                        return out

                    pruned = np.zeros(len(np_valids), dtype=bool)
                    for name, f in flags.items():
                        if name.startswith("prune:"):
                            pruned |= np.asarray(f)
                    # Exception states are terminal even when the search
                    # continues past them (none here: exceptions end the run).
                    keep = np.asarray(unique)
                    if keep.any():
                        h1, h2 = host_keys(np.asarray(fp))
                        idxs = np.nonzero(keep)[0]
                        lvl_keys.append((h1[idxs], h2[idxs]))
                        lvl_pruned.append(pruned[idxs])
                        lvl_rows.append(idxs + start * ne)
                        lvl_states.append(np.asarray(rows_d)[idxs])

                if not lvl_keys:
                    return SearchOutcome("SPACE_EXHAUSTED", explored,
                                         len(visited[0]), depth,
                                         time.time() - t0)

                # ---- one level-wide dedup (sort-unique + visited membership)
                h1 = np.concatenate([k[0] for k in lvl_keys])
                h2 = np.concatenate([k[1] for k in lvl_keys])
                pruned = np.concatenate(lvl_pruned)
                rows = np.concatenate(lvl_rows)
                order = np.lexsort((h2, h1))
                h1s, h2s = h1[order], h2[order]
                first = np.ones(len(order), dtype=bool)
                first[1:] = (h1s[1:] != h1s[:-1]) | (h2s[1:] != h2s[:-1])
                unique_mask = np.zeros(len(order), dtype=bool)
                unique_mask[order] = first
                fresh = unique_mask & ~sorted_member(visited[0], visited[1],
                                                     h1, h2)

                # ---- merge visited (sorted-merge, stays sorted by (h1, h2))
                if fresh.any():
                    nk = np.nonzero(fresh)[0]
                    no = np.lexsort((h2[nk], h1[nk]))
                    mh1 = np.concatenate([visited[0], h1[nk][no]])
                    mh2 = np.concatenate([visited[1], h2[nk][no]])
                    mo = np.lexsort((mh2, mh1))
                    visited = (mh1[mo], mh2[mo])
                    self._host_visited = visited

                expand = fresh & ~pruned
                if not expand.any():
                    return SearchOutcome("SPACE_EXHAUSTED", explored,
                                         len(visited[0]), depth,
                                         time.time() - t0)

                keep_idx = np.nonzero(expand)[0]
                lvl.set(explored=explored, unique=int(len(visited[0])),
                        next_frontier=int(len(keep_idx)))
            tel = getattr(self, "_telemetry", None)
            if tel is not None:
                delta = [explored - getattr(self, "_host_prev_explored",
                                            0)]
                self._host_prev_explored = explored
                lvl_rec = {
                    "depth": depth,
                    "wall": round(time.time() - t_lvl, 4),
                    "explored": explored,
                    "unique": int(len(visited[0])),
                    "next_frontier": int(len(keep_idx)),
                    "per_device": {
                        "explored": delta,
                        "frontier": [int(len(keep_idx))],
                        "load_factor": [0.0], "drops": [0]},
                    "skew": {"explored": tel_mod.skew_metrics(delta)}}
                if self.p.fault is not None:
                    lvl_rec["faults"] = self._fault_block()
                tel.on_level("host", lvl_rec)
            # lvl_states rows align 1:1 with h1/h2/rows concatenation.
            all_rows = (np.concatenate(lvl_states, axis=0)
                        if len(lvl_states) > 1 else lvl_states[0])
            nf = all_rows[keep_idx]
            parent_rows = rows[keep_idx]
            frontier_n = len(nf)
            if frontier_n > self.frontier_cap:
                return SearchOutcome("CAPACITY_EXHAUSTED", explored,
                                     len(visited[0]), depth,
                                     time.time() - t0)
            frontier = jnp.asarray(nf)
            if (self.checkpoint_path and self.checkpoint_every
                    and depth % self.checkpoint_every == 0
                    and not self.record_trace):
                # Everything is already host-side here, so the dump is a
                # plain synchronous atomic write (the device loops use
                # the async drain instead — their readback is the cost).
                from dslabs_tpu.tpu import checkpoint as ckpt_mod

                ckpt_mod.save(self.checkpoint_path, ckpt_mod.SearchCheckpoint(
                    fingerprint=self._ckpt_fingerprint(), depth=depth,
                    explored=explored, elapsed=time.time() - t0,
                    frontier=nf,
                    visited_keys=_keys_to_rows(visited)))

        return SearchOutcome("SPACE_EXHAUSTED", explored, len(visited[0]),
                             depth, 0.0)

    # ------------------------------------------- device-resident wave loop

    def _build_dev_step(self, cap: int):
        """One wave step over frontier chunk ``j``: expand -> in-chunk
        sort-unique -> visited-table insert -> frontier-compact append,
        all on device.  The carry is DONATED (run() jits with
        donate_argnums=0), so the table and frontier update in place
        instead of reallocating per wave."""
        p = self.p
        C = self.chunk
        lanes = self.lanes
        plane = self.plane
        pk = self._pk
        # Spill mode (tpu/spill.py): a chunk that would overflow the
        # frontier buffer or leave table keys unresolved ABORTS — every
        # carry entry (the visited table included) reverts to its
        # pre-chunk state and an abort code rides the f_drop stats slot
        # (bit 0 = frontier full, bit 1 = table full).  The host drains
        # nxt to the spool / evicts the table to the host tier, then
        # re-dispatches the SAME chunk against exactly the state it
        # first saw — nothing is ever dropped or double-counted.
        spill_on = self._spill is not None

        def step(carry, masks):
            cur, cur_n = carry["cur"], carry["cur_n"][0]
            j = carry["j"][0]
            start = j * C
            # The frontier buffers hold PACKED rows (ISSUE 15a): the
            # chunk decodes in-register right here — handlers, flags,
            # and fingerprints all operate on the int32 view.
            rows_chunk = jax.lax.dynamic_slice(cur, (start, 0),
                                               (C, plane))
            rows_chunk = self._unpack_rows(rows_chunk)
            valid = (start + jnp.arange(C)) < cur_n
            ev_pass = carry["evp"][0]
            # dedup=False: the visited table below is the dedup
            # authority and resolves in-batch duplicates natively (the
            # per-bucket reservation admits exactly one copy), so the
            # in-chunk sort-unique prefilter is redundant work here —
            # same ~60% chunk-step saving the sharded single-device
            # path measured.  run_host keeps the prefilter (its host
            # merge requires batch-unique keys).
            (rows, valids, fp, unique, overflow, ev_rem, _event_ids,
             flags, _) = self._expand_chunk(rows_chunk, valid, ev_pass,
                                            masks, dedup=False)
            # Event-window spill (round-4 semantics): valid events past
            # this pass's window re-step the SAME chunk at the next
            # window before j advances — a finite ev_budget costs extra
            # passes, never coverage.
            spill = ev_rem > 0
            j_next = carry["j"] + jnp.where(spill, 0, 1)
            evp_next = jnp.where(spill, carry["evp"] + 1, 0)

            # ---- terminal flags, checkState order (exception first);
            # first-hit successor row kept per flag.
            hit_list = [valids & (rows[:, -1] != 0)]
            for n in p.invariants:
                hit_list.append(valids & ~flags[f"inv:{n}"])
            for n in p.goals:
                hit_list.append(flags[f"goal:{n}"])
            hits = jnp.stack(hit_list)                   # [nf, C*B]
            cnts = jnp.sum(hits, axis=1).astype(jnp.int32)
            idxs = jnp.argmax(hits, axis=1)
            fresh_flag = (carry["flag_cnt"] == 0) & (cnts > 0)
            flag_rows = jnp.where(fresh_flag[:, None], rows[idxs],
                                  carry["flag_rows"])

            pruned = rows[:, -1] != 0        # exception states terminal
            for n in p.prunes:
                pruned = pruned | flags[f"prune:{n}"]

            # ---- device-table dedup (the authority): in-chunk firsts go
            # through the shared open-addressing table; unresolved keys
            # (probe exhausted = table effectively full) are treated as
            # FRESH — sound, may re-explore, never a silent drop — and
            # counted into vis_over (fatal in strict mode at the sync).
            table, inserted, unresolved = visited_mod.insert(
                carry["visited"], fp, unique)
            fresh = inserted | unresolved

            # ---- frontier-compact append of fresh, un-pruned successors
            # Spill mode appends pruned-but-fresh rows TOO: every fresh
            # insert must reach the host refilter so a post-eviction
            # re-discovery of a pruned state is charged to dup_epoch
            # (the drain recomputes the prune mask host-side and drops
            # the rows before they can be re-expanded).
            sel = fresh if spill_on else fresh & ~pruned
            spos = jnp.cumsum(sel) - 1
            nxt_n = carry["nxt_n"][0]
            sdst = jnp.where(sel & (nxt_n + spos < cap), nxt_n + spos, cap)
            # Successors re-encode to the packed storage form before
            # the frontier append.  A live value OUTSIDE its declared
            # domain is counted into the overflow scalar — a wrong
            # spec bound is a loud CapacityOverflow, never silent
            # state corruption.
            if pk is not None:
                rows_store, pack_bad = pk.pack_jnp(rows, count_bad=True)
                overflow = overflow + jnp.sum(
                    pack_bad * sel.astype(jnp.int32))
            else:
                rows_store = rows
            nxt = carry["nxt"].at[sdst].set(rows_store)
            n_sel = jnp.sum(sel).astype(jnp.int32)
            f_drop = jnp.maximum(nxt_n + n_sel - cap, 0)
            n_sel = n_sel - f_drop

            out = {
                "cur": cur, "cur_n": carry["cur_n"],
                "j": j_next, "evp": evp_next,
                "nxt": nxt, "nxt_n": carry["nxt_n"].at[0].add(n_sel),
                "visited": table,
                "vis_n": carry["vis_n"].at[0].add(
                    jnp.sum(inserted).astype(jnp.int32)),
                "explored": carry["explored"].at[0].add(
                    jnp.sum(valids).astype(jnp.int32)),
                "overflow": carry["overflow"].at[0].add(overflow),
                "vis_over": carry["vis_over"].at[0].add(
                    jnp.sum(unresolved).astype(jnp.int32)),
                "f_drop": carry["f_drop"].at[0].add(f_drop),
                "flag_cnt": carry["flag_cnt"] + cnts,
                "flag_rows": flag_rows,
            }
            has_flt = p.fault is not None and self._ev_flt > 0
            if has_flt:
                # Fault-family event counters (ISSUE 19): cumulative
                # like "explored", computed from the event-id table the
                # fault-free program discards — no extra readback, one
                # extra stats lane per family.
                out["fault_cnt"] = carry["fault_cnt"] \
                    + self._fault_chunk_counts(_event_ids, valids)
            if spill_on:
                tbl_full = jnp.any(unresolved)
                front_full = (nxt_n + jnp.sum(sel).astype(jnp.int32)
                              ) > cap
                abort = tbl_full | front_full
                code = (front_full.astype(jnp.int32)
                        + 2 * tbl_full.astype(jnp.int32))
                for k in ("j", "evp", "nxt", "nxt_n", "visited",
                          "vis_n", "explored", "overflow", "vis_over",
                          "flag_cnt", "flag_rows") \
                        + (("fault_cnt",) if has_flt else ()):
                    out[k] = jnp.where(abort, carry[k], out[k])
                out["f_drop"] = jnp.where(abort, code[None],
                                          out["f_drop"])
            # The per-wave scalar stats ride along with every step (the
            # ONLY recurring device->host transfer of the device loop:
            # [explored, overflow, vis_over, f_drop, vis_n, nxt_n, j] ++
            # flag counts ++ (fault model only) fault-family counts) —
            # computed in-program so the sync needs no separate
            # dispatch, and only the LAST chunk's vector of a wave is
            # actually pulled to the host.
            stats = jnp.concatenate([
                jnp.asarray([out["explored"][0], out["overflow"][0],
                             out["vis_over"][0], out["f_drop"][0],
                             out["vis_n"][0], out["nxt_n"][0],
                             out["j"][0]], jnp.int32),
                out["flag_cnt"].astype(jnp.int32)]
                + ([out["fault_cnt"]] if has_flt else []))
            return out, stats

        return step

    def _build_dev_promote(self, cap: int):
        """Between-wave frontier promotion (nxt -> cur), donated like the
        step so the buffers swap in place."""
        plane = self.plane

        def promote(carry):
            out = dict(carry)
            out["cur"] = carry["nxt"][:cap]
            out["cur_n"] = carry["nxt_n"]
            out["nxt"] = jnp.zeros((cap + 1, plane), jnp.int32)
            out["nxt_n"] = jnp.zeros((1,), jnp.int32)
            out["j"] = jnp.zeros((1,), jnp.int32)
            out["evp"] = jnp.zeros((1,), jnp.int32)
            return out

        return promote

    def _build_dev_init(self, cap: int):
        """Carry built ON DEVICE inside one jitted program: only the root
        row crosses the host boundary (UNPACKED — the build packs it
        for storage); the root key is inserted through the same shared
        table code the waves use."""
        lanes = self.lanes
        plane = self.plane
        V = self.visited_cap
        nf = len(self._flag_names)

        def build(row0):
            from dslabs_tpu.tpu.kernels import fingerprint_rows

            fp0 = fingerprint_rows(self._canon_rows(row0))   # [1, 4]
            row0s = self._pack_rows(row0)
            table, _, _ = visited_mod.insert(
                visited_mod.empty_table(V), fp0, jnp.ones((1,), bool))
            out = {
                "cur": jnp.zeros((cap, plane), jnp.int32).at[0].set(
                    row0s[0]),
                "cur_n": jnp.ones((1,), jnp.int32),
                "j": jnp.zeros((1,), jnp.int32),
                "evp": jnp.zeros((1,), jnp.int32),
                "nxt": jnp.zeros((cap + 1, plane), jnp.int32),
                "nxt_n": jnp.zeros((1,), jnp.int32),
                "visited": table,
                "vis_n": jnp.ones((1,), jnp.int32),
                "explored": jnp.zeros((1,), jnp.int32),
                "overflow": jnp.zeros((1,), jnp.int32),
                "vis_over": jnp.zeros((1,), jnp.int32),
                "f_drop": jnp.zeros((1,), jnp.int32),
                "flag_cnt": jnp.zeros((nf,), jnp.int32),
                "flag_rows": jnp.zeros((nf, lanes), jnp.int32),
            }
            if self.p.fault is not None and self._ev_flt > 0:
                out["fault_cnt"] = jnp.zeros((4,), jnp.int32)
            return out

        return build

    def _dev_programs(self, cap: int):
        progs = self._dev_progs.get(cap)
        if progs is None:
            progs = (jax.jit(self._build_dev_step(cap), donate_argnums=0),
                     jax.jit(self._build_dev_promote(cap),
                             donate_argnums=0),
                     jax.jit(self._build_dev_init(cap)))
            self._dev_progs[cap] = progs
        return progs

    def _dev_terminal(self, carry, flag_counts, explored, vis_n, depth,
                      t0, vis_over) -> SearchOutcome:
        """Resolve the first terminal flag (checkState order).  The flag
        rows are the one non-scalar readback of the device loop — paid
        once per RUN, only when a terminal state actually fired."""
        import time

        rows = self._dispatch("device.flags", device_get,
                              carry["flag_rows"])
        for fi, fname in enumerate(self._flag_names):
            if flag_counts[fi] <= 0:
                continue
            st = jax.tree.map(np.asarray,
                              self.unflatten_rows(rows[fi][None]))
            elapsed = time.time() - t0
            if fname == "exc":
                return SearchOutcome(
                    "EXCEPTION_THROWN", explored, vis_n, depth, elapsed,
                    violating_state=st, exception_code=int(st["exc"][0]),
                    visited_overflow=vis_over)
            kind, pname = fname.split(":", 1)
            if kind == "inv":
                return SearchOutcome(
                    "INVARIANT_VIOLATED", explored, vis_n, depth, elapsed,
                    violating_state=st, predicate_name=pname,
                    visited_overflow=vis_over)
            return SearchOutcome(
                "GOAL_FOUND", explored, vis_n, depth, elapsed,
                goal_state=st, predicate_name=pname,
                visited_overflow=vis_over)
        raise AssertionError("flag counts fired without a flag name")

    def _run_device(self, check_initial: bool = True,
                    initial: Optional[dict] = None,
                    resume: bool = False) -> SearchOutcome:
        """The device-resident BFS.  Frontier + visited table live in
        device buffers donated through every wave; host transfers are the
        per-wave stats scalars.  The frontier buffer starts small and
        grows geometrically on overflow (deterministic restart — same
        verdict, amortised cost), up to ``frontier_cap``; overflowing AT
        the cap is the legacy CAPACITY_EXHAUSTED."""
        import time

        t0 = time.time()
        state = (jax.tree.map(jnp.asarray, initial) if initial is not None
                 else self.initial_state())
        self._trace_root = jax.tree.map(np.asarray, state)
        self._fault_counts[:] = 0
        ck = self._load_ckpt() if resume else None
        if ck is not None:
            t0 = time.time() - ck.elapsed
        elif check_initial:
            out = self._check_initial(state, t0)
            if out is not None:
                return out
        C = self.chunk
        user_cap = -(-self.frontier_cap // C) * C
        if self._spill is not None:
            # Spill mode skips the geometric buffer growth (a drain to
            # the host spool replaces every would-be drop, so the only
            # reason to grow is a single chunk's successors exceeding
            # the buffer — which growth cannot amortise anyway) and
            # runs its own per-chunk-synced wave loop.
            try:
                return self._device_attempt_spill(state, user_cap, t0,
                                                  ck)
            finally:
                w = getattr(self, "_ckpt_writer_obj", None)
                if w is not None:
                    w.join()
        # Start the frontier buffer SMALL (2k rows): the per-wave promote
        # zero+copy scales with the buffer, and most searches never need
        # more; the ones that do pay one bounded deterministic restart
        # per x8 growth rung.  A resumed frontier sets the floor.
        cap = min(user_cap, -(-max(C, 1 << 11) // C) * C)
        if ck is not None:
            cap = min(user_cap,
                      max(cap, -(-max(len(ck.frontier), 1) // C) * C))
        try:
            while True:
                # Growth restarts re-seed from the CHECKPOINT when one
                # was loaded (the dump is a consistent level boundary;
                # restarting there is deterministic and cheaper than
                # from the root).
                out = self._device_attempt(state, cap, user_cap, t0, ck)
                if out is not None:
                    return out
                cap = min(cap * 8, user_cap)
        finally:
            w = getattr(self, "_ckpt_writer_obj", None)
            if w is not None:
                # An async dump still draining must land before the
                # caller sees the outcome (kill-resume depends on it).
                w.join()

    def _carry_from_ckpt(self, ck, cap: int):
        """Rebuild the device carry from a unified checkpoint
        (tpu/checkpoint.py): frontier rows pad back to the buffer, the
        visited table is rebuilt by RE-INSERTING the dumped keys (layout
        is engine-local; the key SET is the semantic content), and the
        never-dumped accumulators come back empty — exactly their state
        at a wave boundary."""
        lanes = self.lanes
        plane = self.plane
        V = self.visited_cap
        nf = len(self._flag_names)
        n = len(ck.frontier)
        cur = np.zeros((cap, plane), np.int32)
        if n:
            # ck.frontier is normalized-raw (_load_ckpt); re-encode to
            # the engine's native packed storage.
            cur[:n] = (self._pk.pack_np(ck.frontier)
                       if self._pk is not None else ck.frontier)
        table, n_ins, n_unres = visited_mod.build_table(
            V, ck.visited_keys)
        if n_unres:
            raise CapacityOverflow(
                f"{self.p.name}: visited_cap={V} too small to rebuild "
                f"the checkpoint's visited set ({n_unres} of "
                f"{len(ck.visited_keys)} keys unresolved); raise "
                "visited_cap")
        carry = {
            "cur": jnp.asarray(cur),
            "cur_n": jnp.asarray([n], jnp.int32),
            "j": jnp.zeros((1,), jnp.int32),
            "evp": jnp.zeros((1,), jnp.int32),
            "nxt": jnp.zeros((cap + 1, plane), jnp.int32),
            "nxt_n": jnp.zeros((1,), jnp.int32),
            "visited": table,
            "vis_n": jnp.asarray([n_ins], jnp.int32),
            "explored": jnp.asarray([ck.explored], jnp.int32),
            "overflow": jnp.zeros((1,), jnp.int32),
            "vis_over": jnp.asarray([ck.vis_over], jnp.int32),
            "f_drop": jnp.zeros((1,), jnp.int32),
            "flag_cnt": jnp.zeros((nf,), jnp.int32),
            "flag_rows": jnp.zeros((nf, lanes), jnp.int32),
        }
        if self.p.fault is not None and self._ev_flt > 0:
            # Fault counters are per-PROCESS accounting (like retries):
            # a resumed run counts fault events from the resume point.
            carry["fault_cnt"] = jnp.zeros((4,), jnp.int32)
        return carry

    def _write_dev_ckpt(self, carry, depth: int, explored: int,
                        vis_over: int, nxt_n: int,
                        elapsed: float) -> None:
        """Snapshot the wave-boundary carry into the unified checkpoint:
        the occupied frontier prefix + the occupied visited-table lines
        + counters — never the empty accumulators or buffer padding."""
        if nxt_n:
            frontier = np.asarray(carry["cur"][:nxt_n])
        else:
            frontier = np.zeros((0, self.plane), np.int32)
        self._kick_ckpt(frontier,
                        visited_mod.host_occupied(carry["visited"]),
                        depth, explored, elapsed, vis_over)

    def _device_attempt(self, state, cap: int, user_cap: int,
                        t0, ck=None) -> Optional[SearchOutcome]:
        """One run at a fixed frontier-buffer capacity; None = frontier
        overflowed below the user cap (caller grows and restarts).
        ``ck`` (a loaded SearchCheckpoint) seeds the carry from a dump
        instead of the root."""
        import time

        p = self.p
        C = self.chunk
        step, promote, init = self._dev_programs(cap)
        rt = getattr(self, "_rt_masks", None)
        if ck is not None:
            carry = self._carry_from_ckpt(ck, cap)
            if not len(ck.frontier):
                # A dump saved after the final wave: the search already
                # ended; report the finished verdict from the counters.
                return SearchOutcome(
                    "SPACE_EXHAUSTED", ck.explored,
                    len(ck.visited_keys), ck.depth, time.time() - t0,
                    visited_overflow=ck.vis_over)
        else:
            carry = self._dispatch("device.init", init,
                                   flatten_state(state))
        sdev = None        # stats vector of the latest dispatched step
        # With a finite ev_budget a chunk can spill extra window passes,
        # holding j back — then the sync must watch j and re-dispatch,
        # which precludes the pre-sync speculative dispatch below.
        spill = (self._ev_msg < p.net_cap
                 or self._ev_tmr < p.n_nodes * p.timer_cap)
        if ck is not None:
            depth = ck.depth
            n_chunks = max(1, -(-len(ck.frontier) // C))
            last = (ck.explored, len(ck.visited_keys), ck.vis_over)
        else:
            depth = 0
            n_chunks = 1
            last = (0, 1, 0)   # (explored, unique, vis_over) at last sync
        spec = 0           # chunks of the current wave already dispatched
        while True:
            if (self.max_secs is not None
                    and time.time() - t0 > self.max_secs) \
                    or self._cancelled():
                return SearchOutcome(
                    "TIME_EXHAUSTED", last[0], last[1], depth,
                    time.time() - t0, visited_overflow=last[2],
                    cancelled=self._cancelled())
            if self.max_depth is not None and depth >= self.max_depth:
                return SearchOutcome(
                    "DEPTH_EXHAUSTED", last[0], last[1], depth,
                    time.time() - t0, visited_overflow=last[2])
            depth += 1
            # Live depth for supervision heartbeats (tpu/warden.py).
            self._current_depth = depth
            with tel_mod.phase("search.level", depth=depth,
                               explored0=int(last[0])) as lvl:
                t_wave = time.time()
                # A checkpoint-due wave skips the speculative next-wave
                # dispatch: the snapshot must see the carry at a clean wave
                # boundary, not mid-way through wave depth+1.
                ckpt_due = bool(self.checkpoint_path and self.checkpoint_every
                                and depth % self.checkpoint_every == 0)
                for _ in range(n_chunks - spec):
                    carry, sdev = self._dispatch("device.step", step,
                                                 carry, rt)
                if spill:
                    while True:
                        s = self._dispatch("device.sync", device_get, sdev)
                        if int(s[6]) >= n_chunks:
                            break
                        for _ in range(n_chunks - int(s[6])):
                            carry, sdev = self._dispatch("device.step", step,
                                                         carry, rt)
                    carry = self._dispatch("device.promote", promote, carry)
                    spec = 0
                else:
                    # Double-buffering: the next wave's promotion AND its
                    # first chunk dispatch BEFORE this wave's scalars are
                    # read, so host bookkeeping overlaps device compute.  A
                    # terminal/empty wave makes the speculative chunk a
                    # no-op (flags keep first-hit; empty frontier expands
                    # nothing) — the readback below still reports wave k.
                    # Single-chunk waves skip the speculation: the chunk
                    # would BE the whole next wave, and on termination it is
                    # a full expand wasted (the measured 20% overhead on
                    # small search spaces).  When the wave's last chunk WAS
                    # last wave's speculative dispatch (n_chunks == spec),
                    # its stats vector is already in hand.
                    wave_stats = sdev
                    carry = self._dispatch("device.promote", promote, carry)
                    if n_chunks > 1 and not ckpt_due:
                        carry, sdev = self._dispatch("device.step", step,
                                                     carry, rt)
                        spec = 1
                    else:
                        spec = 0
                    s = self._dispatch("device.sync", device_get, wave_stats)
                (explored, overflow, vis_over, f_drop, vis_n,
                 nxt_n) = (int(x) for x in s[:6])
                nf = len(self._flag_names)
                flag_counts = np.asarray(s[7:7 + nf])
                if self.p.fault is not None and self._ev_flt > 0:
                    # Cumulative from the carry — overwrite, never add.
                    self._fault_counts[:] = np.asarray(
                        s[7 + nf:7 + nf + 4])
                if overflow:
                    raise CapacityOverflow(
                        f"{p.name}: net_cap={p.net_cap}, timer_cap="
                        f"{p.timer_cap}, or max_live_sends={p.max_live_sends} "
                        f"overflowed at depth {depth} ({overflow} drops); "
                        "raise the caps")
                # Early-warning instrumentation (ISSUE 6 satellite): table
                # pressure is visible BEFORE the overflow contract fires.
                limit = (3 * self.visited_cap // 4 if self.strict
                         else self.visited_cap)
                if (not getattr(self, "_warned_visited", False)
                        and vis_n >= int(_visited_warn() * limit)):
                    self._warned_visited = True
                    import warnings

                    warnings.warn(
                        f"{p.name}: visited table at {vis_n}/"
                        f"{self.visited_cap} at depth {depth} — capacity "
                        "pressure; raise visited_cap or enable the spill "
                        "tier (spill=True / DSLABS_SPILL=1) before this "
                        "becomes CapacityOverflow",
                        RuntimeWarning, stacklevel=2)
                if vis_over and self.strict:
                    raise CapacityOverflow(
                        f"{p.name}: visited table full at depth {depth} "
                        f"({vis_over} unresolved keys, cap "
                        f"{self.visited_cap}); raise visited_cap or run "
                        "strict=False for sound treat-as-fresh degradation")
                if self.strict and vis_n > 3 * self.visited_cap // 4:
                    raise CapacityOverflow(
                        f"{p.name}: visited table > 75% full "
                        f"({vis_n}/{self.visited_cap}) at depth {depth}; "
                        "raise visited_cap")
                prev_explored = last[0]
                last = (explored, vis_n, vis_over)
                lvl.set(explored=explored, unique=vis_n,
                        chunks=int(n_chunks), next_frontier=int(nxt_n))
            tel = getattr(self, "_telemetry", None)
            if tel is not None:
                # Fed from the wave's fused stats vector — scalars this
                # loop just read anyway (zero extra transfers).  The
                # per-device lanes are length-1 on the single-device
                # engine but keep the mesh-scope record shape uniform
                # (report heatmap / STATUS.json / skew gauges).
                delta = [explored - prev_explored]
                lvl_rec = {
                    "depth": depth,
                    "wall": round(time.time() - t_wave, 4),
                    "explored": explored, "unique": vis_n,
                    "next_frontier": int(nxt_n),
                    "load_factor": round(vis_n / self.visited_cap, 4),
                    "per_device": {
                        "explored": delta, "frontier": [int(nxt_n)],
                        "load_factor": [round(vis_n / self.visited_cap,
                                              4)],
                        "drops": [0]},
                    "skew": {"explored": tel_mod.skew_metrics(delta)}}
                if self.p.fault is not None:
                    lvl_rec["faults"] = self._fault_block()
                tel.on_level("device", lvl_rec)
            self._last_dev_carry = carry
            if flag_counts.any():
                return self._dev_terminal(carry, flag_counts, explored,
                                          vis_n, depth, t0, vis_over)
            if f_drop:
                if cap < user_cap:
                    return None            # grow the buffer and restart
                return SearchOutcome(
                    "CAPACITY_EXHAUSTED", explored, vis_n, depth,
                    time.time() - t0, visited_overflow=vis_over)
            if ckpt_due:
                # Carry is at a clean wave boundary (spec == 0): cur is
                # wave depth+1's frontier, counters are cumulative.
                # Host copies happen HERE (before the next wave donates
                # the buffers); the file write drains asynchronously.
                self._write_dev_ckpt(carry, depth, explored, vis_over,
                                     nxt_n, time.time() - t0)
            if nxt_n == 0:
                return SearchOutcome(
                    "SPACE_EXHAUSTED", explored, vis_n, depth,
                    time.time() - t0, visited_overflow=vis_over)
            n_chunks = -(-nxt_n // C)

    # ----------------------------------------- host-RAM spill tier mode
    #
    # The capacity-laddered variant of the device loop (ISSUE 6,
    # tpu/spill.py, docs/capacity.md).  Same wave cycle, three changes:
    # the step program ABORTS (wholesale revert + code on the f_drop
    # stats slot) instead of dropping frontier rows or leaving table
    # keys unresolved; the host answers an abort by draining nxt to the
    # frontier spool and/or bulk-evicting the visited table to the host
    # fingerprint tier; and once the tier is live, each level boundary
    # re-filters the would-be frontier against it (one batched
    # readback + corrected promote mask — never per-state sync), so
    # "table full" means "slower, still exact" instead of
    # CapacityOverflow.  Syncs are per chunk (no speculation): spill
    # mode is the degraded-capacity gear, correctness over latency.
    # Every host round-trip goes through the _dispatch seam
    # (device.spill_drain / spill_evict / spill_reinject tags), so
    # supervisor retry/watchdog/FaultPlan and the warden's heartbeat
    # cover the spill path like any other dispatch.

    def _spill_progs(self, cap: int) -> dict:
        cache = getattr(self, "_spill_prog_cache", None)
        if cache is None:
            cache = self._spill_prog_cache = {}
        progs = cache.get(cap)
        if progs is not None:
            return progs
        lanes = self.lanes
        V = self.visited_cap

        def reset(carry):
            out = dict(carry)
            out["nxt"] = jnp.zeros((cap + 1, self.plane), jnp.int32)
            out["nxt_n"] = jnp.zeros((1,), jnp.int32)
            out["f_drop"] = jnp.zeros((1,), jnp.int32)
            return out

        def evict(carry):
            out = dict(carry)
            out["visited"] = visited_mod.empty_table(V)
            out["vis_n"] = jnp.zeros((1,), jnp.int32)
            out["f_drop"] = jnp.zeros((1,), jnp.int32)
            return out

        progs = {"reset": jax.jit(reset, donate_argnums=0),
                 "evict": jax.jit(evict, donate_argnums=0),
                 "inject": {}, "fp": {}, "prune": {}}
        cache[cap] = progs
        return progs

    @staticmethod
    def _pow2_bucket(n: int, cap: int) -> int:
        m = 1
        while m < max(n, 1):
            m <<= 1
        return min(m, cap)

    def _spill_keys_of(self, rows: np.ndarray, cap: int) -> np.ndarray:
        """Fingerprints of host rows (UNPACKED lanes) via the SAME
        device fp program the engines hash with — canonicalize pass
        included, so spill-tier keys match the expand keys bit-exactly
        (jitted per pow2 row bucket so compiles stay O(log cap))."""
        from dslabs_tpu.tpu.kernels import fingerprint_rows

        n = len(rows)
        if not n:
            return np.zeros((0, 4), np.uint32)
        m = self._pow2_bucket(n, max(cap, n))
        progs = self._spill_progs(cap)
        fn = progs["fp"].get(m)
        if fn is None:
            def spill_fingerprints(r):
                return fingerprint_rows(self._canon_rows(r))

            fn = progs["fp"][m] = jax.jit(spill_fingerprints)
        pad = np.zeros((m, rows.shape[1]), np.int32)
        pad[:n] = rows
        return np.asarray(fn(jnp.asarray(pad)))[:n]

    def _spill_keep_mask(self, rows: np.ndarray, cap: int) -> np.ndarray:
        """Exception/prune mask recomputed on drained rows (spill mode
        appends pruned-but-fresh rows so they reach the refilter; they
        must not be re-expanded)."""
        rows = np.asarray(rows)
        keep = rows[:, -1] == 0
        if self.p.prunes and len(rows):
            n = len(rows)
            m = self._pow2_bucket(n, max(cap, n))
            progs = self._spill_progs(cap)
            fn = progs["prune"].get(m)
            if fn is None:
                preds = list(self.p.prunes.values())

                def pruned_of(r):
                    st = self.unflatten_rows(r)
                    acc = jnp.zeros((r.shape[0],), bool)
                    for f in preds:
                        acc = acc | jax.vmap(f)(st)
                    return acc

                fn = progs["prune"][m] = jax.jit(pruned_of)
            pad = np.zeros((m, rows.shape[1]), np.int32)
            pad[:n] = rows
            keep &= ~np.asarray(fn(jnp.asarray(pad)))[:n]
        return keep

    def _spill_drain(self, carry, nxt_n: int, cap: int):
        """Mid-level or boundary drain: read nxt's occupied prefix back
        (ONE batched readback of PACKED rows + their canonical keys),
        reset nxt on device, and hand the host half — refilter against
        the tier, drop exception/pruned rows, spool the keepers — to
        the spill manager's drain queue.  With the async gear (ISSUE
        15c, default on) the device re-dispatches the aborted chunk
        IMMEDIATELY after the reset while the host answers the drain
        in the background; ordering through the single worker keeps
        the refilter-before-next-eviction invariant, so counts stay
        exact."""
        sp = self._spill
        pk = self._pk

        def fetch():
            rows = np.asarray(carry["nxt"])[:nxt_n]
            rows_u = pk.unpack_np(rows) if pk is not None else rows
            return rows, self._spill_keys_of(rows_u, cap)

        if nxt_n:
            rows, keys = self._dispatch("device.spill_drain", fetch)

            def host_half():
                kept = sp.refilter(rows, keys)
                if len(kept):
                    ku = (pk.unpack_np(kept) if pk is not None
                          else kept)
                    kept = kept[self._spill_keep_mask(ku, cap)]
                sp.spool(kept)

            sp.submit_drain(host_half)
        return self._dispatch("device.spill_drain",
                              self._spill_progs(cap)["reset"], carry)

    def _spill_evict_dev(self, carry, cap: int):
        """Bulk eviction: occupied table lines -> host tier, table and
        vis_n restart empty (a fresh epoch).  The tier absorb rides
        the same ordered drain queue as the refilters — every drained
        batch is refiltered against the PRE-eviction tier (the
        exactness invariant, docs/capacity.md)."""
        sp = self._spill

        def fetch():
            return visited_mod.host_occupied(
                np.asarray(carry["visited"]))

        occ = self._dispatch("device.spill_evict", fetch)
        sp.submit_drain(lambda: sp.evict(occ), evict=True)
        return self._dispatch("device.spill_evict",
                              self._spill_progs(cap)["evict"], carry)

    def _spill_inject(self, carry, rows: np.ndarray, cap: int):
        """(Re-)inject a host frontier segment (native packed rows) as
        the live cur — the deferred re-expansion wave, at unchanged
        BFS depth."""
        n = len(rows)
        m = self._pow2_bucket(n, cap)
        plane = self.plane
        progs = self._spill_progs(cap)
        fn = progs["inject"].get(m)
        if fn is None:
            def inject(c, seg, nn):
                out = dict(c)
                out["cur"] = jnp.zeros((cap, plane),
                                       jnp.int32).at[:m].set(seg)
                out["cur_n"] = nn
                out["j"] = jnp.zeros((1,), jnp.int32)
                out["evp"] = jnp.zeros((1,), jnp.int32)
                return out

            fn = progs["inject"][m] = jax.jit(inject, donate_argnums=0)
        pad = np.zeros((m, plane), np.int32)
        pad[:n] = rows
        carry = self._dispatch("device.spill_reinject", fn, carry,
                               jnp.asarray(pad),
                               jnp.asarray([n], jnp.int32))
        return carry, n

    def _spill_wave(self, carry, step, rt, cap: int, n_cur: int):
        """Expand the injected frontier completely: per-chunk dispatch
        + sync, answering abort codes (bit 0 frontier full -> drain;
        bit 1 table full -> drain then evict) by re-dispatching the
        same chunk against the recovered capacity."""
        C = self.chunk
        sp = self._spill
        n_chunks = max(1, -(-n_cur // C))
        while True:
            carry, sdev = self._dispatch("device.step", step, carry, rt)
            s = self._dispatch("device.sync", device_get, sdev)
            code = int(s[3])
            vis_n, nxt_n = int(s[4]), int(s[5])
            if code:
                if (code & 1) and nxt_n == 0:
                    raise CapacityOverflow(
                        f"{self.p.name}: one chunk's fresh successors "
                        f"exceed frontier_cap={cap} even with spill; "
                        f"lower chunk ({C}) or raise frontier_cap")
                if (code & 2) and vis_n == 0:
                    raise CapacityOverflow(
                        f"{self.p.name}: one chunk's unique successors "
                        f"exceed visited_cap={self.visited_cap} even "
                        f"from an empty table; lower chunk ({C}) or "
                        "raise visited_cap")
                carry = self._spill_drain(carry, nxt_n, cap)
                if code & 2:
                    carry = self._spill_evict_dev(carry, cap)
                continue
            if int(s[6]) >= n_chunks:
                # The wave's final sync must stay accurate (the caller
                # derives the exact unique count from its vis_n), so
                # end-of-wave eviction is the BOUNDARY's job.
                return carry, s
            # Proactive mid-wave high-water eviction keeps aborts rare:
            # drain whatever nxt holds (pre-eviction refilter order),
            # then evict, then continue the wave on a fresh epoch.
            if sp.should_evict(vis_n, self.visited_cap):
                carry = self._spill_drain(carry, nxt_n, cap)
                carry = self._spill_evict_dev(carry, cap)

    def _spill_ckpt(self, carry, depth: int, explored: int,
                    elapsed: float) -> None:
        """Synchronous unified dump at a spill-mode level boundary:
        ``visited_keys`` = device table ∪ host tier (exact-deduped, so
        the resumer's unique base is len(keys)); ``frontier`` = every
        spooled segment of the level about to run; spill counters ride
        ``extra__spill_stats``.  CRC + .prev rotation come free from
        tpu/checkpoint.py — kill-mid-spill resume is bit-exact."""
        from dslabs_tpu.tpu import checkpoint as ckpt_mod

        sp = self._spill
        occ = visited_mod.host_occupied(np.asarray(carry["visited"]))
        extra = sp.checkpoint_extra()
        if self._pk is not None:
            # Spool segments are stored in the native packed encoding;
            # the marker rides the dump for loud cross-resume.
            extra["frontier_encoding"] = np.bytes_(
                self._frontier_encoding().encode())
        ckpt_mod.save(self.checkpoint_path, ckpt_mod.SearchCheckpoint(
            fingerprint=self._ckpt_fingerprint(), depth=depth,
            explored=explored, elapsed=elapsed,
            frontier=sp.spool_cur.concat(self.plane),
            visited_keys=sp.checkpoint_keys(occ),
            extra=extra))

    def _spill_carry_from_ckpt(self, ck, cap: int):
        """Spill-mode resume: ALL dumped keys load into the host tier,
        the device table restarts empty (a fresh epoch — the refilter
        makes that exact), and the dumped frontier spools in cap-sized
        segments with the first injected as cur."""
        sp = self._spill
        sp.restore(ck.visited_keys, ck.extra)
        rows = np.asarray(ck.frontier, np.int32)
        if self._pk is not None:
            # The loader normalized the dump to raw lanes; the spool
            # holds the engine's native packed rows.
            rows = self._pk.pack_np(rows) if len(rows) else \
                np.zeros((0, self.plane), np.int32)
        for i in range(0, len(rows), cap):
            sp.spool_cur.push(rows[i:i + cap])
        lanes = self.lanes
        plane = self.plane
        nf = len(self._flag_names)
        carry = {
            "cur": jnp.zeros((cap, plane), jnp.int32),
            "cur_n": jnp.zeros((1,), jnp.int32),
            "j": jnp.zeros((1,), jnp.int32),
            "evp": jnp.zeros((1,), jnp.int32),
            "nxt": jnp.zeros((cap + 1, plane), jnp.int32),
            "nxt_n": jnp.zeros((1,), jnp.int32),
            "visited": visited_mod.empty_table(self.visited_cap),
            "vis_n": jnp.zeros((1,), jnp.int32),
            "explored": jnp.asarray([ck.explored], jnp.int32),
            "overflow": jnp.zeros((1,), jnp.int32),
            "vis_over": jnp.zeros((1,), jnp.int32),
            "f_drop": jnp.zeros((1,), jnp.int32),
            "flag_cnt": jnp.zeros((nf,), jnp.int32),
            "flag_rows": jnp.zeros((nf, lanes), jnp.int32),
        }
        if self.p.fault is not None and self._ev_flt > 0:
            carry["fault_cnt"] = jnp.zeros((4,), jnp.int32)
        seg = sp.spool_cur.pop()
        return self._spill_inject(carry, seg, cap)

    def _device_attempt_spill(self, state, cap: int, t0,
                              ck=None) -> SearchOutcome:
        """The spill-mode device BFS (structure mirrors
        _device_attempt; see the section comment above)."""
        import time

        from dslabs_tpu.tpu import spill as spill_mod

        p = self.p
        sp = self._spill
        step, promote, init = self._dev_programs(cap)
        rt = getattr(self, "_rt_masks", None)
        warn_at = spill_mod.visited_warn_threshold()
        if ck is not None:
            if not len(ck.frontier):
                out = SearchOutcome(
                    "SPACE_EXHAUSTED", ck.explored,
                    len(ck.visited_keys), ck.depth, time.time() - t0,
                    visited_overflow=ck.vis_over)
                sp.attach(out)
                return out
            carry, n_cur = self._spill_carry_from_ckpt(ck, cap)
            depth = ck.depth
            explored = ck.explored
            unique = sp.unique(0)
        else:
            # Fresh start: run N must not see run N-1's tier/spool
            # (the warm-up-then-measure reuse pattern) — restore()
            # handles the resume case above.
            sp.reset_run()
            carry = self._dispatch("device.init", init,
                                   flatten_state(state))
            depth = 0
            n_cur = 1
            explored, unique = 0, 1
        while True:
            if (self.max_secs is not None
                    and time.time() - t0 > self.max_secs) \
                    or self._cancelled():
                out = SearchOutcome(
                    "TIME_EXHAUSTED", explored, unique, depth,
                    time.time() - t0, cancelled=self._cancelled())
                sp.attach(out)
                return out
            if self.max_depth is not None and depth >= self.max_depth:
                out = SearchOutcome("DEPTH_EXHAUSTED", explored, unique,
                                    depth, time.time() - t0)
                sp.attach(out)
                return out
            depth += 1
            self._current_depth = depth
            with tel_mod.phase("search.level", depth=depth,
                               explored0=int(explored)) as lvl:
                t_lvl = time.time()
                # ---- expand the level: cur, then every spooled segment of
                # the same level as deferred re-expansion waves.
                while True:
                    carry, s = self._spill_wave(carry, step, rt, cap, n_cur)
                    explored, overflow = int(s[0]), int(s[1])
                    vis_over, vis_n, nxt_n = int(s[2]), int(s[4]), int(s[5])
                    nf = len(self._flag_names)
                    flag_counts = np.asarray(s[7:7 + nf])
                    if self.p.fault is not None and self._ev_flt > 0:
                        self._fault_counts[:] = np.asarray(
                            s[7 + nf:7 + nf + 4])
                    if overflow:
                        raise CapacityOverflow(
                            f"{p.name}: net_cap={p.net_cap}, timer_cap="
                            f"{p.timer_cap}, or max_live_sends="
                            f"{p.max_live_sends} overflowed at depth "
                            f"{depth} ({overflow} drops); raise the caps")
                    if vis_over:
                        raise AssertionError(
                            "spill mode committed unresolved keys (abort "
                            "contract violated)")
                    unique = sp.unique(vis_n)
                    if flag_counts.any():
                        out = self._dev_terminal(carry, flag_counts,
                                                 explored, unique, depth,
                                                 t0, 0)
                        sp.attach(out)
                        return out
                    load = vis_n / self.visited_cap
                    if load >= warn_at and not getattr(
                            self, "_warned_visited", False):
                        self._warned_visited = True
                        import warnings

                        warnings.warn(
                            f"{p.name}: visited table at "
                            f"{load:.0%} of visited_cap="
                            f"{self.visited_cap} at depth {depth} — "
                            "capacity pressure; the spill tier will evict "
                            f"at {sp.config.high_water:.0%}",
                            RuntimeWarning, stacklevel=2)
                    seg = sp.pop_current()
                    if seg is None:
                        break
                    carry, n_cur = self._spill_inject(carry, seg, cap)
                lvl.set(explored=explored, unique=unique,
                        next_frontier=int(nxt_n))
            tel = getattr(self, "_telemetry", None)
            if tel is not None:
                # Per-level record WITH the spill-overlap wall split
                # (ISSUE 15c satellite): drain_wall = host seconds in
                # drain jobs this level, drain_wait = seconds the
                # driver actually blocked — their gap is host work
                # hidden behind device compute, so the host drain wall
                # is no longer additive with the chunk wall.
                delta = [explored - getattr(self, "_spill_prev_explored",
                                            0)]
                self._spill_prev_explored = explored
                lvl_rec = {
                    "depth": depth,
                    "wall": round(time.time() - t_lvl, 4),
                    "explored": explored, "unique": unique,
                    "next_frontier": int(nxt_n),
                    "load_factor": round(vis_n / self.visited_cap, 4),
                    "spill": sp.level_walls(),
                    "per_device": {
                        "explored": delta, "frontier": [int(nxt_n)],
                        "load_factor": [round(vis_n / self.visited_cap,
                                              4)],
                        "drops": [0]},
                    "skew": {"explored": tel_mod.skew_metrics(delta)}}
                if self.p.fault is not None:
                    lvl_rec["faults"] = self._fault_block()
                tel.on_level("device", lvl_rec)
            # ---- level boundary.  Fast path until the tier/spool is
            # live: the plain on-device promote.
            if not (sp.active
                    or sp.should_evict(vis_n, self.visited_cap)):
                if nxt_n == 0:
                    out = SearchOutcome(
                        "SPACE_EXHAUSTED", explored, unique, depth,
                        time.time() - t0)
                    sp.attach(out)
                    return out
                carry = self._dispatch("device.promote", promote, carry)
                n_cur = nxt_n
                if (self.checkpoint_path and self.checkpoint_every
                        and depth % self.checkpoint_every == 0):
                    self._write_dev_ckpt(carry, depth, explored, 0,
                                         nxt_n, time.time() - t0)
                continue
            # Slow exact path: drain nxt through the refilter, evict at
            # high water (AFTER the drain — the refilter must run
            # against the pre-eviction tier), swap spools, re-inject.
            carry = self._spill_drain(carry, nxt_n, cap)
            if sp.should_evict(vis_n, self.visited_cap):
                carry = self._spill_evict_dev(carry, cap)
                vis_n = 0
            unique = sp.unique(vis_n)
            sp.advance_level()
            if not sp.spool_cur.segments:
                out = SearchOutcome("SPACE_EXHAUSTED", explored, unique,
                                    depth, time.time() - t0)
                sp.attach(out)
                return out
            if (self.checkpoint_path and self.checkpoint_every
                    and depth % self.checkpoint_every == 0):
                self._spill_ckpt(carry, depth, explored,
                                 time.time() - t0)
            seg = sp.spool_cur.pop()
            carry, n_cur = self._spill_inject(carry, seg, cap)
