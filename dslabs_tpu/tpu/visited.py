"""Device-resident visited set: an open-addressing hash table in HBM.

ONE implementation of the 128-bit-key dedup table shared by both search
drivers — the sharded engine's owner-side dedup (sharded.py) and the
single-device engine's device-resident wave loop (engine.py run()).
Extracted from sharded.py so the probe/insert machinery exists exactly
once (hash compaction after Stern & Dill; the GPUexplore-style BFS table
in PAPERS.md).

Layout: ``[BKT * 4, V / BKT]`` uint32 (``[32, VB]``) where V (a power of
two) is the slot count: COLUMN ``b`` is bucket ``b`` and row ``s * 4 +
l`` holds lane ``l`` of its slot ``s``.  This module owns the layout —
:func:`table_shape`, :func:`empty_table`, :func:`with_root`,
:func:`insert`, :func:`build_table` and :func:`host_occupied` are the
only places that know it; per-device tables stack along the rows
(``[D * 32, VB]``, sharded ``P(axis)``).  PROBE NARROW, WRITE NARROW:
the chip pays a gather or a scatter per INDEX it is handed, whether the
index addresses anything or not (a gather 41–51 ns each on the 2^24 /
2^25-slot tables, a scatter 103–125: PERF.md, PRs 34 and 36), so
neither is handed the rows that have nothing to read or write.  One
probe iteration gathers the whole bucket column of each key in the
LIVE blocks of its batch — the blocks of ``K`` = :func:`block_width`
consecutive rows that still hold an unresolved key
(:func:`_live_columns`: the sharded engine sorts a batch's valid keys
first, so under half of a deep level's blocks are live) — and decides
at the batch's full width who wins a slot; then only the WINNERS'
columns are written — their positions compacted, lowest first, into
blocks of ``K`` indices (:func:`write_in_blocks`), each block's columns
read again, patched with the winner's four words and scattered back
whole; a block's unused places scatter to column ``VB``, which
``mode="drop"`` discards.  In a deep level nine keys in ten win
nothing, so one block of ``n / 8`` does where the whole batch was
scattered before.  Both kinds of block LOOP, as many as the data fill
(live blocks; ``ceil(winners / K)``), because the first levels of a
search are the other way round — every successor fresh, every key a
winner — and an unsorted batch (the single-device engine's) has keys
in every block: both stay exact at the price they had.  The tail's
iterations read and write the same way at the tail's width; a batch no
wider than ``K`` (256 keys or fewer) is its own block.  Gather and
scatter address the table in
the one layout the carry has (32 rows = 4 sublane tiles, VB a multiple
of 128), so the chip converts nothing: compiled for a v5e at 2^24
slots and 49,152 keys the insert takes 28 MB of temporaries, where
``table[:V].reshape(VB, 8, 4)[bkt_i]`` + ``table.at[dst].set`` took
4,592 MB and relaid the 268 MB table out four times per probe
iteration (PERF.md, PR 26).  EMPTY slots are all-MAX (a real all-MAX
key — the 2^-128 collider — is remapped by :func:`sanitize_keys`).
Membership and insert happen in one bounded probe loop; claim conflicts
(equal keys or distinct keys hashing to one bucket) are serialised by a
hashed per-bucket min-index reservation — at most one contender wins a
bucket per iteration, which is what makes the whole-column write
race-free and a block's second read of its columns the first one's —
so no sort of the batch is needed.
After ~2 full-batch iterations only deep bucket chains remain; those are
compacted into a small tail so late iterations stop re-scanning the
whole batch (the measured high-load pathology in round 3).

Overflow contract (ISSUE 1): a key whose probe exhausts (table
effectively full) is **unresolved** — it is NOT inserted, and the caller
must treat it as FRESH (sound: the state may be re-explored; never a
silent drop) while surfacing the count as a visible overflow flag.
Strict drivers raise :class:`~dslabs_tpu.tpu.engine.CapacityOverflow`
on a nonzero count (exact unique counts would otherwise drift); beam
drivers report it via ``SearchOutcome.visited_overflow``.

Pallas kernel (ISSUE 12): the probe/insert also exists as a Pallas
kernel (:func:`pallas_insert`) whose body is the SAME traced algorithm
as the jnp path (:func:`insert_jnp`), so the two are bit-identical by
construction: same probe order, same reservation tie-breaks, same
unresolved set.  The TPU compiler (Mosaic) REFUSES the kernel — its
body scatters, and ``scatter`` has no Pallas TPU lowering — so it is on
no default path: :func:`insert` resolves to :func:`insert_jnp` unless
``DSLABS_VISITED_PALLAS`` asks for the kernel by name (``on`` compiles
it or raises; ``interpret`` runs the Pallas interpreter — the CPU
parity path the tests use).  An explicit request never degrades.
``visited.insert`` is a canonical dispatch site
(``telemetry.DISPATCH_SITES``) so the profiler's hot-site selection and
the jaxpr auditor cover whichever variant is active;
:func:`dispatch_site_program` builds the audit entry.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["BKT", "MAXU32", "table_shape", "empty_table", "with_root",
           "sanitize_keys", "host_sanitize_key", "host_home_slot",
           "host_occupied", "block_width", "compact", "write_in_blocks",
           "insert", "insert_jnp", "pallas_insert", "pallas_mode",
           "force_jnp", "dispatch_site_program", "build_table"]

# Trace-time override depth for :func:`force_jnp` — engines that trace
# the probe loop under a batching transform (the lane engine vmaps the
# whole step body over stacked jobs, tpu/lanes.py) pin the jnp oracle
# here: ``pallas_call`` has no batching rule for this kernel, and the
# two variants are bit-identical by construction, so the override is a
# lowering choice, never a semantic one.
_FORCE_JNP = 0


@contextlib.contextmanager
def force_jnp():
    """Pin :func:`insert` to the jnp oracle for programs traced inside
    this context (nested use is fine; trace-time only — already-compiled
    programs are unaffected)."""
    global _FORCE_JNP
    _FORCE_JNP += 1
    try:
        yield
    finally:
        _FORCE_JNP -= 1

# Slots per bucket: the probe loop reads whole buckets (one column of
# 8 x 16-byte keys).
BKT = 8
ROWS = BKT * 4          # table rows: row s * 4 + l = lane l of slot s
MAXU32 = np.uint32(0xFFFFFFFF)


def check_cap(cap: int) -> None:
    if cap & (cap - 1) or cap < BKT:
        raise ValueError(
            f"visited cap must be a power of two >= {BKT} "
            f"(hash-table slot arithmetic), got {cap}")


def table_shape(cap: int, n_devices: int = 1) -> Tuple[int, int]:
    """Shape of ``n_devices`` ``cap``-slot tables stacked along the rows
    (one ``[ROWS, cap / BKT]`` block per device)."""
    check_cap(cap)
    return (n_devices * ROWS, cap // BKT)


def empty_table(cap: int, n_devices: int = 1) -> jnp.ndarray:
    """Fresh all-EMPTY table(s) of :func:`table_shape`."""
    return jnp.full(table_shape(cap, n_devices), MAXU32, jnp.uint32)


def with_root(table: jnp.ndarray, key: jnp.ndarray, home: int,
              owner: int = 0) -> jnp.ndarray:
    """``table`` (possibly stacked) with the [4] ``key`` written to slot
    ``home`` (:func:`host_home_slot`) of device ``owner``'s block — how
    a carry initialiser places the root without a probe."""
    rows = owner * ROWS + (home % BKT) * 4 + np.arange(4)
    return table.at[rows, home // BKT].set(key)


def sanitize_keys(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Remap the all-MAX key (would alias the EMPTY marker) on valid
    rows; [N, 4] uint32 -> [N, 4] uint32."""
    all_max = jnp.all(keys == MAXU32, axis=1)
    return keys.at[:, 3].set(
        jnp.where(all_max & valid, MAXU32 - 1, keys[:, 3]))


def host_sanitize_key(key: np.ndarray) -> np.ndarray:
    """Host-side :func:`sanitize_keys` for a single [4] uint32 key (carry
    initialisers place the root key without a device round-trip)."""
    key = key.copy()
    if (key == MAXU32).all():
        key[3] = np.uint32(MAXU32 - 1)
    return key


def host_home_slot(key: np.ndarray, cap: int) -> int:
    """Slot index of a [4] key's home bucket's first slot — MUST mirror
    :func:`insert`'s addressing (bucket keyed by lane 2: lane 0 is
    owner-routing-biased in the sharded engine, see sharded.py)."""
    check_cap(cap)
    return (int(key[2]) & (cap // BKT - 1)) * BKT


def host_occupied(table: np.ndarray) -> np.ndarray:
    """Occupied keys, ``[K, 4]``, of a HOST copy of a table or of a
    per-device stack of tables (device by device, then in slot order) —
    the bulk-eviction readback of the spill tier (tpu/spill.py) and the
    checkpoint writers share this one definition of "occupied" (any
    lane != EMPTY's all-MAX)."""
    table = np.asarray(table)
    slots = table.reshape(-1, BKT, 4, table.shape[-1]).transpose(
        0, 3, 1, 2).reshape(-1, 4)
    return slots[~(slots == MAXU32).all(axis=1)]


def build_table(cap: int, keys) -> Tuple[jnp.ndarray, int, int]:
    """A fresh table with ``keys`` ([K, 4] uint32) pre-inserted — the
    HOST-SIDE rebuild/pre-seed entry point (engine.py
    ``_carry_from_ckpt``; the sharded and swarm drivers re-insert
    inside their shard_map initialisers instead, where the table must
    be built per device).  Returns ``(table, n_inserted,
    n_unresolved)``; callers treat a nonzero unresolved count as
    CapacityOverflow (the table cannot hold the key set)."""
    keys = jnp.asarray(keys, jnp.uint32).reshape(-1, 4)
    table, ins, unres = insert(empty_table(cap), keys,
                               jnp.ones((keys.shape[0],), bool))
    return (table, int(np.asarray(jnp.sum(ins))),
            int(np.asarray(jnp.sum(unres))))


def block_width(n: int) -> int:
    """``K``: how many rows ONE write of a batch of ``n`` holds — an
    eighth of the batch: the tail's width, the table scatter's block
    (of the full batch and, an eighth again, of the tail) and the
    sharded engine's frontier-append block (one compaction shape in
    the tree).  A batch no wider than ``K`` (``n`` <= 256) is its own
    block."""
    return max(n // 8, min(256, n))


def compact(mask: jnp.ndarray, K: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Positions of the True rows of ``mask`` [n], lowest first, as
    whole blocks of ``K``: ``(idx [ceil(n / K) * K] int32, count)``.
    Entries past ``count`` read ``n``."""
    n = mask.shape[0]
    size = -(-n // K) * K
    rank = jnp.cumsum(mask.astype(jnp.int32))
    idx = jnp.full((size,), n, jnp.int32).at[
        jnp.where(mask, rank - 1, size)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
    return idx, rank[-1]


def write_in_blocks(mask: jnp.ndarray, write, bufs):
    """WRITE NARROW: hand ``write`` the True rows of ``mask`` [n], lowest
    first, ``K = block_width(n)`` a block, as many blocks as they fill
    (a ``while_loop``: the count is the data's).  ``write(bufs, first,
    at, live)`` returns the new ``bufs``; ``at`` [K] are the block's
    rows (clipped), ``live`` which of them are real, ``first`` the rank
    of its first row among the True rows.  Returns ``(bufs, count,
    blocks)``: True rows, blocks written.  The one place the visited
    table's scatter and the sharded engine's frontier append share: the
    chip pays a scatter per INDEX it is handed, written or dropped, so
    neither hands it the rows that write nothing."""
    n = mask.shape[0]
    K = block_width(n)
    idx, count = compact(mask, K)
    blocks = (count + (K - 1)) // K

    def block(st):
        b, bufs = st
        at = jax.lax.dynamic_slice(idx, (b * K,), (K,))
        return b + 1, write(bufs, b * K, jnp.minimum(at, n - 1), at < n)

    _, bufs = jax.lax.while_loop(
        lambda st: st[0] < blocks, block, (jnp.int32(0), bufs))
    return bufs, count, blocks


def _claimed(cols, keys_t):
    """Gathered bucket columns [ROWS, k] with key ``i`` ([4, k]) written
    into the first empty slot of column ``i``."""
    bkt = cols.reshape(BKT, 4, -1)
    first_empty = jnp.argmax(jnp.all(bkt == MAXU32, axis=1), axis=0)
    return jnp.where(jnp.arange(BKT)[:, None, None] == first_empty,
                     keys_t, bkt).reshape(cols.shape)


def _live_columns(table, bkt_i, unres):
    """PROBE NARROW: the bucket columns ``table[:, bkt_i]`` [ROWS, n] of
    the LIVE blocks of a batch — the blocks of ``K`` =
    :func:`block_width` consecutive rows in which some key is still
    unresolved, lowest first, as many gathers of ``K`` indices as there
    are (a ``while_loop``: the count is the data's; the table is only
    read).  The chip pays a gather per INDEX it is handed (41–51 ns
    each on the 2^24 / 2^25-slot tables: PERF.md, PR 36), resolved or
    not, and the sharded engine's ``route`` sorts the valid keys of a
    batch first, so most of a deep level's blocks hold nothing to
    probe.  A dead block's columns stay zero: every use of them is
    masked by ``unres``.  Where ``K`` does not divide the batch the last
    block reads back over its neighbour's rows.  A batch no wider than
    ``K`` is one gather.  Returns ``(cols, indices handed to the
    gather)``."""
    n = bkt_i.shape[0]
    K = block_width(n)
    if n <= K:
        return table[:, bkt_i], jnp.int32(n)
    nb = -(-n // K)
    live, count = compact(jnp.any(jnp.pad(
        unres, (0, nb * K - n)).reshape(nb, K), axis=1), nb)

    def block(st):
        b, cols = st
        at = jnp.minimum(live[b] * K, n - K)
        got = table[:, jax.lax.dynamic_slice(bkt_i, (at,), (K,))]
        return b + 1, jax.lax.dynamic_update_slice(cols, got, (0, at))

    _, cols = jax.lax.while_loop(
        lambda st: st[0] < count, block,
        (jnp.int32(0), jnp.zeros((ROWS, n), table.dtype)))
    return cols, count * K


def _probe_iter(table, keys_t, bkt_i, ps, unres, idx, RT, batch_n):
    """One probe iteration over any batch (keys_t = the keys, [4, n];
    idx = each row's identity for reservation tie-breaks; rows with
    unres=False are inert).  PROBE NARROW: gathers the whole bucket
    column of each key in a block that still holds an unresolved one
    (:func:`_live_columns`), resolves membership across its BKT slots
    at the batch's width, and lets the minimum-index contender of each
    bucket claim the first empty slot; losers re-read the same bucket
    next iteration, full buckets advance by the key's double-hash step.
    WRITE NARROW: only the winners' columns are scattered back, ``K``
    (:func:`block_width` of this batch: an eighth of it) a block,
    lowest index first, as many blocks as the winners fill — the chip
    pays a scatter per INDEX handed to it, written or dropped, and in a
    deep level nine keys in ten win nothing, in the tail's buffer more.
    A batch no wider than ``K`` (a small probe) is its own block, read
    and written.  Returns the blocks scattered and the columns gathered
    last."""
    VB = table.shape[1]
    n = bkt_i.shape[0]
    cols, gathered = _live_columns(table, bkt_i, unres)
    bkt = cols.reshape(BKT, 4, -1)
    eq = jnp.any(jnp.all(bkt == keys_t, axis=1), axis=0)
    has_empty = jnp.any(jnp.all(bkt == MAXU32, axis=1), axis=0)
    want = unres & ~eq & has_empty
    rcell = bkt_i & (RT - 1)
    res = jnp.full((RT + 1,), batch_n, jnp.int32).at[
        jnp.where(want, rcell, RT)].min(idx)
    winner = want & (res[rcell] == idx)
    if n <= block_width(n):
        blocks = jnp.int32(1)
        table = table.at[:, jnp.where(winner, bkt_i, VB)].set(
            _claimed(cols, keys_t), mode="drop")
    else:
        # At most one winner a bucket, so a block's columns are as the
        # gather above read them whatever the blocks before it wrote.
        def write(tbl, _, at, won):
            col = bkt_i[at]
            return tbl.at[:, jnp.where(won, col, VB)].set(
                _claimed(tbl[:, col], keys_t[:, at]), mode="drop")

        table, _, blocks = write_in_blocks(winner, write, table)
    newly = eq | winner
    nb = (bkt_i.astype(jnp.uint32) + ps).astype(jnp.int32) & (VB - 1)
    bkt_i = jnp.where(unres & ~newly & ~has_empty, nb, bkt_i)
    return table, bkt_i, newly & unres, winner & unres, blocks, gathered


def insert_jnp(table: jnp.ndarray, keys: jnp.ndarray, valid: jnp.ndarray,
               max_iters: int = 64, count_blocks: bool = False,
               count_cols: bool = False):
    """Membership + insert of a key batch in one bounded probe — the
    pure-jnp reference implementation (the Pallas kernel's parity
    oracle AND the CPU/interpret fallback; :func:`insert` dispatches).

    ``table`` [32, V/8] uint32 (:func:`table_shape`; V a power of
    two), ``keys`` [N, 4] uint32 (pre-:func:`sanitize_keys`-ed or raw —
    sanitisation is applied here), ``valid`` [N] bool.

    Returns ``(table', inserted, unresolved)`` where ``inserted[i]`` is
    True iff key i claimed a slot this call (exactly one copy of each
    distinct key ever wins, even with in-batch duplicates) and
    ``unresolved[i]`` is True iff the probe exhausted before key i
    resolved — the table-full overflow case.  Callers MUST treat
    unresolved keys as fresh (sound re-exploration, never a silent
    drop) and surface ``sum(unresolved)`` as a visible overflow flag.
    With ``count_blocks`` one value more: the write blocks the probe
    scattered (:func:`_probe_iter`), an int32 scalar; with ``count_cols``
    another, last: the bucket columns it gathered (indices handed to
    the table's gather, full phase and tail together), an int32 scalar.
    Pure jnp — usable under jit, inside shard_map bodies, and inside
    the Pallas kernel body.
    """
    # Named in the HLO's metadata wherever it is traced
    # (tpu/telemetry.py DEVICE_SCOPES).  A ``with`` in this frame, not a
    # wrapper function: a frame more under all that the insert traces
    # changes how long the tracing takes (PERF.md section 7).
    with jax.named_scope("dslabs.visited_insert"):
        VB = table.shape[1]
        check_cap(VB * BKT)
        n = keys.shape[0]
        skeys = sanitize_keys(keys, valid)
        keys_t = skeys.T
        slot0 = (skeys[:, 2] & jnp.uint32(VB - 1)).astype(jnp.int32)
        pstep = (skeys[:, 1] | jnp.uint32(1)).astype(jnp.uint32)
        # Reservations go through a small HASHED table (bkt_i mod RT): a
        # collision between two DISTINCT buckets just makes one contender
        # retry next iteration — a winner must still re-win its own cell.
        RT = 1 << max((n * 2 - 1).bit_length(), 10)
        # Tail threshold: once fewer than T keys remain unresolved, compact
        # them so late iterations stop re-scanning the whole batch.
        T = block_width(n)
        ridx = jnp.arange(n, dtype=jnp.int32)

        def full_cond(st):
            _, _, resolved, _, it, _, _ = st
            # ONE guaranteed full-batch iteration: below 50% table load the
            # first bucket read resolves all but the full-bucket collisions,
            # which fit the tail buffer.
            return ((it < 1) | (jnp.sum(~resolved) > T)) & (
                it < max_iters) & jnp.any(~resolved)

        def full_body(st):
            tbl, bkt_i, resolved, ins, it, wb, pc = st
            tbl, bkt_i, newly, winner, blocks, gathered = _probe_iter(
                tbl, keys_t, bkt_i, pstep, ~resolved, ridx, RT, n)
            return (tbl, bkt_i, resolved | newly, ins | winner, it + 1,
                    wb + blocks, pc + gathered)

        table, bkt_i, resolved, inserted, _, wb, pc = jax.lax.while_loop(
            full_cond, full_body,
            (table, slot0, ~valid, jnp.zeros(n, bool), jnp.int32(0),
             jnp.int32(0), jnp.int32(0)))

        # ---- tail phase: compact the unresolved few into [T] slots.
        tail_idx = compact(~resolved, T)[0][:T]
        tclip = tail_idx.clip(0, n - 1)
        tval = tail_idx < n
        t_keys_t = keys_t[:, tclip]
        t_bkt = bkt_i[tclip]
        t_ps = pstep[tclip]
        t_id = jnp.arange(T, dtype=jnp.int32)

        def tail_cond(st):
            _, _, t_unres, _, it, _, _ = st
            return (it < max_iters) & jnp.any(t_unres)

        def tail_body(st):
            tbl, tb, t_unres, t_ins, it, wb, pc = st
            tbl, tb, newly, winner, blocks, gathered = _probe_iter(
                tbl, t_keys_t, tb, t_ps, t_unres, t_id, RT, n)
            return (tbl, tb, t_unres & ~newly, t_ins | winner, it + 1,
                    wb + blocks, pc + gathered)

        table, _, t_unres, t_ins, _, wb, pc = jax.lax.while_loop(
            tail_cond, tail_body,
            (table, t_bkt, tval, jnp.zeros(T, bool), jnp.int32(0), wb, pc))
        resolved = resolved.at[tclip].max(tval & ~t_unres)
        inserted = inserted.at[tclip].max(t_ins & tval)
        return (table, inserted, ~resolved) + (
            (wb,) if count_blocks else ()) + ((pc,) if count_cols else ())


# ------------------------------------------------- Pallas bucket kernel
#
# ISSUE 12 leg (c): the probe/insert as a Pallas kernel.  The body runs
# the SAME traced algorithm as insert_jnp over the table resident in
# VMEM, so jnp-vs-Pallas parity is bit-exact by construction and the
# jnp path stays the oracle.  Mosaic refuses the kernel today (scatter
# has no Pallas TPU lowering), so it runs only where it is asked for by
# name: ``on`` compiles it (and raises what the compiler raises),
# ``interpret`` is the CPU parity path.

def pallas_mode() -> str:
    """Resolved DSLABS_VISITED_PALLAS knob: ``off`` (default: the jnp
    path) | ``on`` (the compiled kernel — raises off-TPU and whatever
    the TPU compiler raises; never degrades) | ``interpret`` (the
    Pallas interpreter — the CPU parity/test path)."""
    v = os.environ.get("DSLABS_VISITED_PALLAS", "off").strip().lower()
    if v in ("0", "off", "false", "no", "", "auto"):
        return "off"
    if v == "interpret":
        return "interpret"
    if v in ("1", "on", "true", "yes", "pallas"):
        return "on"
    raise ValueError(
        f"DSLABS_VISITED_PALLAS={v!r}: expected off | on | interpret")


def _pallas_interpret() -> Optional[bool]:
    """None = use the jnp path; True/False = pallas_call's interpret
    flag.  Decided at TRACE time (env + backend are trace-stable, so
    rebuilt programs lower identically — the J5 retrace contract)."""
    mode = pallas_mode()
    if mode == "off":
        return None
    if mode == "interpret":
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "DSLABS_VISITED_PALLAS=on asks for the compiled Pallas "
            f"insert, but the backend is {jax.default_backend()!r}; "
            "use DSLABS_VISITED_PALLAS=interpret for the interpreter")
    return False


def pallas_insert(table: jnp.ndarray, keys: jnp.ndarray,
                  valid: jnp.ndarray, max_iters: int = 64, *,
                  interpret: bool, count_blocks: bool = False,
                  count_cols: bool = False):
    """:func:`insert_jnp` as one Pallas kernel: table + key batch load
    into VMEM, the bounded probe runs on-chip, and the table writes
    back through an input/output alias (the in-place update the
    engines' donated carries rely on).  Same signature and bit-exact
    results as the jnp path; ``interpret=True`` runs the Pallas
    interpreter (the CPU parity path — no TPU hardware needed);
    ``interpret=False`` compiles for the TPU, which Mosaic refuses
    today (scatter)."""
    from jax.experimental import pallas as pl

    n = keys.shape[0]

    def kernel(table_ref, keys_ref, valid_ref, out_table_ref,
               ins_ref, unres_ref, counts_ref):
        tbl, ins, unres, wb, pc = insert_jnp(
            table_ref[...], keys_ref[...], valid_ref[...] != 0,
            max_iters, count_blocks=True, count_cols=True)
        out_table_ref[...] = tbl
        ins_ref[...] = ins.astype(jnp.int32)
        unres_ref[...] = unres.astype(jnp.int32)
        counts_ref[...] = jnp.stack([wb, pc])

    kwargs = {}
    if not interpret:
        # Compiled Mosaic: pin everything to VMEM (the default ANY can
        # land the table in slow HBM) and let in-batch claim conflicts
        # serialise exactly as traced.
        from jax.experimental.pallas import tpu as pltpu

        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        kwargs = dict(in_specs=[vmem, vmem, vmem],
                      out_specs=(vmem, vmem, vmem, vmem))
    table2, ins, unres, counts = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((2,), jnp.int32)),
        input_output_aliases={0: 0},
        interpret=bool(interpret), **kwargs)(
            table, keys, valid.astype(jnp.int32))
    return (table2, ins != 0, unres != 0) + (
        (counts[0],) if count_blocks else ()) + (
            (counts[1],) if count_cols else ())


def insert(table: jnp.ndarray, keys: jnp.ndarray, valid: jnp.ndarray,
           max_iters: int = 64, count_blocks: bool = False,
           count_cols: bool = False):
    """THE probe/insert entry point both engines trace:
    :func:`insert_jnp` by default on every backend; the Pallas kernel
    only where :func:`pallas_mode` asks for it by name (``on`` compiles
    or raises, ``interpret`` interprets).  Contract and return values
    are identical across paths (see ``insert_jnp``)."""
    interp = None if _FORCE_JNP else _pallas_interpret()
    if interp is None:
        return insert_jnp(table, keys, valid, max_iters, count_blocks,
                          count_cols)
    return pallas_insert(table, keys, valid, max_iters, interpret=interp,
                         count_blocks=count_blocks, count_cols=count_cols)


def dispatch_site_program(cap: int, batch: int):
    """The ``visited.insert`` audit-site entry (ISSUE 12): the ACTIVE
    probe/insert variant as a standalone jitted program over abstract
    args, shaped like one owner-side dedup call — what the jaxpr
    auditor lowers (J1/J2/J4: no callbacks, no f64, no collectives in
    the single-device kernel) and the profiler's hot-site table counts
    via ``telemetry.DISPATCH_SITES``."""
    args = (jax.ShapeDtypeStruct(table_shape(cap), jnp.uint32),
            jax.ShapeDtypeStruct((batch, 4), jnp.uint32),
            jax.ShapeDtypeStruct((batch,), jnp.bool_))

    def visited_insert(t, k, v):
        return insert(t, k, v)

    def build():
        return jax.jit(visited_insert, donate_argnums=0)

    return dict(fn=build(), args=args, donate=(0,), multi=False,
                builder=build)
