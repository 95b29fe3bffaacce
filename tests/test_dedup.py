"""The shared device-resident visited table (dslabs_tpu/tpu/visited.py)
and the single-device device-resident wave loop built on it (ISSUE 1):

* collision/eviction unit tests with crafted keys sharing one bucket;
* the overflow contract — a full table treats unresolved keys as FRESH
  (sound, may re-explore; never a silent drop) behind a visible flag,
  in the module, the single-device engine, and the sharded engine;
* dedup parity — the device-table loop must produce the IDENTICAL
  unique-state set and final verdict as the legacy host ``sorted_member``
  loop (``run_host``, the parity oracle) on lab0 pingpong and lab1
  clientserver;
* the transfer contract — per-wave device->host transfers in the device
  loop are scalars only (no [N, 4] fingerprint pulls), counted through
  the ``engine.device_get`` instrumented wrapper.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import dslabs_tpu.tpu.engine as engine  # noqa: E402
from dslabs_tpu.tpu import visited as visited_mod  # noqa: E402
from dslabs_tpu.tpu.engine import CapacityOverflow, TensorSearch  # noqa: E402
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402

BKT = visited_mod.BKT


def _keys_in_bucket(n, cap, bucket=3, seed=0):
    """Craft n distinct keys whose home bucket (lane 2 & (cap/BKT - 1))
    is ``bucket`` — bucket-collision fodder for the probe loop."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2 ** 32, size=(n, 4), dtype=np.uint64).astype(
        np.uint32)
    vb = cap // BKT
    keys[:, 2] = (keys[:, 2] & ~np.uint32(vb - 1)) | np.uint32(bucket)
    # Distinctness: lane 3 is a counter, so no two crafted keys collide.
    keys[:, 3] = np.arange(n, dtype=np.uint32)
    return jnp.asarray(keys)


def test_bucket_collision_spills_to_probe_chain():
    """More same-bucket keys than one bucket holds: the overflow walks
    the double-hash chain, every key inserts exactly once, and a second
    insert of the same batch resolves all of them as known."""
    cap = 1 << 10
    keys = _keys_in_bucket(BKT + 5, cap)
    valid = jnp.ones((keys.shape[0],), bool)
    table, ins, unres = visited_mod.insert(
        visited_mod.empty_table(cap), keys, valid)
    assert int(ins.sum()) == keys.shape[0]
    assert int(unres.sum()) == 0
    # Home bucket completely full, spill landed elsewhere.
    home = visited_mod.host_occupied(np.asarray(table)[:, 3:4])
    assert home.shape[0] == BKT
    _, ins2, unres2 = visited_mod.insert(table, keys, valid)
    assert int(ins2.sum()) == 0 and int(unres2.sum()) == 0


def test_in_batch_duplicates_insert_once():
    cap = 1 << 10
    base = _keys_in_bucket(4, cap, seed=1)
    dup = jnp.concatenate([base, base, base])
    valid = jnp.ones((dup.shape[0],), bool)
    table, ins, unres = visited_mod.insert(
        visited_mod.empty_table(cap), dup, valid)
    assert int(ins.sum()) == 4          # one copy of each distinct key
    assert int(unres.sum()) == 0
    assert visited_mod.host_occupied(table).shape[0] == 4


def test_full_table_overflow_is_visible_and_fresh():
    """The overflow contract at module level: with every slot taken, new
    keys come back UNRESOLVED (visible flag) — candidates for sound
    re-exploration, never silently swallowed as 'seen'."""
    cap = BKT                           # one bucket = the whole table
    fill = _keys_in_bucket(BKT, cap, bucket=0, seed=2)
    table, ins, unres = visited_mod.insert(
        visited_mod.empty_table(cap), fill, jnp.ones((BKT,), bool))
    assert int(ins.sum()) == BKT and int(unres.sum()) == 0
    more = _keys_in_bucket(3, cap, bucket=0, seed=3)
    more = more.at[:, 3].add(1000)      # distinct from the fill batch
    table, ins2, unres2 = visited_mod.insert(
        table, more, jnp.ones((3,), bool))
    assert int(ins2.sum()) == 0
    assert int(unres2.sum()) == 3       # all flagged, none dropped
    # Known keys still resolve as known even when the table is full.
    _, ins3, unres3 = visited_mod.insert(
        table, fill, jnp.ones((BKT,), bool))
    assert int(ins3.sum()) == 0 and int(unres3.sum()) == 0


# ------------------------------------------- the independent host model

class _HostTable:
    """A sequential numpy open-addressing table with the insert's
    addressing and nothing else of it: ``cap / BKT`` buckets of BKT
    slots, home bucket ``lane 2 mod buckets``, double-hash step ``lane 1
    | 1`` (odd, so a probe walks every bucket), first empty slot of the
    first bucket with room.  One key at a time, in batch order."""

    def __init__(self, cap):
        self.vb = cap // BKT
        self.buckets = [[] for _ in range(self.vb)]

    def insert(self, keys, valid):
        """(inserted, unresolved) flags of one batch."""
        ins = np.zeros(len(keys), bool)
        unres = np.zeros(len(keys), bool)
        for i, (key, ok) in enumerate(zip(keys, valid)):
            if not ok:
                continue
            key = tuple(int(x) for x in visited_mod.host_sanitize_key(key))
            b = key[2] % self.vb
            for _ in range(self.vb):
                if key in self.buckets[b]:
                    break
                if len(self.buckets[b]) < BKT:
                    self.buckets[b].append(key)
                    ins[i] = True
                    break
                b = (b + (key[1] | 1)) % self.vb
            else:
                unres[i] = True
        return ins, unres

    def keys(self):
        return sorted(k for b in self.buckets for k in b)


def _rand_keys(n, seed, cap=None, buckets=None):
    """n distinct random keys; with ``buckets``, crowded into that many
    home buckets of a ``cap``-slot table."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2 ** 32, size=(n, 4), dtype=np.uint64).astype(
        np.uint32)
    keys[:, 3] = np.arange(n, dtype=np.uint32) + np.uint32(seed << 16)
    if buckets is not None:
        vb = cap // BKT
        keys[:, 2] = (keys[:, 2] & ~np.uint32(vb - 1)) | rng.integers(
            0, buckets, size=n).astype(np.uint32)
    return keys


def _dup_batches():
    base = _rand_keys(40, 1)
    return 1 << 10, [(np.concatenate([base, base[::-1], base[5:25]]),
                      None)]


def _crowded_batches():
    cap = 1 << 9
    return cap, [(_rand_keys(150, 2, cap, buckets=3), None),
                 (_rand_keys(90, 3, cap, buckets=2), None)]


def _reinsert_batches():
    first, more = _rand_keys(200, 4), _rand_keys(60, 5)
    return 1 << 10, [(first, None),
                     (np.concatenate([more[:30], first, more[30:]]),
                      None)]


def _overflow_full_batches():
    """Exactly ``cap`` keys fill the table (every probe walks every
    bucket); of the next batch the known keys are seen and every new
    key is unresolved."""
    cap = 4 * BKT
    fill, more = _rand_keys(cap, 6), _rand_keys(12, 7)
    return cap, [(fill, None),
                 (np.concatenate([more[:6], fill[::3], more[6:]]), None)]


def _invalid_batches():
    keys = _rand_keys(120, 8)
    batch = np.concatenate([keys, keys[:40]])
    valid = np.ones(len(batch), bool)
    valid[:60:2] = False          # an invalid first copy: the later one wins
    valid[100:120] = False        # keys that appear on no valid row
    batch[6] = 0xFFFFFFFF         # the EMPTY marker on an invalid row
    batch[130] = 0xFFFFFFFF       # ... and on a valid one (sanitised)
    return 1 << 10, [(batch, valid), (batch, ~valid)]


def _parallel_model(table, keys, valid, max_iters=64):
    """The insert's PARALLEL semantics as plain numpy, every iteration
    at the batch's full width and every winner written on its own: what
    the table must hold SLOT FOR SLOT however the device issues the
    write (a whole-batch scatter until PR 34; blocks of
    ``visited.block_width`` winners since).  Returns ``(table, inserted,
    unresolved, write_blocks)``."""
    table = np.array(table)
    vb, n = table.shape[1], len(keys)
    keys = np.array([visited_mod.host_sanitize_key(k) if ok else k
                     for k, ok in zip(keys, valid)], np.uint32)
    at = (keys[:, 2] & np.uint32(vb - 1)).astype(np.int64)
    step = (keys[:, 1] | np.uint32(1)).astype(np.int64)
    cells = 1 << max((n * 2 - 1).bit_length(), 10)
    width = visited_mod.block_width(n)
    unres, ins = np.array(valid, bool), np.zeros(n, bool)
    full = tail = blocks = 0
    while unres.any():
        if tail == 0 and full < max_iters and (
                full < 1 or unres.sum() > width):
            full += 1
            batch = n
        elif tail < max_iters:
            tail += 1
            batch = width
        else:
            break
        slots = table[:, at].reshape(BKT, 4, n)
        eq = (slots == keys.T[None]).all(axis=1).any(axis=0)
        empty = (slots == visited_mod.MAXU32).all(axis=1)
        want = unres & ~eq & empty.any(axis=0)
        cell = at & (cells - 1)
        first = np.full(cells, n)
        np.minimum.at(first, cell[want], np.nonzero(want)[0])
        winner = want & (first[cell] == np.arange(n))
        for i in np.nonzero(winner)[0]:
            slot = int(empty[:, i].argmax())
            table[slot * 4:slot * 4 + 4, at[i]] = keys[i]
        block = visited_mod.block_width(batch)
        blocks += 1 if batch <= block else -(-int(winner.sum()) // block)
        ins |= winner
        at = np.where(unres & ~eq & ~winner & ~empty.any(axis=0),
                      (at + step) & (vb - 1), at)
        unres &= ~(eq | winner)
    return table, ins, unres, blocks


# Block-edge batches: 2,048 keys, so a write block holds K = 256
# winners (visited.block_width) and the table's 4,096 buckets are the
# reservation's 4,096 cells — keys of distinct home buckets all win in
# the first iteration.
_EDGE_N, _EDGE_CAP = 2048, 1 << 15


def _own_bucket_keys(n, seed, first=0, cap=_EDGE_CAP):
    """n distinct keys, key i at home in bucket ``first + i`` of a
    ``cap``-slot table."""
    keys = _rand_keys(n, seed)
    vb = cap // BKT
    keys[:, 2] = (keys[:, 2] & ~np.uint32(vb - 1)) | (
        np.arange(first, first + n, dtype=np.uint32))
    return keys


def _known_then(fresh, at_end=True):
    """A first batch of 2,048 keys, then the same batch with ``fresh``
    of its rows replaced by new keys (in the last positions, or spread
    from the first): exactly ``fresh`` winners in one iteration."""
    base = _own_bucket_keys(_EDGE_N, 11)
    again = base.copy()
    new = _own_bucket_keys(fresh, 12, first=_EDGE_N)
    rows = (np.arange(_EDGE_N - fresh, _EDGE_N) if at_end
            else np.arange(fresh) * (_EDGE_N // max(fresh, 1)))
    again[rows] = new
    return _EDGE_CAP, [(base, None), (again, None)]


def _zero_winners_batches():
    return _known_then(0)


def _exactly_k_winners_batches():
    return _known_then(256, at_end=False)


def _k_plus_one_winners_batches():
    return _known_then(257, at_end=False)


def _every_key_fresh_batches():
    return _EDGE_CAP, [(_own_bucket_keys(_EDGE_N, 13), None),
                       (_rand_keys(_EDGE_N, 14), None)]


def _winners_last_batches():
    return _known_then(300)


def _narrow_batches():
    """n < 256: the batch is its own block."""
    return 1 << 10, [(_rand_keys(200, 15), None),
                     (_rand_keys(9, 16), None)]


def _tail_winners_batches():
    """Half the batch crowded into 40 buckets: the first iteration
    leaves under K keys unresolved, each still to win a slot, and the
    tail's iterations write them."""
    cap = 1 << 13
    keys = _rand_keys(_EDGE_N, 17)
    keys[::2] = _rand_keys(_EDGE_N, 18, cap, buckets=40)[::2]
    return cap, [(keys, None), (keys[::-1].copy(), None)]


def _tail_blocks_batches():
    """4,096 keys (a tail of 512, written 256 a block): 400 pairs of
    fresh keys share a home bucket, so the first iteration seats one of
    each pair and the tail's first iteration the other 400 — two
    blocks."""
    known = _own_bucket_keys(3296, 19)
    pairs = np.concatenate([_own_bucket_keys(400, 20, first=3296),
                            _own_bucket_keys(400, 21, first=3296)])
    return _EDGE_CAP, [(known, None),
                       (np.concatenate([known, pairs]), None)]


@pytest.mark.parametrize("make", [
    _dup_batches, _crowded_batches, _reinsert_batches,
    _overflow_full_batches, _invalid_batches,
    _zero_winners_batches, _exactly_k_winners_batches,
    _k_plus_one_winners_batches, _every_key_fresh_batches,
    _winners_last_batches, _narrow_batches, _tail_winners_batches,
    _tail_blocks_batches,
], ids=["in-batch-duplicates", "crowded-buckets", "re-insert",
        "overflow-full-table", "invalid-rows-inert",
        "zero-winners", "exactly-K-winners", "K+1-winners",
        "every-key-fresh", "winners-in-the-last-positions",
        "batch-narrower-than-K", "tail-entered-with-winners-left",
        "tail-writes-two-blocks"])
def test_insert_matches_sequential_host_model(make):
    """``inserted``, ``unresolved`` and the table's key set against the
    sequential host model, and the table SLOT FOR SLOT with the blocks
    it was written in against the parallel one, batch after batch on
    one table."""
    cap, batches = make()
    table, model = visited_mod.empty_table(cap), _HostTable(cap)
    for keys, valid in batches:
        valid = np.ones(len(keys), bool) if valid is None else valid
        want_table, par_ins, par_unres, want_blocks = _parallel_model(
            table, keys, valid)
        table, ins, unres, blocks = visited_mod.insert(
            table, jnp.asarray(keys), jnp.asarray(valid),
            count_blocks=True)
        want_ins, want_unres = model.insert(keys, valid)
        assert np.array_equal(np.asarray(ins), want_ins)
        assert np.array_equal(np.asarray(unres), want_unres)
        assert np.array_equal(par_ins, want_ins)
        assert np.array_equal(par_unres, want_unres)
        assert np.array_equal(np.asarray(table), want_table)
        assert int(blocks) == want_blocks
        got = visited_mod.host_occupied(table)
        assert sorted(map(tuple, got.tolist())) == model.keys()
    assert table.shape == visited_mod.table_shape(cap)


@pytest.mark.parametrize("make,blocks", [
    (_zero_winners_batches, [8, 0]),
    (_exactly_k_winners_batches, [8, 1]),
    (_k_plus_one_winners_batches, [8, 2]),
    (_winners_last_batches, [8, 2]),
    (_tail_blocks_batches, [8, 1 + 2]),
], ids=["zero-winners", "exactly-K", "K+1", "last-positions",
        "tail-of-two-blocks"])
def test_write_blocks_follow_the_winners(make, blocks):
    """An iteration scatters ``ceil(winners / K)`` blocks of its own
    batch's ``K``: none where nothing won, 8 where all 2,048 keys did,
    two in a tail whose 512 places hold 400 winners."""
    cap, batches = make()
    table = visited_mod.empty_table(cap)
    for (keys, _), want in zip(batches, blocks):
        table, ins, unres, got = visited_mod.insert(
            table, jnp.asarray(keys), jnp.ones((len(keys),), bool),
            count_blocks=True)
        assert int(got) == want
        assert int(ins.sum()) <= want * visited_mod.block_width(len(keys))
        assert not np.asarray(unres).any()


# Live-block batches (PR 37): the probe gathers bucket columns only for
# the blocks of K = visited.block_width(n) consecutive rows that still
# hold an unresolved key.  2,048 rows are 8 blocks of 256; every valid
# key has a home bucket of its own unless the case says otherwise, so
# ONE full iteration resolves the batch and the tail never runs.

def _valid_rows(n, rows):
    valid = np.zeros(n, bool)
    valid[rows] = True
    return valid


def _prefix_batches(rows):
    def make():
        return _EDGE_CAP, [(_own_bucket_keys(_EDGE_N, 31),
                            _valid_rows(_EDGE_N, np.arange(rows)))]
    return make


def _bucket_prefix_batches():
    """The mesh's shape: four received buckets of two blocks each, the
    valid keys a prefix of each bucket."""
    rows = np.concatenate([np.arange(b, b + 100)
                           for b in (0, 512, 1024, 1536)])
    return _EDGE_CAP, [(_own_bucket_keys(_EDGE_N, 32),
                        _valid_rows(_EDGE_N, rows))]


def _scattered_batches():
    """Valid keys all over an unsorted batch (the single-device
    engine's): every block live, the parent's width."""
    return _EDGE_CAP, [(_own_bucket_keys(_EDGE_N, 33),
                        _valid_rows(_EDGE_N, np.arange(3, _EDGE_N, 7)))]


def _last_block_batches():
    return _EDGE_CAP, [(_own_bucket_keys(_EDGE_N, 34),
                        _valid_rows(_EDGE_N, np.arange(2000, _EDGE_N)))]


def _no_valid_key_batches():
    return _EDGE_CAP, [(_own_bucket_keys(_EDGE_N, 35),
                        np.zeros(_EDGE_N, bool))]


def _ragged_last_block_batches():
    """2,055 rows: K = 256 does not divide the batch, and the ninth
    block (rows 2,048-2,054) reads back over its neighbour's rows."""
    n = _EDGE_N + 7
    return _EDGE_CAP, [(_own_bucket_keys(n, 36),
                        _valid_rows(n, np.arange(_EDGE_N, n)))]


def _second_iteration_batches():
    """Rows 1,700-1,999 (blocks 6 and 7) share the home buckets of rows
    0-299, which win them first: 300 keys, more than K, go into a
    SECOND full iteration, and only their two blocks are read again (a
    second full iteration needs more than K unresolved keys, so two
    live blocks are the fewest it can have)."""
    keys = _own_bucket_keys(_EDGE_N, 37)
    keys[1700:2000, 2] = keys[:300, 2]
    return _EDGE_CAP, [(keys, None)]


def _tail_one_block_batches():
    """16,384 rows: the tail's 2,048 places are 8 blocks of 256.  200
    pairs of fresh keys share a home bucket; the first iteration seats
    one of each pair, and the tail compacts the other 200, lowest
    first, into the first of its eight blocks."""
    cap, n = 1 << 17, 1 << 14
    known = _own_bucket_keys(n - 400, 38, cap=cap)
    pairs = np.concatenate([
        _own_bucket_keys(200, seed, first=n - 400, cap=cap)
        for seed in (39, 40)])
    return cap, [(known, None), (np.concatenate([known, pairs]), None)]


@pytest.mark.parametrize("make,cols", [
    (_prefix_batches(200), [256]),
    (_prefix_batches(600), [3 * 256]),
    (_prefix_batches(_EDGE_N), [8 * 256]),
    (_bucket_prefix_batches, [4 * 256]),
    (_scattered_batches, [8 * 256]),
    (_last_block_batches, [256]),
    (_no_valid_key_batches, [0]),
    (_ragged_last_block_batches, [256]),
    (_second_iteration_batches, [8 * 256 + 2 * 256]),
    (_tail_one_block_batches, [8 * 1998, 8 * 2048 + 256]),
], ids=["prefix-of-1-block", "prefix-of-3-blocks", "prefix-of-8-blocks",
        "four-bucket-prefixes", "scattered-all-live", "last-block-only",
        "no-valid-key", "K-does-not-divide-the-batch",
        "second-iteration-two-blocks-live", "tail-fills-one-block-of-8"])
def test_probe_gathers_only_the_live_blocks(make, cols):
    """PROBE NARROW: an iteration hands the table's gather ``K`` indices
    for each block of its batch that still holds an unresolved key and
    none for the others — and the table, SLOT FOR SLOT, ``inserted``,
    ``unresolved`` and the write blocks are what the host models give,
    which read every column at the batch's full width."""
    cap, batches = make()
    table, model = visited_mod.empty_table(cap), _HostTable(cap)
    for (keys, valid), want_cols in zip(batches, cols):
        valid = np.ones(len(keys), bool) if valid is None else valid
        want_table, par_ins, par_unres, want_blocks = _parallel_model(
            table, keys, valid)
        table, ins, unres, blocks, got_cols = visited_mod.insert(
            table, jnp.asarray(keys), jnp.asarray(valid),
            count_blocks=True, count_cols=True)
        want_ins, want_unres = model.insert(keys, valid)
        assert np.array_equal(np.asarray(ins), want_ins)
        assert np.array_equal(np.asarray(unres), want_unres)
        assert np.array_equal(par_ins, want_ins)
        assert np.array_equal(par_unres, want_unres)
        assert np.array_equal(np.asarray(table), want_table)
        assert int(blocks) == want_blocks
        assert int(got_cols) == want_cols
        assert not want_unres.any()
    got = visited_mod.host_occupied(table)
    assert sorted(map(tuple, got.tolist())) == model.keys()


def test_overflow_unresolved_are_exactly_the_keys_that_did_not_fit():
    """A table with room for SOME of a batch: which keys win the last
    slots is the reservation's business, but ``unresolved`` is exactly
    the valid keys that are not in the table afterwards, ``inserted``
    exactly those that are, and the two fill it to the last slot."""
    cap = 2 * BKT
    first, more = _rand_keys(cap - 5, 9), _rand_keys(11, 10)
    table, ins, unres = visited_mod.insert(
        visited_mod.empty_table(cap), jnp.asarray(first),
        jnp.ones((len(first),), bool))
    assert int(ins.sum()) == cap - 5 and int(unres.sum()) == 0
    table, ins, unres = visited_mod.insert(
        table, jnp.asarray(more), jnp.ones((len(more),), bool))
    ins, unres = np.asarray(ins), np.asarray(unres)
    stored = set(map(tuple, visited_mod.host_occupied(table).tolist()))
    assert len(stored) == cap
    assert int(ins.sum()) == 5 and int(unres.sum()) == 6
    assert not (ins & unres).any()
    for key, i, u in zip(more.tolist(), ins, unres):
        assert (tuple(key) in stored) == bool(i) == (not u)
    assert stored >= set(map(tuple, first.tolist()))


@pytest.mark.parametrize("n_devices", [1, 3],
                         ids=["one-device", "stacked"])
def test_host_occupied_returns_exactly_the_inserted_keys(n_devices):
    """``host_occupied`` of a table, and of per-device tables stacked
    the way the sharded carry stacks them, is the inserted keys —
    device by device; and a root placed by ``with_root`` sits where
    the probe looks for it."""
    cap = 1 << 8
    per_dev = [_rand_keys(50 + 7 * d, 20 + d) for d in range(n_devices)]
    stacked = visited_mod.empty_table(cap, n_devices)
    assert stacked.shape == visited_mod.table_shape(cap, n_devices)
    rows = visited_mod.table_shape(cap)[0]
    root = _rand_keys(1, 30)[0]
    owner = n_devices - 1
    stacked = visited_mod.with_root(
        stacked, jnp.asarray(root),
        visited_mod.host_home_slot(root, cap), owner)
    tables = []
    for d, keys in enumerate(per_dev):
        t, ins, unres = visited_mod.insert(
            stacked[d * rows:(d + 1) * rows], jnp.asarray(keys),
            jnp.ones((len(keys),), bool))
        assert int(ins.sum()) == len(keys) and int(unres.sum()) == 0
        tables.append(np.asarray(t))
    _, ins, _ = visited_mod.insert(
        jnp.asarray(tables[owner]), jnp.asarray(root[None]),
        jnp.ones((1,), bool))
    assert int(ins.sum()) == 0          # the root was found, not added
    per_dev[owner] = np.concatenate([per_dev[owner], root[None]])
    got = visited_mod.host_occupied(np.concatenate(tables))
    assert got.shape == (sum(map(len, per_dev)), 4)
    at = 0
    for keys in per_dev:                # device by device, in order
        part = got[at:at + len(keys)]
        assert sorted(map(tuple, part.tolist())) == sorted(
            map(tuple, keys.tolist()))
        at += len(keys)


def _pruned_pingpong(w=2):
    pp = make_pingpong_protocol(w)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def _pruned_clientserver(nc=2, w=1):
    cs = make_clientserver_protocol(n_clients=nc, w=w)
    return dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]})


def _table_key_set(search):
    """Extract the device table's occupied keys as a set of
    (h1, h2) uint64 pairs (the host oracle's key format)."""
    rows = visited_mod.host_occupied(
        search._last_dev_carry["visited"]).astype(np.uint64)
    h1 = (rows[:, 0] << np.uint64(32)) | rows[:, 1]
    h2 = (rows[:, 2] << np.uint64(32)) | rows[:, 3]
    return set(zip(h1.tolist(), h2.tolist()))


@pytest.mark.parametrize("proto,chunk", [
    (_pruned_pingpong(), 64),
    (_pruned_clientserver(), 128),
], ids=["lab0-pingpong", "lab1-clientserver"])
def test_device_table_matches_host_oracle(proto, chunk):
    """Verdict + unique COUNT + unique SET parity: the device-table loop
    against the legacy host sorted_member loop on the same protocol."""
    dev = TensorSearch(proto, chunk=chunk)
    d = dev.run()
    host = TensorSearch(proto, chunk=chunk)
    h = host.run_host()
    assert d.end_condition == h.end_condition == "SPACE_EXHAUSTED"
    assert d.unique_states == h.unique_states
    assert d.states_explored == h.states_explored
    assert d.visited_overflow == 0
    host_set = set(zip(host._host_visited[0].tolist(),
                       host._host_visited[1].tolist()))
    assert _table_key_set(dev) == host_set


@pytest.mark.parametrize("depth", [2, 4])
def test_device_table_depth_limited_parity(depth):
    proto = _pruned_clientserver()
    d = TensorSearch(proto, chunk=128, max_depth=depth).run()
    h = TensorSearch(proto, chunk=128, max_depth=depth).run_host()
    assert d.end_condition == h.end_condition == "DEPTH_EXHAUSTED"
    assert d.unique_states == h.unique_states
    assert d.states_explored == h.states_explored


def test_goal_verdict_parity_device_vs_host():
    pp = make_pingpong_protocol(2)
    d = TensorSearch(pp, chunk=64).run()
    h = TensorSearch(pp, chunk=64).run_host()
    assert d.end_condition == h.end_condition == "GOAL_FOUND"
    assert d.predicate_name == h.predicate_name
    assert d.depth == h.depth           # BFS shortest goal depth


def test_engine_strict_raises_on_table_full():
    """Single-device strict engine: a too-small table is a LOUD
    CapacityOverflow (exact unique counts cannot survive
    treat-as-fresh), never a silent drop or hang."""
    proto = _pruned_clientserver()
    with pytest.raises(CapacityOverflow):
        TensorSearch(proto, chunk=64, visited_cap=BKT).run()


def test_engine_beam_degrades_treat_as_fresh():
    """strict=False + a full table: the search still terminates (depth
    bound), reports a nonzero visited_overflow, and explores at LEAST
    the true space (re-exploration is sound; dropping would undercount)."""
    proto = _pruned_clientserver()
    exact = TensorSearch(proto, chunk=64, max_depth=4).run()
    tiny = TensorSearch(proto, chunk=64, max_depth=4, visited_cap=BKT,
                        strict=False).run()
    assert tiny.end_condition == "DEPTH_EXHAUSTED"
    assert tiny.visited_overflow > 0
    assert tiny.states_explored >= exact.states_explored


def test_sharded_beam_degrades_treat_as_fresh():
    """The same contract on the sharded engine (strict=False): overflow
    visible via SearchOutcome.visited_overflow, search sound.  The
    visited_cap is PER DEVICE (8 owner shards), so the space must be
    deep/wide enough that some owner's BKT-slot table fills AND then
    receives a further key — lab1 c3-w2 at depth 5 (83 unique states,
    ~10 per owner) is the smallest config that reliably does."""
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    proto = _pruned_clientserver(nc=3, w=2)
    mesh = make_mesh(8)
    exact = ShardedTensorSearch(
        proto, mesh, chunk_per_device=64, frontier_cap=1 << 10,
        visited_cap=1 << 12, strict=False, max_depth=5).run()
    assert exact.visited_overflow == 0
    tiny = ShardedTensorSearch(
        proto, mesh, chunk_per_device=64, frontier_cap=1 << 10,
        visited_cap=BKT, strict=False, max_depth=5).run()
    assert tiny.end_condition == "DEPTH_EXHAUSTED"
    assert tiny.visited_overflow > 0
    assert tiny.states_explored >= exact.states_explored


def test_sharded_strict_raises_on_table_full():
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    proto = _pruned_clientserver(nc=3, w=2)
    mesh = make_mesh(8)
    with pytest.raises(CapacityOverflow):
        ShardedTensorSearch(
            proto, mesh, chunk_per_device=64, frontier_cap=1 << 10,
            visited_cap=BKT, strict=True).run()


def test_device_loop_transfers_scalars_only(monkeypatch):
    """The acceptance contract: per-wave device->host transfers in the
    device-resident run() are scalars/short stat vectors — no [N, 4]
    fingerprint pulls, no state-row pulls.  Counted via the
    engine.device_get instrumented wrapper."""
    sizes = []
    real = engine.device_get

    def spy(x):
        arr = real(x)
        sizes.append(arr.size)
        return arr

    monkeypatch.setattr(engine, "device_get", spy)
    proto = _pruned_clientserver()
    search = TensorSearch(proto, chunk=128)
    out = search.run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert sizes, "device loop must route readbacks through device_get"
    stats_len = 7 + len(search._flag_names)
    assert max(sizes) <= stats_len, (
        f"a non-scalar readback leaked into the wave loop: {sizes}")
    # One stats vector per wave (+ spill re-syncs, none here): bounded by
    # the level count, nothing per-chunk or per-state.
    assert len(sizes) <= out.depth + 2
