"""Host-RAM spill tier: the sound capacity ladder under every engine.

ROADMAP item #4 ("bigger-than-HBM searches").  Before this module the
device visited table and the frontier buffer were hard walls: a strict
search that crossed either raised :class:`CapacityOverflow` and the
failover ladder could not help (smaller rungs have LESS capacity), and
a beam search silently narrowed (an early chip run dropped millions of
states with only a flag to show for it).  This module turns both walls
into the classic explicit-state tiering trick (disk-based / hash-compaction
checkers a la Stern & Dill): cold state moves OFF the fast device onto
host RAM, and "full" degrades to "slower, still exact".

Three cooperating pieces, all engine-agnostic (the drivers in
engine.py / sharded.py own the device half):

* :class:`HostVisitedTier` — the cold half of the visited set: an
  exact, sorted host-side store of 128-bit fingerprints (the same
  (h1, h2) uint64 representation the host parity loop uses).  When the
  device table crosses the load-factor high-water mark, its occupied
  key lines are EVICTED here in bulk and the table restarts empty; at
  every level boundary the batch of would-be-fresh states is
  RE-FILTERED against this tier (one batched readback + a corrected
  promote mask — never a per-state host sync), so a state discovered
  before an eviction is never re-expanded after one.

* :class:`FrontierSpool` — the overflow-safe frontier: rows that would
  be dropped (beam) or fatal (strict) at frontier capacity are spilled
  here and re-injected as deferred re-expansion waves AT THE SAME BFS
  DEPTH, so level/depth accounting — and therefore the soundness of a
  ``DEPTH_EXHAUSTED`` verdict — is preserved exactly.  Two spools
  (current level being consumed, next level being assembled) swap at
  each level boundary.

* :class:`SpillManager` — the bookkeeping that keeps strict counts
  EXACT across tiers.  Within one eviction epoch the device table
  dedups perfectly; across epochs a re-discovered state is counted
  once more by the device (``dup_epoch``) and the refilter both drops
  the duplicate row and subtracts the double count:

      unique = len(tier) + vis_n_device_epoch - dup_epoch

  The refilter invariants that make this exact (derived in
  docs/capacity.md):

  - every batch of rows leaving the device (a mid-level drain or the
    level-boundary promote) is refiltered against the tier BEFORE the
    next eviction can add its own keys to the tier — so a first
    discovery is never mistaken for a re-discovery;
  - each drained batch spans a single eviction epoch, so it is
    internally duplicate-free (the device table guaranteed that);
  - an aborted chunk step is reverted WHOLESALE on device (table
    included), so a retried chunk re-runs against exactly the state it
    first saw.

Checkpoints: the unified dump (tpu/checkpoint.py) stays engine- and
tier-agnostic — ``visited_keys`` stores the UNION of the device table
and the host tier (deduplicated), ``frontier`` stores the injected
rows plus every spooled segment, and the spill counters ride an
``extra__spill_stats`` array.  The host tier therefore inherits the
CRC32 checksum and ``.prev`` rotation like everything else, a non-
spill engine can resume a spill dump (if its table fits the key set),
and a spill engine resumes ANY dump by loading all keys into the tier
and starting the device table empty — which is why kill-mid-spill
resume is bit-exact.

Env knobs: ``DSLABS_SPILL`` (default engine opt-in), ``DSLABS_SPILL_
HIGH_WATER`` (eviction trigger, default 0.60 of visited_cap),
``DSLABS_SPILL_HOST_CAP`` (max keys the tier accepts before raising —
the supervisor's capacity ladder escalates it), ``DSLABS_VISITED_WARN``
(early-warning load factor, default 0.85), ``DSLABS_DROPPED_WARN``
(beam dropped-states warning threshold, default 1e6).
"""

from __future__ import annotations

import dataclasses
import os
import queue as queue_mod
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

__all__ = ["SpillConfig", "SpillStats", "HostVisitedTier",
           "FrontierSpool", "SpillManager", "spill_env_default",
           "spill_manager_for_audit",
           "VISITED_WARN_DEFAULT", "DROPPED_WARN_DEFAULT",
           "visited_warn_threshold", "dropped_warn_threshold",
           "TIER_FORMAT", "TierMismatch", "TierCorrupt",
           "save_tier", "load_tier", "peek_tier_meta"]

VISITED_WARN_DEFAULT = 0.85
DROPPED_WARN_DEFAULT = 1_000_000


def spill_env_default() -> bool:
    v = os.environ.get("DSLABS_SPILL")
    if v is None:
        return False
    return v.strip().lower() not in ("0", "", "off", "false", "no")


def spill_manager_for_audit() -> "SpillManager":
    """A minimally-configured manager whose only job is flipping an
    engine into spill mode so the sanitizer's jaxpr audit
    (dslabs_tpu/analysis/jaxpr_audit.py) can lower and check the
    spill-variant step/drain/evict programs — the audit never runs a
    search, so the tier stays empty and the tiny host cap is free."""
    return SpillManager(SpillConfig(high_water=0.60, host_cap=1 << 16))


def visited_warn_threshold() -> float:
    """Load factor past which the early-warning fires (satellite:
    operators must see pressure BEFORE overflow)."""
    try:
        return float(os.environ.get("DSLABS_VISITED_WARN", "") or
                     VISITED_WARN_DEFAULT)
    except ValueError:
        return VISITED_WARN_DEFAULT


def dropped_warn_threshold() -> int:
    try:
        return int(os.environ.get("DSLABS_DROPPED_WARN", "") or
                   DROPPED_WARN_DEFAULT)
    except ValueError:
        return DROPPED_WARN_DEFAULT


def _async_env_default() -> bool:
    v = os.environ.get("DSLABS_SPILL_ASYNC")
    if v is None:
        return True
    return v.strip().lower() not in ("0", "", "off", "false", "no")


@dataclasses.dataclass(frozen=True)
class SpillConfig:
    """Spill-tier knobs.  ``high_water``: device-table load factor that
    triggers a bulk eviction at the next boundary (the abort-and-retry
    backstop in the step programs catches anything that outruns it).
    ``host_cap``: max keys the host tier accepts; crossing it raises
    CapacityOverflow (host RAM is large, not infinite) — the
    supervisor's capacity ladder retries with a bigger tier.
    ``async_drain`` (ISSUE 15c, default ON; DSLABS_SPILL_ASYNC=0 pins
    the legacy sync-per-chunk gear): the drain's host half — tier
    refilter, prune mask, spool, eviction absorb — runs on a single
    ordered worker while the device re-dispatches the next chunk, so
    host round-trips stop serializing against device compute.  The
    single ordered queue preserves every exactness invariant (each
    batch refilters against the pre-eviction tier; counts are read
    behind a barrier)."""

    high_water: float = float(
        os.environ.get("DSLABS_SPILL_HIGH_WATER", "") or 0.60)
    host_cap: int = int(
        os.environ.get("DSLABS_SPILL_HOST_CAP", "") or (1 << 26))
    async_drain: bool = dataclasses.field(
        default_factory=_async_env_default)


@dataclasses.dataclass
class SpillStats:
    """The accounting SearchOutcome surfaces (never a silent spill).

    ``drain_wall_ms``/``drain_wait_ms`` are the async-drain wall split
    (ISSUE 15c): total host milliseconds spent inside drain jobs vs
    milliseconds the driver actually BLOCKED at a barrier waiting for
    them — their difference is host work that overlapped device
    compute (the pipelining win; zero wait = full overlap)."""

    spilled_keys: int = 0        # keys evicted device -> host tier
    host_tier_hits: int = 0      # re-discoveries the refilter removed
    respilled_frontier: int = 0  # frontier rows through the host spool
    evictions: int = 0           # bulk table evictions
    reinjections: int = 0        # deferred re-expansion waves injected
    drain_wall_ms: int = 0       # host ms inside drain jobs
    drain_wait_ms: int = 0       # host ms blocked at drain barriers

    @property
    def overlap_ms(self) -> int:
        return max(0, self.drain_wall_ms - self.drain_wait_ms)

    @property
    def overlap_ratio(self) -> float:
        """Fraction of drain-host wall hidden behind device compute."""
        if self.drain_wall_ms <= 0:
            return 0.0
        return round(self.overlap_ms / self.drain_wall_ms, 4)

    def as_array(self) -> np.ndarray:
        return np.asarray([self.spilled_keys, self.host_tier_hits,
                           self.respilled_frontier, self.evictions,
                           self.reinjections, self.drain_wall_ms,
                           self.drain_wait_ms], np.int64)

    @classmethod
    def from_array(cls, a) -> "SpillStats":
        a = np.asarray(a, np.int64).reshape(-1)
        vals = [int(x) for x in a[:7]]
        vals += [0] * (7 - len(vals))     # pre-round-2 dumps: 5 slots
        return cls(*vals)


def _rows_to_u64(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """[K, 4] uint32 device-format key rows -> (h1, h2) uint64 pairs —
    the host tier's native representation (same packing as
    engine.host_keys; duplicated here to keep spill.py import-light)."""
    keys = np.asarray(keys, np.uint64).reshape(-1, 4)
    h1 = (keys[:, 0] << np.uint64(32)) | keys[:, 1]
    h2 = (keys[:, 2] << np.uint64(32)) | keys[:, 3]
    return h1, h2


def _u64_to_rows(h1: np.ndarray, h2: np.ndarray) -> np.ndarray:
    rows = np.empty((len(h1), 4), np.uint32)
    rows[:, 0] = (h1 >> np.uint64(32)).astype(np.uint32)
    rows[:, 1] = (h1 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    rows[:, 2] = (h2 >> np.uint64(32)).astype(np.uint32)
    rows[:, 3] = (h2 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return rows


class HostVisitedTier:
    """Exact host-RAM fingerprint set: sorted (h1, h2) uint64 arrays.

    Membership reuses the collision-safe forward scan of
    ``engine.sorted_member`` (imported lazily — engine imports nothing
    from this module at top level, so no cycle)."""

    def __init__(self, host_cap: int = 1 << 26):
        self.h1 = np.empty((0,), np.uint64)
        self.h2 = np.empty((0,), np.uint64)
        self.host_cap = host_cap

    def __len__(self) -> int:
        return len(self.h1)

    def nbytes(self) -> int:
        return int(self.h1.nbytes + self.h2.nbytes)

    def absorb(self, keys: np.ndarray) -> int:
        """Merge [K, 4] key rows into the tier (sorted-merge, exact
        dedup against the existing set AND within the batch).  Returns
        the number of NEW keys added; raises CapacityOverflow past
        ``host_cap`` (the ladder escalates the cap, never silently
        drops a key)."""
        if not len(keys):
            return 0
        h1, h2 = _rows_to_u64(keys)
        order = np.lexsort((h2, h1))
        h1, h2 = h1[order], h2[order]
        first = np.ones(len(h1), bool)
        first[1:] = (h1[1:] != h1[:-1]) | (h2[1:] != h2[:-1])
        h1, h2 = h1[first], h2[first]
        fresh = ~self._contains_u64(h1, h2)
        n_new = int(fresh.sum())
        if n_new == 0:
            return 0
        if len(self) + n_new > self.host_cap:
            from dslabs_tpu.tpu.engine import CapacityOverflow

            raise CapacityOverflow(
                f"host spill tier full: {len(self)} + {n_new} keys > "
                f"host_cap {self.host_cap} "
                "(raise DSLABS_SPILL_HOST_CAP or let the supervisor's "
                "capacity ladder escalate it)")
        mh1 = np.concatenate([self.h1, h1[fresh]])
        mh2 = np.concatenate([self.h2, h2[fresh]])
        mo = np.lexsort((mh2, mh1))
        self.h1, self.h2 = mh1[mo], mh2[mo]
        return n_new

    def _contains_u64(self, h1, h2) -> np.ndarray:
        from dslabs_tpu.tpu.engine import sorted_member

        if not len(self.h1) or not len(h1):
            return np.zeros(len(h1), bool)
        return sorted_member(self.h1, self.h2, h1, h2)

    def contains(self, keys: np.ndarray) -> np.ndarray:
        """[K, 4] key rows -> bool membership mask."""
        h1, h2 = _rows_to_u64(keys)
        return self._contains_u64(h1, h2)

    def key_rows(self) -> np.ndarray:
        """The whole tier as [K, 4] uint32 rows (checkpoint union)."""
        return _u64_to_rows(self.h1, self.h2)


# ------------------------------------------------- tier persistence
#
# Versioned on-disk format for the exact host tier (ISSUE 16 satellite:
# the cross-job memo store persists one tier per spec signature).  Same
# durability discipline as tpu/checkpoint.py: CRC32 content checksum,
# atomic tmp+replace with one-deep ``.prev`` rotation, and a LOUD
# refusal — never a silent empty tier — when the file is foreign (pack
# descriptor or symmetry flag differs from what the consumer expects)
# or torn (checksum mismatch on every candidate).

TIER_FORMAT = "dslabs-visited-tier-v1"


class TierMismatch(RuntimeError):
    """The tier on disk belongs to a different configuration (foreign
    pack descriptor, symmetry flag, or format version): its (h1, h2)
    fingerprints hash a DIFFERENT encoding of state, so absorbing them
    would silently corrupt exact-dedup counts."""


class TierCorrupt(RuntimeError):
    """No candidate tier file passed the content checksum."""


def _tier_checksum(h1: np.ndarray, h2: np.ndarray,
                   meta_blob: bytes) -> np.uint32:
    import zlib

    crc = zlib.crc32(meta_blob)
    crc = zlib.crc32(np.ascontiguousarray(h1).tobytes(), crc)
    crc = zlib.crc32(np.ascontiguousarray(h2).tobytes(), crc)
    return np.uint32(crc & 0xFFFFFFFF)


def save_tier(path: str, h1: np.ndarray, h2: np.ndarray,
              meta: Optional[dict] = None) -> None:
    """Atomic checksummed tier dump with one-deep rotation.  ``meta``
    pins the encoding identity (``pack`` descriptor signature,
    ``sym`` perm count, anything else the producer wants checked);
    :func:`load_tier` refuses a mismatch loudly."""
    import json

    full = {"fmt": TIER_FORMAT}
    full.update(meta or {})
    blob = json.dumps(full, sort_keys=True).encode()
    h1 = np.asarray(h1, np.uint64)
    h2 = np.asarray(h2, np.uint64)
    host = {"meta": np.bytes_(blob), "h1": h1, "h2": h2,
            "checksum": _tier_checksum(h1, h2, blob)}
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **host)
    if os.path.exists(path):
        os.replace(path, path + ".prev")
    os.replace(tmp, path)


def peek_tier_meta(path: str) -> Optional[dict]:
    """The tier's meta dict without loading the key arrays, or None
    when no readable candidate exists."""
    import json

    for cand in (path, path + ".prev"):
        if not os.path.exists(cand):
            continue
        try:
            with np.load(cand) as z:
                if "meta" in z.files:
                    return json.loads(z["meta"].item().decode())
        except Exception:  # noqa: BLE001 — torn file: try .prev
            continue
    return None


def load_tier(path: str, expect_meta: Optional[dict] = None
              ) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Load and VERIFY a tier dump -> ``(h1, h2, meta)``.

    * A checksum-failing main file falls back to ``.prev`` with a
      warning; when every candidate fails, :class:`TierCorrupt`.
    * ``expect_meta``: every key the caller passes must match the
      stored meta EXACTLY (plus the format version, always checked) —
      a foreign pack descriptor or symmetry flag raises
      :class:`TierMismatch` naming both sides, never returns keys."""
    import json
    import warnings

    last_err: Optional[str] = None
    for cand in (path, path + ".prev"):
        if not os.path.exists(cand):
            continue
        try:
            with np.load(cand) as z:
                data = {k: z[k] for k in z.files}
        except Exception as e:  # noqa: BLE001 — torn zip: try .prev
            last_err = f"{cand}: unreadable ({type(e).__name__}: {e})"
            continue
        if not all(k in data for k in ("meta", "h1", "h2", "checksum")):
            last_err = f"{cand}: not a tier dump (missing entries)"
            continue
        blob = data["meta"].item()
        h1 = np.asarray(data["h1"], np.uint64)
        h2 = np.asarray(data["h2"], np.uint64)
        want = int(np.uint32(data["checksum"]))
        got = int(_tier_checksum(h1, h2, blob))
        if want != got:
            last_err = (f"{cand}: tier checksum mismatch "
                        f"(stored {want:#010x}, computed {got:#010x})")
            continue
        if cand.endswith(".prev") and last_err:
            warnings.warn(f"tier {path}: main dump unusable "
                          f"({last_err}); resuming from .prev",
                          RuntimeWarning, stacklevel=2)
        meta = json.loads(blob.decode())
        if meta.get("fmt") != TIER_FORMAT:
            raise TierMismatch(
                f"{cand}: tier format {meta.get('fmt')!r} != expected "
                f"{TIER_FORMAT!r} — refusing a cross-version tier")
        for k, v in (expect_meta or {}).items():
            if meta.get(k) != v:
                raise TierMismatch(
                    f"{cand}: tier {k!r} mismatch — stored "
                    f"{meta.get(k)!r}, expected {v!r} (a foreign "
                    "encoding must never seed exact-dedup state)")
        return h1, h2, meta
    raise TierCorrupt(
        f"{path}: no loadable tier candidate "
        f"({last_err or 'no file exists'})")


class FrontierSpool:
    """Host-side queue of frontier row segments for ONE BFS level."""

    def __init__(self):
        self.segments: List[np.ndarray] = []

    def push(self, rows: np.ndarray) -> None:
        if len(rows):
            self.segments.append(np.asarray(rows, np.int32))

    def pop(self) -> Optional[np.ndarray]:
        return self.segments.pop(0) if self.segments else None

    def rows(self) -> int:
        return sum(len(s) for s in self.segments)

    def concat(self, lanes: int) -> np.ndarray:
        if not self.segments:
            return np.zeros((0, lanes), np.int32)
        return np.concatenate(self.segments, axis=0)


class _DrainWorker:
    """The async drain's single ordered worker (ISSUE 15c): jobs run
    strictly in submission order on one daemon thread, so a refilter
    submitted before an eviction always sees the pre-eviction tier —
    the exactness invariant needs ORDER, not synchrony.  A job that
    raises (e.g. the tier's CapacityOverflow) parks the exception and
    skips the rest of the queue; the next :meth:`barrier` re-raises it
    on the driver thread — loud, never swallowed."""

    def __init__(self):
        self._q: "queue_mod.Queue" = queue_mod.Queue()
        self._thread: Optional[threading.Thread] = None
        self._exc: Optional[BaseException] = None
        self.busy_secs = 0.0

    def _loop(self) -> None:
        while True:
            fn = self._q.get()
            try:
                if fn is not None and self._exc is None:
                    t0 = time.time()
                    fn()
                    self.busy_secs += time.time() - t0
            except BaseException as e:  # noqa: BLE001 — re-raised at
                self._exc = e           # the next barrier
            finally:
                self._q.task_done()

    def submit(self, fn) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name="dslabs-spill-drain")
            self._thread.start()
        self._q.put(fn)

    def pending(self) -> bool:
        return self._q.unfinished_tasks > 0

    def barrier(self) -> None:
        self._q.join()
        if self._exc is not None:
            e, self._exc = self._exc, None
            raise e


class SpillManager:
    """Per-run spill state shared by a driver's device half.

    The driver owns WHEN (load-factor checks, abort codes from the
    step program); this object owns the host tier, the two spools, the
    exact-count bookkeeping, the refilter math, and — since ISSUE 15c
    — the async drain queue that overlaps all of that host work with
    the next device chunk."""

    def __init__(self, config: Optional[SpillConfig] = None):
        self.config = config or SpillConfig()
        self.tier = HostVisitedTier(host_cap=self.config.host_cap)
        self.spool_cur = FrontierSpool()    # level being consumed
        self.spool_next = FrontierSpool()   # level being assembled
        self.stats = SpillStats()
        self._worker: Optional[_DrainWorker] = None
        self._walls_reported = (0.0, 0.0)   # (busy, wait) last snapshot
        # Optional telemetry recorder (tpu/telemetry.py), set by the
        # owning engine at run start: evictions and reinjections become
        # flight-recorder events (host bookkeeping only — the device
        # round-trips themselves are already spans via _dispatch).
        self.telemetry = None
        # Device-table inserts THIS EPOCH that duplicate a tier key
        # (refilter hits); reset at each eviction — see the module
        # docstring's unique formula.
        self.dup_epoch = 0

    def reset_run(self) -> None:
        """Fresh-run reset: tier, spools, counters, and epoch all
        restart empty (the worker thread survives).  Called by the
        drivers at the top of every NON-resume run — an engine reused
        across runs (the bench's warm-up-then-measure pattern) must
        not refilter run 2 against run 1's tier: that dropped live
        states as 're-discoveries' and corrupted counts (the latent
        reuse bug ISSUE 15's capacity2 phase exposed).  Resume paths
        call :meth:`restore` instead, which rebuilds the tier from the
        dump."""
        self.barrier()
        self.tier = HostVisitedTier(host_cap=self.config.host_cap)
        self.spool_cur = FrontierSpool()
        self.spool_next = FrontierSpool()
        self.stats = SpillStats()
        self.dup_epoch = 0
        if self._worker is not None:
            self._worker.busy_secs = 0.0
        self._walls_reported = (0.0, 0.0)

    # ----------------------------------------------------- async drain

    def submit_drain(self, fn, evict: bool = False) -> None:
        """Queue one drain job (refilter+spool, or an eviction
        absorb).  Async gear: runs on the ordered worker while the
        device continues; sync gear (async_drain=False): runs inline
        — byte-identical semantics, the legacy timing."""
        if not self.config.async_drain:
            fn()
            return
        if self._worker is None:
            self._worker = _DrainWorker()
        self._worker.submit(fn)

    def barrier(self) -> None:
        """Wait for every queued drain job; re-raises a parked job
        exception.  Every count/spool READ goes behind this — the
        driver blocks only when it actually needs the numbers, which
        is what turns the drain wall into overlap."""
        w = self._worker
        if w is None:
            return
        if not w.pending():
            # Queue already drained — but a parked exception from a
            # completed job must STILL surface here (losing it would
            # be the silent-swallow this class exists to prevent).
            w.barrier()
            return
        t0 = time.time()
        try:
            w.barrier()
        finally:
            self.stats.drain_wait_ms += int(
                (time.time() - t0) * 1000)
            self.stats.drain_wall_ms = int(w.busy_secs * 1000)

    def level_walls(self) -> dict:
        """Drain wall split SINCE THE LAST CALL — the per-level
        spill-overlap numbers the drivers attach to their level
        records (telemetry satellite)."""
        busy = (self._worker.busy_secs if self._worker is not None
                else 0.0)
        self.stats.drain_wall_ms = int(busy * 1000)
        wait = self.stats.drain_wait_ms / 1000.0
        pb, pw = self._walls_reported
        self._walls_reported = (busy, wait)
        return {"drain_wall": round(busy - pb, 4),
                "drain_wait": round(wait - pw, 4),
                "drain_overlap": round(max(0.0, (busy - pb)
                                           - (wait - pw)), 4)}

    # ------------------------------------------------------------ state

    @property
    def active(self) -> bool:
        """Spill machinery engaged: once anything has been tiered or
        spooled, level boundaries must run the refilter path.  Until
        then the driver keeps its fast on-device promote."""
        self.barrier()
        return (len(self.tier) > 0 or bool(self.spool_cur.segments)
                or bool(self.spool_next.segments))

    def should_evict(self, vis_n: int, cap: int) -> bool:
        return vis_n >= int(self.config.high_water * cap)

    def unique(self, vis_n_device: int) -> int:
        """Exact distinct-state count across tiers (module docstring).
        Reads behind the drain barrier: pending refilters still owe
        their dup_epoch corrections."""
        self.barrier()
        return len(self.tier) + int(vis_n_device) - self.dup_epoch

    # ------------------------------------------------------- operations

    def evict(self, occupied_keys: np.ndarray) -> int:
        """Bulk-absorb the device table's occupied key lines; the
        caller clears the device table (and its vis_n) right after.
        Returns keys newly tiered."""
        n_new = self.tier.absorb(occupied_keys)
        self.stats.spilled_keys += n_new
        self.stats.evictions += 1
        self.dup_epoch = 0
        if self.telemetry is not None:
            self.telemetry.event("spill_evict", keys=n_new,
                                 tier=len(self.tier))
        return n_new

    def refilter(self, rows: np.ndarray,
                 keys: np.ndarray) -> np.ndarray:
        """The corrected promote mask: drop rows whose key is already
        in the host tier (a re-discovery of a pre-eviction state) and
        charge the duplicate device-table insert to ``dup_epoch``.
        Returns the kept rows."""
        if not len(rows) or not len(self.tier):
            return np.asarray(rows, np.int32)
        hit = self.tier.contains(keys)
        n_hit = int(hit.sum())
        if n_hit:
            self.stats.host_tier_hits += n_hit
            self.dup_epoch += n_hit
            rows = np.asarray(rows)[~hit]
        return np.asarray(rows, np.int32)

    def spool(self, rows: np.ndarray) -> None:
        """Queue refiltered NEXT-level rows for deferred re-expansion."""
        if len(rows):
            self.stats.respilled_frontier += len(rows)
            self.spool_next.push(rows)
            if self.telemetry is not None:
                # Live-monitor feed (STATUS.json "spill" block): the
                # tier/spool sizes a watcher reads to see how deep the
                # capacity detour currently is.
                self.telemetry.event(
                    "spill_spool", rows=len(rows),
                    spool_rows=self.spool_next.rows(),
                    tier=len(self.tier))

    def pop_current(self) -> Optional[np.ndarray]:
        self.barrier()
        seg = self.spool_cur.pop()
        if seg is not None:
            self.stats.reinjections += 1
            if self.telemetry is not None:
                self.telemetry.event("spill_reinject", rows=len(seg),
                                     tier=len(self.tier))
        return seg

    def advance_level(self) -> None:
        """Level boundary: the assembled next level becomes current."""
        self.barrier()
        assert not self.spool_cur.segments, \
            "advance_level with unconsumed current-level segments"
        self.spool_cur, self.spool_next = (self.spool_next,
                                           FrontierSpool())

    # ------------------------------------------------------ checkpoints

    def checkpoint_keys(self, device_keys: np.ndarray) -> np.ndarray:
        """visited_keys for the unified dump: device ∪ tier, exact-
        deduplicated (the resumer's unique base is len(keys))."""
        self.barrier()
        parts = [np.asarray(device_keys, np.uint32).reshape(-1, 4),
                 self.tier.key_rows()]
        allk = np.concatenate(parts, axis=0)
        if not len(allk):
            return allk
        h1, h2 = _rows_to_u64(allk)
        order = np.lexsort((h2, h1))
        h1, h2 = h1[order], h2[order]
        first = np.ones(len(h1), bool)
        first[1:] = (h1[1:] != h1[:-1]) | (h2[1:] != h2[:-1])
        return _u64_to_rows(h1[first], h2[first])

    def checkpoint_extra(self) -> dict:
        return {"spill_stats": self.stats.as_array()}

    def restore(self, visited_keys: np.ndarray,
                extra: Optional[dict] = None) -> None:
        """Resume-from-dump: ALL dumped keys load into the host tier
        and the device epoch restarts empty — bit-exact by the unique
        formula (len(tier) + 0 - 0 = the dump's distinct count)."""
        self.barrier()
        self.tier = HostVisitedTier(host_cap=self.config.host_cap)
        self.spool_cur = FrontierSpool()
        self.spool_next = FrontierSpool()
        self.dup_epoch = 0
        self.tier.absorb(visited_keys)
        if extra and "spill_stats" in extra:
            self.stats = SpillStats.from_array(extra["spill_stats"])

    def attach(self, outcome) -> None:
        """Surface the accounting on a SearchOutcome (never silent)."""
        self.barrier()
        if self._worker is not None:
            self.stats.drain_wall_ms = int(
                self._worker.busy_secs * 1000)
        outcome.spilled_keys = self.stats.spilled_keys
        outcome.host_tier_hits = self.stats.host_tier_hits
        outcome.respilled_frontier = self.stats.respilled_frontier
        outcome.spill_drain_ms = self.stats.drain_wall_ms
        outcome.spill_wait_ms = self.stats.drain_wait_ms
