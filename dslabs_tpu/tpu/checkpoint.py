"""Unified, engine-agnostic search checkpoints (atomic .npz snapshots).

Extracted from the sharded engine's round-4 checkpointing (sharded.py)
and generalised so every rung of the failover ladder — the sharded
driver, the single-device device-resident wave loop, and the host-dedup
parity loop — can dump and resume the SAME file (docs/resilience.md).
That engine-portability is what makes supervisor failover
(sharded -> single-device -> host) resumable: the dump stores the
search's SEMANTIC state, not any engine's carry layout:

  frontier      [n, lanes] int32   live frontier rows (occupied only)
  visited_keys  [K, 4]     uint32  occupied visited-table lines (the
                                   128-bit keys; table layout is
                                   rebuilt on load by re-insertion)
  depth / explored / elapsed / vis_over / dropped   scalars
  fp_map        [M, 9]     int64   optional trace chain (sharded
                                   record_trace mode)
  extra__<name> arrays             optional engine-extension arrays
                                   (``SearchCheckpoint.extra``): state a
                                   non-BFS driver needs beyond the core
                                   layout — the swarm explorer
                                   (tpu/swarm.py) stores walker depths,
                                   event histories, PRNG keys, and the
                                   restart seed pool here, and the
                                   host-RAM spill tier (tpu/spill.py)
                                   its running counters as
                                   ``extra__spill_stats``.  Covered by
                                   the content checksum like every
                                   other entry; loaders that do not
                                   know a key simply ignore it.

Spill-mode dumps (tpu/spill.py, docs/capacity.md) stay TIER-AGNOSTIC
on purpose: ``visited_keys`` stores the exact-deduplicated UNION of
the device table and the host tier and ``frontier`` includes every
host-spooled segment, so a non-spill engine resumes a spill dump (if
its table fits the key set), a spill engine resumes any dump (all keys
load into the tier, the device epoch restarts empty), and the host
tier inherits the CRC32 checksum + ``.prev`` rotation below without
any format change — kill-mid-spill resume is bit-exact.

Every dump carries a **config fingerprint** of the search it belongs
to: the protocol's packed-lane shape (protocol name, node/message/timer
widths, net/timer caps, node count) plus the strict and record_trace
flags.  Engine knobs that do not change state identity (chunk sizes,
frontier/visited capacities, device count, ev budgets) are deliberately
EXCLUDED — a dump written by an 8-device sharded run resumes on a
single-device engine, or under a different chunk size, unchanged.
That width-freedom is load-bearing twice over: the supervisor's
ELASTIC degraded-mesh ladder (ISSUE 9, docs/resilience.md) resumes the
same dump on progressively halved meshes (frontier rows re-split into
contiguous per-device shares, visited keys re-inserted per owner), and
the swarm explorer's own fingerprint family follows the same rule (no
D/K pin — walker state redistributes on load, tpu/swarm.py).  A
fingerprint mismatch is refused LOUDLY (:class:`CheckpointMismatch`
names both fingerprints); a checkpoint is never resumed silently into
a search it does not describe.

Writes are torn-write-proof twice over: the dump is written to a tmp
file and ``os.replace``d into place (a kill mid-write leaves the
previous complete dump), the PREVIOUS dump is rotated to ``<path>.prev``
first, and every dump carries a CRC32 content checksum.  The loader
verifies the checksum and falls back to the rotated ``.prev`` dump WITH
A LOUD WARNING on any truncation/corruption — a machine dying mid-write
(the warden's SIGKILL included, tpu/warden.py) costs at most one
checkpoint interval, never the run.  :class:`AsyncCheckpointWriter` is
the shared skip-if-busy background drain (one in-flight dump, never a
queue).
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import threading
import warnings
import zlib
from typing import Optional

import numpy as np

__all__ = ["FORMAT_VERSION", "CheckpointMismatch", "CheckpointCorrupt",
           "SearchCheckpoint", "config_fingerprint", "save", "load",
           "peek_fingerprint", "peek_depth", "AsyncCheckpointWriter",
           "default_flight_log",
           "default_status_path", "run_dir_layout"]


def default_flight_log(checkpoint_path) -> "Optional[str]":
    """The run-dir convention for the telemetry flight recorder
    (tpu/telemetry.py): a ``flight.jsonl`` beside the dump, so a
    killed/wedged run leaves its last-N-dispatches trail next to the
    state it would have resumed from.  ``None`` when no checkpoint is
    configured."""
    if not checkpoint_path:
        return None
    return os.path.join(
        os.path.dirname(os.path.abspath(checkpoint_path)),
        "flight.jsonl")


def default_status_path(checkpoint_path) -> "Optional[str]":
    """The live-monitor convention (tpu/telemetry.py): an atomic
    ``STATUS.json`` beside the dump, rewritten at level boundaries so
    ``telemetry watch <run-dir>`` can render the run from another
    process.  ``None`` when no checkpoint is configured."""
    if not checkpoint_path:
        return None
    return os.path.join(
        os.path.dirname(os.path.abspath(checkpoint_path)),
        "STATUS.json")


def default_costs_path(checkpoint_path) -> "Optional[str]":
    """The cost-ledger convention (tpu/tracing.py ``CostMeter``): an
    append-only ``COSTS.jsonl`` beside the dump.  The checking service
    keeps ONE ledger at its root (every job charged into it); a
    standalone checkpointed run that wants metering uses this per-run
    location.  ``None`` when no checkpoint is configured."""
    if not checkpoint_path:
        return None
    return os.path.join(
        os.path.dirname(os.path.abspath(checkpoint_path)),
        "COSTS.jsonl")


def run_dir_layout(checkpoint_path) -> dict:
    """Everything a checkpointed run keeps in its directory — the one
    place the layout is defined (docs/observability.md):

      checkpoint        the atomic .npz dump (+ ``.prev`` rotation)
      flight_log        telemetry flight recorder (tpu/telemetry.py)
      status            live-monitor STATUS.json (telemetry watch)
      costs             append-only cost ledger (tpu/tracing.py)
    """
    return {
        "checkpoint": checkpoint_path,
        "prev": (checkpoint_path + ".prev") if checkpoint_path else None,
        "flight_log": default_flight_log(checkpoint_path),
        "status": default_status_path(checkpoint_path),
        "costs": default_costs_path(checkpoint_path),
    }


FORMAT_VERSION = "dslabs-search-ckpt-v7"


class CheckpointMismatch(RuntimeError):
    """A checkpoint's config fingerprint does not match the live search.

    Raised instead of silently resuming (or silently ignoring) a dump
    from a different protocol/capacity configuration — the message
    names BOTH fingerprints so the divergent knob is attributable."""


class CheckpointCorrupt(RuntimeError):
    """Every candidate dump (main and the rotated ``.prev``) failed its
    checksum/read — there is nothing sound to resume.  Raised loudly
    instead of resuming a torn dump or silently starting from the
    root."""


@dataclasses.dataclass
class SearchCheckpoint:
    """The engine-agnostic snapshot of a BFS at a level boundary."""

    fingerprint: str
    depth: int
    explored: int
    elapsed: float
    frontier: np.ndarray        # [n, lanes] int32, live rows only
    visited_keys: np.ndarray    # [K, 4] uint32, occupied lines only
    vis_over: int = 0
    dropped: int = 0
    fp_map: Optional[np.ndarray] = None   # [M, 9] int64 trace chain
    # Engine-extension arrays (saved as ``extra__<name>`` entries): the
    # swarm explorer's walker state rides here — see module docstring.
    extra: Optional[dict] = None


def config_fingerprint(protocol, strict: bool,
                       record_trace: bool = False,
                       symmetry: int = 0) -> str:
    """The semantic identity a dump must share with the search resuming
    it: packed-lane layout + verdict-affecting flags.  Engine-local
    throughput knobs (chunk, caps, mesh size, ev budget) are excluded
    by design — see the module docstring.  ``symmetry`` (the active
    canonicalize pass's permutation count, 0 = off — ISSUE 15) DOES
    participate: a symmetry-reduced dump's visited keys and unique
    counts describe the quotient space, which an unreduced search must
    refuse loudly rather than resume into.  The bit-packed frontier
    ENCODING deliberately does not (it is a storage codec, converted
    loudly on resume via the dump's ``frontier_encoding`` marker)."""
    base = (FORMAT_VERSION, protocol.name, protocol.n_nodes,
            protocol.node_width, protocol.msg_width,
            protocol.timer_width, protocol.net_cap,
            protocol.timer_cap, bool(strict), bool(record_trace))
    if symmetry:
        base = base + (f"sym{symmetry}",)
    # Fault scenarios (ISSUE 19) change the event grid and the reachable
    # space: a scenario dump must never resume into a fault-free search
    # (or a differently-parameterised scenario) and vice versa.  The
    # signature is derived here, not at call sites, so every producer
    # (engine, sharded, swarm seed loader) gets it for free.
    fl = getattr(protocol, "fault", None)
    if fl is not None:
        base = base + (fl.signature(),)
    return repr(base)


def _content_checksum(host: dict) -> np.uint32:
    """CRC32 over every entry's name, dtype/shape, and raw bytes (sorted
    key order; the ``checksum`` entry itself excluded) — the torn-write
    detector the loader verifies before trusting a dump."""
    crc = 0
    for key in sorted(host):
        if key == "checksum":
            continue
        arr = np.asarray(host[key])
        crc = zlib.crc32(key.encode(), crc)
        crc = zlib.crc32(repr((arr.dtype.str, arr.shape)).encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
    return np.uint32(crc & 0xFFFFFFFF)


def save(path: str, ckpt: SearchCheckpoint) -> None:
    """Atomic checksummed dump with one-deep rotation: write to
    ``path + '.tmp'``, rotate any existing dump to ``path + '.prev'``,
    then ``os.replace`` the tmp into place.  A kill at ANY point leaves
    at least one complete, checksum-verifiable dump on disk."""
    host = {
        "config": np.bytes_(ckpt.fingerprint.encode()),
        "depth": np.int64(ckpt.depth),
        "explored": np.int64(ckpt.explored),
        "elapsed": np.float64(ckpt.elapsed),
        "vis_over": np.int64(ckpt.vis_over),
        "dropped": np.int64(ckpt.dropped),
        "frontier": np.asarray(ckpt.frontier, np.int32),
        "visited_keys": np.asarray(ckpt.visited_keys, np.uint32),
    }
    if ckpt.fp_map is not None and len(ckpt.fp_map):
        host["fp_map"] = np.asarray(ckpt.fp_map, np.int64)
    for name, arr in (ckpt.extra or {}).items():
        host[f"extra__{name}"] = np.asarray(arr)
    host["checksum"] = _content_checksum(host)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **host)
    if os.path.exists(path):
        os.replace(path, path + ".prev")
    os.replace(tmp, path)
    _archive_level(path, int(ckpt.depth))


def _archive_level(path: str, depth: int) -> None:
    """Per-level checkpoint archiving (ISSUE 16, service/memo.py): when
    ``DSLABS_MEMO_LEVELS`` names a directory, every completed dump is
    ALSO copied there as ``level_<depth>.npz`` — the incremental
    re-check ladder resumes a spec-edited job from the deepest level
    below its divergence bound.  Best-effort by design: the archive
    must never fail a live dump, and every consumer re-verifies the
    copy's own checksum + config fingerprint before seeding from it."""
    lvl_dir = os.environ.get("DSLABS_MEMO_LEVELS")
    if not lvl_dir:
        return
    try:
        os.makedirs(lvl_dir, exist_ok=True)
        dst = os.path.join(lvl_dir, f"level_{depth}.npz")
        tmp = dst + ".tmp"
        shutil.copyfile(path, tmp)
        os.replace(tmp, dst)
    except OSError:
        pass


def _candidates(path: str):
    """Load order: the main dump, then the rotated previous dump."""
    return (path, path + ".prev")


def peek_fingerprint(path: str) -> Optional[str]:
    """The dump's fingerprint WITHOUT loading the arrays (callers that
    only need a resumability boolean must not pay the full load), or
    None when no readable dump exists.  An unreadable/truncated main
    dump falls through to ``.prev`` — resumability must track what the
    loader would actually resume."""
    if not path:
        return None
    for cand in _candidates(path):
        if not os.path.exists(cand):
            continue
        try:
            with np.load(cand) as z:
                if "config" in z.files:
                    return z["config"].item().decode()
        except Exception:
            continue
    return None


def peek_depth(path: str) -> Optional[int]:
    """The dump's checkpointed depth without loading the state arrays
    (the warden's heartbeat reports it as the durable-resume point), or
    None when no readable dump exists."""
    if not path:
        return None
    for cand in _candidates(path):
        if not os.path.exists(cand):
            continue
        try:
            with np.load(cand) as z:
                if "depth" in z.files:
                    return int(z["depth"])
        except Exception:
            continue
    return None


def _load_verified(path: str) -> dict:
    """Read EVERY entry of a dump and verify the content checksum.
    Raises :class:`CheckpointCorrupt` on truncation, unreadable zip
    content, a missing checksum, or a checksum mismatch."""
    try:
        with np.load(path) as z:
            data = {k: z[k] for k in z.files}
    except Exception as e:
        raise CheckpointCorrupt(
            f"{path}: unreadable/truncated checkpoint "
            f"({type(e).__name__}: {e})") from e
    if "config" not in data:
        raise CheckpointCorrupt(
            f"{path}: not a search checkpoint (no config fingerprint)")
    if "checksum" not in data:
        raise CheckpointCorrupt(
            f"{path}: no content checksum (pre-{FORMAT_VERSION} or "
            "torn dump)")
    want = int(np.uint32(data["checksum"]))
    got = int(_content_checksum(data))
    if want != got:
        raise CheckpointCorrupt(
            f"{path}: content checksum mismatch (stored {want:#010x}, "
            f"computed {got:#010x}) — torn or corrupted dump")
    return data


def load(path: str, fingerprint: str) -> Optional[SearchCheckpoint]:
    """Load and VERIFY a dump.  ``None`` when no file exists; a loud
    :class:`CheckpointMismatch` (naming both fingerprints) when the
    dump belongs to a different configuration.  A corrupt/truncated
    main dump (failed checksum, unreadable zip) falls back to the
    rotated ``.prev`` dump with a LOUD warning — one checkpoint
    interval lost, never the run; when every candidate is corrupt the
    loader raises :class:`CheckpointCorrupt` instead of silently
    restarting from the root."""
    if not path:
        return None
    errors = []
    seen_any = False
    for cand in _candidates(path):
        if not os.path.exists(cand):
            continue
        seen_any = True
        try:
            data = _load_verified(cand)
        except CheckpointCorrupt as e:
            warnings.warn(
                f"checkpoint {cand} failed verification ({e}); "
                "falling back to the rotated previous dump",
                RuntimeWarning, stacklevel=2)
            errors.append(e)
            continue
        found = data["config"].item().decode()
        if found != fingerprint:
            raise CheckpointMismatch(
                f"refusing to resume {cand}: checkpoint fingerprint\n"
                f"  {found}\ndoes not match the live search's\n"
                f"  {fingerprint}\n(dump from a different protocol/"
                "capacity config — delete the file or fix the config)")
        return SearchCheckpoint(
            fingerprint=found,
            depth=int(data["depth"]),
            explored=int(data["explored"]),
            elapsed=float(data["elapsed"]),
            frontier=np.asarray(data["frontier"], np.int32),
            visited_keys=np.asarray(data["visited_keys"], np.uint32),
            vis_over=int(data["vis_over"]) if "vis_over" in data else 0,
            dropped=int(data["dropped"]) if "dropped" in data else 0,
            fp_map=(np.asarray(data["fp_map"], np.int64)
                    if "fp_map" in data else None),
            extra=({k[len("extra__"):]: np.asarray(v)
                    for k, v in data.items()
                    if k.startswith("extra__")} or None))
    if not seen_any:
        return None
    raise CheckpointCorrupt(
        f"no readable checkpoint at {path} (main and .prev both failed "
        "verification): " + "; ".join(str(e) for e in errors))


class AsyncCheckpointWriter:
    """Skip-if-busy background dump drain (one thread, never a queue).

    ``kick(fn)`` runs ``fn`` (host readback + :func:`save`) on a daemon
    thread unless a prior dump is still draining — a checkpoint tick
    that lands mid-drain is SKIPPED, not queued, so dumps can never
    back up behind a slow disk.  ``join()`` blocks until the in-flight
    dump (if any) completes; callers must join before reporting an
    outcome a kill-resume test depends on."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None

    def busy(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def kick(self, fn) -> bool:
        if self.busy():
            return False
        th = threading.Thread(target=fn, daemon=True)
        self._thread = th
        th.start()
        return True

    def join(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            self._thread.join()
