"""Bit-packed frontier encoding (ISSUE 15 leg (a), ROADMAP #4a).

HBM bytes-per-state is the binding constraint on frontier width
everywhere: every protocol lane is stored as a full int32 even though
the spec already declares tiny enum/counter domains (a lab1 message tag
is one of two values; a ballot flag is a bit).  This module derives a
**packing descriptor** from the compiled spec's declared domains
(``TensorProtocol.lane_domains``, emitted by ``ProtocolSpec.compile()``
— enum tag cardinalities, node-index ranges, counter budgets) and
provides fused ``pack``/``unpack`` device functions so the frontier
SoA, the spill spool segments, and checkpoint rows are stored packed
while the expand/check handlers keep operating on the existing int32
view, decoded in-register at expand time.

Semantics are BIT-EXACT by construction: fingerprints, predicates, and
handlers all run on the unpacked int32 rows — packing is purely a
storage encoding, so the unique/explored/verdict trajectory of a packed
search is identical to the unpacked one (pinned by
tests/test_packing.py).

Descriptor model (``LanePacking``):

* every flat state lane (nodes ++ net ++ timers ++ exc, the
  ``flatten_state`` order) gets a ``(word, shift, width, lo, sentinel)``
  entry: the 32-bit word it lives in, its bit offset, its bit width,
  its domain bias, and whether the lane can hold the engine's SENTINEL
  (net/timer lanes — empty rows are all-SENTINEL);
* a bounded lane ``[lo, hi]`` encodes ``v - lo`` in
  ``ceil(log2(hi - lo + 1 [+ 1 sentinel code]))`` bits; SENTINEL maps
  to the all-ones code of the lane (which the domain can never reach —
  the width derivation reserves it);
* an unbounded lane (``None`` domain — hand twins declare nothing)
  stays a raw 32-bit word, SENTINEL passes through untouched;
* a **delta lane** (``("delta", bits)`` domain, from
  ``Field(delta=bits)`` — ISSUE 18 leg (b)) is an unbounded
  monotone-ish counter (view numbers, liveness ticks) packed as
  ``v - base`` in ``bits`` bits, where ``base`` is a per-lane int32
  the CALLER carries (the sharded engine tracks the per-level minimum
  and re-bases at promote).  Delta lanes are opt-in
  (``derive_packing(..., delta=True)``) because the base plumbing is
  an engine contract; with ``delta=False`` (the single-device default)
  a delta domain derives as raw, so both engines agree on the static
  part of the layout.  A value outside the ``[base, base + window)``
  wire window counts as out-of-domain — loud, never silent;
* lanes are laid out first-fit in declaration order and never straddle
  a word boundary, so pack/unpack are shift+mask on one word each.

The device codec assembles all words at once (ISSUE 32): ``pack_jnp``
contracts the shifted lanes over the lane axis with the constant 0/1
``[lanes, words]`` matrix, one byte plane at a time, so no per-word
``[N]`` / ``[N, 1]`` column is ever an operand (the TPU's (8, 128) tile
pads such a column 128x).  Exact, because the fields of a word are
bit-disjoint: a word's byte plane is a sum of disjoint bits, <= 255;
each addend (0..255) is exact in bf16 and the 0/1 weights leave it so;
the f32 accumulator holds every integer below 2^24.  ``pack_np`` /
``unpack_np`` are the host ORACLE the device bits are held to
(tests/test_packing.py, a case per descriptor).

A protocol with no declared domains derives the **identity** descriptor
(``words == lanes``, pack/unpack return their input), which is how the
packed path ships ON by default without touching the hand twins'
lowered programs: identity packing traces to the identical jaxpr.

Packing never guesses: a live value OUTSIDE its declared domain is
counted by ``pack_jnp(..., count_bad=True)`` and surfaced by the engine
as a loud :class:`~dslabs_tpu.tpu.engine.CapacityOverflow` — a wrong
bound is a crash with a name, never silent state corruption.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import List, Optional, Tuple

import numpy as np

__all__ = ["LanePacking", "derive_packing", "RAW_WIDTH"]

RAW_WIDTH = 32

# Engine SENTINEL (duplicated to keep this module import-light; pinned
# equal by tests/test_packing.py).
_SENTINEL = np.int32(2 ** 31 - 1)


def _width_for(lo: int, hi: int, sentinel: bool) -> int:
    """Bit width for domain [lo, hi] (+1 reserved all-ones sentinel
    code when the lane can hold SENTINEL)."""
    span = hi - lo + 1
    codes = span + (1 if sentinel else 0)
    w = max(1, int(codes - 1).bit_length())
    # Sentinel lanes need the all-ones code strictly above the domain:
    # 2^w - 1 >= span, guaranteed by bit_length(codes - 1) with the +1.
    return w


@dataclasses.dataclass(frozen=True)
class LanePacking:
    """Per-lane packing descriptor for one protocol's flat state rows.

    Arrays are all length ``lanes`` (np int64/bool constants baked into
    the traced programs): ``word``/``shift``/``width`` place each lane,
    ``lo`` is the domain bias, ``sent`` marks SENTINEL-capable lanes,
    ``raw`` marks 32-bit passthrough lanes, ``dlt`` marks
    delta-from-base lanes (bias supplied at pack/unpack time via the
    ``base`` vector instead of the static ``lo``)."""

    lanes: int
    words: int
    word: np.ndarray
    shift: np.ndarray
    width: np.ndarray
    lo: np.ndarray
    sent: np.ndarray
    raw: np.ndarray
    dlt: Optional[np.ndarray] = None

    def __post_init__(self):
        if self.dlt is None:
            object.__setattr__(self, "dlt",
                               np.zeros(self.lanes, bool))

    # ------------------------------------------------------------ meta

    @property
    def identity(self) -> bool:
        """True when packing is a no-op (every lane raw, one word per
        lane) — the hand-twin default; callers skip the wrap entirely."""
        return self.words == self.lanes and bool(self.raw.all())

    @property
    def has_delta(self) -> bool:
        """True when any lane is delta-from-base encoded — pack/unpack
        then REQUIRE the ``base`` vector (a missing base is a loud
        ValueError, never a silent zero-bias decode)."""
        return bool(self.dlt.any())

    @property
    def delta_lanes(self) -> np.ndarray:
        """Flat lane indices of the delta-encoded lanes, in order."""
        return np.nonzero(self.dlt)[0]

    @property
    def bytes_per_state(self) -> int:
        """Packed bytes per stored frontier row."""
        return int(self.words) * 4

    @property
    def bytes_per_state_unpacked(self) -> int:
        return int(self.lanes) * 4

    @property
    def pack_ratio(self) -> float:
        """unpacked/packed bytes — >= 1.0; the capacity multiplier on
        frontier_cap/visited-spool width at fixed HBM."""
        return self.bytes_per_state_unpacked / max(self.bytes_per_state,
                                                   1)

    def signature(self) -> str:
        """Stable identity of the ENCODING (not the protocol): two
        descriptors with equal signatures produce byte-identical packed
        rows.  Rides checkpoints as the ``frontier_encoding`` marker."""
        if self.identity:
            return "raw"
        parts = [
            np.asarray([self.lanes, self.words], np.int64),
            self.word.astype(np.int64), self.shift.astype(np.int64),
            self.width.astype(np.int64), self.lo.astype(np.int64),
            self.sent.astype(np.int64), self.raw.astype(np.int64),
        ]
        # Delta lanes extend the blob ONLY when present, so every
        # pre-existing (static-domain) descriptor keeps its signature
        # and old checkpoints keep resuming.
        if self.has_delta:
            parts.append(self.dlt.astype(np.int64))
        blob = np.concatenate(parts).tobytes()
        return f"packed:{self.words}w:{zlib.crc32(blob) & 0xFFFFFFFF:08x}"

    def descriptor(self) -> dict:
        """The reportable packing descriptor (bench / STATUS.json):
        lane -> word/offset/width plus the headline byte counts."""
        return {
            "lanes": int(self.lanes),
            "words": int(self.words),
            "bytes_per_state": self.bytes_per_state,
            "bytes_per_state_unpacked": self.bytes_per_state_unpacked,
            "pack_ratio": round(self.pack_ratio, 3),
            "signature": self.signature(),
            "lane_bits": [int(w) for w in self.width],
            "delta_lanes": int(self.dlt.sum()),
        }

    # ----------------------------------------------- word/lane ranges

    def _word_ranges(self) -> List[Tuple[int, int, int]]:
        """[(word, lane_start, lane_end)] — lanes are assigned to words
        contiguously in order, so each word covers one lane slice."""
        out = []
        for w in range(self.words):
            idx = np.nonzero(self.word == w)[0]
            out.append((w, int(idx[0]), int(idx[-1]) + 1))
        return out

    # ------------------------------------------------------- jnp path

    def _require_base(self, base):
        if self.has_delta and base is None:
            raise ValueError(
                "packing descriptor has delta lanes but no base vector "
                "was supplied — the caller must carry the per-level "
                "base (see ISSUE 18 leg (b))")

    def _lo_eff_jnp(self, base):
        """Effective per-lane bias: the static ``lo`` except on delta
        lanes, where the caller's ``base`` vector [lanes] supplies it."""
        import jax.numpy as jnp

        lo = jnp.asarray(self.lo, jnp.int32)
        if not self.has_delta:
            return lo
        return jnp.where(jnp.asarray(self.dlt),
                         jnp.asarray(base, jnp.int32).reshape(-1), lo)

    def pack_jnp(self, rows, base=None, count_bad: bool = False):
        """[N, lanes] int32 -> [N, words] int32 (device).  With
        ``count_bad``, also returns an int32 [N] vector counting each
        row's values OUTSIDE their declared domain (callers mask to
        live rows and raise loudly — a wrong bound must never silently
        corrupt a stored state).  ``base`` is the [lanes] int32 bias
        vector, required iff the descriptor has delta lanes.

        Bit-identical to :meth:`pack_np`, the oracle: a word's fields
        are bit-disjoint, so each of its four byte planes is a sum of
        disjoint bits <= 255 — exact as bf16 addends times a 0/1 matrix
        accumulated in f32 — and the planes are OR'd back in place."""
        import jax
        import jax.numpy as jnp

        self._require_base(base)
        if self.identity:
            return ((rows, jnp.zeros((rows.shape[0],), jnp.int32))
                    if count_bad else rows)
        lo = self._lo_eff_jnp(base)
        raw = jnp.asarray(self.raw)
        sent = jnp.asarray(self.sent)
        shift = jnp.asarray(self.shift, jnp.uint32)
        mask = jnp.asarray(
            ((np.uint64(1) << self.width.astype(np.uint64)) - 1
             ).astype(np.uint32))
        is_sent = rows == _SENTINEL
        enc = (rows.astype(jnp.uint32) - lo.astype(jnp.uint32)) & mask
        enc = jnp.where(raw[None, :], rows.astype(jnp.uint32), enc)
        enc = jnp.where((sent & ~raw)[None, :] & is_sent, mask[None, :],
                        enc)
        shifted = (enc << shift[None, :]).astype(jnp.int32)
        # lanes -> words as ONE dense contraction per byte plane (see
        # the module docstring): no per-word column is ever an operand.
        onto = jnp.asarray(
            self.word[:, None] == np.arange(self.words)[None, :],
            jnp.bfloat16)                              # [lanes, words]
        packed = jnp.zeros((rows.shape[0], self.words), jnp.int32)
        for b in range(0, RAW_WIDTH, 8):
            plane = ((shifted >> b) & 0xFF).astype(jnp.bfloat16)
            byte = jax.lax.dot_general(
                plane, onto, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            packed = packed | (byte.astype(jnp.int32) << b)
        if not count_bad:
            return packed
        # Out-of-domain detection on bounded lanes: value not SENTINEL
        # and (v - lo) has bits above the lane width, or collides with
        # the reserved sentinel code.
        span = (rows.astype(jnp.uint32) - lo.astype(jnp.uint32))
        over = span > mask[None, :]
        hit_sent = sent[None, :] & (span == mask[None, :])
        bad = (~raw)[None, :] & ~is_sent & (over | hit_sent)
        return packed, jnp.sum(bad, axis=1).astype(jnp.int32)

    def unpack_jnp(self, packed, base=None):
        """[N, words] int32 -> [N, lanes] int32 (device; exact inverse
        of :meth:`pack_jnp` on in-domain rows — with the SAME ``base``
        the rows were packed against)."""
        import jax.numpy as jnp

        self._require_base(base)
        if self.identity:
            return packed
        pu = packed.astype(jnp.uint32)
        parts = []
        for w, s, e in self._word_ranges():
            sh = jnp.asarray(self.shift[s:e], jnp.uint32)
            mk = jnp.asarray(
                ((np.uint64(1) << self.width[s:e].astype(np.uint64)) - 1
                 ).astype(np.uint32))
            parts.append((pu[:, w:w + 1] >> sh[None, :]) & mk[None, :])
        bits = jnp.concatenate(parts, axis=1)
        lo = self._lo_eff_jnp(base)
        raw = jnp.asarray(self.raw)
        sent = jnp.asarray(self.sent)
        mask = jnp.asarray(
            ((np.uint64(1) << self.width.astype(np.uint64)) - 1
             ).astype(np.uint32))
        val = bits.astype(jnp.int32) + lo[None, :]
        val = jnp.where(raw[None, :], bits.astype(jnp.int32), val)
        return jnp.where((sent & ~raw)[None, :] & (bits == mask[None, :]),
                         _SENTINEL, val)

    def rebase_words(self, base_old, base_new):
        """[words] int32: what to ADD to every word of a packed row to
        move it from ``base_old`` to ``base_new`` (both [lanes] int32
        bias vectors; only the delta lanes are read).  A delta lane
        stores ``value - base`` in its field, so the field changes by
        ``base_old - base_new``; a word's fields are bit-disjoint, so
        the shifted differences of its delta lanes sum to one addend,
        two's complement.  Exact — bit for bit ``pack(unpack(row,
        base_old), base_new)`` — while every re-based field stays in
        its window: the CALLER's to know (tpu/sharded.py
        ``_rebase_rows`` says why the level promote does)."""
        import jax.numpy as jnp

        lanes = self.delta_lanes
        diff = (jnp.asarray(base_old, jnp.int32)
                - jnp.asarray(base_new, jnp.int32))[jnp.asarray(lanes)]
        shifted = jnp.left_shift(
            diff, jnp.asarray(self.shift[lanes], jnp.int32))
        onto = jnp.asarray(
            self.word[lanes][:, None] == np.arange(self.words)[None, :])
        return jnp.sum(jnp.where(onto, shifted[:, None], 0),
                       axis=0).astype(jnp.int32)

    # ------------------------------------------------------ host path

    def _lo_eff_np(self, base) -> np.ndarray:
        if not self.has_delta:
            return self.lo
        return np.where(self.dlt,
                        np.asarray(base, np.int64).reshape(-1), self.lo)

    def pack_np(self, rows: np.ndarray, base=None) -> np.ndarray:
        """Host-side mirror of :meth:`pack_jnp` (exact same bits)."""
        self._require_base(base)
        rows = np.asarray(rows, np.int32).reshape(-1, self.lanes)
        if self.identity:
            return rows
        lo_eff = self._lo_eff_np(base)
        mask = ((np.uint64(1) << self.width.astype(np.uint64)) - 1
                ).astype(np.uint32)
        is_sent = rows == _SENTINEL
        enc = ((rows.astype(np.uint32)
                - lo_eff.astype(np.uint32)) & mask)
        enc = np.where(self.raw[None, :], rows.astype(np.uint32), enc)
        enc = np.where((self.sent & ~self.raw)[None, :] & is_sent,
                       mask[None, :], enc)
        shifted = enc << self.shift.astype(np.uint32)[None, :]
        out = np.zeros((len(rows), self.words), np.uint32)
        for w, s, e in self._word_ranges():
            out[:, w] = shifted[:, s:e].sum(axis=1, dtype=np.uint32)
        return out.astype(np.int32)

    def unpack_np(self, packed: np.ndarray, base=None) -> np.ndarray:
        self._require_base(base)
        packed = np.asarray(packed, np.int32).reshape(-1, self.words)
        if self.identity:
            return packed
        pu = packed.astype(np.uint32)
        bits = np.zeros((len(packed), self.lanes), np.uint32)
        for w, s, e in self._word_ranges():
            mk = ((np.uint64(1) << self.width[s:e].astype(np.uint64)) - 1
                  ).astype(np.uint32)
            bits[:, s:e] = ((pu[:, w:w + 1]
                             >> self.shift[s:e].astype(np.uint32)[None, :])
                            & mk[None, :])
        mask = ((np.uint64(1) << self.width.astype(np.uint64)) - 1
                ).astype(np.uint32)
        val = (bits.astype(np.int64)
               + self._lo_eff_np(base).astype(np.int64)).astype(np.int32)
        val = np.where(self.raw[None, :], bits.astype(np.int32), val)
        return np.where((self.sent & ~self.raw)[None, :]
                        & (bits == mask[None, :]), _SENTINEL, val)


def _flat_domains(protocol) -> Tuple[List[Optional[Tuple[int, int]]],
                                     List[bool]]:
    """Expand ``protocol.lane_domains`` to per-flat-lane (domain,
    sentinel-capable) in ``flatten_state`` order: nodes ++ net ++
    timers ++ exc."""
    p = protocol
    ld = getattr(p, "lane_domains", None) or {}
    nodes = list(ld.get("nodes") or [None] * p.node_width)
    msg = list(ld.get("msg") or [None] * p.msg_width)
    tmr = list(ld.get("timer") or [None] * p.timer_width)
    exc = ld.get("exc")
    if len(nodes) != p.node_width or len(msg) != p.msg_width \
            or len(tmr) != p.timer_width:
        raise ValueError(
            f"{p.name}: lane_domains shape mismatch "
            f"(nodes {len(nodes)}/{p.node_width}, msg "
            f"{len(msg)}/{p.msg_width}, timer {len(tmr)}/"
            f"{p.timer_width})")
    doms: List[Optional[Tuple[int, int]]] = []
    sent: List[bool] = []
    doms += nodes
    sent += [False] * p.node_width
    for _ in range(p.net_cap):
        doms += msg
        sent += [True] * p.msg_width
    for _ in range(p.n_nodes * p.timer_cap):
        doms += tmr
        sent += [True] * p.timer_width
    doms.append(exc)
    sent.append(False)
    return doms, sent


def derive_packing(protocol, lanes: int,
                   delta: bool = False) -> LanePacking:
    """Derive the packing descriptor for one protocol's flat rows.
    ``lanes`` is the engine's flat row width (cross-checked).  No
    declared domains -> the identity descriptor.

    ``delta`` opts into the delta-from-base lanes (ISSUE 18 leg (b)):
    a ``("delta", bits)`` domain packs ``v - base`` in ``bits`` bits
    with a caller-carried base vector.  With ``delta=False`` (the
    single-device engine) delta domains derive as raw 32-bit lanes —
    correct, just uncompressed — so a spec annotated for the mesh
    still runs unchanged on one chip."""
    doms, sent_caps = _flat_domains(protocol)
    if len(doms) != lanes:
        raise ValueError(
            f"{protocol.name}: domain expansion produced {len(doms)} "
            f"lanes, engine rows have {lanes}")
    word = np.zeros(lanes, np.int64)
    shift = np.zeros(lanes, np.int64)
    width = np.zeros(lanes, np.int64)
    lo = np.zeros(lanes, np.int64)
    sent = np.zeros(lanes, bool)
    raw = np.zeros(lanes, bool)
    dlt = np.zeros(lanes, bool)
    cur_word, cur_bits = 0, 0
    for i, (dom, s_cap) in enumerate(zip(doms, sent_caps)):
        is_dlt = False
        if dom is None:
            w, is_raw, lo_i = RAW_WIDTH, True, 0
        elif isinstance(dom, tuple) and len(dom) and dom[0] == "delta":
            bits = int(dom[1])
            if bits < 1:
                raise ValueError(
                    f"{protocol.name}: lane {i} delta width {bits} "
                    "must be >= 1 bit")
            is_dlt = delta and bits < RAW_WIDTH
            if is_dlt:
                w, is_raw, lo_i = bits, False, 0
            else:
                w, is_raw, lo_i = RAW_WIDTH, True, 0
        else:
            lo_i, hi_i = int(dom[0]), int(dom[1])
            if hi_i < lo_i:
                raise ValueError(
                    f"{protocol.name}: lane {i} domain [{lo_i}, {hi_i}] "
                    "is empty (hi < lo)")
            w = _width_for(lo_i, hi_i, s_cap)
            is_raw = w >= RAW_WIDTH or hi_i >= int(_SENTINEL)
            if is_raw:
                w, lo_i = RAW_WIDTH, 0
        if cur_bits + w > 32:
            cur_word += 1
            cur_bits = 0
        word[i] = cur_word
        shift[i] = cur_bits
        width[i] = w
        lo[i] = lo_i
        sent[i] = s_cap and not is_raw
        raw[i] = is_raw
        dlt[i] = is_dlt
        cur_bits += w
    return LanePacking(lanes=lanes, words=int(cur_word + 1), word=word,
                       shift=shift, width=width, lo=lo, sent=sent,
                       raw=raw, dlt=dlt)
