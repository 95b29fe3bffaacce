"""Protocol schema compiler: declarative bounded-state specs -> tensor
twins (SURVEY §8.1 "Protocol IR ... schema compiler for bounded protocol
state").

The hand-written twins in ``tpu/protocols/`` are expert artifacts: lane
layouts, one-hot muxing, send/set row budgeting, SENTINEL discipline.
This module mechanises exactly that layer.  A :class:`ProtocolSpec`
declares what the reference framework gets from a ``Node`` subclass —
node kinds with bounded integer fields, message/timer types with
payload fields, and handlers — and ``compile()`` derives the
:class:`~dslabs_tpu.tpu.engine.TensorProtocol`:

- fields -> packed node lanes (layout, offsets, init vector),
- message/timer enums -> tags + fixed-width records,
- handlers -> the engine's ``step_message``/``step_timer`` contract,
  with per-(kind, instance, type) guard conditions, jnp.where field
  merges, and exact send/set row budgets counted from the handler's
  ``ctx.send``/``ctx.set_timer`` calls (finalize-style loud assertion,
  never truncation).

Handlers are plain Python functions written against the tiny
:class:`Ctx` combinator API — reads, conditional writes, sends, timer
sets, and integer arithmetic on traced scalars — NOT raw jax: the
compiler owns every tensor-shape decision, which is what makes a new
protocol searchable without twin-authoring expertise (the reference
analog: any Node subclass is searchable for free,
framework/src/dslabs/framework/Node.java:106-602 + Search.java:405-505).

First-cut scope (deliberate): single-instance node kinds with scalar
or small-array int fields, handlers without cross-node reads (exactly
the Node contract — nodes communicate only by messages/timers).  The
lab 0 and lab 1 specs in ``tpu/specs.py`` compile to twins that match
the hand-written ones state-for-state (tests/test_compiler.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Field", "MessageType", "TimerType", "NodeKind",
           "ProtocolSpec", "Ctx", "SpecError", "Fragment"]


class SpecError(Exception):
    """A structured spec-conformance failure raised at
    :meth:`ProtocolSpec.compile` time (ISSUE 10 satellite: malformed
    specs used to surface as bare KeyError/shape errors deep inside the
    engine; now the offending handler and field are named at the
    compile gate, which is what lets the conformance linter —
    ``python -m dslabs_tpu.analysis conformance`` — treat compile as
    the C4 spec-hygiene authority for generated twins, ROADMAP #3).

    ``handler``/``kind``/``field``/``line`` carry the structured
    location; ``code`` is the sanitizer rule that owns the failure
    (C4 unless stated otherwise)."""

    def __init__(self, message: str, *, spec: Optional[str] = None,
                 handler: Optional[str] = None,
                 kind: Optional[str] = None,
                 field: Optional[str] = None,
                 line: Optional[int] = None,
                 code: str = "C4"):
        self.spec = spec
        self.handler = handler
        self.kind = kind
        self.field = field
        self.line = line
        self.code = code
        loc = ""
        if handler:
            loc = f" [handler {handler}" + (
                f" @ line {line}]" if line else "]")
        super().__init__(f"{code}: {message}{loc}")


@dataclasses.dataclass(frozen=True)
class Field:
    """A bounded int field of a node: scalar (size 1) or a small int
    array (size > 1).  ``init`` is an int or a per-instance callable
    ``(instance_index) -> int | list``.

    ``lo``/``hi`` declare the field's value DOMAIN — the input to the
    bit-packed frontier encoding (ISSUE 15, tpu/packing.py): a field
    with ``hi`` set is stored in ``ceil(log2(hi - lo + 1))`` bits on
    the packed frontier; ``hi=None`` (the default) keeps the full
    int32 lane.  Domains are enforced loudly: an out-of-domain live
    value is a CapacityOverflow, never silent corruption, and init
    values are range-checked at compile time.

    ``delta`` declares an UNBOUNDED monotone-ish counter (view
    numbers, liveness ticks — fields a static ``hi`` cannot cap) for
    the delta-from-level-base encoding (ISSUE 18, tpu/packing.py):
    the mesh engine stores ``v - base`` in ``delta`` bits, carrying
    the per-level base alongside the frontier; engines that do not
    track a base (the single-device path) keep the full int32 lane.
    ``delta`` and ``hi`` are mutually exclusive.

    ``index_group`` names a node KIND whose instances index this array
    field (size must equal that kind's count): when the kind is
    declared in the spec's ``symmetry`` groups, the canonicalize pass
    permutes this array's elements together with the node ids
    (tpu/symmetry.py) — per-member bitmaps/counters stay coherent
    under relabeling."""

    name: str
    size: int = 1
    init: object = 0
    lo: int = 0
    hi: Optional[int] = None
    index_group: Optional[str] = None
    delta: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class MessageType:
    """``bounds`` maps payload field name -> (lo, hi) domain for the
    packed encoding (tpu/packing.py); undeclared fields keep full
    int32 lanes.  Tag/from/to lanes derive their domains from the
    spec itself (tag cardinality, node count)."""

    name: str
    fields: Tuple[str, ...] = ()
    bounds: Optional[Dict[str, Tuple[int, int]]] = None


@dataclasses.dataclass(frozen=True)
class TimerType:
    name: str
    fields: Tuple[str, ...] = ()
    min_ms: int = 10
    max_ms: int = 10
    bounds: Optional[Dict[str, Tuple[int, int]]] = None


@dataclasses.dataclass(frozen=True)
class NodeKind:
    """``count`` instances of a node kind, each with the same fields.
    Twin node indices are assigned kind-by-kind in declaration order.
    ``fields`` may mix plain :class:`Field`s with
    :class:`~dslabs_tpu.tpu.slots.Slots` blocks (ISSUE 20) — the spec
    expands each block to its struct-of-arrays lanes at construction
    and remembers the declaration for the Ctx slot ops."""

    name: str
    count: int
    fields: Tuple[Field, ...]


class Fragment:
    """A composable sub-state-machine (ISSUE 20): a named bundle of
    fields (plain or :class:`~dslabs_tpu.tpu.slots.Slots`), message and
    timer types, and handlers, attached to a node kind with
    :meth:`ProtocolSpec.include`.  This is how lab4's shardstore spec
    states its shape — a per-group Paxos fragment + a reconfiguration-
    epoch fragment + a 2PC vote fragment composed onto the server kind
    — instead of one monolithic handler set.  Inclusion is structural:
    fields append to the kind's layout, types merge into the spec's
    enums (same-name re-declarations must be identical), handlers
    register under the including kind, and the (kind, fragment) pair is
    recorded on ``spec.fragments`` so the memo fingerprint and the
    conformance linter see the composition."""

    def __init__(self, name: str, fields: Sequence[object] = (),
                 messages: Sequence[MessageType] = (),
                 timers: Sequence[TimerType] = ()):
        self.name = name
        self.fields = tuple(fields)
        self.messages = tuple(messages)
        self.timers = tuple(timers)
        self.handlers: Dict[str, Callable] = {}
        self.timer_handlers: Dict[str, Callable] = {}

    def on(self, msg: str):
        def reg(fn):
            self.handlers[msg] = fn
            return fn
        return reg

    def on_timer(self, timer: str):
        def reg(fn):
            self.timer_handlers[timer] = fn
            return fn
        return reg

    def scoped(self, fn):
        """``fn`` run under this fragment's device scope
        (:func:`handler_scope`): for a helper of the fragment that
        OTHER code calls — the including spec's handlers injecting a
        command into ``gpaxos``' log — so that its operations name the
        fragment they belong to, whoever called them.  The fragment's
        own handlers are scoped where they are invoked
        (:meth:`ProtocolSpec._invoke`)."""
        return _scoped(self.name, fn)


# What a spec built of fragments calls its OWN handlers' scope (the
# store's effect switch and wiring around lab 4's ``gpaxos``).
OWN_SCOPE = "spec"


def handler_scope(owner: str):
    """``dslabs.expand.handlers.<owner>``: one scope level under the
    engine's ``expand.handlers`` naming the :class:`Fragment` (or the
    spec itself, :data:`OWN_SCOPE`) an operation of the handler vmaps
    came from.  HLO metadata only; a profile's reader splits the
    handlers' device time by it (``benchmark/layer_metrics/
    gpaxos_handlers_pct.deep.py``)."""
    from dslabs_tpu.tpu import telemetry

    return telemetry.device_scope("expand.handlers." + owner)


def _scoped(owner: str, fn):
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with handler_scope(owner):
            return fn(*args, **kwargs)
    return run


class Ctx:
    """Handler combinator context for ONE (kind, instance) under ONE
    guard condition.  All mutation is conditional on the guard (and any
    ``when`` refinement): the compiler merges every branch with
    jnp.where, exactly the hand-twin discipline."""

    def __init__(self, spec, st, kind, idx, cond, sends, sets,
                 handler=None, excs=None):
        self._spec = spec
        self._st = st
        self._kind = kind
        self._idx = idx
        self._cond = cond
        self._sends = sends
        self._sets = sets
        self._excs = excs if excs is not None else []
        self._handler = handler        # (name, firstlineno) or None

    def _err(self, message: str, field: Optional[str] = None):
        name, line = self._handler or (None, None)
        return SpecError(message, spec=self._spec.name, handler=name,
                         kind=self._kind, field=field, line=line)

    def _key(self, field: str, op: str):
        key = (self._kind, self._idx, field)
        if key not in self._st:
            declared = sorted({f for k, _, f in self._st
                               if k == self._kind})
            raise self._err(
                f"{op} of undeclared field {field!r} on kind "
                f"{self._kind!r} (declared: {declared})", field=field)
        return key

    # ---------------------------------------------------------- accessors

    def get(self, field: str):
        """Current value of ``field`` (scalar, or [size] vector)."""
        return self._st[self._key(field, "get")]

    def put(self, field: str, value, when=True):
        """Conditionally set ``field`` (guard & when)."""
        import jax.numpy as jnp

        key = self._key(field, "put")
        cur = self._st[key]
        val = jnp.asarray(value, jnp.int32)
        self._st[key] = jnp.where(self._cond & when, val, cur).astype(
            jnp.int32)

    def _check_static_index(self, field: str, i, size: int, op: str):
        """A STATIC index outside the declared range is a loud
        compile-gate error (ISSUE 20): the one-hot mux would otherwise
        return a silent 0 / drop the write — exactly the class of bug
        the slot layer exists to retire.  Traced indices pass through
        (the mux masks them, matching the hand twins)."""
        if isinstance(i, (int, np.integer)) and not 0 <= int(i) < size:
            raise self._err(
                f"{op} of field {field!r}: static index {int(i)} "
                f"outside declared range [0, {size})", field=field)

    def get_at(self, field: str, i):
        """Dynamic element read of an array field — one-hot select, the
        engine's static-indexing rule (traced-index gathers are the
        measured vmap pathology).  Size-1 array fields unpack as
        scalars; treat them as one-element vectors."""
        import jax.numpy as jnp

        vec = jnp.atleast_1d(self._st[self._key(field, "get_at")])
        self._check_static_index(field, i, vec.shape[0], "get_at")
        oh = jnp.arange(vec.shape[0]) == i
        return jnp.sum(jnp.where(oh, vec, 0))

    def put_at(self, field: str, i, value, when=True):
        import jax.numpy as jnp

        key = self._key(field, "put_at")
        cur = self._st[key]
        vec = jnp.atleast_1d(cur)
        self._check_static_index(field, i, vec.shape[0], "put_at")
        oh = (jnp.arange(vec.shape[0]) == i) & self._cond & when
        out = jnp.where(oh, jnp.asarray(value, jnp.int32), vec).astype(
            jnp.int32)
        self._st[key] = out if cur.ndim else out[0]

    def cond(self, extra):
        """A refined child context (guard & extra) for nested logic."""
        return Ctx(self._spec, self._st, self._kind, self._idx,
                   self._cond & extra, self._sends, self._sets,
                   handler=self._handler, excs=self._excs)

    # ------------------------------------------------------------- slots

    def _slot_block(self, block: str, op: str):
        decl = self._spec.slot_blocks.get((self._kind, block))
        if decl is None:
            declared = sorted(b for k, b in self._spec.slot_blocks
                              if k == self._kind)
            raise self._err(
                f"{op} of undeclared Slots block {block!r} on kind "
                f"{self._kind!r} (declared: {declared})", field=block)
        touched = getattr(self._spec, "_touched_slots", None)
        if touched is not None:
            touched.add((self._kind, block))
        return decl

    def slot_get(self, block: str, field: str, i):
        """Read one record field of LOGICAL slot ``i`` (the block's
        ``base`` offset is spec data, not handler arithmetic)."""
        decl = self._slot_block(block, "slot_get")
        if isinstance(i, (int, np.integer)) and not (
                decl.base <= int(i) < decl.base + decl.n):
            raise self._err(
                f"slot_get of block {block!r}: static slot index "
                f"{int(i)} outside declared range "
                f"[{decl.base}, {decl.base + decl.n})", field=field)
        return self.get_at(decl.lane(field), i - decl.base)

    def slot_put(self, block: str, field: str, i, value, when=True):
        decl = self._slot_block(block, "slot_put")
        if isinstance(i, (int, np.integer)) and not (
                decl.base <= int(i) < decl.base + decl.n):
            raise self._err(
                f"slot_put of block {block!r}: static slot index "
                f"{int(i)} outside declared range "
                f"[{decl.base}, {decl.base + decl.n})", field=field)
        self.put_at(decl.lane(field), i - decl.base, value, when=when)

    def slot_clear_upto(self, block: str, upto, when=True):
        """Slot-windowed garbage bound: every slot with logical index
        STRICTLY below ``upto`` resets to its declared ``clear`` value
        (all record fields) — the lab3 log-GC pattern as one lowering.
        ``upto`` may be traced; the window mask rides the guard."""
        import jax.numpy as jnp

        decl = self._slot_block(block, "slot_clear_upto")
        idx = jnp.arange(decl.n) + decl.base
        win = (idx < upto) & self._cond & when
        for sf in decl.fields:
            key = self._key(decl.lane(sf.name), "slot_clear_upto")
            cur = jnp.atleast_1d(self._st[key])
            self._st[key] = jnp.where(win, sf.clear, cur).astype(
                jnp.int32)

    # ------------------------------------------------------------ quorum

    def quorum(self, name: str):
        """The spec-declared quorum ``name`` in resolved form
        (tpu/quorum.py Quorum: group size, vote threshold, reducers)."""
        q = self._spec.resolved_quorums().get(name)
        if q is None:
            raise self._err(
                f"read of undeclared quorum {name!r} (declared: "
                f"{sorted(self._spec.resolved_quorums())})", field=name)
        touched = getattr(self._spec, "_touched_quorums", None)
        if touched is not None:
            touched.add(name)
        return q

    def fail(self, code: int, when=True):
        """Raise the tensor analog of a handler exception: the step's
        ``exc`` lane becomes ``code`` when the guard (and ``when``)
        holds — the hand twins' pack-width guard discipline, now a
        combinator.  ``code`` must be a static positive int so the
        packed exc lane's domain is known at compile time."""
        if not isinstance(code, (int, np.integer)) or int(code) <= 0:
            raise self._err(
                f"fail() code must be a static positive int, got "
                f"{code!r}")
        self._excs.append((int(code), self._cond & when))

    # ------------------------------------------------------------ effects

    def send(self, msg: str, to, when=True, **fields):
        m = self._spec._mspec.get(msg)
        if m is None:
            raise self._err(
                f"send of undeclared message {msg!r} (declared: "
                f"{sorted(self._spec._mspec)})", field=msg)
        sent = getattr(self._spec, "_touched_sends", None)
        if sent is not None:
            sent.add(msg)
        unknown = sorted(set(fields) - set(m.fields))
        missing = sorted(set(m.fields) - set(fields))
        if unknown or missing:
            raise self._err(
                f"send({msg!r}): "
                + (f"unknown fields {unknown}" if unknown else "")
                + (" and " if unknown and missing else "")
                + (f"missing fields {missing}" if missing else ""),
                field=(unknown or missing)[0])
        self._sends.append(
            (self._spec._msg_row(msg, self.node_index(), to, fields),
             self._cond & when))

    def set_timer(self, timer: str, when=True, **fields):
        t = self._spec._tspec.get(timer)
        if t is None:
            raise self._err(
                f"set_timer of undeclared timer {timer!r} (declared: "
                f"{sorted(self._spec._tspec)})", field=timer)
        unknown = sorted(set(fields) - set(t.fields))
        missing = sorted(set(t.fields) - set(fields))
        if unknown or missing:
            raise self._err(
                f"set_timer({timer!r}): "
                + (f"unknown fields {unknown}" if unknown else "")
                + (" and " if unknown and missing else "")
                + (f"missing fields {missing}" if missing else ""),
                field=(unknown or missing)[0])
        self._sets.append(
            (self._spec._timer_row(timer, self.node_index(), fields),
             self._cond & when))

    def node_index(self):
        return self._spec._node_index(self._kind, self._idx)


class ProtocolSpec:

    def __init__(self, name: str,
                 nodes: Sequence[NodeKind],
                 messages: Sequence[MessageType],
                 timers: Sequence[TimerType],
                 net_cap: int = 16,
                 timer_cap: int = 4,
                 symmetry: Sequence[str] = (),
                 fault: Optional[object] = None,
                 quorums: Sequence[object] = (),
                 max_live_sends: Optional[int] = None):
        self.name = name
        # Multi-instance slot blocks (ISSUE 20, tpu/slots.py): each
        # Slots declaration inside NodeKind.fields expands to its
        # struct-of-arrays lanes here; the declaration itself is kept
        # for Ctx slot ops, fingerprinting, and conformance.
        self.slot_blocks: Dict[Tuple[str, str], object] = {}
        self.nodes = [self._expand_kind(k) for k in nodes]
        # Quorum declarations (ISSUE 20, tpu/quorum.py): resolved (and
        # refused when empty/unknown) at validate(); handlers reach
        # them via ctx.quorum(name).
        self.quorums = tuple(quorums)
        self._quorums_resolved: Optional[Dict[str, object]] = None
        # Composed sub-state machines: (kind, fragment name) pairs in
        # inclusion order — structural identity for the memo
        # fingerprint (service/memo.py).
        self.fragments: List[Tuple[str, str]] = []
        # handler function -> the fragment that brought it (the device
        # scope its operations name, :meth:`_invoke`)
        self._fragment_of: Dict[Callable, str] = {}
        self.max_live_sends = max_live_sends
        # Declarative fault model (ISSUE 19, tpu/faults.py): when set,
        # a hidden controller node kind ("$fault") is appended LAST so
        # partition/crash/drop/dup budgets live in ordinary bounded
        # Fields — packing, symmetry, spill and checkpoints carry them
        # with zero special cases.  compile() attaches the lowered
        # FaultLanes descriptor to TensorProtocol.fault; fault=None
        # specs lower byte-identically to the pre-fault program.
        self.fault = fault
        if fault is not None:
            from dslabs_tpu.tpu.faults import controller_kind
            self.nodes.append(controller_kind(fault, self.nodes))
        self.messages = list(messages)
        self.timers = list(timers)
        self.net_cap = net_cap
        self.timer_cap = timer_cap
        # Symmetry groups (ISSUE 15, tpu/symmetry.py): names of node
        # KINDS whose instances are interchangeable — handlers must
        # treat every member identically (the C5 conformance rule).
        # compile() emits the canonical-relabeling permutation tables;
        # the engines' opt-in canonicalize pass (default OFF) dedups
        # symmetric twins to one representative.
        self.symmetry = tuple(symmetry)
        # (kind, message/timer name) -> handler(ctx, payload dict)
        self.handlers: Dict[Tuple[str, str], Callable] = {}
        self.timer_handlers: Dict[Tuple[str, str], Callable] = {}
        self.initial_messages: List[tuple] = []   # (msg, frm, to, fields)
        self.initial_timers: List[tuple] = []     # (timer, node, fields)
        self.goals: Dict[str, Callable] = {}      # name -> fn(view)
        self.invariants: Dict[str, Callable] = {}
        self.decode_message: Optional[Callable] = None
        self.decode_timer: Optional[Callable] = None
        self._reindex_types()

    def _reindex_types(self) -> None:
        """(Re)build the tag/spec/width tables — called at construction
        and after a :meth:`include` merges fragment types in."""
        self._mtag = {m.name: i for i, m in enumerate(self.messages)}
        self._mspec = {m.name: m for m in self.messages}
        # Timer tag 0 is reserved (SENTINEL-adjacent "no tag") to keep
        # records visibly distinct from zeroed lanes.
        self._ttag = {t.name: 1 + i for i, t in enumerate(self.timers)}
        self._tspec = {t.name: t for t in self.timers}
        self._mw = 3 + max((len(m.fields) for m in self.messages),
                           default=0)
        self._tw = 3 + max((len(t.fields) for t in self.timers),
                           default=0)       # [tag, min, max, fields...]

    def _expand_kind(self, kind: NodeKind) -> NodeKind:
        """Expand Slots blocks inside a kind's fields to their lowered
        array Fields, recording each declaration for the Ctx slot
        ops."""
        from dslabs_tpu.tpu.slots import Slots, expand_slots

        if not any(isinstance(f, Slots) for f in kind.fields):
            return kind
        out: List[Field] = []
        for f in kind.fields:
            if isinstance(f, Slots):
                if (kind.name, f.name) in self.slot_blocks:
                    raise SpecError(
                        f"duplicate Slots block {f.name!r} on kind "
                        f"{kind.name!r}", spec=self.name,
                        kind=kind.name, field=f.name)
                self.slot_blocks[(kind.name, f.name)] = f
                out.extend(expand_slots(f, Field))
            else:
                out.append(f)
        return dataclasses.replace(kind, fields=tuple(out))

    def include(self, kind: str, fragment: "Fragment") -> None:
        """Compose a :class:`Fragment` onto a declared node kind: its
        fields append to the kind's layout, its message/timer types
        merge into the spec enums (identical re-declaration tolerated,
        conflicting redefinition refused), and its handlers register
        under the kind.  Must run before :meth:`compile`."""
        for pos, k in enumerate(self.nodes):
            if k.name == kind:
                break
        else:
            raise SpecError(
                f"include of fragment {fragment.name!r} on unknown "
                f"node kind {kind!r} (declared: "
                f"{sorted(x.name for x in self.nodes)})",
                spec=self.name, kind=kind, field=fragment.name)
        if (kind, fragment.name) in self.fragments:
            raise SpecError(
                f"fragment {fragment.name!r} included twice on kind "
                f"{kind!r}", spec=self.name, kind=kind,
                field=fragment.name)
        ext = self._expand_kind(dataclasses.replace(
            self.nodes[pos],
            fields=self.nodes[pos].fields + tuple(fragment.fields)))
        self.nodes[pos] = ext
        for m in fragment.messages:
            cur = next((x for x in self.messages if x.name == m.name),
                       None)
            if cur is None:
                self.messages.append(m)
            elif cur != m:
                raise SpecError(
                    f"fragment {fragment.name!r} redeclares message "
                    f"{m.name!r} with a different shape",
                    spec=self.name, kind=kind, field=m.name)
        for t in fragment.timers:
            cur = next((x for x in self.timers if x.name == t.name),
                       None)
            if cur is None:
                self.timers.append(t)
            elif cur != t:
                raise SpecError(
                    f"fragment {fragment.name!r} redeclares timer "
                    f"{t.name!r} with a different shape",
                    spec=self.name, kind=kind, field=t.name)
        for msg, fn in fragment.handlers.items():
            if (kind, msg) in self.handlers:
                raise SpecError(
                    f"fragment {fragment.name!r} handler for "
                    f"{msg!r} collides with an existing handler on "
                    f"kind {kind!r}", spec=self.name, kind=kind,
                    field=msg)
            self.handlers[(kind, msg)] = fn
            self._fragment_of[fn] = fragment.name
        for tmr, fn in fragment.timer_handlers.items():
            if (kind, tmr) in self.timer_handlers:
                raise SpecError(
                    f"fragment {fragment.name!r} timer handler for "
                    f"{tmr!r} collides with an existing handler on "
                    f"kind {kind!r}", spec=self.name, kind=kind,
                    field=tmr)
            self.timer_handlers[(kind, tmr)] = fn
            self._fragment_of[fn] = fragment.name
        self.fragments.append((kind, fragment.name))
        self._reindex_types()

    def resolved_quorums(self) -> Dict[str, object]:
        """Declared quorums resolved against the node kinds (cached);
        raises the structured refusal for empty/unknown groups."""
        if self._quorums_resolved is None:
            from dslabs_tpu.tpu.quorum import resolve_quorums
            self._quorums_resolved = resolve_quorums(self)
        return self._quorums_resolved

    # ------------------------------------------------------------- layout

    def on(self, kind: str, msg: str):
        def reg(fn):
            self.handlers[(kind, msg)] = fn
            return fn
        return reg

    def on_timer(self, kind: str, timer: str):
        def reg(fn):
            self.timer_handlers[(kind, timer)] = fn
            return fn
        return reg

    def _instances(self):
        for kind in self.nodes:
            for i in range(kind.count):
                yield kind, i

    def _node_index(self, kind_name: str, idx: int) -> int:
        base = 0
        for kind in self.nodes:
            if kind.name == kind_name:
                return base + idx
            base += kind.count
        raise KeyError(kind_name)

    def _layout(self):
        """(kind, idx, field) -> (offset, size); total width."""
        off = 0
        table = {}
        for kind, i in self._instances():
            for f in kind.fields:
                table[(kind.name, i, f.name)] = (off, f.size)
                off += f.size
        return table, off

    def decode_tables(self):
        """What an adapter's decoders and predicates address a compiled
        twin's rows by, whatever its caps: (message tag -> name, timer
        tag -> name, (kind, instance, field) -> first node lane)."""
        return ({tag: name for name, tag in self._mtag.items()},
                {tag: name for name, tag in self._ttag.items()},
                {k: off for k, (off, _) in self._layout()[0].items()})

    def _msg_row(self, name, frm, to, fields):
        import jax.numpy as jnp

        m = self._mspec[name]
        vals = dict(fields)
        lanes = [jnp.asarray(self._mtag[name], jnp.int32),
                 jnp.asarray(frm, jnp.int32), jnp.asarray(to, jnp.int32)]
        for f in m.fields:
            lanes.append(jnp.asarray(vals.pop(f), jnp.int32))
        assert not vals, f"{name}: unknown fields {sorted(vals)}"
        while len(lanes) < self._mw:
            lanes.append(jnp.zeros((), jnp.int32))
        return jnp.stack(lanes)

    def _timer_row(self, name, node, fields):
        import jax.numpy as jnp

        t = self._tspec[name]
        vals = dict(fields)
        lanes = [jnp.asarray(node, jnp.int32),
                 jnp.asarray(self._ttag[name], jnp.int32),
                 jnp.asarray(t.min_ms, jnp.int32),
                 jnp.asarray(t.max_ms, jnp.int32)]
        for f in t.fields:
            lanes.append(jnp.asarray(vals.pop(f), jnp.int32))
        assert not vals, f"{name}: unknown fields {sorted(vals)}"
        while len(lanes) < 1 + self._tw:
            lanes.append(jnp.zeros((), jnp.int32))
        return jnp.stack(lanes)

    # ----------------------------------------------------------- validate

    def _handler_id(self, fn):
        try:
            return (fn.__name__, fn.__code__.co_firstlineno)
        except AttributeError:
            return (getattr(fn, "__name__", repr(fn)), None)

    def validate(self) -> None:
        """The C4 spec-hygiene compile gate (ISSUE 10): handler
        registrations must reference declared node kinds and declared
        message/timer types, and initial messages/timers must name
        declared types — raised as structured :class:`SpecError`
        instead of the bare KeyError/shape errors malformed specs used
        to die with deep inside the engine.  Run automatically at the
        top of :meth:`compile`; the conformance linter
        (dslabs_tpu/analysis/conformance.py) reports the same failures
        as findings without raising."""
        from dslabs_tpu.tpu.faults import FAULT_KIND, validate_fault
        n_ctrl = sum(1 for k in self.nodes if k.name == FAULT_KIND)
        if n_ctrl != (1 if self.fault is not None else 0):
            raise SpecError(
                f"node kind name {FAULT_KIND!r} is reserved for the "
                "fault controller (declare faults via fault=FaultModel"
                "(...), not as a node kind)",
                spec=self.name, kind=FAULT_KIND, code="C6")
        if self.fault is not None:
            for (kind, _msg) in list(self.handlers) + \
                    list(self.timer_handlers):
                if kind == FAULT_KIND:
                    raise SpecError(
                        "handlers may not be registered on the fault "
                        "controller kind — protocols observe faults "
                        "only through message loss and timer silence",
                        spec=self.name, kind=FAULT_KIND, code="C6")
            validate_fault(self)
        # Quorum declarations resolve (and refuse empty/unknown
        # groups) at the same gate (ISSUE 20, tpu/quorum.py).
        self._quorums_resolved = None
        self.resolved_quorums()
        kinds = {k.name for k in self.nodes}
        for (kind, msg), fn in self.handlers.items():
            name, line = self._handler_id(fn)
            if kind not in kinds:
                raise SpecError(
                    f"handler registered for unknown node kind "
                    f"{kind!r} (declared: {sorted(kinds)})",
                    spec=self.name, handler=name, kind=kind, line=line)
            if msg not in self._mtag:
                raise SpecError(
                    f"handler registered for unknown message {msg!r} "
                    f"(declared: {sorted(self._mtag)})",
                    spec=self.name, handler=name, kind=kind, field=msg,
                    line=line)
        for (kind, timer), fn in self.timer_handlers.items():
            name, line = self._handler_id(fn)
            if kind not in kinds:
                raise SpecError(
                    f"timer handler registered for unknown node kind "
                    f"{kind!r} (declared: {sorted(kinds)})",
                    spec=self.name, handler=name, kind=kind, line=line)
            if timer not in self._ttag:
                raise SpecError(
                    f"timer handler registered for unknown timer "
                    f"{timer!r} (declared: {sorted(self._ttag)})",
                    spec=self.name, handler=name, kind=kind,
                    field=timer, line=line)
        for name, *_ in self.initial_messages:
            if name not in self._mspec:
                raise SpecError(
                    f"initial message of undeclared type {name!r}",
                    spec=self.name, field=name)
        for name, *_ in self.initial_timers:
            if name not in self._tspec:
                raise SpecError(
                    f"initial timer of undeclared type {name!r}",
                    spec=self.name, field=name)
        kind_counts = {k.name: k.count for k in self.nodes}
        for g in self.symmetry:
            if g not in kinds:
                raise SpecError(
                    f"symmetry group names unknown node kind {g!r} "
                    f"(declared: {sorted(kinds)})",
                    spec=self.name, kind=g, code="C5")
        for kind in self.nodes:
            for f in kind.fields:
                if f.hi is not None and f.hi < f.lo:
                    raise SpecError(
                        f"field {f.name!r} on kind {kind.name!r} has "
                        f"empty domain [{f.lo}, {f.hi}]",
                        spec=self.name, kind=kind.name, field=f.name)
                if f.index_group is not None:
                    if f.index_group not in kind_counts:
                        raise SpecError(
                            f"field {f.name!r} on kind {kind.name!r} "
                            f"declares index_group for unknown kind "
                            f"{f.index_group!r}",
                            spec=self.name, kind=kind.name,
                            field=f.name, code="C5")
                    if f.size != kind_counts[f.index_group]:
                        raise SpecError(
                            f"field {f.name!r} on kind {kind.name!r} "
                            f"has size {f.size} but index_group "
                            f"{f.index_group!r} has "
                            f"{kind_counts[f.index_group]} instances",
                            spec=self.name, kind=kind.name,
                            field=f.name, code="C5")
                if f.delta is not None and f.hi is not None:
                    raise SpecError(
                        f"field {f.name!r} on kind {kind.name!r} "
                        "declares both hi= and delta= — a bounded "
                        "domain and the delta-from-base lane are "
                        "mutually exclusive", spec=self.name,
                        kind=kind.name, field=f.name, code="C5")
                # Init values must sit inside the declared domain —
                # the packed encoding would otherwise corrupt the root
                # state silently (tpu/packing.py).
                if f.hi is not None:
                    for i in range(kind.count):
                        v = f.init(i) if callable(f.init) else f.init
                        vals = np.atleast_1d(np.asarray(v)).tolist()
                        for x in vals:
                            if not (f.lo <= int(x) <= f.hi):
                                raise SpecError(
                                    f"init value {x} of field "
                                    f"{f.name!r} on kind {kind.name!r} "
                                    f"outside declared domain "
                                    f"[{f.lo}, {f.hi}]",
                                    spec=self.name, kind=kind.name,
                                    field=f.name)

    # -------------------------------------------- packing / symmetry

    def _lane_domains(self) -> dict:
        """Per-lane value domains for the bit-packed frontier encoding
        (tpu/packing.py): the structural lanes (message/timer tags,
        from/to node indices, timer min/max) derive from the spec
        itself; field/payload lanes from the declared ``lo``/``hi``
        bounds, ``None`` (full int32) where undeclared."""
        n_nodes = sum(k.count for k in self.nodes)
        nodes = []
        for kind, _i in self._instances():
            for f in kind.fields:
                if f.hi is not None:
                    dom = (f.lo, f.hi)
                elif f.delta is not None:
                    # Delta-from-base lane (ISSUE 18): engines that
                    # carry a level base pack this in f.delta bits;
                    # others derive it as raw (packing.derive_packing).
                    dom = ("delta", int(f.delta))
                else:
                    dom = None
                nodes += [dom] * f.size
        node_dom = (0, max(n_nodes - 1, 0))

        def _merge(entries):
            """Union of (lo, hi) domains; None poisons."""
            lo = hi = None
            for e in entries:
                if e is None:
                    return None
                lo = e[0] if lo is None else min(lo, e[0])
                hi = e[1] if hi is None else max(hi, e[1])
            return (0, 0) if lo is None else (lo, hi)

        msg = [(0, max(len(self.messages) - 1, 0)), node_dom, node_dom]
        for j in range(self._mw - 3):
            entries = []
            for m in self.messages:
                if j < len(m.fields):
                    entries.append((m.bounds or {}).get(m.fields[j]))
                else:
                    entries.append((0, 0))      # zero-padded lane
            msg.append(_merge(entries))
        tmr = [(0, len(self.timers)),
               _merge([(t.min_ms, t.min_ms) for t in self.timers]),
               _merge([(t.max_ms, t.max_ms) for t in self.timers])]
        for j in range(self._tw - 3):
            entries = []
            for t in self.timers:
                if j < len(t.fields):
                    entries.append((t.bounds or {}).get(t.fields[j]))
                else:
                    entries.append((0, 0))
            tmr.append(_merge(entries))
        # The exc lane spans the declared ctx.fail codes; without any
        # the compiled steps never set it (_normalize_step pads exc=0)
        # and the lane is a constant.
        return {"nodes": nodes, "msg": msg, "timer": tmr,
                "exc": (0, getattr(self, "_exc_hi", 0))}

    def _symmetry_spec(self, table):
        """Build the canonical-relabeling permutation tables for the
        declared symmetry groups (tpu/symmetry.py SymmetrySpec), or
        None when no groups are declared."""
        if not self.symmetry:
            return None
        import itertools

        from dslabs_tpu.tpu.symmetry import SymmetrySpec

        n_nodes = sum(k.count for k in self.nodes)
        _, nw = self._layout()
        bases = {}
        off = 0
        for kind in self.nodes:
            bases[kind.name] = off
            off += kind.count
        groups = []
        total = 1
        for g in self.symmetry:
            count = next(k.count for k in self.nodes if k.name == g)
            groups.append((g, bases[g], count))
            for i in range(2, count + 1):
                total *= i
        if total > 720:
            raise SpecError(
                f"symmetry groups expand to {total} permutations "
                "(> 720) — the fused canonicalize pass enumerates "
                "them; shrink the groups", spec=self.name, code="C5")
        per_group = [list(itertools.permutations(range(c)))
                     for _g, _b, c in groups]
        relabs, lane_srcs = [], []
        for combo in itertools.product(*per_group):
            relab = np.arange(n_nodes, dtype=np.int64)
            lane_src = np.arange(nw, dtype=np.int64)
            for (g, base, count), sigma in zip(groups, combo):
                # new position j holds old member sigma[j]
                for j in range(count):
                    relab[base + sigma[j]] = base + j
                kind = next(k for k in self.nodes if k.name == g)
                for j in range(count):
                    for f in kind.fields:
                        dst, size = table[(g, j, f.name)]
                        src, _ = table[(g, sigma[j], f.name)]
                        lane_src[dst:dst + size] = np.arange(
                            src, src + size)
                # Group-indexed array fields permute their ELEMENTS
                # with the group (per-member bitmaps stay coherent).
                # Restricted to fields on kinds OUTSIDE the group
                # itself (validated below), so every assignment reads
                # original (identity) positions — no composition.
                for kind2, i2 in self._instances():
                    for f in kind2.fields:
                        if f.index_group != g:
                            continue
                        if kind2.name == g:
                            raise SpecError(
                                f"field {f.name!r}: index_group on a "
                                f"kind inside its own symmetry group "
                                f"{g!r} is unsupported",
                                spec=self.name, kind=kind2.name,
                                field=f.name, code="C5")
                        o2, _ = table[(kind2.name, i2, f.name)]
                        for j in range(count):
                            lane_src[o2 + j] = o2 + sigma[j]
            relabs.append(relab)
            lane_srcs.append(lane_src)
        # Identity permutation first (the canonicalizer's cheap first
        # candidate); itertools.product with sorted permutations
        # yields it first already, but pin it explicitly.
        order = sorted(range(len(relabs)),
                       key=lambda i: 0 if (relabs[i]
                                           == np.arange(n_nodes)).all()
                       else 1)
        return SymmetrySpec(
            relab=np.stack([relabs[i] for i in order]),
            lane_src=np.stack([lane_srcs[i] for i in order]),
            groups=tuple((g, b, c) for g, b, c in groups))

    # ------------------------------------------------------------ compile

    def compile(self):
        """-> TensorProtocol (the engine contract, engine.py:94-146).

        One ``compile.twin`` span a call (``spec``, ``instances``: node
        instances, ``invocations``: handlers the budget dry-run ran);
        its seconds are the process's ``twin_build_s``
        (``compile_cache.totals()``)."""
        from dslabs_tpu.tpu import compile_cache, telemetry

        t0 = time.monotonic()
        with telemetry.phase(
                "compile.twin", spec=self.name,
                instances=sum(k.count for k in self.nodes)) as span:
            protocol = self._compile()
            span.set(invocations=self._invocations)
        compile_cache.twin_built(time.monotonic() - t0)
        return protocol

    def _compile(self):
        import jax.numpy as jnp

        from dslabs_tpu.tpu.engine import SENTINEL, TensorProtocol

        self.validate()
        table, nw = self._layout()
        n_nodes = sum(k.count for k in self.nodes)
        spec = self

        def unpack(nodes):
            st = {}
            for key, (off, size) in table.items():
                st[key] = (nodes[off] if size == 1
                           else nodes[off:off + size])
            return st

        def repack(st):
            # A size-1 field unpacks as a scalar, but the slot ops hand
            # a ONE-slot array back as a [1] vector (atleast_1d): take
            # either form for what the table says it is.
            return jnp.concatenate(
                [jnp.reshape(st[key], (size,))
                 for key, (_off, size) in table.items()]).astype(jnp.int32)

        # Static send/set budgets: trace each handler once with a dummy
        # context to COUNT its effect rows (the finalize-assert
        # discipline of the hand twins, without the hand counting).
        max_sends, max_sets = self._count_budgets()

        uses_exc = self._exc_hi > 0

        def _finalize(groups, budget, width):
            """Merge per-invocation row groups into one [budget, width]
            block.  Invocations are pairwise mutually exclusive (see
            _count_budgets), so row j of the step is jnp.minimum over
            every group's SENTINEL-blanked row j: at most one group
            contributes live rows, SENTINEL (int32 max) loses every
            minimum, and an all-false step yields an all-blank block —
            exactly the hand twins' jnp.minimum merge discipline."""
            blank = jnp.full((width,), SENTINEL, jnp.int32)
            merged = [blank] * budget
            for rows in groups:
                assert len(rows) <= budget, (len(rows), budget)
                for j, (rec, cond) in enumerate(rows):
                    merged[j] = jnp.minimum(
                        merged[j], jnp.where(cond, rec, blank))
            return (jnp.stack(merged) if merged
                    else jnp.zeros((0, width), jnp.int32))

        def _exc_lane(excs):
            out = jnp.zeros((), jnp.int32)
            for code, cond in excs:
                out = jnp.maximum(out, jnp.where(cond, code, 0))
            return out

        def step_message(nodes, msg):
            st = unpack(nodes)
            send_groups, set_groups, excs = [], [], []
            tag, frm, to = msg[0], msg[1], msg[2]
            for kind, i in spec._instances():
                here = to == spec._node_index(kind.name, i)
                for m in spec.messages:
                    fn = spec.handlers.get((kind.name, m.name))
                    if fn is None:
                        continue
                    cond = here & (tag == spec._mtag[m.name])
                    payload = {f: msg[3 + j]
                               for j, f in enumerate(m.fields)}
                    payload["_from"] = frm
                    sends, sets = [], []
                    ctx = Ctx(spec, st, kind.name, i, cond, sends, sets,
                              handler=spec._handler_id(fn), excs=excs)
                    spec._invoke(fn, ctx, payload, m.name)
                    send_groups.append(sends)
                    set_groups.append(sets)
            out = (repack(st),
                   _finalize(send_groups, max_sends, spec._mw),
                   _finalize(set_groups, max_sets, 1 + spec._tw))
            return out + ((_exc_lane(excs),) if uses_exc else ())

        def step_timer(nodes, node_idx, timer):
            st = unpack(nodes)
            send_groups, set_groups, excs = [], [], []
            tag = timer[0]
            for kind, i in spec._instances():
                here = node_idx == spec._node_index(kind.name, i)
                for t in spec.timers:
                    fn = spec.timer_handlers.get((kind.name, t.name))
                    if fn is None:
                        continue
                    cond = here & (tag == spec._ttag[t.name])
                    payload = {f: timer[3 + j]
                               for j, f in enumerate(t.fields)}
                    sends, sets = [], []
                    ctx = Ctx(spec, st, kind.name, i, cond, sends, sets,
                              handler=spec._handler_id(fn), excs=excs)
                    spec._invoke(fn, ctx, payload, t.name)
                    send_groups.append(sends)
                    set_groups.append(sets)
            out = (repack(st),
                   _finalize(send_groups, max_sends, spec._mw),
                   _finalize(set_groups, max_sets, 1 + spec._tw))
            return out + ((_exc_lane(excs),) if uses_exc else ())

        def init_nodes():
            out = np.zeros((nw,), np.int32)
            for (kind_name, i, fname), (off, size) in table.items():
                kind = next(k for k in self.nodes if k.name == kind_name)
                f = next(x for x in kind.fields if x.name == fname)
                v = f.init(i) if callable(f.init) else f.init
                out[off:off + size] = v
            return out

        def init_messages():
            rows = []
            for name, frm, to, fields in self.initial_messages:
                m = self._mspec[name]
                rec = np.zeros((self._mw,), np.int32)
                rec[0:3] = [self._mtag[name], frm, to]
                for j, f in enumerate(m.fields):
                    rec[3 + j] = fields[f]
                rows.append(rec)
            return (np.stack(rows) if rows
                    else np.zeros((0, self._mw), np.int32))

        def init_timers():
            rows = []
            for name, node, fields in self.initial_timers:
                t = self._tspec[name]
                rec = np.zeros((1 + self._tw,), np.int32)
                rec[0:4] = [node, self._ttag[name], t.min_ms, t.max_ms]
                for j, f in enumerate(t.fields):
                    rec[4 + j] = fields[f]
                rows.append(rec)
            return (np.stack(rows) if rows
                    else np.zeros((0, 1 + self._tw), np.int32))

        def _pred(fn):
            def wrapped(state):
                return fn(_View(spec, table, state["nodes"]))
            return wrapped

        fault_lanes = None
        if self.fault is not None:
            from dslabs_tpu.tpu.faults import compile_fault_lanes
            fault_lanes = compile_fault_lanes(self, table, nw,
                                              init_nodes())

        return TensorProtocol(
            name=self.name,
            n_nodes=n_nodes,
            node_width=nw,
            lane_domains=self._lane_domains(),
            symmetry=self._symmetry_spec(table),
            fault=fault_lanes,
            msg_width=self._mw,
            timer_width=self._tw,
            net_cap=self.net_cap,
            timer_cap=self.timer_cap,
            max_sends=max(max_sends, 1),
            max_sets=max(max_sets, 1),
            max_live_sends=self.max_live_sends,
            init_nodes=init_nodes,
            init_messages=init_messages,
            init_timers=init_timers,
            step_message=step_message,
            step_timer=step_timer,
            msg_dest=lambda msg: msg[2],
            goals={k: _pred(v) for k, v in self.goals.items()},
            invariants={k: _pred(v) for k, v in self.invariants.items()},
            decode_message=self.decode_message,
            decode_timer=self.decode_timer,
        )

    def scoped(self, fn):
        """``fn`` run under the spec's OWN handler scope: for a callback
        the spec hands to a fragment (lab 4's effect switch, which
        ``gpaxos`` drives for every executed slot)."""
        return _scoped(OWN_SCOPE, fn)

    def _invoke(self, fn, ctx: "Ctx", payload: dict, typ: str):
        """Run one handler under the compile gate: a KeyError on the
        payload dict (reading a field the message/timer type does not
        declare) surfaces as a structured SpecError naming the handler
        — the bare-KeyError shape this satellite retires.  In a spec
        built of fragments the handler runs under the device scope of
        the fragment that brought it (the spec's own: ``spec``); a spec
        with none adds no scope, so its lowered text is what it was."""
        scope = (handler_scope(self._fragment_of.get(fn, OWN_SCOPE))
                 if self.fragments else contextlib.nullcontext())
        try:
            with scope:
                return fn(ctx, payload)
        except KeyError as e:
            name, line = self._handler_id(fn)
            missing = e.args[0] if e.args else "?"
            raise SpecError(
                f"read of field {missing!r} not declared by "
                f"{typ!r} (payload fields: "
                f"{sorted(k for k in payload if k != '_from')})",
                spec=self.name, handler=name, field=str(missing),
                line=line) from e

    def _count_budgets(self) -> Tuple[int, int]:
        """Count worst-case send/set rows by running every handler once
        with a counting context (handlers are straight-line over the
        combinators, so one run = its static row count).

        Handler invocations within one step are pairwise mutually
        exclusive — each is guarded by ``(to == node_idx) & (tag ==
        mtag)`` and at most one (node, type) pair matches a delivered
        record — so the compiled step MERGES their row groups
        (jnp.minimum over SENTINEL-blanked rows) instead of
        concatenating them.  The budget is therefore the MAX single
        invocation's row count, not the sum: this is what keeps
        MAX_SENDS at hand-twin scale for lab3/lab4, where summing
        across ~40 handler instances would explode the send block
        (ISSUE 20).

        Also records exc-lane usage for :meth:`_lane_domains`:
        ``self._exc_hi`` is the largest static ``ctx.fail`` code (0
        when no handler fails)."""
        import jax.numpy as jnp

        table, _ = self._layout()
        max_sends = max_sets = 0
        self._exc_hi = 0
        # Coverage record for the conformance linter's soft C4 half:
        # which Slots blocks and quorums the dry-run actually touched.
        self._touched_slots = set()
        self._touched_quorums = set()
        self._touched_sends = set()
        # The dry-run's values are thrown away, so none of it belongs
        # on an accelerator: under the host's CPU device as the default
        # one, its operands, every primitive a handler binds and every
        # tiny program those compile are the CPU backend's (60,000
        # eager operations at lab 4's n = 3, each a launch where the
        # default device is a TPU).  A process without a CPU backend
        # (JAX_PLATFORMS names another alone) runs it where it always
        # ran.  Not under jit / eval_shape: tracing that many binds was
        # measured slower than executing them (docs/specs.md).
        with _on_host_cpu():
            # ONE set of zero operands a call: arrays are immutable,
            # and each invocation's Ctx gets its own dict of them (a
            # ``ctx.put`` rebinds a key of THAT dict).
            zero = jnp.zeros((), jnp.int32)
            zeros = {key: (zero if size == 1
                           else jnp.zeros((size,), jnp.int32))
                     for key, (_, size) in table.items()}
            false = jnp.asarray(False)
            # an instance's message handlers, then its timer handlers
            calls = [(kind, i, typ, fn, frm)
                     for kind, i in self._instances()
                     for types, handlers, frm in (
                         (self.messages, self.handlers, {"_from": zero}),
                         (self.timers, self.timer_handlers, {}))
                     for typ in types
                     for fn in [handlers.get((kind.name, typ.name))]
                     if fn is not None]
            self._invocations = len(calls)
            for kind, i, typ, fn, frm in calls:
                sends, sets, excs = [], [], []
                ctx = Ctx(self, dict(zeros), kind.name, i, false, sends,
                          sets, handler=self._handler_id(fn), excs=excs)
                self._invoke(fn, ctx,
                             {f: zero for f in typ.fields} | frm, typ.name)
                max_sends = max(max_sends, len(sends))
                max_sets = max(max_sets, len(sets))
                for code, _c in excs:
                    self._exc_hi = max(self._exc_hi, code)
        return (max_sends, max_sets)


def _on_host_cpu():
    """The process's first CPU device as JAX's default device, for the
    block; no change where JAX has no CPU backend (``JAX_PLATFORMS``
    names another platform alone)."""
    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


class _View:
    """Read-only predicate view over the packed lanes of one state."""

    def __init__(self, spec, table, nodes):
        self._table = table
        self._nodes = nodes

    def get(self, kind: str, idx: int, field: str):
        off, size = self._table[(kind, idx, field)]
        return (self._nodes[off] if size == 1
                else self._nodes[off:off + size])
