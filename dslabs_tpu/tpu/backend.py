"""The tensor engine as a harness-selectable search strategy.

SURVEY §8.1: "a ``Search``/``SearchSettings``-shaped plugin point; the TPU
backend is a new ``Search`` strategy selectable by settings" (reference
entry points ``Search.bfs/dfs``, Search.java:390-402).  This module is
that plugin point: :func:`tensor_bfs` accepts the SAME object
``SearchState`` + ``SearchSettings`` the lab search tests build, runs the
search on the TPU tensor engine via the lab's protocol twin, and returns
an object ``SearchResults`` whose terminal states are REAL object states
(reconstructed by trace replay on the object twin, tpu/trace.py) — so
staged searches (``results.goal_matching_state`` fed into the next
``bfs``) and trace assertions keep working unchanged.

Pipeline per call (what a call BUILDS on the way — the bound twin, the
engine with its traced and compiled programs, the compiled trace step —
the process keeps, in one bounded least-recently-used table keyed by the
call's input: :class:`_Kept`.  The first call of a shape, caps and
predicate structure pays for all of it, a compile included — seconds on
the CPU, a minute on a chip; a repeated one finds it and pays for the
search, the replays and the object twin's steps: tenths of a second.
Roots, results and witnesses are derived anew every call):

1. **Twin resolution** — registered :class:`TwinAdapter`\\ s inspect the
   object state's node composition and return a :class:`TwinBinding`
   (tensor protocol + address/command maps + lane predicates).  No twin =
   loud :class:`NoTensorTwin`, never a silent object-path fallback.
2. **Root derivation** — a depth-0 canonical state maps to the twin's
   initial state.  A STAGED state (a goal state from a previous
   tensor-backend phase) carries a :class:`TensorProvenance` history
   (event ids + staged ops like dropPendingMessages); the tensor root is
   re-derived by replaying that history through the twin's transition,
   the exact inverse of how the object state itself was materialised.
3. **Settings compilation** — the link matrix / sender / receiver /
   network flags become a [NN, NN] delivery matrix (the twin's
   ``deliver_message`` mask), per-node timer gating a [NN] vector, and
   every invariant/goal/prune ``StatePredicate`` is translated to a lane
   predicate via its ``tkey`` metadata (combinators translate
   structurally).  Untranslatable predicate = loud NoTensorTwin.
4. **Run** — ShardedTensorSearch, strict=True (drops are fatal: lab
   verdicts must be exact), record_trace=True; capacity ladder retries
   CapacityOverflow with doubled caps (no hand-tuned budgets).  Every
   call starts on the first rung; each rung's engine is kept on its own.
   Partition, timer gating, ``max_depth``, ``max_time`` and the
   recorder are runtime settings, set on the engine every call.
5. **Results adaptation** — end conditions map onto the object
   ``EndCondition`` (the object checker treats the depth limit as a
   prune, so tensor DEPTH_EXHAUSTED reports SPACE_EXHAUSTED); terminal
   tensor states are replayed onto the object twin and re-checked with
   the ORIGINAL object predicate — a twin/object verdict divergence
   raises instead of returning a wrong answer.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import os
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from dslabs_tpu.tpu import compile_cache, telemetry

__all__ = ["NoTensorTwin", "TensorProvenance", "TwinBinding",
           "cache_info", "clear_cache", "probe_fleet", "probe_round",
           "probe_secs", "probe_table", "probe_walker_steps",
           "probe_walkers", "register_adapter", "tensor_bfs",
           "tensor_dfs"]


class NoTensorTwin(RuntimeError):
    """No tensor twin / translation exists for this search configuration.

    Raised loudly (the test errors) rather than silently falling back to
    the object checker: ``--search-backend tensor`` must mean the tensor
    engine actually ran the search."""


@dataclasses.dataclass
class TensorProvenance:
    """How a staged object state was produced, in twin terms: the binding
    config it belongs to and the ordered history of events and staged ops
    (``("ev_msg", net_slot)``, ``("ev_tmr", node, queue_slot)``,
    ``("drop",)``, ``("undrop_from", name)``, ``("undrop_to", name)``,
    ``("undrop_all",)``) from the twin's initial state.  Events are
    recorded CAP-INDEPENDENTLY — canonical network packing keeps occupied
    slot indices identical across any net_cap >= occupancy, and timer
    (node, queue-slot) pairs do not reference the grid stride — so the
    history replays identically under a different capacity-ladder rung
    than the one that recorded it.  Lets the next search phase re-derive
    the tensor root without an object->tensor state encoder."""

    key: tuple
    history: List[tuple] = dataclasses.field(default_factory=list)


def _norm_event(p, ev: int) -> tuple:
    """Grid event id (relative to protocol p's caps) -> cap-independent
    provenance op."""
    if ev < p.net_cap:
        return ("ev_msg", int(ev))
    t = ev - p.net_cap
    return ("ev_tmr", int(t) // p.timer_cap, int(t) % p.timer_cap)


def _denorm_event(p, op: tuple) -> int:
    # Capacity misses here are ladder-retryable, not twin-missing: a
    # history recorded by a phase that escalated the capacity ladder can
    # reference slots beyond a lower rung's caps (ADVICE r4).  Lazy
    # import (like every jax-adjacent import in this module) so the
    # object-only path never pays the engine import.
    from dslabs_tpu.tpu.engine import CapacityOverflow

    if op[0] == "ev_msg":
        if op[1] >= p.net_cap:
            raise CapacityOverflow(
                f"provenance slot {op[1]} beyond net_cap {p.net_cap}")
        return op[1]
    if op[2] >= p.timer_cap:
        raise CapacityOverflow(
            f"provenance timer slot {op[2]} beyond timer_cap "
            f"{p.timer_cap}")
    return p.net_cap + op[1] * p.timer_cap + op[2]


class TwinBinding:
    """A resolved (object configuration -> tensor twin) binding.

    Subclasses (one per lab family, see tpu/adapters/) provide:

    - ``key``: hashable config identity (stable across staged phases)
    - ``build_protocol(net_cap, timer_cap) -> TensorProtocol`` (no masks)
    - ``addr_index``: root-address name -> twin node index
    - ``predicate(tkey) -> fn(state_slice) -> bool`` lane predicate
    - ``initial_caps() -> (net_cap, timer_cap)`` starting capacities
    """

    key: tuple = ()
    addr_index: Dict[str, int] = {}

    def build_protocol(self, net_cap: int, timer_cap: int):
        raise NotImplementedError

    def initial_caps(self) -> Tuple[int, int]:
        raise NotImplementedError

    def probe_caps(self) -> Tuple[int, int]:
        """(net_cap, timer_cap) of the dfs entry's swarm probe
        (:func:`probe_fleet`).  Default: the capacity ladder's TOP rung
        outright — walkers hold K rows, not a frontier, so wide caps
        cost little, and at base caps every truncated step would
        restart a walker below the very depths the probe exists to
        reach (the truncation count is loud:
        ``SearchOutcome.swarm_overflow``).  A binding whose deployments
        outgrow that rung along a deep walk overrides it."""
        net_cap, timer_cap = self.initial_caps()
        top = len(_LADDER) - 1
        return net_cap << top, timer_cap + 2 * top

    def probe_binding(self) -> "TwinBinding":
        """The binding the dfs entry's swarm probe is built through
        (:func:`probe_fleet`): this one, unless a deep walk needs a
        wider twin than the strict BFS's (``PaxosBinding``: spare log
        slots).  Idempotent."""
        return self

    def twin_key(self) -> tuple:
        """Hashable identity of the twin ``build_protocol`` returns at
        given caps, read AFTER ``check_settings``: everything that
        ``build_protocol``, the decoders it binds and ``predicate``
        read off this binding.  The lab entry keeps twins, engines and
        trace steps under it (:class:`_Kept`), so two bindings of equal
        ``twin_key`` must be interchangeable.  Default ``key``;
        bindings whose decoders or modelling flags read more extend
        it."""
        return self.key

    def predicate(self, tkey) -> Callable:
        raise NotImplementedError

    def check_settings(self, settings) -> None:
        """Hook: raise NoTensorTwin when the settings demand events the
        twin does not model (e.g. live timers on an unmodeled node).
        Bindings whose twins model every node's full event surface can
        keep the default no-op."""

    def derive_root(self, search, state):
        """Hook: object initial/staged state -> (tensor root pytree or
        None for the twin initial, provenance history).  Default = the
        module-level provenance replay; bindings whose twin initial
        state BAKES IN a staged prefix (lab 4's joined root) override
        with validation-based mapping."""
        return derive_root(self, search, state)

    def msg_mask_fn(self) -> Callable:
        """fn(msg_record, [NN*NN] link matrix) -> deliverable, for the
        default [tag, frm, to, ...] record layout; bindings whose twins
        do not carry frm/to lanes (e.g. lab 1's [tag, c, s]) override
        with their own lane mapping."""
        nn = len(self.addr_index)

        def fn(msg, marr, nn=nn):
            import jax.numpy as jnp

            k = (msg[1].clip(0, nn - 1) * nn
                 + msg[2].clip(0, nn - 1))
            return jnp.sum(jnp.where(jnp.arange(nn * nn) == k, marr,
                                     False))
        return fn

    @staticmethod
    def tmr_mask_fn(nn: int) -> Callable:
        def fn(node, tarr, nn=nn):
            import jax.numpy as jnp

            return jnp.sum(jnp.where(jnp.arange(nn) == node, tarr,
                                     False))
        return fn


_ADAPTERS: List[Callable] = []


def register_adapter(fn: Callable) -> Callable:
    """Register ``fn(object_state) -> Optional[TwinBinding]``."""
    _ADAPTERS.append(fn)
    return fn


def _load_adapters() -> None:
    # Import for registration side effects; lazy to avoid jax import cost
    # on the object path.
    from dslabs_tpu.tpu.adapters import paxos as _p  # noqa: F401
    from dslabs_tpu.tpu.adapters import shardstore as _ss  # noqa: F401
    from dslabs_tpu.tpu.adapters import simple as _s  # noqa: F401


def resolve_binding(state) -> TwinBinding:
    _load_adapters()
    for fn in _ADAPTERS:
        b = fn(state)
        if b is not None:
            return b
    kinds = sorted({type(n).__name__ for n in state.nodes()})
    raise NoTensorTwin(
        f"no tensor twin adapter matches node composition {kinds} — "
        "the tensor search backend only covers protocols with registered "
        "twins (tpu/adapters/)")


# ------------------------------------------------------------ predicates

def translate_predicate(binding: TwinBinding, pred) -> Callable:
    """Object StatePredicate -> twin lane predicate, recursing through
    combinator structure; loud NoTensorTwin when untranslatable."""
    import jax.numpy as jnp

    st = getattr(pred, "structure", None)
    if st is not None:
        op = st[0]
        subs = [translate_predicate(binding, q) for q in st[1:]]
        if op == "not":
            return lambda s, f=subs[0]: ~f(s)
        if op == "and":
            return lambda s, a=subs[0], b=subs[1]: a(s) & b(s)
        if op == "or":
            return lambda s, a=subs[0], b=subs[1]: a(s) | b(s)
        if op == "implies":
            return lambda s, a=subs[0], b=subs[1]: ~a(s) | b(s)
    tkey = getattr(pred, "tkey", None)
    if tkey is None:
        raise NoTensorTwin(
            f"predicate {pred.name!r} has no tensor translation key and "
            "no combinator structure")
    fn = binding.predicate(tkey)
    if fn is None:
        raise NoTensorTwin(
            f"binding {binding.key} cannot translate predicate "
            f"{pred.name!r} (tkey {tkey!r})")
    return fn


def _predicates_key(settings) -> tuple:
    """For each of invariants, goals and prunes, in order, the ``(name,
    predicate_signature)`` of every predicate: what an engine's flag
    programs are traced from."""
    return tuple(tuple((q.name, predicate_signature(q)) for q in group)
                 for group in (settings.invariants, settings.goals,
                               settings.prunes))


def predicate_signature(pred) -> tuple:
    """What :func:`translate_predicate` reads of ``pred``, as a value:
    the combinator tree down to the ``tkey``\\ s, by the very recursion
    it makes.  Two predicates of equal signature translate to the same
    lane predicate on one binding; a name says nothing."""
    st = getattr(pred, "structure", None)
    if st is not None and st[0] in ("not", "and", "or", "implies"):
        return (st[0],) + tuple(predicate_signature(q) for q in st[1:])
    return ("tkey", getattr(pred, "tkey", None))


# ---------------------------------------------- what is kept across calls

@dataclasses.dataclass(eq=False)
class _Engine:
    """A kept ``ShardedTensorSearch`` — its traced and compiled programs
    — and whether a warm run has completed on it.  ``serial`` names the
    engine in a call's ``entry.build_engine`` span, and the engine is
    registered under ``telemetry.KEPT_SUPERSTEP``, so that the reader of
    a traced call finds the text of the very superstep an attempt ran
    (a lab process keeps several engines)."""
    search: Any
    warmed: bool = False
    serial: int = dataclasses.field(
        default_factory=itertools.count(1).__next__)
    _text: Optional[str] = None

    def __post_init__(self):
        telemetry.register_program(telemetry.KEPT_SUPERSTEP, self)

    def as_text(self) -> str:
        """The optimised text of this engine's superstep: the
        executable's that its build compiled or loaded
        (``aot_warmup``), asked once."""
        if self._text is None:
            self._text = self.search._aot_exes["superstep"].as_text()
        return self._text

    def rest(self) -> None:
        """Drop what the finished run left on the engine for the replay.
        No device buffer is among it (the carry is a local of ``run()``),
        but the host's child-to-parent map holds a row for every state
        discovered: ``run()`` empties these when it starts, a kept engine
        should not hold them until then."""
        s = self.search
        s._fp_map, s._level_records = {}, []
        s._deep_samples = s._trace_root = None


class _Kept:
    """What a lab call builds and the next call of equal input needs
    again, in ONE least-recently-used table of at most ``BOUND`` entries
    for the process:

    * ``("twin", twin_key, net_cap, timer_cap, env)`` — what
      ``binding.build_protocol`` returned;
    * ``("engine", twin_key, net_cap, timer_cap, frontier_cap,
      visited_cap, chunk, devices, predicates, env)`` — an
      :class:`_Engine`; ``predicates`` is :func:`_predicates_key`;
    * ``("step", twin_key, net_cap, timer_cap, env)`` — the one compiled
      trace step (:func:`_trace_step`), by the caps the protocol HAS.

    ``env`` is every ``DSLABS_*`` variable as it stands: the engine's
    constructor reads several.  A key is derived from the call's input
    alone; one that cannot be hashed is ``None``, and ``None`` is never
    found and never kept (a bypass: the caller builds what it needs, as
    every call did before).  The table holds programs, never answers.

    ``BOUND``: test22's five phases take five entries (a twin, a step,
    three predicate sets), lab 1's three searches five, a rung climbed
    three more.  Nothing outside this module sets it."""

    BOUND = 16

    def __init__(self):
        self._lock = threading.Lock()
        self._table: "collections.OrderedDict" = collections.OrderedDict()
        self._leased: set = set()
        self.hits = self.misses = self.bypasses = 0

    @property
    def built(self) -> int:
        """How often a caller was sent away to build."""
        return self.misses + self.bypasses

    def get(self, key, lease: Optional[set] = None):
        """The value kept under ``key``, or None.  With ``lease`` (the
        keys one call holds) the value is an engine: one that another
        call is running is not handed out a second time, and one handed
        out is this call's until its ``leasing`` ends."""
        with self._lock:
            if key is None or (lease is not None and key in self._leased):
                self.bypasses += 1
                return None
            if key not in self._table:
                self.misses += 1
                return None
            self.hits += 1
            self._table.move_to_end(key)
            if lease is not None:
                self._leased.add(key)
                lease.add(key)
            return self._table[key]

    def put(self, key, value, lease: Optional[set] = None):
        """Keep ``value`` under ``key`` (the least recently used entries
        beyond ``BOUND`` go) unless the key is ``None`` or taken: a call
        that was refused a running engine keeps its own to itself."""
        with self._lock:
            if (key is None or key in self._table
                    or (lease is not None and key in self._leased)):
                return value
            self._table[key] = value
            if lease is not None:
                self._leased.add(key)
                lease.add(key)
            while len(self._table) > self.BOUND:
                self._table.popitem(last=False)
        return value

    @contextlib.contextmanager
    def leasing(self):
        """The set of engine keys one call holds, released at the end."""
        lease: set = set()
        try:
            yield lease
        finally:
            with self._lock:
                mine = [self._table[k] for k in lease if k in self._table]
            for engine in mine:
                engine.rest()
            with self._lock:
                self._leased -= lease

    def clear(self) -> None:
        with self._lock:
            self._table.clear()
            self.hits = self.misses = self.bypasses = 0

    def info(self) -> dict:
        with self._lock:
            kinds = [k[0] for k in self._table]
            return {"hits": self.hits, "misses": self.misses,
                    "bypasses": self.bypasses, "bound": self.BOUND,
                    "entries": len(kinds),
                    **{kind: kinds.count(kind)
                       for kind in ("twin", "engine", "step")}}


_KEPT = _Kept()


def clear_cache() -> None:
    """Forget every kept twin, engine and trace step (for tests: the
    next call builds everything, as a fresh process would)."""
    _KEPT.clear()


def cache_info() -> dict:
    """Counters and contents of the lab entry's cache."""
    return _KEPT.info()


def _key(kind: str, binding: TwinBinding, *parts) -> Optional[tuple]:
    """The key of one kept thing, or None where the input cannot be
    expressed as one (an unhashable ``tkey`` or ``twin_key``)."""
    key = (kind, binding.twin_key()) + parts + (tuple(sorted(
        kv for kv in os.environ.items() if kv[0].startswith("DSLABS_"))),)
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _trace_step(binding: TwinBinding, p):
    """The compiled single-event step of twin ``p`` — ``step(row,
    event_id) -> (successor row, valid, over)`` — that root derivation,
    witness replay and the sampled re-check all step through: built once
    a (twin, caps) a process (``entry.root.build``: absent when it was
    kept), UNMASKED.  A history's events were valid under the masks of
    the phases that produced them, not under THIS phase's masks (a
    ``deliver_timers(False)`` phase 3 must still replay phase 1's
    election timers); masks only gate validity, never the transition,
    so the unmasked step reproduces each original successor exactly."""
    # By the twin's name too: a probe's twin is stepped under the
    # caller's binding as well as its own (``probe_fleet``).
    key = _key("step", binding, p.name, p.net_cap, p.timer_cap)
    step = _KEPT.get(key)
    if step is None:
        import jax
        import jax.numpy as jnp

        from dslabs_tpu.tpu.engine import TensorSearch

        with telemetry.phase("entry.root.build"):
            # No predicate is read by a step: without them the step's
            # key in the executable store is the twin's and the caps',
            # as its key in ``_Kept`` is, whichever call asks first.
            replayer = TensorSearch(
                dataclasses.replace(p, deliver_message=None,
                                    deliver_timer=None, invariants={},
                                    goals={}, prunes={}), chunk=1)
            args = (jax.ShapeDtypeStruct((replayer.lanes,), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32))
            # Compiled here, ahead of the first event, so that a replay
            # is its events' round trips and nothing else — or loaded:
            # the step traces every handler once more, so the executable
            # store (tpu/compile_cache.py) is asked first.
            step = _KEPT.put(key, compile_cache.stored(
                compile_cache.program_key(replayer.store_key(),
                                          "step_one", args),
                "step_one",
                lambda: jax.jit(replayer._step_one).lower(*args).compile(),
                replayer._store_devices()))
    return step


# -------------------------------------------------------------- settings

def _addr_name(a) -> str:
    return str(a.root_address())


def compile_masks(binding: TwinBinding, settings):
    """TestSettings network/timer gating -> ([NN*NN] link matrix,
    [NN] timer vector) bool arrays.  The matrix reproduces
    TestSettings.should_deliver's precedence exactly: link override ->
    sender -> receiver -> network_active (testing/settings.py:138-151).
    The arrays are passed to the jitted programs as RUNTIME arguments
    (engine deliver_*_rt) so staged phases never recompile; lookups are
    one-hot select-reduces, never traced-index gathers (the measured
    ~1 GB/s pathology under the flat vmap)."""
    idx = binding.addr_index
    nn = len(idx)
    names = {i: a for a, i in idx.items()}
    mat = np.zeros((nn, nn), dtype=bool)
    link = {(_addr_name(f), _addr_name(t)): v
            for (f, t), v in settings._link_active.items()}
    snd = {_addr_name(a): v for a, v in settings._sender_active.items()}
    rcv = {_addr_name(a): v for a, v in settings._receiver_active.items()}
    for fi in range(nn):
        for ti in range(nn):
            f, t = names[fi], names[ti]
            v = link.get((f, t))
            if v is None:
                v = snd.get(f)
            if v is None:
                v = rcv.get(t)
            if v is None:
                v = settings._network_active
            mat[fi, ti] = v
    from dslabs_tpu.core.address import LocalAddress

    tvec = np.array(
        [settings.should_deliver_timer(LocalAddress(names[i]))
         for i in range(nn)], dtype=bool)
    return mat.reshape(-1), tvec



# ------------------------------------------------------------ state root

def derive_root(binding: TwinBinding, search, state):
    """Object initial state -> (tensor root pytree or None for the twin
    initial, provenance history list).  Depth-0 canonical states map to
    the twin initial; staged states replay their provenance history."""
    import jax.numpy as jnp

    from dslabs_tpu.tpu.engine import (CapacityOverflow, SENTINEL,
                                       flatten_state)

    prov = getattr(state, "_tensor_provenance", None)
    if prov is None:
        if state.depth != 0:
            raise NoTensorTwin(
                "staged search from a state with no tensor provenance "
                "(depth {}) — only states produced by a previous "
                "tensor-backend phase can seed a new phase".format(
                    state.depth))
        # Pre-search staged mutations on the pristine state (e.g.
        # drop_pending_messages before the first bfs) are recorded on
        # the instance and replayed like any provenance history.
        staged = list(getattr(state, "_staged_ops", []))
        prov = TensorProvenance(binding.key, staged)
        if not staged:
            return None, []
    if prov.key != binding.key:
        raise NoTensorTwin(
            f"staged state's provenance {prov.key} does not match the "
            f"current binding {binding.key}")
    events = sum(op[0] in ("ev_msg", "ev_tmr") for op in prov.history)
    p = search.p
    step = _trace_step(binding, p)
    with telemetry.phase("entry.root.eager"):
        row = np.asarray(flatten_state(search.initial_state()))[0]
    o0, o1 = search._off[0], search._off[1]
    dropped: List[np.ndarray] = []
    with telemetry.phase("entry.root.replay", events=events,
                         staged_ops=len(prov.history) - events):
        for op in prov.history:
            if op[0] in ("ev_msg", "ev_tmr"):
                ev = _denorm_event(p, op)
                succ, valid, over = step(jnp.asarray(row), jnp.int32(ev))
                if int(over):
                    # The replayed transition itself overflowed this
                    # rung's net/timer caps — a truncated root would
                    # corrupt every downstream verdict, so escalate the
                    # ladder instead.
                    raise CapacityOverflow(
                        f"provenance replay of {op!r} overflowed caps "
                        f"(net_cap={p.net_cap}, timer_cap={p.timer_cap})")
                if not bool(valid):
                    raise NoTensorTwin(
                        f"provenance replay hit undeliverable event "
                        f"{op!r}")
                row = np.asarray(succ)
            elif op[0] == "drop":
                net = row[o0:o1].reshape(p.net_cap, p.msg_width)
                dropped.extend(r.copy() for r in net if r[0] != SENTINEL)
                row = row.copy()
                row[o0:o1] = SENTINEL
            elif op[0].startswith("undrop"):
                net = row[o0:o1].reshape(p.net_cap, p.msg_width).copy()
                want = (binding.addr_index[op[1]] if len(op) > 1
                        else None)
                back = []
                for r in dropped:
                    if op[0] == "undrop_from" and int(r[1]) != want:
                        continue
                    if op[0] == "undrop_to" and int(r[2]) != want:
                        continue
                    back.append(r)
                have = [r for r in net if r[0] != SENTINEL]
                merged = ({tuple(r) for r in have}
                          | {tuple(r) for r in back})
                rows = sorted(merged)
                if len(rows) > p.net_cap:
                    raise CapacityOverflow(
                        f"undrop needs {len(rows)} net slots > cap "
                        f"{p.net_cap}")
                net[:] = SENTINEL
                for i, r in enumerate(rows):
                    net[i] = r
                row = row.copy()
                row[o0:o1] = net.reshape(-1)
            else:
                raise NoTensorTwin(f"unknown staged op {op!r}")
    with telemetry.phase("entry.root.eager"):
        return (search.unflatten_rows(jnp.asarray(row[None])),
                list(prov.history))


# ------------------------------------------------------------------- run

# Capacity escalation ladder: (frontier_cap, visited_cap) per attempt,
# with net/timer caps doubling alongside.  No hand-tuned budgets: every
# CapacityOverflow retries one rung up, and the last failure is loud.
_LADDER = [(1 << 14, 1 << 19), (1 << 17, 1 << 22), (1 << 19, 1 << 24)]


def _run_tensor(binding: TwinBinding, settings, state, lease: set,
                chunk=512):
    """One BFS through the capacity ladder.  Each rung's twin and engine
    are looked up in what the process kept (:class:`_Kept`) and built
    only where nothing was; ``lease`` collects the engines this call
    holds, for ``tensor_bfs`` to release when it is through with them.
    The lookups stand in this frame, around the very constructor calls
    they save: a build is made at the stack depth it always was."""
    import jax

    from dslabs_tpu.tpu.engine import CapacityOverflow
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
    from dslabs_tpu.tpu.supervisor import install_retry
    from dslabs_tpu.utils.flags import GlobalSettings

    net_cap, timer_cap = binding.initial_caps()
    mesh = make_mesh(len(jax.devices()))
    devices = tuple(d.id for d in mesh.devices.flat)
    last: Optional[Exception] = None
    # Where a call's seconds go, stage by stage (telemetry.PHASES):
    # each stage below is a phase of the call ``tensor_bfs`` opened, on
    # every attempt (``attempt``: a rung that overflows throws its root,
    # its warm run and its search away), with ``cached`` = 1 where it
    # built nothing.
    for attempt, (f_cap, v_cap) in enumerate(_LADDER):
        caps = (net_cap << attempt, timer_cap + 2 * attempt)
        with telemetry.phase("entry.bind", attempt=attempt,
                             twin=str(binding.key[0])) as span:
            if attempt == 0:
                # check_settings BEFORE build_protocol: bindings bind
                # settings-dependent modelling flags there (lab4's
                # live-master-timer / controller-debris surface) and the
                # protocol shape must reflect them on the FIRST attempt,
                # not after a capacity retry.
                binding.check_settings(settings)
            key = _key("twin", binding, *caps)
            twin = _KEPT.get(key)
            span.set(cached=int(twin is not None))
            if twin is None:
                twin = _KEPT.put(key, binding.build_protocol(*caps))
            protocol, marr, tarr = _bind_protocol(binding, settings,
                                                  *caps, twin=twin)
        with telemetry.phase("entry.build_engine", attempt=attempt,
                             frontier_cap=f_cap, visited_cap=v_cap) as span:
            key = _key("engine", binding, *caps, f_cap, v_cap, chunk,
                       devices, _predicates_key(settings))
            kept = _KEPT.get(key, lease)
            span.set(cached=int(kept is not None))
            if kept is None:
                # The engine's programs are compiled where it is built,
                # or loaded from the executable store before anything is
                # traced (aot_warmup; the masks are arguments of the
                # superstep, so they are set first): a kept engine
                # dispatches executables, as a supervisor's does.
                search = ShardedTensorSearch(
                    protocol, mesh, chunk_per_device=chunk,
                    frontier_cap=f_cap, visited_cap=v_cap, strict=True,
                    record_trace=True)
                search.set_runtime_masks(marr, tarr)
                search.aot_warmup()
                kept = _KEPT.put(key, _Engine(search), lease)
            else:
                # The build's seconds are the call's that built it.
                kept.search.compile_secs = 0.0
            span.set(engine=kept.serial)
            search = kept.search
            # Everything a call sets on its engine is set on EVERY call,
            # kept engine or new, so that nothing of the call before
            # leaks: the caller's recorder if one is current
            # (telemetry.use), or none; the transient-dispatch retry
            # (tpu/supervisor.py: a preemption or transient XLA error
            # mid-search retries with backoff instead of failing the lab
            # test; semantic errors like CapacityOverflow pass straight
            # through to the capacity ladder below), with this call's
            # own budget; the delivery masks; the once-a-search
            # capacity-pressure warning, the dispatch annotations'
            # running index and the last readback's per-device counters;
            # and, below, the limits.
            recorder = telemetry.current()
            if recorder is not None:
                recorder.attach(search)
            else:
                search._telemetry = None
            install_retry(search)
            search.set_runtime_masks(marr, tarr)
            search._warned_visited, search._dispatch_i = False, -1
            search._last_per_device = None
        rel = None
        if settings.depth_limited():
            rel = settings.max_depth - state.depth
            if rel < 0:
                raise NoTensorTwin("staged state already beyond max_depth")
        try:
            # Inside the attempt: a root recorded by a phase that ran at
            # a higher ladder rung can overflow this rung's caps, and
            # must escalate rather than fail the test (ADVICE r4).
            built = _KEPT.built
            with telemetry.phase("entry.derive_root",
                                 attempt=attempt) as span:
                root, history = binding.derive_root(search, state)
                span.set(cached=int(_KEPT.built == built))
            if (settings.max_time_secs is not None
                    and (rel is None or rel > 2) and not kept.warmed):
                # Warm-up excludes compile time from the test's time
                # budget (the reference charges neither JIT nor class
                # loading to maxTime; on the accelerator a cold twin
                # compile alone can exceed a 30 s search budget).  A
                # phase within 2 levels of its depth limit skips it —
                # the warm-up WOULD BE the whole search — and so does
                # an engine that has completed one: its programs are
                # compiled (a root it has not started from yet still
                # compiles its small carry initialiser).
                search.max_depth, search.max_secs = 2, None
                with telemetry.phase("entry.warm_run", attempt=attempt):
                    search.run(initial=root, check_initial=False)
                kept.warmed = True
            search.max_depth = rel
            search.max_secs = (
                None if settings.max_time_secs is None
                else settings.max_time_secs * GlobalSettings.time_scale)
            with telemetry.phase("entry.search", attempt=attempt):
                outcome = search.run(initial=root)
            return search, outcome, history
        except CapacityOverflow as e:
            # ``explored``: what the attempt's last stats readback had
            # counted (sharded._sync_checks), 0 where it never ran.
            telemetry.mark("entry.capacity_retry", attempt=attempt,
                           overflow=str(e)[:96], explored=sum(
                               (search._last_per_device
                                or {}).get("explored", ())))
            last = e
            continue
    raise last


def _materialize(binding, search, outcome, state, history):
    """Tensor terminal state -> object SearchState via trace replay, with
    provenance attached for the next staged phase."""
    from dslabs_tpu.tpu.trace import replay_on_object

    obj = replay_on_object(search, outcome, state,
                           step=_trace_step(binding, search.p))
    obj._tensor_provenance = TensorProvenance(
        binding.key, list(history) + [_norm_event(search.p, e)
                                      for e in outcome.trace])
    return obj


def _sampled_value_recheck(binding, search, outcome, settings, state):
    """Value-level invariants (RESULTS_OK and friends) collapse to
    constant-true lane predicates on the twin, so the tensor search can
    never falsify them mid-run; before an exhaust verdict is trusted,
    replay the outcome's sampled deepest states on the OBJECT twin and
    check every value-level invariant there (ADVICE r4).  Returns the
    first violated ``(object_state, predicate, result)`` or ``None``."""
    if not outcome.samples:
        return None
    value_preds = [p for p in settings.invariants
                   if getattr(translate_predicate(binding, p),
                              "value_level", False)]
    if not value_preds:
        return None
    from dslabs_tpu.tpu.trace import replay_on_object

    step = _trace_step(binding, search.p)
    for tr in outcome.samples:
        shim = dataclasses.replace(outcome, trace=list(tr))
        obj = replay_on_object(search, shim, state, step=step)
        for p in value_preds:
            r = p.check(obj)
            if not r.value:
                return obj, p, r
    return None


def _bind_protocol(binding, settings, net_cap, timer_cap,
                   with_goals=True, twin=None):
    """Assemble the runnable twin for one capacity rung: protocol with
    translated predicates + runtime mask arrays — ONE code path for the
    BFS ladder and the rollout probe, so both always search identically
    configured twins.  ``twin``: what ``build_protocol`` gave for these
    caps, where the caller has it already."""
    marr, tarr = compile_masks(binding, settings)
    protocol = (twin if twin is not None
                else binding.build_protocol(net_cap, timer_cap))
    inv = {p.name: translate_predicate(binding, p)
           for p in settings.invariants}
    goals = ({p.name: translate_predicate(binding, p)
              for p in settings.goals} if with_goals else {})
    prunes = {p.name: translate_predicate(binding, p)
              for p in settings.prunes}
    protocol = dataclasses.replace(
        protocol, invariants=inv, goals=goals, prunes=prunes,
        deliver_message_rt=binding.msg_mask_fn(),
        deliver_timer_rt=TwinBinding.tmr_mask_fn(len(tarr)))
    return protocol, marr, tarr


# What the dfs entry's probe is built at where the caller names no
# size.  The width follows the probe's BUDGET, not the platform alone:
# a walker has to make its bound's steps inside the budget to reach the
# depths the probe exists for, so the fleet is as wide as the walker
# steps the platform makes in the budget pay for, each walker walking
# its whole bound — 128 at the least (what the probe always had, and
# all a CPU's rate ever pays for: its tests are timed at it) and 8,192
# at the most (a v5e's step is device-bound well before: wider buys no
# throughput).  The rates are measured: five servers' Paxos, the widest
# twin the labs bind, 8.2 us a walker step on a v5e at 8,192 walkers
# and 4.9 at 256 (PERF.md section 6, PR 43) — the slower is entered;
# narrower twins and fleets walk faster and get a fleet narrower than
# they could fill, which costs nothing.  A CPU
# makes 7-14 K walker steps a second at 128 walkers of three servers'
# Paxos (tests/test_swarm_probe.py's hit); it is entered lower, so that
# no budget the settings can give widens a CPU's fleet.
PROBE_WALKERS_MIN, PROBE_WALKERS_MAX = 128, 8192
PROBE_WALKER_STEPS_PER_SEC = {"cpu": 3_000, "tpu": 120_000}
PROBE_DEPTH = 192           # the deepest bound where settings give none
PROBE_SECS = 10.0           # the probe's budget where settings give none
# Table slots a walker step of the budget: six a fresh key (twice the
# keys at a third full), a third of the steps fresh (25.8 % over five
# servers' window) — and 2^18 at the least, what the probe always had.
PROBE_TABLE_SLOTS_A_STEP, PROBE_TABLE_MIN = 2, 1 << 18
# A dispatch is at most 64 steps of the fleet (the module's default) and
# at most 32,768 walker steps: the clock is read between dispatches, and
# five servers' 8,192 walkers take 67 ms a step on a v5e (PERF.md).
PROBE_ROUND_STEPS, PROBE_ROUND_WALKER_STEPS = 64, 1 << 15


def probe_secs(settings) -> float:
    """The probe's share of a dfs call's time: a third of the settings'
    ``max_time`` and PROBE_SECS at the most, by the global time
    scale."""
    from dslabs_tpu.utils.flags import GlobalSettings

    budget = PROBE_SECS
    if settings.max_time_secs is not None:
        budget = min(budget, settings.max_time_secs / 3)
    return budget * GlobalSettings.time_scale


def probe_walker_steps(secs: float) -> int:
    """Walker steps the platform makes in ``secs`` seconds of walking
    (PROBE_WALKER_STEPS_PER_SEC; a platform with no measured rate gets
    the CPU's)."""
    import jax

    rate = PROBE_WALKER_STEPS_PER_SEC
    return int(secs * rate.get(jax.default_backend(), rate["cpu"]))


def probe_walkers(walker_steps: int, depth: int) -> int:
    """The probe's fleet width where the caller names none: the power
    of two of walkers that each make ``depth`` of ``walker_steps``
    steps (PROBE_DEPTH where the bound is shallower: several probes a
    walker), within PROBE_WALKERS_MIN and PROBE_WALKERS_MAX."""
    fit = max(walker_steps // max(depth, PROBE_DEPTH), 1)
    return max(PROBE_WALKERS_MIN,
               min(PROBE_WALKERS_MAX, 1 << (fit.bit_length() - 1)))


def probe_round(walkers: int) -> int:
    """Steps of the fleet a dispatch, for a fleet ``walkers`` wide (128
    walkers: 64, what the probe always had; 8,192: 4)."""
    return max(1, min(PROBE_ROUND_STEPS,
                      PROBE_ROUND_WALKER_STEPS // walkers))


def probe_table(walker_steps: int) -> int:
    """Visited-table slots a device for a walk of ``walker_steps``: the
    power of two that holds PROBE_TABLE_SLOTS_A_STEP slots a step,
    PROBE_TABLE_MIN at the least (five servers' 27 s window of 3.3 M
    walker steps: 2^23, a tenth full at its end)."""
    need = max(PROBE_TABLE_SLOTS_A_STEP * walker_steps, PROBE_TABLE_MIN)
    return 1 << (need - 1).bit_length()


def probe_fleet(binding, settings, state, walkers=None,
                steps_per_round=None, visited_cap=None, strict=False):
    """THE builder of the dfs entry's swarm fleet (tpu/swarm.py
    ``SwarmSearch``), for ``_rollout_probe`` and for the benchmark's
    driver alike: the twin bound WITHOUT goals through
    ``binding.probe_binding()`` at its probe caps, one device, depth
    bounds spread up to the settings' relative ``max_depth``
    (``PROBE_DEPTH`` where they give none), retry boundary, recorder,
    runtime masks, and the root derived from ``state``.  ->
    ``(search, root, history, probe binding)`` with ``root`` None for
    the twin's own initial state — the fleet's rows are the PROBE
    binding's twin's, so whatever decodes or steps them goes through
    that binding — or None where the settings leave no depth to walk.
    ``walkers`` defaults to what the probe's budget pays for
    (:func:`probe_secs`, :func:`probe_walker_steps`,
    :func:`probe_walkers`), ``steps_per_round`` to
    :func:`probe_round`'s and ``visited_cap`` to :func:`probe_table`'s
    for that budget; ``strict``: a truncated or refused step or a full
    table raises."""
    from dslabs_tpu.tpu.sharded import make_mesh
    from dslabs_tpu.tpu.supervisor import install_retry
    from dslabs_tpu.tpu.swarm import SwarmSearch

    # ``state``'s provenance is the caller's binding's, whose key the
    # root is derived under (events replay alike on either twin; the
    # trace step is kept by the twin's name beside the binding's key).
    base, binding = binding, binding.probe_binding()
    binding.check_settings(settings)
    protocol, marr, tarr = _bind_protocol(
        binding, settings, *binding.probe_caps(), with_goals=False)
    rel = (settings.max_depth - state.depth
           if settings.depth_limited() else PROBE_DEPTH)
    if rel <= 0:
        return None
    budget = probe_walker_steps(probe_secs(settings))
    walkers = int(walkers or probe_walkers(budget, rel))
    search = SwarmSearch(
        protocol, mesh=make_mesh(1), walkers_per_device=walkers,
        max_steps=rel, seed=0,
        steps_per_round=int(steps_per_round or probe_round(walkers)),
        visited_cap=int(visited_cap or probe_table(budget)),
        strict=strict)
    install_retry(search)
    recorder = telemetry.current()
    if recorder is not None:
        recorder.attach(search)
    search.set_runtime_masks(marr, tarr)
    root, history = base.derive_root(search, state)
    return search, root, history, binding


def _rollout_probe(binding, settings, state):
    """Swarm deep probe before a dfs-routed BFS: a diversified
    random-walk fleet (tpu/swarm.py ``SwarmSearch`` — ONE walker
    implementation; the ad-hoc per-backend rollout loop is retired)
    reaches depth d in O(d) steps, so the deep-narrow violations the
    object RandomDFS could hit inside a time budget are covered BEFORE
    the level-by-level search starts.  This function keeps only the
    BUDGET ACCOUNTING — the fleet is :func:`probe_fleet`'s; walker
    mechanics, dedup, overflow-restart counting, and the
    minimize/replay witness pipeline all live in the swarm subsystem.
    Returns ((probe binding, search, outcome, history), probe_secs) on
    a terminal hit — the fleet's rows are the probe binding's twin's —
    else (None, probe_secs); capacity overflows skip the probe (the BFS
    ladder owns caps)."""
    import time

    import jax

    from dslabs_tpu.tpu.engine import CapacityOverflow

    t_probe = time.time()
    try:
        fleet = probe_fleet(binding, settings, state)
        if fleet is None:
            return None, time.time() - t_probe
        search, root, history, binding = fleet
        span = telemetry.current_phase()
        if span is not None:            # ``entry.probe``, tensor_bfs's
            span.set(walkers=search.n_devices * search.walkers,
                     max_steps=search.max_steps)
        search.max_secs = probe_secs(settings)
        outcome = search.run(
            initial=(jax.tree.map(jax.numpy.asarray, root)
                     if root is not None else None),
            check_initial=False)
    except CapacityOverflow:
        return None, time.time() - t_probe
    if outcome.end_condition in ("INVARIANT_VIOLATED",
                                 "EXCEPTION_THROWN"):
        return ((binding, search, outcome, history),
                time.time() - t_probe)
    return None, time.time() - t_probe


def _object_minimize_verify(obj, pred, result):
    """Probe witnesses run the OBJECT pipeline too (ISSUE 5): the
    replayed object state is minimized with search/minimize.py (the
    reference TraceMinimizer discipline) and the minimized event
    history is INDEPENDENTLY replayed with search/replay.py under the
    violated predicate — a probe verdict ships only after the tensor
    witness (already minimized/replay-verified in tpu/swarm.py) is
    confirmed end-to-end on the object twin.  Returns the minimized
    ``(state, predicate_result)``; any divergence is a loud
    NoTensorTwin, never a silently-wrong trace."""
    from dslabs_tpu.search.minimize import minimize_trace
    from dslabs_tpu.search.replay import replay_trace
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.settings import SearchSettings

    mini = minimize_trace(obj, result)
    r2 = pred.check(mini)
    if r2.value:
        raise NoTensorTwin(
            f"object minimization broke the violation of "
            f"{pred.name!r} (minimizer/predicate divergence)")
    events = []
    s = mini
    while s.previous is not None:
        events.insert(0, s.previous_event)
        s = s.previous
    replayed = replay_trace(s, events,
                            SearchSettings().add_invariant(pred))
    if replayed.end_condition is not EndCondition.INVARIANT_VIOLATED:
        raise NoTensorTwin(
            f"replaying the minimized witness did not reproduce the "
            f"violation of {pred.name!r} "
            f"(got {replayed.end_condition})")
    return mini, r2


def tensor_bfs(initial_state, settings=None, _probe_first=False):
    """The tensor-strategy analog of search.bfs (Search.java:390-402 via
    SURVEY §8.1): same inputs, same SearchResults contract.  One call is
    one ``entry.tensor_bfs`` (or ``entry.tensor_dfs``) phase whose id
    every stage and dispatch inside carries (tpu/telemetry.py) — opened
    by a ``with`` in this frame, not by a wrapper: how long JAX takes to
    trace and lower the engine's first dispatch depends on the Python
    stack depth it is made at (PERF.md, PR 25)."""
    from dslabs_tpu.search.results import EndCondition, SearchResults
    from dslabs_tpu.search.settings import SearchSettings

    settings = settings if settings is not None else SearchSettings()
    binding = resolve_binding(initial_state)
    with telemetry.call("entry.tensor_dfs" if _probe_first
                        else "entry.tensor_bfs",
                        key=str(binding.key)[:96]), \
            _KEPT.leasing() as lease:
        trip = probe_secs = None
        if _probe_first:
            with telemetry.phase("entry.probe"):
                trip, probe_secs = _rollout_probe(binding, settings,
                                                  initial_state)
            if trip is None and settings.max_time_secs is not None:
                # The probe spends part of the SAME maxTime contract the
                # object RandomDFS honours — deduct it from the BFS's
                # budget (on a copy; the caller's settings are theirs).
                import copy as _copy

                settings = _copy.copy(settings)
                settings.max_time_secs = max(
                    1.0, settings.max_time_secs - probe_secs)
        if trip is not None:
            # the probe's rows are its own binding's twin's
            binding, search, outcome, history = trip
        else:
            search, outcome, history = _run_tensor(
                binding, settings, initial_state, lease)
        results = SearchResults(settings.invariants, settings.goals)
        results.discovered_count = outcome.unique_states
        # Degradation stats ride along so exhaust verdicts are auditable:
        # dropped (beam truncation) and visited_overflow (table-full
        # treat-as-fresh re-exploration) are both 0 on strict runs.
        results.dropped = outcome.dropped
        results.visited_overflow = outcome.visited_overflow
        results.tensor_outcome = outcome
        results.probe_secs = probe_secs
        end = outcome.end_condition
        by_name = {p.name: p for p in (settings.invariants + settings.goals)}
        if end == "GOAL_FOUND":
            with telemetry.phase("entry.replay"):
                obj = _materialize(binding, search, outcome, initial_state,
                                   history)
                pred = by_name[outcome.predicate_name]
                r = pred.check(obj)
                if not r.value:
                    raise NoTensorTwin(
                        f"twin/object divergence: tensor goal "
                        f"{outcome.predicate_name!r} does not hold on the "
                        "replayed object state")
            results.goal_found(obj, r)
            results.end_condition = EndCondition.GOAL_FOUND
        elif end == "INVARIANT_VIOLATED":
            with telemetry.phase("entry.replay"):
                obj = _materialize(binding, search, outcome, initial_state,
                                   history)
                pred = by_name[outcome.predicate_name]
                r = pred.check(obj)
                if r.value:
                    raise NoTensorTwin(
                        f"twin/object divergence: tensor invariant violation "
                        f"{outcome.predicate_name!r} holds on the replayed "
                        "object state")
                if trip is not None:
                    # Probe (swarm) witnesses: object-level minimize + replay
                    # verification on top of the tensor-level pipeline the
                    # swarm already ran (outcome.witness).
                    obj, r = _object_minimize_verify(obj, pred, r)
                    if outcome.witness is not None:
                        outcome.witness.object_verified = True
            results.invariant_violated(obj, r)
            results.end_condition = EndCondition.INVARIANT_VIOLATED
        elif end == "EXCEPTION_THROWN":
            with telemetry.phase("entry.replay"):
                obj = _materialize(binding, search, outcome, initial_state,
                                   history)
            results.exception_thrown(obj)
            results.end_condition = EndCondition.EXCEPTION_THROWN
        else:
            with telemetry.phase("entry.recheck"):
                hit = _sampled_value_recheck(binding, search, outcome,
                                             settings, initial_state)
            if hit is not None:
                obj, pred, r = hit
                results.invariant_violated(obj, r)
                results.end_condition = EndCondition.INVARIANT_VIOLATED
            elif end == "TIME_EXHAUSTED":
                results.end_condition = EndCondition.TIME_EXHAUSTED
            else:
                # SPACE_EXHAUSTED, DEPTH_EXHAUSTED, CAPACITY_EXHAUSTED: the
                # object checker treats the depth limit as a prune and
                # reports SPACE_EXHAUSTED (Search.java:222-229).
                results.end_condition = EndCondition.SPACE_EXHAUSTED
        return results


def tensor_dfs(initial_state, settings=None):
    """Tensor strategy for dfs call sites: a RANDOM-ROLLOUT deep probe
    (engine.random_rollouts — RandomDFS's O(d) depth reach, restoring
    the coverage the round-4 advisor flagged) followed by a strict BFS
    under the same settings.  The probe's violations carry full
    replayable traces through the same materialisation path; when it
    finds nothing, BFS contributes what RandomDFS never could —
    exhaustiveness at every level it completes."""
    return tensor_bfs(initial_state, settings, _probe_first=True)
