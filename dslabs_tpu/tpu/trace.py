"""TPU trace reconstruction: from a violating/goal row in the tensor
search back to a minimized, human-readable OBJECT trace.

Pipeline (SURVEY §8.1 "trace reconstruction"; SearchState.java:361-474,
TraceMinimizer.java:33-61):

1. The engine spills (parent frontier row, event id) per level when
   ``record_trace=True``; ``SearchOutcome.trace`` is the root-first event-id
   list for the terminal row (engine._reconstruct).
2. :func:`decode_trace` replays that list in TENSOR space one state at a
   time, reading each step's concrete message/timer lanes *before*
   stepping — event ids alone are meaningless without the parent state's
   canonical network/timer contents.
3. :func:`replay_on_object` maps each record through the protocol's
   ``decode_message``/``decode_timer`` and replays the resulting envelopes
   on the object-twin SearchState, rebuilding the parent chain the
   existing minimizer and human-readable printer consume.

The result: a TPU INVARIANT_VIOLATED/GOAL_FOUND outcome yields the same
trace artifact (minimizable, printable, saveable) as the object backend.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import numpy as np

from dslabs_tpu.testing.events import MessageEnvelope, TimerEnvelope
from dslabs_tpu.tpu.engine import SearchOutcome, TensorSearch

__all__ = ["decode_trace", "replay_on_object", "reconstruct_object_trace",
           "MessageTemplate"]


class MessageTemplate:
    """A decoded message whose full payload the twin does not model (e.g.
    a PaxosReply's application result value).  At replay time the
    template resolves against the object state's OWN network — the object
    execution that produced the network is the source of truth for
    application-level values — falling back to ``fallback`` only when no
    network message matches (e.g. the message was constructed but its
    object counterpart was GC'd; ambiguity is a loud error, never a
    guess)."""

    def __init__(self, cls, fallback, match):
        self.cls = cls
        self.fallback = fallback
        self.match = match

    def resolve(self, state, frm, to):
        cands = {m.message for m in state.network()
                 if m.frm.root_address() == frm.root_address()
                 and m.to.root_address() == to.root_address()
                 and isinstance(m.message, self.cls)
                 and self.match(m.message)}
        if len(cands) == 1:
            return next(iter(cands))
        if not cands:
            if self.fallback is None:
                # A None fallback means the binding has no way to build
                # this message without an object-side candidate — failing
                # here keeps "ambiguity is a loud error, never a guess"
                # (a None message would fail far away with an obscure
                # handler error; ADVICE r4).
                raise ValueError(
                    f"template resolution found no {self.cls.__name__} "
                    f"candidate from {frm} to {to} in the object network "
                    "and the binding provides no fallback")
            return self.fallback
        raise ValueError(
            f"ambiguous template resolution: {len(cands)} distinct "
            f"{self.cls.__name__} candidates from {frm} to {to}")


def decode_trace(search: TensorSearch, outcome: SearchOutcome,
                 step=None) -> List[Tuple[str, tuple]]:
    """Replay ``outcome.trace`` (event-id list) in tensor space; return
    root-first records ``("message", lanes)`` / ``("timer", node, lanes)``.
    ``step``: a compiled ``search._step_one`` (``(row, int32 event id) ->
    (successor row, valid, over)``) where the caller keeps one — the lab
    entry does (tpu/backend.py ``_trace_step``); jitted here otherwise."""
    if outcome.trace is None:
        raise ValueError("outcome has no trace "
                         "(run the search with record_trace=True)")
    p = search.p
    # Replay from the root the trace was recorded against — for staged
    # searches (run(initial=...)) that is NOT the protocol initial state.
    root = getattr(search, "_trace_root", None)
    if root is None:
        root = jax.tree.map(np.asarray, search.initial_state())
    from dslabs_tpu.tpu.engine import flatten_state
    row = np.asarray(flatten_state(
        jax.tree.map(jax.numpy.asarray, root)))[0]
    if step is None:
        step = jax.jit(search._step_one)
    records: List[Tuple[str, tuple]] = []
    tgrid = p.n_nodes * p.timer_cap
    for ev in outcome.trace:
        state = search._slice_state(row)       # numpy views
        if ev < p.net_cap:
            rec = np.asarray(state["net"][ev]).copy()
            records.append(("message", (rec,)))
        elif ev < p.net_cap + tgrid:
            t_idx = ev - p.net_cap
            node, slot = t_idx // p.timer_cap, t_idx % p.timer_cap
            rec = np.asarray(state["timers"][node, slot]).copy()
            records.append(("timer", (node, rec)))
        else:
            # Fault-segment event (ISSUE 19): record the controller's
            # human-readable label (CUT / HEAL / CRASH(kind[i]) / ...)
            # so witness traces NAME the fault that enabled them.
            f_idx = ev - p.net_cap - tgrid
            records.append(("fault", (p.fault.event_label(f_idx),)))
        succ_row, valid, _ = step(jax.numpy.asarray(row),
                                  jax.numpy.int32(ev))
        assert bool(valid), (
            f"trace replay hit an undeliverable event {ev} — "
            "reconstruction mapping is corrupt")
        row = np.asarray(succ_row)
    return records


def replay_on_object(search: TensorSearch, outcome: SearchOutcome,
                     initial_object_state,
                     settings=None, step=None):
    """Replay the reconstructed record list on the object twin, returning
    the final object SearchState (whose parent chain IS the trace).
    ``step``: as :func:`decode_trace`'s."""
    p = search.p
    if p.decode_message is None or p.decode_timer is None:
        raise ValueError(f"{p.name}: protocol has no object-twin decoders")
    state = initial_object_state
    for kind, payload in decode_trace(search, outcome, step):
        if kind == "fault":
            # The object twin has no fault controller — a scenario
            # witness replays in tensor space only (decode_trace's
            # per-step validity asserts are the replay verification).
            raise NotImplementedError(
                f"{p.name}: trace contains fault event "
                f"{payload[0]!r}; object-twin replay does not model "
                "fault scenarios — verify the witness with "
                "decode_trace instead")
        if kind == "message":
            frm, to, msg = p.decode_message(payload[0])
            if isinstance(msg, MessageTemplate):
                msg = msg.resolve(state, frm, to)
            event = MessageEnvelope(frm, to, msg)
        else:
            node, rec = payload
            to, timer, mn, mx = p.decode_timer(node, rec)
            event = TimerEnvelope(to, timer, mn, mx)
        nxt = state.step_event(event, settings, skip_checks=True)
        assert nxt is not None, (
            f"object twin rejected reconstructed event {event!r} — "
            "tensor/object divergence")
        state = nxt
    return state


def reconstruct_object_trace(search: TensorSearch, outcome: SearchOutcome,
                             initial_object_state, predicate=None,
                             settings=None, minimize: bool = True):
    """Full pipeline: tensor outcome -> replayed object state ->
    (optionally) minimized against ``predicate`` (the object analog of the
    violated invariant / matched goal).  Returns the final SearchState;
    ``.print_trace()`` gives the human-readable causal trace."""
    end = replay_on_object(search, outcome, initial_object_state, settings)
    if minimize and predicate is not None:
        from dslabs_tpu.search.minimize import minimize_trace

        result = predicate.check(end)
        end = minimize_trace(end, result)
    return end
