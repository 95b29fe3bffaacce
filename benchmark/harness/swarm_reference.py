"""The plain reference of a random search: the repo's object checker
(``dslabs_tpu/search/``, the labs' own Paxos — no line of ``tpu/``)
on the state and settings the lab test builds, with the clients'
values drawn from the seed.

A random walk has no count an exhaustive search could repeat, so the
comparison is made of what CAN be held to the object checker:

* :func:`replay_walker` — a walker's recorded history, replayed event
  by event on the object state from the root: every event applies,
  every object invariant holds on every state of the way, the twin's
  own step from the root ends on the walker's row, and the object
  state reached decodes to that row (:func:`decoded_mismatches`); the
  rows of the way are kept as digests beside the keys the fleet's own
  fingerprint gives them, so that two states the dedup cannot tell
  apart are seen (``distinct``: a key that reads part of a row fails
  here, on deep states, where the shallow counts do not move);
* :func:`row_digest_fn`, :func:`distinct_rows` — the states a fleet
  stood on, counted at the timed size WITHOUT the program's
  fingerprint: every walker's whole row after every step of the
  warm-up digested by a plain multilinear hash defined here, the
  digests deduplicated on the host.  The fleet's ``fresh`` has to equal
  that count: a dedup key that merges two states, or a table that
  loses or invents one, reads otherwise;
* :func:`bfs_counts` — the object checker's cumulative unique counts
  by depth.  A state a walker first saw at walk depth ``d`` lies at
  BFS depth ``<= d``, and the fleet's table holds the root, so the
  fleet's cumulative fresh count through ``d`` is at most the
  checker's less one, and EQUAL to it where the fleet left no state of
  those depths unseen (:func:`cumulative`).
"""

from __future__ import annotations

import hashlib
import random
from typing import Dict, List, Optional

from benchmark.harness import states

STATUSES = ("EMPTY", "ACCEPTED", "CHOSEN", "CLEARED")


def build_state(spec: dict, seed: int):
    """PaxosTest's search state for ``spec``: ``servers`` PaxosServers
    and ``clients`` clients, client ``i`` APPENDing one seeded value to
    the key the test names (``key``: upstream's ``foo``).  Results are
    not pinned: the test holds them to APPENDS_LINEARIZABLE."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload
    from dslabs_tpu.labs.clientserver.kvstore import KVStore
    from dslabs_tpu.labs.paxos.paxos import PaxosClient, PaxosServer
    from dslabs_tpu.search.search_state import SearchState
    from dslabs_tpu.testing.generator import NodeGenerator

    if spec["kind"] != "paxos" or spec["commands_per_client"] != 1:
        raise ValueError(f"one APPEND a client of a lab 3 state, not "
                         f"{spec!r}")
    rng = random.Random(seed)
    group = tuple(LocalAddress(f"server{i}")
                  for i in range(1, spec["servers"] + 1))
    state = SearchState(NodeGenerator(
        server_supplier=lambda a: PaxosServer(a, group, KVStore()),
        client_supplier=lambda a: PaxosClient(a, group),
        workload_supplier=lambda a: None))
    for a in group:
        state.add_server(a)
    for i in range(1, spec["clients"] + 1):
        state.add_client_worker(
            LocalAddress(f"client{i}"),
            kv_workload([f"APPEND:{spec['key']}:{states._word(rng, 4)}"]))
    return state


def predicate(name: str):
    from dslabs_tpu.labs.clientserver import kv_workload
    from dslabs_tpu.labs.paxos import predicates as paxos_predicates
    from dslabs_tpu.testing import predicates

    for module in (predicates, paxos_predicates, kv_workload):
        if hasattr(module, name):
            return getattr(module, name)
    raise KeyError(name)


def build_settings(spec: dict):
    """The test's ``SearchSettings``: invariants, prunes, ``max_depth``
    and ``max_time`` as the configuration's ``search`` states them."""
    from dslabs_tpu.search.settings import SearchSettings

    s = SearchSettings()
    if spec.get("max_depth") is not None:
        s.set_max_depth(spec["max_depth"])
    if spec.get("max_time") is not None:
        s.max_time(spec["max_time"])
    for name in spec["invariants"]:
        s.add_invariant(predicate(name))
    for name in spec["prunes"]:
        s.add_prune(predicate(name))
    return s


def bfs_counts(spec: dict, seed: int, upto: int) -> Dict[int, int]:
    """Cumulative unique counts (the root among them) at depths
    1..``upto`` by the object checker's BFS on the seeded state."""
    from dslabs_tpu.search.search import BFS

    counts = {}
    for d in range(1, upto + 1):
        res = BFS(states.settings({"max_depth": d, "max_time": 900})
                  ).run(build_state(spec, seed))
        counts[d] = int(res.discovered_count)
    return counts


def cumulative(fresh_by_depth: List[int]) -> Dict[int, int]:
    """The fleet's fresh inserts by walk depth (depth 1 first) as
    cumulative counts WITH the root, an exhaustive search's way."""
    out, total = {}, 1
    for d, n in enumerate(fresh_by_depth, start=1):
        total += int(n)
        out[d] = total
    return out


def decoded_mismatches(binding, search, row, obj) -> List[str]:
    """Where the object state ``obj`` and the twin's ``row`` disagree,
    read through the lab's own predicate library on both sides (the
    object predicate on ``obj``, its lane translation on ``row``): every
    server's status of every log slot, every client's progress, and the
    sizes of the network and of every node's timer queue."""
    import numpy as np

    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.paxos.predicates import has_status
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, NONE_DECIDED,
                                               client_done)
    from dslabs_tpu.tpu.backend import translate_predicate
    from dslabs_tpu.tpu.engine import SENTINEL

    lanes = search._slice_state(np.asarray(row))
    preds = [CLIENTS_DONE, NONE_DECIDED]
    preds += [client_done(LocalAddress(c)) for c in binding.client_names]
    preds += [has_status(LocalAddress(a), slot, status)
              for a in binding.server_names
              for slot in range(1, binding.S + 1) for status in STATUSES]
    bad = [p.name for p in preds
           if bool(p.check(obj).value)
           != bool(translate_predicate(binding, p)(lanes))]
    net = int(np.sum(lanes["net"][:, 0] != SENTINEL))
    if net != len(set(obj.network())):
        bad.append(f"network {net} rows for {len(set(obj.network()))}")
    for name, i in binding.addr_index.items():
        held = int(np.sum(lanes["timers"][i][:, 0] != SENTINEL))
        queued = len(list(obj.timers(LocalAddress(name))))
        if held != queued:
            bad.append(f"{name} timers {held} for {queued}")
    return bad


def replay_walker(binding, search, state, row, events,
                  invariants) -> dict:
    """One walker's history on the object checker.  ``state``: the
    seeded object root; ``row``, ``events``: the walker's state row and
    grid event ids (``SwarmSearch.walker_snapshot``).  Every event is
    decoded from the twin's row BEFORE the step (an event id means
    nothing without its parent's network and timers) through the
    binding's decoders, applied to the object state, and stepped on the
    twin by the lab entry's compiled trace step."""
    import jax.numpy as jnp
    import numpy as np

    from dslabs_tpu.testing.events import MessageEnvelope, TimerEnvelope
    from dslabs_tpu.tpu import backend
    from dslabs_tpu.tpu.engine import flatten_state, row_fingerprints
    from dslabs_tpu.tpu.trace import MessageTemplate

    p = search.p
    step = backend._trace_step(binding, p)
    at = np.asarray(flatten_state(search.initial_state()))[0]
    out = {"events": len(events), "applied": 0, "violated": None,
           "row_equal": False, "decoded": None, "rows": set(),
           "keys": set()}
    obj, way = state, [at]
    for ev in events:
        lanes = search._slice_state(at)
        if ev < p.net_cap:
            frm, to, msg = p.decode_message(np.asarray(lanes["net"][ev]))
            if isinstance(msg, MessageTemplate):
                msg = msg.resolve(obj, frm, to)
            event = MessageEnvelope(frm, to, msg)
        else:
            node, slot = divmod(ev - p.net_cap, p.timer_cap)
            to, timer, lo, hi = p.decode_timer(
                node, np.asarray(lanes["timers"][node, slot]))
            event = TimerEnvelope(to, timer, lo, hi)
        nxt = obj.step_event(event, None, skip_checks=True)
        succ, valid, over = step(jnp.asarray(at), jnp.int32(ev))
        if nxt is None or not bool(valid) or int(over):
            return out
        obj, at = nxt, np.asarray(succ)
        way.append(at)
        out["applied"] += 1
        for inv in invariants:
            if out["violated"] is None and not inv.check(obj).value:
                out["violated"] = f"{inv.name} at event {out['applied']}"
    out["row_equal"] = bool(np.array_equal(at, np.asarray(row)))
    out["rows"] = {hashlib.blake2b(r.tobytes(), digest_size=16).digest()
                   for r in way}
    out["keys"] = {k.tobytes() for k in np.asarray(
        row_fingerprints(jnp.asarray(np.stack(way))))}
    out["decoded"] = decoded_mismatches(binding, search, row, obj)
    return out


def row_digest_fn(lanes: int):
    """A compiled ``[K, lanes] int32 rows -> [K, 4] uint32``: four
    multilinear sums of a row's lanes, ``sum(x_l * c_jl) mod 2**32``,
    with odd coefficients of a fixed generator — every lane is read,
    and no line of the program's fingerprint (``engine._fingerprint32``
    mixes by shifts and adds) is used.  Two rows that differ collide in
    one sum with probability about 2**-32 and in all four with 2**-128:
    held against the rows' own bytes by tests/test_swarm_probe.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    coef = np.random.Generator(np.random.PCG64(0x5EED)).integers(
        0, 2 ** 32, size=(4, lanes), dtype=np.uint32) | np.uint32(1)

    def digests(rows):
        x = rows.astype(jnp.uint32)
        return jnp.stack([jnp.sum(x * c[None, :], axis=1, dtype=jnp.uint32)
                          for c in jnp.asarray(coef)], axis=1)

    return jax.jit(digests)


def distinct_rows(digests) -> int:
    """How many different rows a list of ``[K, 4]`` digest arrays
    holds."""
    import numpy as np

    flat = np.ascontiguousarray(np.concatenate(digests), np.uint32)
    return len(np.unique(flat.view([("d", np.uint32, 4)])))


def walker_sample(seed: int, fleet: int, drawn: int) -> List[int]:
    """``drawn`` distinct walkers of a fleet of ``fleet``, by the
    seed."""
    return random.Random(seed).sample(range(fleet), min(drawn, fleet))
