"""Driver ``lab4_phases``: a closed loop of ONE caller making the
DEPENDENT calls of a staged lab 4 search test through the lab entry
point ``backend.tensor_bfs`` — what ``run_tests.py --lab 4 --part 3
--search-backend tensor`` dispatches ShardStorePart2Test's search tests
to — one call at a time, until the window ends.

``lab_phases``' contract (dependent phases as data, whole cycles, set-up
is ONE whole cycle through the same path, the reference from the very
state and settings each timed call was given, pinned answers) with what
lab 4's staged tests add to it:

* **A phase may bind another twin than the phase before it.**  The root
  is the Join phase's state (``build_state``: shard masters, one-server
  groups, the config controller's worker with one ``Join`` a group); the
  phase that starts from ITS goal state first adds the store client's
  worker to that state (``adds``; ``add_client``), as the test does, so
  the lab entry binds the 2PC twin and VALIDATES the staged state as
  that twin's canonical root instead of replaying its provenance.
* ``start`` is ``root``, ``goal of <phase>`` or ``start of <phase>``
  (the very state an earlier phase of the cycle was given: test08's
  exhaust searches the state its goal search did).
* ``nodes_off`` (``settings.node_active(node, False)``) beside
  ``partition`` and ``timers_off``; a goal may be ``{"client_done":
  <address>}``.
* Where the configuration pins an exhausted phase's discovered count,
  the live reference is held to it too.

The seed draws the two values of the client's one ``MultiPut``; the keys
are upstream's (``key_to_shard`` decides the groups).  The twins are
value-blind, so the device's work does not depend on the seed."""

from __future__ import annotations

import random
import time

from benchmark.drivers import lab_calls, lab_phases
from benchmark.drivers.lab_phases import GOAL_OF, end_to_end, reference
from benchmark.harness import spans as spans_mod
from benchmark.harness import states
from benchmark.harness.runner import Check, equal

START_OF = "start of "

__all__ = ["add_client", "build_settings", "build_state", "end_to_end",
           "measure", "prepare", "verify"]


def _addresses(spec: dict):
    from dslabs_tpu.core.address import LocalAddress

    masters = tuple(LocalAddress(f"shardmaster{i}")
                    for i in range(1, spec["shard_masters"] + 1))
    groups = {g: tuple(LocalAddress(f"server{g}-{i}") for i in
                       range(1, spec["servers_per_group"] + 1))
              for g in range(1, spec["groups"] + 1)}
    return masters, groups


def build_state(spec: dict, seed: int):
    """The Join phase's ``SearchState``: the shard masters (PaxosServers
    over a ``ShardMaster``), every group's ShardStoreServers, and the
    config controller's worker with ``Join(g)`` for every group.  The
    seed is not read here: it draws the client's values
    (``add_client``)."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.paxos.paxos import PaxosClient, PaxosServer
    from dslabs_tpu.labs.shardedstore.shardmaster import (Join, Ok,
                                                          ShardMaster)
    from dslabs_tpu.labs.shardedstore.shardstore import (ShardStoreClient,
                                                         ShardStoreServer)
    from dslabs_tpu.search.search_state import SearchState
    from dslabs_tpu.testing.generator import NodeGenerator
    from dslabs_tpu.testing.workload import Workload

    if spec["kind"] != "shardstore":
        raise ValueError(f"lab4_phases builds lab 4 states, not {spec!r}")
    masters, groups = _addresses(spec)
    group_of = {a: g for g, members in groups.items() for a in members}
    controller = LocalAddress(spec["controller"]["address"])
    shards = spec["shards"]

    def server(a):
        if a in masters:
            return PaxosServer(a, masters, ShardMaster(shards))
        return ShardStoreServer(a, masters, shards, groups[group_of[a]],
                                group_of[a])

    state = SearchState(NodeGenerator(
        server_supplier=server,
        client_supplier=lambda a: (
            PaxosClient(a, masters) if a == controller
            else ShardStoreClient(a, masters, shards)),
        workload_supplier=lambda a: None))
    for a in masters + tuple(group_of):
        state.add_server(a)
    joins = [Join(g, frozenset(members)) for g, members in groups.items()]
    state.add_client_worker(controller, Workload(
        commands=joins, results=[Ok()] * len(joins)))
    return state


def add_client(state, spec: dict, seed: int):
    """The store client's worker, added to ``state`` (a Join phase's goal
    state) in place as the test adds it: one ``MultiPut`` of the
    configuration's keys to two values drawn from the seed."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.shardedstore.txkvstore import MultiPut, MultiPutOk
    from dslabs_tpu.testing.workload import Workload

    rng = random.Random(seed)
    client = spec["client"]
    puts = {key: states._word(rng, 4) for key in client["keys"]}
    state.add_client_worker(
        LocalAddress(client["address"]),
        Workload(commands=[MultiPut(puts)], results=[MultiPutOk()]))
    return state


def build_settings(phase: dict, start):
    """The ``SearchSettings`` of one phase, for the state it starts
    from: ``lab_phases``' (invariants, goals, prunes, partition, gated
    timers, ``max_time``, a ``max_depth`` relative to that state's
    depth), then this driver's two additions."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.testing.predicates import client_done

    done = [g for g in phase["goals"]
            if isinstance(g, dict) and "client_done" in g]
    s = lab_phases.build_settings(
        dict(phase, goals=[g for g in phase["goals"] if g not in done]),
        start)
    for goal in done:
        s.add_goal(client_done(LocalAddress(goal["client_done"])))
    for node in phase["nodes_off"]:
        s.node_active(LocalAddress(node), False)
    return s


def _record(name: str, results, wall_s: float) -> dict:
    """``lab_calls``' record of a call, and what the ``.lab4`` readers
    divide by: the states the answering search explored."""
    rec = lab_calls._record(name, results, wall_s)
    out = getattr(results, "tensor_outcome", None)
    if out is not None:
        rec["states_explored"] = int(out.states_explored)
    return rec


def one_cycle(ctx, traced=()):
    """The cycle's calls, in order, each from the state its phase names:
    ``(records, given)`` — ``given[i]`` is the ``(state, settings)`` call
    ``i`` was handed, for the reference."""
    from dslabs_tpu.tpu import backend

    cfg = ctx.cell.config
    spec = cfg["deployment"]["object_state"]
    goals, starts, records, given = {}, {}, [], []
    for name in ctx.cell.params["cycle"]:
        phase = cfg["phases"][name]
        if phase["start"] == "root":
            start = build_state(spec, ctx.seed)
        elif phase["start"].startswith(START_OF):
            start = starts[phase["start"][len(START_OF):]]
        else:
            start = goals[phase["start"][len(GOAL_OF):]]
            if start is None:
                raise RuntimeError(f"phase {name} starts from the "
                                   f"{phase['start']}, which found none")
        if phase.get("adds") == "client":
            add_client(start, spec, ctx.seed)
        settings = build_settings(phase, start)
        if name in traced:
            ctx.tracer.start()
        t = time.time()
        with spans_mod.span("call." + name):
            results = backend.tensor_bfs(start, settings)
        wall_s = time.time() - t
        if name in traced:
            ctx.tracer.stop()
        goals[name], starts[name] = results.goal_matching_state, start
        records.append(_record(name, results, wall_s))
        given.append((start, settings))
        ctx.note(f"{name}: {records[-1]['end_condition']}, "
                 f"{records[-1]['discovered_count']} discovered, depth "
                 f"{records[-1]['terminal_depth']}, {wall_s:.2f}s")
    return records, given


def prepare(ctx) -> None:
    t = time.time()
    with spans_mod.span("warmup"):
        one_cycle(ctx)
    ctx.state["warmup_s"] = time.time() - t


def measure(ctx, seconds: float) -> dict:
    traced = (tuple(ctx.cell.params["traced_phases"])
              if ctx.tracer is not None else ())
    calls, given = [], []
    t0 = time.time()
    while time.time() - t0 < seconds:
        records, handed = one_cycle(ctx, () if calls else traced)
        calls += records
        given += handed
    ctx.state["given"] = given
    return {"calls": calls, "warmup_s": ctx.state["warmup_s"],
            "attempted": len(calls), "failed": 0}


def verify(ctx, measured: dict) -> list:
    """``lab_phases``' comparison, and the live reference's discovered
    count against the configuration's where it pins one."""
    calls = measured["calls"]
    ref = reference(ctx, calls)
    pinned = ctx.cell.config["reference"]
    checks = []
    bad = 0
    for i, (c, r) in enumerate(zip(calls, ref)):
        want = pinned[c["kind"]]
        mine = [equal("reference." + key, r[key], want[key])
                for key in ("end_condition", "terminal_depth",
                            "discovered_count") if key in want]
        mine += [equal("end_condition", c["end_condition"],
                       r["end_condition"]),
                 equal("terminal_depth", c["terminal_depth"],
                       r["terminal_depth"]),
                 equal("terminal_holds", c["terminal_holds"], True),
                 equal("platform", c.get("platform"),
                       ctx.dev["platform"]),
                 equal("counters", sum(c["counters"].values()), 0)]
        if r["end_condition"] == "SPACE_EXHAUSTED":
            mine.append(equal("discovered_count", c["discovered_count"],
                              r["discovered_count"]))
        bad += not all(m.ok for m in mine)
        checks += [Check(f"call{i}.{c['kind']}.{m.name}", m.value,
                         m.limit, m.ok) for m in mine]
    measured["failed"] = bad
    return checks
