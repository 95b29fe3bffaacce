"""Driver ``lab_phases``: a closed loop of ONE caller making the
DEPENDENT calls of a staged lab search test through the lab entry point
``backend.tensor_bfs`` — what ``run_tests.py --lab 3 --search-backend
tensor`` dispatches PaxosTest's search tests to — one call at a time,
until the window ends.

The contract, beside ``lab_calls``' (whose call record this keeps, so
that the ``.lab`` per-layer metrics read both):

* **Dependent phases.**  The configuration's ``phases`` are data: where
  a phase starts (``root``: a fresh state built from the seed; ``goal of
  <phase>``: the object state ``tensor_bfs`` materialised as that
  phase's ``goal_matching_state`` earlier in the same cycle, carrying
  its ``TensorProvenance``), its partition, the nodes whose timers are
  gated, invariants, goals, prunes, ``max_time`` and a ``max_depth``
  relative to the start state's depth.  The cell's ``cycle`` orders
  them; a phase can only start from a phase before it.  Nothing but the
  object ``SearchState`` and ``SearchSettings`` the test itself would
  build reaches the program.
* **Whole cycles.**  Set-up is ONE whole cycle through the same path: it
  fills the persistent compile cache, so that nothing compiles to it
  inside the window.  The window runs whole cycles and ends at the first
  cycle boundary past ``--seconds``.  ``verdict_s`` is the wall seconds
  of all the window's calls over their number.  A traced run's slice is
  the calls named by ``traced_phases``, in the window's first cycle.
* **The reference starts from the same state.**  After the window the
  object checker (``dslabs_tpu/search/search.py`` ``BFS``) runs each
  timed call once more ON THE VERY OBJECT STATE AND SETTINGS that call
  was given — two checkers may stop at different goal states of equal
  depth, and everything downstream of a goal state depends on which.
  ``correct`` holds every timed call to it: equal end condition, equal
  (minimal) goal depth, the original goal predicate true on the replayed
  object state, equal discovered count where the space was exhausted,
  all guarantee counters zero, the cell's platform.  The live reference
  is held to the configuration's pinned answers where they do not depend
  on which goal state an earlier phase returned; the pinned end
  conditions are verdicts, so a ``TIME_EXHAUSTED`` on either side is not
  correct.

The seed draws the key and the two values of the clients' APPENDs.  Both
clients append to ONE key, client 2 expecting client 1's value before its
own (test22's ``X`` and ``XY``); the twin is value-blind, so the device's
work does not depend on the seed."""

from __future__ import annotations

import random
import time

from benchmark.drivers.lab_calls import _record
from benchmark.harness import spans as spans_mod
from benchmark.harness import states
from benchmark.harness.runner import Check, equal

GOAL_OF = "goal of "


def build_state(spec: dict, seed: int):
    """A fresh lab 3 ``SearchState``: ``servers`` PaxosServers and
    ``clients`` clients, each with one seeded APPEND to the one seeded
    key, expecting the concatenation up to its own value."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload
    from dslabs_tpu.labs.clientserver.kvstore import KVStore
    from dslabs_tpu.labs.paxos.paxos import PaxosClient, PaxosServer
    from dslabs_tpu.search.search_state import SearchState
    from dslabs_tpu.testing.generator import NodeGenerator

    if spec["kind"] != "paxos" or spec["commands_per_client"] != 1:
        raise ValueError(f"lab_phases builds lab 3 states of one command "
                         f"a client, not {spec!r}")
    rng = random.Random(seed)
    key = states._word(rng)
    values = [states._word(rng, 4) for _ in range(spec["clients"])]
    group = tuple(LocalAddress(f"server{i}")
                  for i in range(1, spec["servers"] + 1))
    state = SearchState(NodeGenerator(
        server_supplier=lambda a: PaxosServer(a, group, KVStore()),
        client_supplier=lambda a: PaxosClient(a, group),
        workload_supplier=lambda a: None))
    for a in group:
        state.add_server(a)
    for i, value in enumerate(values):
        state.add_client_worker(
            LocalAddress(f"client{i + 1}"),
            kv_workload([f"APPEND:{key}:{value}"],
                        ["".join(values[:i + 1])]))
    return state


def _predicate(name):
    from dslabs_tpu.labs.paxos import predicates as paxos_predicates
    from dslabs_tpu.testing import predicates

    if isinstance(name, dict):
        return _predicate(name["negate"]).negate()
    return getattr(predicates, name, None) or getattr(paxos_predicates,
                                                      name)


def build_settings(phase: dict, start):
    """The ``SearchSettings`` of one phase, for the state it starts
    from (its ``max_depth`` is relative to that state's depth)."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.search.settings import SearchSettings

    s = SearchSettings().max_time(phase["max_time"])
    for name in phase["invariants"]:
        s.add_invariant(_predicate(name))
    for name in phase["goals"]:
        s.add_goal(_predicate(name))
    for name in phase["prunes"]:
        s.add_prune(_predicate(name))
    if phase["partition"]:
        s.partition(*map(LocalAddress, phase["partition"]))
    if phase["timers_off"] == "all":
        s.deliver_timers(False)
    else:
        for node in phase["timers_off"]:
            s.deliver_timers(LocalAddress(node), False)
    if phase["max_depth"] is not None:
        s.set_max_depth(start.depth + phase["max_depth"])
    return s


def one_cycle(ctx, traced=()):
    """The cycle's calls, in order, each from the state its phase names:
    ``(records, given)`` — ``given[i]`` is the ``(state, settings)`` call
    ``i`` was handed, for the reference."""
    from dslabs_tpu.tpu import backend

    cfg = ctx.cell.config
    root = build_state(cfg["deployment"]["object_state"], ctx.seed)
    goals, records, given = {}, [], []
    for name in ctx.cell.params["cycle"]:
        phase = cfg["phases"][name]
        start = (root if phase["start"] == "root"
                 else goals[phase["start"][len(GOAL_OF):]])
        if start is None:
            raise RuntimeError(f"phase {name} starts from the "
                               f"{phase['start']}, which found none")
        settings = build_settings(phase, start)
        if name in traced:
            ctx.tracer.start()
        t = time.time()
        with spans_mod.span("call." + name):
            results = backend.tensor_bfs(start, settings)
        wall_s = time.time() - t
        if name in traced:
            ctx.tracer.stop()
        goals[name] = results.goal_matching_state
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


def reference(ctx, calls: list) -> list:
    """The object checker's record for every timed call, from the very
    state and settings that call was given.  Cycles whose earlier phases
    returned the same goal state (the same history from the root) share
    one run of it."""
    from dslabs_tpu.search.search import BFS

    done, out = {}, []
    for c, (start, settings) in zip(calls, ctx.state["given"]):
        prov = getattr(start, "_tensor_provenance", None)
        key = (c["kind"], tuple(prov.history) if prov else ())
        if key not in done:
            t = time.time()
            results = BFS(settings).run(start)
            done[key] = _record(c["kind"], results, time.time() - t)
            ctx.note(f"object checker, {c['kind']}: "
                     f"{done[key]['end_condition']}, "
                     f"{done[key]['discovered_count']} discovered, depth "
                     f"{done[key]['terminal_depth']}, "
                     f"{done[key]['wall_s']:.2f}s")
        out.append(done[key])
    return out


def verify(ctx, measured: dict) -> list:
    calls = measured["calls"]
    ref = reference(ctx, calls)
    pinned = ctx.cell.config["reference"]
    checks = []
    bad = 0
    for i, (c, r) in enumerate(zip(calls, ref)):
        want = pinned[c["kind"]]
        mine = [equal("reference.end_condition", r["end_condition"],
                      want["end_condition"])]
        if "terminal_depth" in want:
            mine.append(equal("reference.terminal_depth",
                              r["terminal_depth"],
                              want["terminal_depth"]))
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


def end_to_end(ctx, measured: dict) -> dict:
    calls = measured["calls"]
    return {"verdict_s": sum(c["wall_s"] for c in calls) / len(calls)}
