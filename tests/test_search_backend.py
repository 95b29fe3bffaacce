"""The tensor engine as a harness search strategy (tpu/backend.py):
verdict parity against the object checker on the ACTUAL lab search-test
configurations — partitions, timer gating, staged phases, provenance
replay — not twin-shaped parity fixtures.

These are the CI guards for the adapter layer's collapse arguments
(tpu/adapters/paxos.py docstring): every entry runs the same
SearchState + SearchSettings through both strategies and diffs the
verdicts (and, for depth-limited exhaustive entries, the exact
discovered counts)."""

import os

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.core.address import LocalAddress
from dslabs_tpu.search.results import EndCondition
from dslabs_tpu.search.search import bfs
from dslabs_tpu.search.settings import SearchSettings
from dslabs_tpu.utils.flags import GlobalSettings

SLOW = not os.environ.get("DSLABS_SLOW_TESTS")


@pytest.fixture
def tensor_backend():
    GlobalSettings.search_backend = "tensor"
    yield
    GlobalSettings.search_backend = "object"


def _lab0_state():
    import tests.test_lab0_search as L0

    return L0.make_state()


def test_lab0_goal_and_exhaust_verdicts(tensor_backend):
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, RESULTS_OK)

    settings = (SearchSettings().add_invariant(RESULTS_OK)
                .add_goal(CLIENTS_DONE))
    res = bfs(_lab0_state(), settings)
    assert res.end_condition == EndCondition.GOAL_FOUND
    goal = res.goal_matching_state
    assert goal.depth > 0
    # The replayed goal state is a REAL object state: the original
    # object predicate holds on it (checked again here, not only
    # inside the backend).
    assert CLIENTS_DONE.check(goal).value

    s2 = (SearchSettings().add_invariant(RESULTS_OK)
          .add_prune(CLIENTS_DONE))
    res2 = bfs(_lab0_state(), s2)
    assert res2.end_condition == EndCondition.SPACE_EXHAUSTED

    GlobalSettings.search_backend = "object"
    obj = bfs(_lab0_state(), s2)
    assert obj.end_condition == EndCondition.SPACE_EXHAUSTED
    assert obj.discovered_count == res2.discovered_count


def test_lab0_violation_verdict(tensor_backend):
    from dslabs_tpu.testing.predicates import NONE_DECIDED

    settings = SearchSettings().add_invariant(NONE_DECIDED)
    res = bfs(_lab0_state(), settings)
    assert res.end_condition == EndCondition.INVARIANT_VIOLATED
    bad = res.invariant_violating_state
    assert bad is not None
    assert not NONE_DECIDED.check(bad).value


def test_no_twin_fails_loudly(tensor_backend):
    from dslabs_tpu.labs.primarybackup.viewserver import ViewServer
    from dslabs_tpu.search.search_state import SearchState
    from dslabs_tpu.testing.generator import NodeGenerator
    from dslabs_tpu.tpu.backend import NoTensorTwin

    gen = NodeGenerator(server_supplier=lambda a: ViewServer(a),
                        client_supplier=lambda a: None,
                        workload_supplier=lambda a: None)
    state = SearchState(gen)
    state.add_server(LocalAddress("viewserver"))
    with pytest.raises(NoTensorTwin):
        bfs(state, SearchSettings())


def test_lab1_multiclient_verdicts(tensor_backend):
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload
    import tests.test_lab1 as L1
    from dslabs_tpu.search.search_state import SearchState
    from dslabs_tpu.testing.generator import NodeGenerator
    from dslabs_tpu.labs.clientserver.clientserver import (SimpleClient,
                                                           SimpleServer)
    from dslabs_tpu.labs.clientserver.kvstore import KVStore
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, RESULTS_OK)

    def mk():
        gen = NodeGenerator(
            server_supplier=lambda a: SimpleServer(a, KVStore()),
            client_supplier=lambda a: SimpleClient(a, L1.SERVER),
            workload_supplier=lambda a: None)
        state = SearchState(gen)
        state.add_server(L1.SERVER)
        for i in (1, 2):
            state.add_client_worker(
                LocalAddress(f"client{i}"),
                kv_workload([f"APPEND:foo:{i}"]))
        return state

    settings = (SearchSettings().add_invariant(RESULTS_OK)
                .add_goal(CLIENTS_DONE).max_time(60))
    res = bfs(mk(), settings)
    assert res.end_condition == EndCondition.GOAL_FOUND

    GlobalSettings.search_backend = "object"
    obj = bfs(mk(), settings)
    assert obj.end_condition == EndCondition.GOAL_FOUND
    assert obj.goal_matching_state.depth == res.goal_matching_state.depth


@pytest.mark.skipif(SLOW, reason="lab3 twin compile is slow on CPU "
                    "(DSLABS_SLOW_TESTS=1 enables)")
def test_lab3_partitioned_staged_phases(tensor_backend):
    """The test20-shaped staged search: partitioned goal phase, then
    CLIENTS_DONE from the provenance-replayed goal state, with
    goal-depth parity against the object checker."""
    import tests.test_lab3_paxos as T

    def mk():
        state = T.make_search_state(3)
        state.add_client_worker(
            T.client(1), T.kv_workload(["PUT:foo:bar", "GET:foo"],
                                       ["PutOk", "bar"]))
        return state

    settings = SearchSettings().max_time(120)
    settings.partition(T.server(1), T.server(2), T.client(1))
    settings.add_invariant(T.RESULTS_OK)
    settings.add_invariant(T.LOGS_CONSISTENT_ALL_SLOTS)
    settings.add_goal(T.NONE_DECIDED.negate())
    res = bfs(mk(), settings)
    assert res.end_condition == EndCondition.GOAL_FOUND
    goal = res.goal_matching_state

    s2 = SearchSettings().max_time(120)
    s2.add_invariant(T.RESULTS_OK)
    s2.add_invariant(T.LOGS_CONSISTENT_ALL_SLOTS)
    s2.add_goal(T.CLIENTS_DONE)
    res2 = bfs(goal, s2)
    assert res2.end_condition == EndCondition.GOAL_FOUND

    GlobalSettings.search_backend = "object"
    obj = bfs(mk(), settings)
    assert obj.end_condition == EndCondition.GOAL_FOUND
    assert obj.goal_matching_state.depth == goal.depth


@pytest.mark.skipif(SLOW, reason="lab3 twin compile is slow on CPU "
                    "(DSLABS_SLOW_TESTS=1 enables)")
def test_lab3_depth_limited_count_parity(tensor_backend):
    """Depth-limited exhaustive runs are order-independent: the tensor
    backend's discovered count must equal the object checker's exactly
    under the SAME settings (partition + timer gating) — the live guard
    for the adapter's state-collapse argument."""
    import tests.test_lab3_paxos as T

    def mk():
        state = T.make_search_state(3)
        state.add_client_worker(T.client(1),
                                T.kv_workload(["PUT:foo:bar"]))
        return state

    settings = SearchSettings().max_time(120).set_max_depth(4)
    settings.partition(T.server(1), T.server(2), T.client(1))
    settings.deliver_timers(T.server(3), False)
    settings.add_invariant(T.LOGS_CONSISTENT_ALL_SLOTS)
    res = bfs(mk(), settings)
    assert res.end_condition == EndCondition.SPACE_EXHAUSTED

    GlobalSettings.search_backend = "object"
    obj = bfs(mk(), settings)
    assert obj.end_condition == EndCondition.SPACE_EXHAUSTED
    assert obj.discovered_count == res.discovered_count


@pytest.mark.skipif(SLOW, reason="lab3 twin compile is slow on CPU "
                    "(DSLABS_SLOW_TESTS=1 enables)")
def test_lab3_singleton_goal_parity(tensor_backend):
    """test27's singleton-group search: the twin's n == 1 win-on-own-vote
    cascade (election and agreement complete inside one transition, like
    the object's synchronous self-deliveries) reaches CLIENTS_DONE."""
    import tests.test_lab3_paxos as T

    def mk():
        state = T.make_search_state(1)
        state.add_client_worker(
            T.client(1), T.kv_workload(["PUT:foo:bar", "GET:foo"],
                                       ["PutOk", "bar"]))
        return state

    settings = SearchSettings().max_time(60)
    settings.add_invariant(T.RESULTS_OK)
    settings.add_invariant(T.LOGS_CONSISTENT_ALL_SLOTS)
    settings.add_goal(T.CLIENTS_DONE)
    res = bfs(mk(), settings)
    assert res.end_condition == EndCondition.GOAL_FOUND

    GlobalSettings.search_backend = "object"
    obj = bfs(mk(), settings)
    assert obj.end_condition == EndCondition.GOAL_FOUND
    assert obj.goal_matching_state.depth == res.goal_matching_state.depth


@pytest.mark.skipif(SLOW, reason="lab3 twin compile is slow on CPU "
                    "(DSLABS_SLOW_TESTS=1 enables)")
def test_lab3_test22_five_phase_parity(tensor_backend):
    """PaxosTest test22's five phases as the benchmark's ``paxos3-suite``
    cell makes them (its configuration's phases, its driver's state and
    settings): the partitioned goal search from the root, then four
    searches from THAT goal state.  The object checker runs every phase
    from the very state the tensor phase was given — two checkers may
    stop at different goal states of equal depth, and what lies beyond a
    goal state depends on which — and must agree on verdict, minimal
    goal depth and exhausted count."""
    import os

    from benchmark.harness import manifest
    from dslabs_tpu.search.search import BFS

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = manifest.load_cell(root, "paxos3-suite")
    phases = cell.config["phases"]
    state = cell.driver.build_state(
        cell.config["deployment"]["object_state"], 22)
    goals = {}
    for name in phases:         # all five, not only the cell's cycle
        start = (state if phases[name]["start"] == "root"
                 else goals[phases[name]["start"][len("goal of "):]])
        settings = cell.driver.build_settings(phases[name], start)
        res = bfs(start, settings)
        assert res.tensor_outcome.dropped == 0
        obj = BFS(settings).run(start)
        want = cell.config["reference"][name]
        assert (obj.end_condition.name == res.end_condition.name
                == want["end_condition"]), name
        if res.end_condition == EndCondition.GOAL_FOUND:
            goals[name] = goal = res.goal_matching_state
            assert goal.depth == obj.goal_matching_state.depth, name
            assert goal.depth == want.get("terminal_depth", goal.depth)
            assert any(g.check(goal).value for g in settings.goals)
            assert goal._tensor_provenance.history, name
        else:
            assert res.discovered_count == obj.discovered_count, name
    assert sorted(goals) == ["decide", "finish13", "finish23"]


@pytest.mark.skipif(SLOW, reason="a minute of object checker for the "
                    "commit goal (DSLABS_SLOW_TESTS=1 enables)")
def test_lab4_test09_staged_tx_parity(tensor_backend):
    """ShardStorePart2Test test09's staged search as the benchmark's
    ``shardtx-suite`` cell makes it (its configuration's phases, its
    driver's states and settings): the Join search on the shard-master
    twin, then from ITS goal state plus the client the cross-group 2PC
    goal search and the done-pruned exhaust on the 2PC twin — which
    validates the staged state as its root.  The object checker runs
    every phase from the very state the tensor phase was given."""
    import os

    from benchmark.harness import manifest
    from dslabs_tpu.search.search import BFS
    from dslabs_tpu.tpu import telemetry

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = manifest.load_cell(root, "shardtx-suite")
    drv, spec = cell.driver, cell.config["deployment"]["object_state"]
    phases = cell.config["phases"]
    goals, starts, attempts = {}, {}, {}
    for name in phases:
        kind, _, frm = phases[name]["start"].partition(" of ")
        start = (drv.build_state(spec, 9) if kind == "root"
                 else goals[frm] if kind == "goal" else starts[frm])
        if phases[name]["adds"] == "client":
            drv.add_client(start, spec, 9)
        settings = drv.build_settings(phases[name], start)
        tel = telemetry.Telemetry(ring=1 << 14)
        with telemetry.use(tel):
            res = bfs(start, settings)
        attempts[name] = [r["attempt"] for r in tel.ring
                          if r["t"] == "phase"
                          and r["name"] == "entry.search"]
        assert res.tensor_outcome.dropped == 0
        obj = BFS(settings).run(start)
        want = cell.config["reference"][name]
        assert (obj.end_condition.name == res.end_condition.name
                == want["end_condition"]), name
        starts[name] = start
        if res.end_condition == EndCondition.GOAL_FOUND:
            goals[name] = goal = res.goal_matching_state
            assert (goal.depth == obj.goal_matching_state.depth
                    == want["terminal_depth"]), name
            assert any(g.check(goal).value for g in settings.goals)
        else:
            assert (res.discovered_count == obj.discovered_count
                    == want["discovered_count"]), name
    assert sorted(goals) == ["commit", "join"]
    # commit's eighth level appends 16,836 rows: over rung 0's 2^14 a
    # device on ONE device (the chip climbs on every call: PERF.md,
    # PR 33), under it on this suite's eight
    assert attempts["join"] == attempts["exhaust6"] == [0]
    assert attempts["commit"] in ([0], [0, 1])


def test_lab2_single_server_verdicts(tensor_backend):
    """test16-shaped lab2 search through the tensor backend: the
    ViewServer + PBServer + client stack reaches CLIENTS_DONE with the
    object checker's goal depth."""
    import tests.test_lab2_pb as L2
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, RESULTS_OK)

    def mk():
        workload = L2.kv_workload(["PUT:foo:bar", "GET:foo"],
                                  ["PutOk", "bar"])
        state = L2.make_search_state(workload)
        state.add_server(L2.server(1))
        state.add_client_worker(L2.client(1))
        return state

    settings = (SearchSettings().add_invariant(RESULTS_OK)
                .add_goal(CLIENTS_DONE).max_time(90))
    res = bfs(mk(), settings)
    assert res.end_condition == EndCondition.GOAL_FOUND

    GlobalSettings.search_backend = "object"
    obj = bfs(mk(), settings)
    assert obj.end_condition == EndCondition.GOAL_FOUND
    assert obj.goal_matching_state.depth == res.goal_matching_state.depth


def test_lab4_two_phase_tensor(tensor_backend):
    """The ShardStorePart1Test.test10 flow end-to-end on the tensor
    strategy: the JOIN phase runs on the join twin, its goal state
    materialises as a real object state, and the MAIN phase validates
    that state as the canonical joined root of the shardstore twin
    (ShardStoreBinding.derive_root) — goal found, then the done-pruned
    depth-limited space matches the object checker's count exactly."""
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, RESULTS_OK,
                                               client_done)
    import tests.test_lab4_shardstore as lab4

    def staged():
        state = lab4.make_search(1, 1, 1, 10)
        joined = lab4._joined_state(state, 1)
        joined.add_client_worker(
            LocalAddress("client1"),
            kv_workload(["PUT:foo:bar", "GET:foo"], ["PutOk", "bar"]))
        return joined

    # Phase 1 (inside _joined_state) already ran on the tensor backend;
    # the staged state must carry join-twin provenance.
    joined = staged()
    assert getattr(joined, "_tensor_provenance", None) is not None
    assert joined._tensor_provenance.key[0] == "ss-join"
    assert client_done(lab4.CCA).check(joined).value

    settings = SearchSettings().max_time(240)
    settings.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    settings.node_active(lab4.CCA, False)
    settings.deliver_timers(lab4.CCA, False)
    settings.deliver_timers(lab4.shard_master(1), False)
    res = bfs(joined, settings)
    assert res.end_condition == EndCondition.GOAL_FOUND
    goal = res.goal_matching_state
    assert CLIENTS_DONE.check(goal).value

    # Done-pruned depth-limited exhaust: exact count parity vs object.
    settings.clear_goals().add_prune(CLIENTS_DONE)
    settings.set_max_depth(joined.depth + 4)
    res2 = bfs(joined, settings)
    assert res2.end_condition == EndCondition.SPACE_EXHAUSTED

    GlobalSettings.search_backend = "object"
    joined_obj = staged()
    obj = bfs(joined_obj, settings)
    assert obj.end_condition == EndCondition.SPACE_EXHAUSTED
    assert obj.discovered_count == res2.discovered_count


def test_lab1_infinite_workload_tensor(tensor_backend):
    """ClientServerPart2Test.test11's shape on the tensor strategy with
    DERANDOMIZED streams (round-4 verdict item 8): exhaust verdicts,
    the add-a-client staged reuse, and — the part the old global-rng
    streams refused — terminal-state decode through the counter-mode
    command reconstruction (_StreamPairs)."""
    from dslabs_tpu.labs.clientserver.kv_workload import (
        different_keys_infinite_workload)
    from dslabs_tpu.labs.clientserver.kvstore import Put
    from dslabs_tpu.search.search import dfs
    from dslabs_tpu.testing.predicates import (RESULTS_OK,
                                               client_has_results)
    import tests.test_lab1 as L1

    state = L1._search_state(
        workload_factory=lambda: different_keys_infinite_workload())
    settings = SearchSettings().add_invariant(RESULTS_OK)
    settings.max_time(5)
    res = bfs(state, settings)
    assert res.end_condition in (EndCondition.TIME_EXHAUSTED,
                                 EndCondition.SPACE_EXHAUSTED)

    settings.set_max_depth(1000).max_time(5)
    res = dfs(state, settings)
    assert not res.terminal_found()

    state.add_client_worker(LocalAddress("client2"),
                            different_keys_infinite_workload())
    res = dfs(state, settings)
    assert not res.terminal_found()

    # Terminal-state materialisation through the stream reconstruction:
    # the goal state's results must be the ACTUAL commands the object
    # client drew — the counter-mode stream's first Put.
    state2 = L1._search_state(
        workload_factory=lambda: different_keys_infinite_workload())
    s2 = (SearchSettings().add_invariant(RESULTS_OK)
          .add_goal(client_has_results(LocalAddress("client1"), 1))
          .max_time(60))
    res2 = bfs(state2, s2)
    assert res2.end_condition == EndCondition.GOAL_FOUND
    goal = res2.goal_matching_state
    worker = goal.client_workers()[LocalAddress("client1")]
    assert len(worker.results) >= 1
    sent = worker.sent_commands[0]
    assert isinstance(sent, Put) and sent.key.startswith("client1-")


def test_lab1_deep_probe_dfs(tensor_backend):
    """The dfs-routed rollout probe (engine.random_rollouts via
    backend._rollout_probe): a violation that only exists ~24 levels
    deep — far past what a level-by-level search clears in this time
    budget — must still be found, with a real replayed object state
    (the round-4 advisor's RandomDFS depth-reach gap, closed)."""
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload
    from dslabs_tpu.search.search import dfs
    from dslabs_tpu.testing.predicates import client_has_results
    import tests.test_lab1 as L1

    w = 10
    state = L1._search_state(workload_factory=lambda: kv_workload(
        [f"PUT:key{i}:v{i}" for i in range(1, w + 1)]))
    settings = SearchSettings().max_time(45).set_max_depth(1000)
    settings.add_invariant(
        client_has_results(LocalAddress("client1"), w - 1).negate())
    res = dfs(state, settings)
    assert res.end_condition == EndCondition.INVARIANT_VIOLATED
    bad = res.invariant_violating_state
    assert bad is not None
    assert len(bad.client_workers()[LocalAddress("client1")].results) \
        >= w - 1
    assert bad.depth >= 2 * (w - 1)       # deep, as constructed


# ------------------------------------------------ what the lab entry keeps
# tpu/backend.py keeps a call's twin, engine (with its traced and
# compiled programs) and trace step in one bounded table, keyed by what
# the call's input lets it observe.  A hit may change how long a call
# takes and nothing else.

def _lab1(seed=5):
    """Lab 1, 2 clients, 2 seeded APPENDs each: 80 states."""
    from benchmark.harness import states

    return states.build({"kind": "clientserver", "clients": 2,
                         "commands_per_client": 2}, seed)


def _lab1_settings(kind):
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, NONE_DECIDED,
                                               RESULTS_OK)

    s = SearchSettings().max_time(60)
    if kind == "goal":
        return s.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    if kind == "exhaust":
        return s.add_invariant(RESULTS_OK).add_prune(CLIENTS_DONE)
    return s.add_invariant(NONE_DECIDED)


def _recorded(state, settings):
    """``(results, phases)`` of one ``tensor_bfs`` call: the ``entry.*``
    phase records it wrote, in order."""
    from dslabs_tpu.tpu import backend
    from dslabs_tpu.tpu import telemetry as tel_mod

    tel = tel_mod.Telemetry(ring=1 << 12)
    with tel_mod.use(tel):
        results = backend.tensor_bfs(state, settings)
    return results, [r for r in tel.ring if r["t"] == "phase"
                     and r["name"].startswith("entry.")]


def _answer(results):
    """Everything of a call's answer that a kept engine could spoil."""
    out = results.tensor_outcome
    terminal = (results.goal_matching_state
                or results.invariant_violating_state)
    return {
        "end": results.end_condition, "discovered": results.discovered_count,
        "explored": out.states_explored, "depth": out.depth,
        "predicate": out.predicate_name,
        "witness": None if out.trace is None else list(map(int, out.trace)),
        "samples": out.samples and [list(map(int, t)) for t in out.samples],
        "terminal_depth": terminal and terminal.depth,
        "provenance": terminal and (terminal._tensor_provenance.key,
                                    terminal._tensor_provenance.history),
        "counters": (out.dropped, out.visited_overflow, out.retries),
    }


def _cached(phases, name):
    return [r.get("cached") for r in phases if r["name"] == name]


_BUILD_STAGES = ("entry.bind", "entry.build_engine", "entry.derive_root")
# What a call that builds nothing leaves alone in compile_cache.totals():
# nothing is lowered, compiled, or asked of the persistent cache.  (Its
# ``trace_n`` moves: the eager operations of ``initial_state()`` look
# their jaxprs up, in milliseconds.)
_NO_COMPILE = ("lower_n", "lower_s", "backend_compile_n",
               "backend_compile_s", "cache_hit_n", "cache_miss_n")


@pytest.mark.parametrize("kind", ["goal", "exhaust", "violation"])
def test_a_repeated_lab1_call_builds_nothing_and_answers_alike(kind):
    from dslabs_tpu.tpu import backend, compile_cache

    backend.clear_cache()
    fresh, first = _recorded(_lab1(), _lab1_settings(kind))
    assert [_cached(first, s) for s in _BUILD_STAGES] == [[0], [0], [1]]
    assert _cached(first, "entry.warm_run") == [None]
    before = compile_cache.totals()
    again, second = _recorded(_lab1(), _lab1_settings(kind))
    after = compile_cache.totals()
    assert [_cached(second, s) for s in _BUILD_STAGES] == [[1], [1], [1]]
    assert not {"entry.warm_run", "entry.root.build"} & {
        r["name"] for r in second}
    assert {k: after[k] for k in _NO_COMPILE} == {
        k: before[k] for k in _NO_COMPILE}
    assert after["trace_s"] - before["trace_s"] < 0.25
    assert _answer(again) == _answer(fresh)
    assert (fresh.end_condition.name, fresh.discovered_count) == {
        "goal": ("GOAL_FOUND", fresh.discovered_count),
        "exhaust": ("SPACE_EXHAUSTED", 80),
        "violation": ("INVARIANT_VIOLATED", fresh.discovered_count)}[kind]
    info = backend.cache_info()
    assert (info["twin"], info["engine"], info["bypasses"]) == (1, 1, 0)
    # the witness replay and the sampled re-check step through ONE kept
    # program, built by whichever needed it first
    assert info["step"] == 1


def test_predicate_signature_is_the_structure_not_the_name():
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, NONE_DECIDED,
                                               StatePredicate, client_done)
    from dslabs_tpu.tpu.backend import predicate_signature as sig

    a = LocalAddress("client1")
    assert sig(CLIENTS_DONE) == ("tkey", ("CLIENTS_DONE",))
    assert sig(NONE_DECIDED.negate()) == ("not", ("tkey",
                                                  ("NONE_DECIDED",)))
    assert sig(client_done(a)) == sig(client_done(LocalAddress("client1")))
    assert sig(client_done(a)) != sig(client_done(LocalAddress("client2")))
    both = CLIENTS_DONE.and_(NONE_DECIDED)
    assert sig(both) == ("and", sig(CLIENTS_DONE), sig(NONE_DECIDED))
    assert len({sig(both), sig(CLIENTS_DONE.or_(NONE_DECIDED)),
                sig(CLIENTS_DONE.implies(NONE_DECIDED)),
                sig(NONE_DECIDED.and_(CLIENTS_DONE))}) == 4
    renamed = StatePredicate("Clients got expected results",
                             lambda s: True, tkey=("CLIENTS_DONE",))
    assert sig(renamed) == sig(CLIENTS_DONE)
    assert sig(StatePredicate("no key", lambda s: True)) == ("tkey", None)


def _same_name_other_goal():
    """A goal that carries CLIENTS_DONE's NAME and means something else:
    client 1 alone is done."""
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, RESULTS_OK,
                                               StatePredicate, client_done)

    one = client_done(LocalAddress("client1"))
    other = StatePredicate(CLIENTS_DONE.name, one._fn, tkey=one.tkey)
    return (_lab1(), SearchSettings().max_time(60)
            .add_invariant(RESULTS_OK).add_goal(other))


def _other_commands():
    return _lab1(seed=6), _lab1_settings("goal")


@pytest.mark.parametrize("variant, twin_kept", [
    (_same_name_other_goal, 1), (_other_commands, 0), ("symmetry", 1)])
def test_the_key_tells_apart(variant, twin_kept, monkeypatch):
    """A call that differs from a kept one in what the engine was built
    from gets an engine of its own — other predicates under equal names,
    other command values, a changed ``DSLABS_SYMMETRY`` — and the call
    it differs from still finds its own afterwards."""
    from dslabs_tpu.tpu import backend

    backend.clear_cache()
    base, _ = _recorded(_lab1(), _lab1_settings("goal"))
    if variant == "symmetry":
        monkeypatch.setenv("DSLABS_SYMMETRY", "0")
        state, settings = _lab1(), _lab1_settings("goal")
    else:
        state, settings = variant()
    res, phases = _recorded(state, settings)
    assert _cached(phases, "entry.build_engine") == [0]
    # the environment is part of the twin's key too: one discipline
    assert _cached(phases, "entry.bind") == [
        0 if variant == "symmetry" else twin_kept]
    assert backend.cache_info()["engine"] == 2
    assert res.end_condition == EndCondition.GOAL_FOUND
    goal = res.goal_matching_state
    assert any(g.check(goal).value for g in settings.goals)
    if variant is _same_name_other_goal:
        # client 1 is done sooner than both are
        assert goal.depth < base.goal_matching_state.depth
    else:
        assert goal.depth == base.goal_matching_state.depth
    if variant is _other_commands:
        assert (goal._tensor_provenance.key
                != base.goal_matching_state._tensor_provenance.key)
    monkeypatch.delenv("DSLABS_SYMMETRY", raising=False)
    back, phases = _recorded(_lab1(), _lab1_settings("goal"))
    assert _cached(phases, "entry.build_engine") == [1]
    assert _answer(back) == _answer(base)


def test_shardstore_modelling_flags_are_in_the_twins_key(tensor_backend):
    """``ShardStoreBinding.check_settings`` binds ``_model_mh`` /
    ``_model_ctl``, which change the protocol ``build_protocol``
    returns: the key is read after it and holds them."""
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload
    from dslabs_tpu.tpu import backend
    import tests.test_lab4_shardstore as lab4

    joined = lab4._joined_state(lab4.make_search(1, 1, 1, 10), 1)
    joined.add_client_worker(
        LocalAddress("client1"),
        kv_workload(["PUT:foo:bar", "GET:foo"], ["PutOk", "bar"]))
    binding = backend.resolve_binding(joined)
    assert binding.key[0] == "shardstore"

    def frozen():
        s = SearchSettings()
        s.node_active(lab4.CCA, False)
        s.deliver_timers(lab4.CCA, False)
        return s

    keys = {}
    for name, settings in (
            ("frozen", frozen().deliver_timers(lab4.shard_master(1), False)),
            ("master timers", frozen()),
            ("controller", SearchSettings().deliver_timers(
                lab4.shard_master(1), False))):
        binding.check_settings(settings)
        keys[name] = (binding._model_mh, binding._model_ctl,
                      backend._key("twin", binding, 48, 6))
    assert [k[:2] for k in keys.values()] == [
        (False, False), (True, False), (False, True)]
    assert len({k[2] for k in keys.values()}) == 3
    assert all(k[2] is not None and k[2][1][:len(binding.key)]
               == binding.key for k in keys.values())


@pytest.mark.parametrize("leaky", ["max_time", "partition", "max_depth"])
def test_a_kept_engine_carries_no_setting_over(leaky):
    """A call with a time budget, a partition or a depth limit, then one
    without, on the very same engine (all three are runtime settings,
    none is in the key): the second explores the whole space."""
    s = _lab1_settings("exhaust")
    if leaky == "max_time":
        s.max_time(1e-9)
    elif leaky == "partition":
        s.partition(LocalAddress("server"), LocalAddress("client1"))
    else:
        s.set_max_depth(3)
    narrowed, _ = _recorded(_lab1(), s)
    assert narrowed.discovered_count < 80
    assert narrowed.end_condition == (
        EndCondition.TIME_EXHAUSTED if leaky == "max_time"
        else EndCondition.SPACE_EXHAUSTED)
    from dslabs_tpu.testing.predicates import CLIENTS_DONE, RESULTS_OK

    unbounded = (SearchSettings().add_invariant(RESULTS_OK)
                 .add_prune(CLIENTS_DONE))
    whole, phases = _recorded(_lab1(), unbounded)
    assert _cached(phases, "entry.build_engine") == [1]
    assert "entry.warm_run" not in {r["name"] for r in phases}
    assert (whole.end_condition, whole.discovered_count) == (
        EndCondition.SPACE_EXHAUSTED, 80)
    assert whole.tensor_outcome.depth > 3


def test_what_a_call_sets_on_a_kept_engine_it_sets_once():
    """The recorder and the retry boundary of a call replace the call
    before's: a kept engine's dispatches are recorded once, by the
    current recorder alone, through one boundary; with no recorder
    current, by none."""
    from dslabs_tpu.tpu import backend
    from dslabs_tpu.tpu import telemetry as tel_mod

    backend.clear_cache()
    tels = [tel_mod.Telemetry(ring=1 << 12) for _ in range(2)]
    engines, boundaries, dispatches = [], [], []
    for tel in tels + [None]:
        with tel_mod.use(tel):
            res = backend.tensor_bfs(_lab1(), _lab1_settings("exhaust"))
        assert res.discovered_count == 80
        (kept,) = [v for k, v in backend._KEPT._table.items()
                   if k[0] == "engine"]
        engines.append(kept.search)
        boundaries.append(kept.search._dispatch_boundary)
        assert kept.search._telemetry is tel
        assert kept.search._dispatch_hook == boundaries[-1].dispatch
        # and nothing of the finished search stays on it but programs
        assert kept.search._fp_map == {} and kept.search._trace_root is None
        dispatches.append([len([r for r in t.ring if r["t"] == "span"])
                           for t in tels])
    assert engines[0] is engines[1] is engines[2]
    assert len({id(b) for b in boundaries}) == 3
    # call 1 warmed (to depth 2) and searched; call 2 searched, through
    # the second recorder only; call 3 was recorded by neither
    first, second = dispatches[0][0], dispatches[1][1]
    assert dispatches == [[first, 0], [first, second], [first, second]]
    assert 0 < second < first


def test_a_reentrant_call_gets_an_engine_of_its_own():
    """A call made while a call of equal key still holds the kept engine
    (here: from inside the goal predicate, which the outer call checks on
    its replayed object state) is not handed that engine."""
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, RESULTS_OK,
                                               StatePredicate)
    from dslabs_tpu.tpu import backend

    inner = []

    def goal(s):
        if not inner:
            inner.append(None)
            inner[0] = _recorded(_lab1(), settings())
        return CLIENTS_DONE.check(s).value

    def settings():
        return (SearchSettings().max_time(60).add_invariant(RESULTS_OK)
                .add_goal(StatePredicate("done, and asks again", goal,
                                         tkey=CLIENTS_DONE.tkey)))

    backend.clear_cache()
    base, _ = _recorded(_lab1(), _lab1_settings("goal"))
    inner.append(None)          # this call's predicate asks nothing
    _recorded(_lab1(), settings())              # keeps the engine
    inner.clear()
    assert backend.cache_info()["bypasses"] == 0
    outer, phases = _recorded(_lab1(), settings())
    assert _cached(phases, "entry.build_engine") == [1]
    nested, nested_phases = inner[0]
    assert _cached(nested_phases, "entry.build_engine") == [0]
    info = backend.cache_info()
    assert (info["bypasses"], info["engine"]) == (1, 2)
    for res in (outer, nested):
        assert (res.goal_matching_state.depth, res.discovered_count) == (
            base.goal_matching_state.depth, base.discovered_count)
    # and the engine is free again once its call has returned
    _, phases = _recorded(_lab1(), settings())
    assert _cached(phases, "entry.build_engine") == [1]
    assert backend.cache_info()["bypasses"] == 1


def test_the_table_is_bounded_and_least_recently_used_goes(monkeypatch):
    from dslabs_tpu.tpu import backend

    kept = backend._Kept()
    monkeypatch.setattr(backend._Kept, "BOUND", 3)
    for i in range(3):
        assert kept.get(("twin", i)) is None
        assert kept.put(("twin", i), i) == i
    assert kept.get(("twin", 0)) == 0           # now the most recent
    kept.put(("twin", 3), 3)                    # ("twin", 1) goes
    assert [kept.get(("twin", i)) for i in range(4)] == [0, None, 2, 3]
    assert (kept.hits, kept.misses, len(kept._table)) == (4, 4, 3)
    # a key that cannot be expressed is never found and never kept
    assert kept.put(None, "x") == "x" and kept.get(None) is None
    assert (kept.bypasses, len(kept._table)) == (1, 3)
    # an engine handed out is not handed out again until its call ends,
    # and the call that was refused keeps its own to itself
    import types

    e0, mine = (backend._Engine(types.SimpleNamespace(_fp_map={1: 2}))
                for _ in range(2))
    kept.put(("engine", 0), e0)
    with kept.leasing() as outer:
        assert kept.get(("engine", 0), outer) is e0
        with kept.leasing() as inner:
            assert kept.get(("engine", 0), inner) is None
            assert kept.put(("engine", 0), mine, inner) is mine
        assert kept.get(("engine", 0)) is e0
        # what a run leaves for the replay stays while its call runs
        assert e0.search._fp_map == {1: 2}
    assert e0.search._fp_map == {} and e0.search._trace_root is None
    with kept.leasing() as again:
        assert kept.get(("engine", 0), again) is e0
    assert backend.cache_info()["bound"] == 3 and backend._KEPT is not kept


def test_concurrent_calls_never_share_a_kept_engine():
    """More threads than cores take, use and release the engine of ONE
    key (and now and then clear the table): at no time do two hold the
    same object, and every thread gets one."""
    import sys
    import threading
    import time
    import types

    from dslabs_tpu.tpu import backend

    kept = backend._Kept()
    held, clashes, done = set(), [], []
    guard = threading.Lock()

    def caller(n):
        for i in range(300):
            with kept.leasing() as lease:
                engine = kept.get(("engine", 0), lease)
                if engine is None:
                    engine = kept.put(("engine", 0), backend._Engine(
                        types.SimpleNamespace()), lease)
                with guard:
                    if id(engine) in held:
                        clashes.append((n, i))
                    held.add(id(engine))
                if i % 7 == n % 7:
                    time.sleep(0)
                if i % 97 == n:
                    kept.clear()
                with guard:
                    held.discard(id(engine))
        done.append(n)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(n,))
                   for n in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert sorted(done) == list(range(32)) and not clashes
    assert not kept._leased


@pytest.mark.skipif(SLOW, reason="lab3 twin compile is slow on CPU "
                    "(DSLABS_SLOW_TESTS=1 enables)")
def test_lab3_test22_five_phases_twice_in_a_row(tensor_backend):
    """test22's five phases, then the five again from a fresh state of
    the same seed: the second round constructs nothing (on every rung a
    phase stands on), warms nothing, and gives the first round's
    answers, witnesses and provenances."""
    import os

    from benchmark.harness import manifest
    from dslabs_tpu.tpu import backend

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = manifest.load_cell(root, "paxos3-suite")
    phases = cell.config["phases"]
    backend.clear_cache()
    rounds = []
    for _ in range(2):
        state = cell.driver.build_state(
            cell.config["deployment"]["object_state"], 22)
        goals, got = {}, {}
        for name in phases:
            start = (state if phases[name]["start"] == "root"
                     else goals[phases[name]["start"][len("goal of "):]])
            res, recs = _recorded(
                start, cell.driver.build_settings(phases[name], start))
            goals[name] = res.goal_matching_state
            got[name] = (_answer(res), recs)
        rounds.append(got)
    for name in phases:
        assert rounds[1][name][0] == rounds[0][name][0], name
        assert (rounds[0][name][0]["end"].name
                == cell.config["reference"][name]["end_condition"])
        first, recs = rounds[0][name][1], rounds[1][name][1]
        for stage in _BUILD_STAGES:     # as many rungs, nothing built
            assert _cached(recs, stage) == [1] * len(
                _cached(first, stage)), (name, stage)
        assert not {"entry.warm_run", "entry.root.build"} & {
            r["name"] for r in recs}, name
    # what round one built is what is kept: three predicate sets on the
    # first rung, and a rung more for the phase that climbs (finish23)
    built = {stage: sum(c == 0 for name in phases for c in _cached(
        rounds[0][name][1], stage)) for stage in _BUILD_STAGES}
    info = backend.cache_info()
    assert (info["twin"], info["engine"]) == (
        built["entry.bind"], built["entry.build_engine"])
    assert info["engine"] >= 3 and info["step"] == info["twin"]
    assert info["entries"] <= info["bound"] and info["bypasses"] == 0
