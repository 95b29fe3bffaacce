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
