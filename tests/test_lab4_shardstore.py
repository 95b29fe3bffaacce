"""Lab 4 tests — behavioural port of the TransactionalKVStore unit semantics
and ShardStorePart1Test run tests (basic ops, join/leave handoff, shard
movement, wrong-group routing)."""

import time

import pytest

from dslabs_tpu.harness import (RUN_TESTS, SEARCH_TESTS,
                                UNRELIABLE_TESTS, lab_test)
from dslabs_tpu.core.address import LocalAddress
from dslabs_tpu.labs.clientserver.kv_workload import get, get_result, put, put_ok
from dslabs_tpu.labs.clientserver.kvstore import KeyNotFound
from dslabs_tpu.labs.paxos.paxos import PaxosClient, PaxosServer
from dslabs_tpu.labs.shardedstore.shardmaster import (Join, Leave, Move, Ok,
                                                      Query, ShardConfig,
                                                      ShardMaster)
from dslabs_tpu.labs.shardedstore.shardstore import (ShardStoreClient,
                                                     ShardStoreServer,
                                                     key_to_shard)
from dslabs_tpu.labs.shardedstore.txkvstore import (MultiGet, MultiGetResult,
                                                    MultiPut, MultiPutOk,
                                                    Swap, SwapOk,
                                                    TransactionalKVStore,
                                                    KEY_NOT_FOUND)
from dslabs_tpu.runner.run_settings import RunSettings
from dslabs_tpu.runner.run_state import RunState
from dslabs_tpu.testing.generator import NodeGenerator
from dslabs_tpu.testing.predicates import CLIENTS_DONE, RESULTS_OK

CCA = LocalAddress("configController")
MOVER = LocalAddress("mover")
NUM_SHARDS = 10


def shard_master(i):
    return LocalAddress(f"shardmaster{i}")


def server(g, i):
    return LocalAddress(f"server{g}-{i}")


def group(g, n=3):
    return frozenset(server(g, i) for i in range(1, n + 1))


# --------------------------------------------------------- unit: txkvstore

@lab_test("4", 40, "TransactionalKVStore semantics", part=3, categories=(RUN_TESTS,))
def test_txkvstore_semantics():
    kv = TransactionalKVStore()
    assert kv.execute(MultiPut({"a": "1", "b": "2"})) == MultiPutOk()
    r = kv.execute(MultiGet({"a", "b", "c"}))
    assert r == MultiGetResult({"a": "1", "b": "2", "c": KEY_NOT_FOUND})
    assert kv.execute(Swap("a", "b")) == SwapOk()
    assert kv.execute(MultiGet({"a", "b"})) == MultiGetResult(
        {"a": "2", "b": "1"})
    # Swap with a missing key moves the value and deletes the other side.
    assert kv.execute(Swap("a", "missing")) == SwapOk()
    assert kv.execute(MultiGet({"a", "missing"})) == MultiGetResult(
        {"a": KEY_NOT_FOUND, "missing": "2"})
    # Plain KVStore ops still work.
    assert kv.execute(put("x", "y")) == put_ok()
    assert kv.execute(get("x")) == get_result("y")


@lab_test("4", 15, "keyToShard matches reference hashing", part=2, categories=(RUN_TESTS,))
def test_key_to_shard():
    assert key_to_shard("key-3", 10) == 3
    assert key_to_shard("key-10", 10) == 10  # 10 mod 10 = 0 -> +10
    assert key_to_shard("key-13", 10) == 3
    s = key_to_shard("foo", 10)
    assert 1 <= s <= 10
    assert key_to_shard("foo", 10) == s  # deterministic
    # 10+ trailing digits overflow Java's 32-bit int accumulation
    # (ShardStoreNode.java keyToShard: hash = hash*10 + digit in int
    # arithmetic); 12345678901 wraps to -539222987, mod 10 -> 3.
    assert key_to_shard("x12345678901", 10) == 3
    # 4294967296 == 2^32 wraps to exactly 0 -> mod adjusts to numShards.
    assert key_to_shard("k4294967296", 10) == 10


# ------------------------------------------------------------- run fixtures

def _make_generator(servers_per_group, num_shard_masters, num_shards):
    masters = tuple(shard_master(i) for i in range(1, num_shard_masters + 1))

    def server_supplier(a):
        if a in masters:
            return PaxosServer(a, masters, ShardMaster(num_shards))
        name = str(a)
        g = int(name.split("server")[1].split("-")[0])
        grp = tuple(server(g, i) for i in range(1, servers_per_group + 1))
        return ShardStoreServer(a, masters, num_shards, grp, g)

    def client_supplier(a):
        # Config-controller-style clients (CCA, the movement driver) talk
        # to the shard-master group directly; everything else is a store
        # client routing by shard.
        if a == CCA or a == MOVER:
            return PaxosClient(a, masters)
        return ShardStoreClient(a, masters, num_shards)

    return masters, NodeGenerator(server_supplier=server_supplier,
                                  client_supplier=client_supplier,
                                  workload_supplier=lambda a: None)


def make_state(num_groups, servers_per_group=3, num_shard_masters=3,
               num_shards=NUM_SHARDS):
    masters, gen = _make_generator(servers_per_group, num_shard_masters,
                                   num_shards)
    state = RunState(gen)
    for m in masters:
        state.add_server(m)
    for g in range(1, num_groups + 1):
        for i in range(1, servers_per_group + 1):
            state.add_server(server(g, i))
    return state


def send_check(client, command, expected, timeout=8):
    client.send_command(command)
    result = client.get_result(timeout=timeout)
    assert result == expected, f"{command} -> {result} (expected {expected})"


@lab_test("4", 1, "Single group, basic workload", points=10, part=2, categories=(RUN_TESTS,))
def test_basic_single_group():
    state = make_state(1)
    settings = RunSettings().max_time(30)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())
    c = state.add_client(LocalAddress("client1"))
    send_check(c, put("key-1", "v1"), put_ok())
    send_check(c, get("key-1"), get_result("v1"))
    send_check(c, get("key-7"), KeyNotFound())
    send_check(c, put("key-7", "v7"), put_ok())
    send_check(c, get("key-7"), get_result("v7"))
    state.stop()


@lab_test("4", 3, "Shards move when group joins", points=15, part=2, categories=(RUN_TESTS,))
def test_join_moves_shards():
    state = make_state(2)
    settings = RunSettings().max_time(60)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())

    c = state.add_client(LocalAddress("client1"))
    for i in range(1, NUM_SHARDS + 1):
        send_check(c, put(f"key-{i}", f"v{i}"), put_ok())

    # Join the second group: half the shards (with data) must move.
    send_check(cc, Join(2, group(2)), Ok())
    for i in range(1, NUM_SHARDS + 1):
        send_check(c, get(f"key-{i}"), get_result(f"v{i}"))

    # Data written after the reconfiguration lands in the right group too.
    send_check(c, put("key-1", "v1b"), put_ok())
    send_check(c, get("key-1"), get_result("v1b"))

    # Leave group 1: all shards drain to group 2, nothing is lost.
    send_check(cc, Leave(1), Ok())
    for i in range(2, NUM_SHARDS + 1):
        send_check(c, get(f"key-{i}"), get_result(f"v{i}"))
    send_check(c, get("key-1"), get_result("v1b"))
    state.stop()


@lab_test("4", 4, "Shards move when moved by ShardMaster", points=15, part=2, categories=(RUN_TESTS,))
def test_move_command_relocates_data():
    state = make_state(2)
    settings = RunSettings().max_time(60)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())
    send_check(cc, Join(2, group(2)), Ok())

    c = state.add_client(LocalAddress("client1"))
    send_check(c, put("key-3", "v3"), put_ok())

    cc.send_command(Query(-1))
    config = cc.get_result(timeout=5)
    assert isinstance(config, ShardConfig)
    dest = 2 if 3 in config.groups()[1][1] else 1
    send_check(cc, Move(dest, 3), Ok())

    send_check(c, get("key-3"), get_result("v3"))
    send_check(c, put("key-3", "v3b"), put_ok())
    send_check(c, get("key-3"), get_result("v3b"))
    state.stop()


@lab_test("4", 1, "Single group, simple transactional workload", points=5, part=3, categories=(RUN_TESTS,))
def test_single_group_transactions():
    """Transactions whose key set lives in one group run without 2PC."""
    state = make_state(1)
    settings = RunSettings().max_time(30)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())
    c = state.add_client(LocalAddress("client1"))
    send_check(c, MultiPut({"a1": "x", "b1": "y"}), MultiPutOk())
    send_check(c, MultiGet({"a1", "b1"}),
               MultiGetResult({"a1": "x", "b1": "y"}))
    send_check(c, Swap("a1", "b1"), SwapOk())
    send_check(c, MultiGet({"a1", "b1"}),
               MultiGetResult({"a1": "y", "b1": "x"}))
    state.stop()


@lab_test("4", 2, "Multi-group, simple transactional workload", points=5, part=3, categories=(RUN_TESTS,))
def test_cross_group_transactions():
    """2PC: transactions spanning groups commit atomically."""
    state = make_state(2)
    settings = RunSettings().max_time(60)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())
    send_check(cc, Join(2, group(2)), Ok())
    c = state.add_client(LocalAddress("client1"))
    # key-1..key-10 span both groups (shards 1..10 split 5/5).
    send_check(c, MultiPut({f"key-{i}": f"v{i}" for i in range(1, 6)}),
               MultiPutOk())
    send_check(c, MultiGet({f"key-{i}" for i in range(1, 6)}),
               MultiGetResult({f"key-{i}": f"v{i}" for i in range(1, 6)}))
    send_check(c, Swap("key-1", "key-2"), SwapOk())
    send_check(c, MultiGet({"key-1", "key-2"}),
               MultiGetResult({"key-1": "v2", "key-2": "v1"}))
    # Swap against a missing key across groups.
    send_check(c, Swap("key-3", "key-9"), SwapOk())
    send_check(c, MultiGet({"key-3", "key-9"}),
               MultiGetResult({"key-3": KEY_NOT_FOUND, "key-9": "v3"}))
    state.stop()


@lab_test("4", 13, "Concurrent cross-group swaps (extended)", points=0, part=3, categories=(RUN_TESTS,))
def test_concurrent_cross_group_swaps():
    """Concurrent conflicting 2PC transactions stay atomic: swaps permute
    values, so the value multiset is preserved (TransactionalKVStoreWorkload
    MULTI_GETS_MATCH spirit)."""
    import threading
    state = make_state(2)
    settings = RunSettings().max_time(60)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())
    send_check(cc, Join(2, group(2)), Ok())
    setup = state.add_client(LocalAddress("setup-client"))
    keys = ["key-1", "key-5", "key-6", "key-10"]
    send_check(setup, MultiPut({k: k for k in keys}), MultiPutOk())

    errors = []

    def swapper(name, k1, k2, n):
        c = state.add_client(LocalAddress(name))
        try:
            for _ in range(n):
                c.send_command(Swap(k1, k2))
                assert c.get_result(timeout=20) == SwapOk()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=swapper, args=("swap-a", "key-1", "key-6", 4)),
        threading.Thread(target=swapper, args=("swap-b", "key-5", "key-10", 4)),
        threading.Thread(target=swapper, args=("swap-c", "key-1", "key-10", 3)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors

    reader = state.add_client(LocalAddress("reader-client"))
    reader.send_command(MultiGet(set(keys)))
    result = reader.get_result(timeout=20)
    assert isinstance(result, MultiGetResult)
    # Swaps only permute: the multiset of values is invariant.
    assert sorted(result.as_dict().values()) == sorted(keys)
    state.stop()


# ------------------------------------------- additional reference ports (p2)

def _join_leave_body(state, n_keys=30):
    """test02JoinLeave body (ShardStorePart1Test.java:75-121, scaled
    100 -> 30 keys): keys survive joins, rewrites, and leaves."""
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())
    c = state.add_client(LocalAddress("client1"))
    kv = {}
    for i in range(1, n_keys + 1):
        kv[f"key-{i}"] = f"v{i}"
        send_check(c, put(f"key-{i}", f"v{i}"), put_ok())

    send_check(cc, Join(2, group(2)), Ok())
    send_check(cc, Join(3, group(3)), Ok())
    time.sleep(2)
    for k, v in kv.items():
        send_check(c, get(k), get_result(v))

    for i in range(1, n_keys + 1):
        kv[f"key-{i}"] = f"w{i}"
        send_check(c, put(f"key-{i}", f"w{i}"), put_ok())

    send_check(cc, Leave(1), Ok())
    send_check(cc, Leave(2), Ok())
    time.sleep(2)
    for k, v in kv.items():
        send_check(c, get(k), get_result(v))
    state.stop()


@lab_test("4", 2, "Multi-group join/leave", points=15, part=2, categories=(RUN_TESTS,))
def test02_join_leave():
    state = make_state(3)
    state.start(RunSettings().max_time(120))
    _join_leave_body(state)


@lab_test("4", 5, "Progress with majorities in each group", points=15, part=2, categories=(RUN_TESTS,))
def test05_progress_with_majorities():
    """test05ProgressWithMajorities: one server per group (and one shard
    master) cut off; join/leave still completes."""
    state = make_state(3)
    settings = RunSettings().max_time(120)
    for g in range(1, 4):
        settings.receiver_active(server(g, 3), False)
        settings.sender_active(server(g, 3), False)
    settings.receiver_active(shard_master(3), False)
    settings.sender_active(shard_master(3), False)
    state.start(settings)
    _join_leave_body(state, n_keys=15)


@lab_test("4", 8, "Multi-group join/leave", points=20, part=2, categories=(RUN_TESTS, UNRELIABLE_TESTS,))
def test08_join_leave_unreliable():
    state = make_state(3)
    settings = RunSettings().max_time(180)
    settings.network_deliver_rate(0.8)
    state.start(settings)
    _join_leave_body(state, n_keys=10)


def _run_with_background(state, settings, background, length_secs,
                         n_clients=3, max_wait=4.0):
    """Shared body of test06/test07/test09: infinite-workload clients run
    while a background thread perturbs the system."""
    import threading

    from dslabs_tpu.labs.clientserver.kv_workload import \
        different_keys_infinite_workload

    cc = state.add_client(CCA)
    for g in range(1, 4):
        send_check(cc, Join(g, group(g)), Ok(), timeout=20)
    for i in range(1, n_clients + 1):
        state.add_client_worker(LocalAddress(f"client{i}"),
                                different_keys_infinite_workload(10))
    stop = threading.Event()
    th = threading.Thread(target=background, args=(stop,), daemon=True)
    th.start()
    time.sleep(length_secs)
    stop.set()
    th.join(10)
    state.stop()
    r = RESULTS_OK.check(state)
    assert r.value, r.error_message()
    for w in state.client_workers().values():
        mw = w.max_wait(state.stop_time)
        assert mw is not None and mw[0] < max_wait, f"max wait {mw}"


@lab_test("4", 6, "Repeated partitioning of each group", points=20, part=2, categories=(RUN_TESTS,))
def test06_repeated_partitioning():
    """test06RepeatedPartitioning (scaled 50s -> 8s): a minority of each
    group keeps dropping out."""
    import random as _random

    state = make_state(3)
    settings = RunSettings().max_time(60)
    state.start(settings)

    def partitioner(stop):
        rng = _random.Random(3)
        while not stop.is_set():
            settings.reconnect()
            for g in range(1, 4):
                srvs = [server(g, i) for i in range(1, 4)]
                rng.shuffle(srvs)
                settings.node_active(srvs[0], False)
            if stop.wait(1.5):
                break
            settings.reconnect()
            if stop.wait(1.5):
                break
        settings.reconnect()

    _run_with_background(state, settings, partitioner, length_secs=8,
                         max_wait=2.5)


def _constant_movement(deliver_rate=None, length_secs=8):
    """test07ConstantMovement: shards keep moving between groups while
    clients run."""
    import random as _random

    state = make_state(3)
    settings = RunSettings().max_time(90)
    if deliver_rate is not None:
        settings.network_deliver_rate(deliver_rate)
    state.start(settings)
    mover_client = [None]

    def mover(stop):
        rng = _random.Random(9)
        mc = state.add_client(MOVER)
        mover_client[0] = mc
        while not stop.is_set():
            g = rng.randrange(1, 4)
            s = rng.randrange(1, NUM_SHARDS + 1)
            try:
                mc.send_command(Move(g, s))
                mc.get_result(timeout=5)
            except TimeoutError:
                pass
            if stop.wait(0.3):
                break

    _run_with_background(state, settings, mover, length_secs=length_secs)


@lab_test("4", 7, "Repeated shard movement", points=20, part=2, categories=(RUN_TESTS,))
def test07_constant_movement():
    _constant_movement()


@lab_test("4", 9, "Repeated shard movement", points=30, part=2, categories=(RUN_TESTS, UNRELIABLE_TESTS,))
def test09_constant_movement_unreliable():
    _constant_movement(deliver_rate=0.8)


# ----------------------------------------------------------- search fixtures

def make_search(num_groups, servers_per_group=1, num_shard_masters=1,
                num_shards=NUM_SHARDS):
    from dslabs_tpu.search.search_state import SearchState

    masters, gen = _make_generator(servers_per_group, num_shard_masters,
                                   num_shards)
    state = SearchState(gen)
    for m in masters:
        state.add_server(m)
    for g in range(1, num_groups + 1):
        for i in range(1, servers_per_group + 1):
            state.add_server(server(g, i))
    return state


def _joined_state(state, n_groups, servers_per_group=1,
                  num_shard_masters=1):
    """Drive the Join commands to completion through the config
    controller, narrowed to the {CCA, shard masters} partition exactly as
    the reference does (ShardStoreBaseTest.java:209-220) — the groups
    learn the config during the NEXT search phase, not here."""
    from dslabs_tpu.search.search import bfs
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.predicates import client_done
    from dslabs_tpu.testing.workload import Workload

    cmds = [Join(g, group(g, servers_per_group))
            for g in range(1, n_groups + 1)]
    state.add_client_worker(CCA, Workload(commands=cmds,
                                          results=[Ok()] * len(cmds)))

    masters = [shard_master(i) for i in range(1, num_shard_masters + 1)]
    settings = SearchSettings().max_time(420)
    settings.add_invariant(RESULTS_OK)
    settings.partition(CCA, *masters)
    # Store servers are cut off anyway; their timers only add noise.
    for a in list(state.servers):
        if "server" in str(a):
            settings.deliver_timers(a, False)
    settings.add_goal(client_done(CCA))
    results = bfs(state, settings)
    assert results.end_condition == EndCondition.GOAL_FOUND, results
    return results.goal_matching_state


@lab_test("4", 10, "Single client, single group", points=20, part=2, categories=(SEARCH_TESTS,))
def test10_single_client_single_group_search():
    """ShardStorePart1Test.test10: put/get completes and the done-pruned
    space stays clean with one single-server group."""
    from dslabs_tpu.search.search import bfs
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    state = make_search(1, 1, 1, 10)
    joined = _joined_state(state, 1)
    joined.add_client_worker(
        LocalAddress("client1"),
        kv_workload(["PUT:foo:bar", "GET:foo"], ["PutOk", "bar"]))

    settings = SearchSettings().max_time(240)
    settings.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    settings.node_active(CCA, False)
    settings.deliver_timers(CCA, False)
    # The singleton shard master is already the decided leader; its
    # election/heartbeat timers only multiply interleavings.
    settings.deliver_timers(shard_master(1), False)
    results = bfs(joined, settings)
    assert results.end_condition == EndCondition.GOAL_FOUND, results

    settings.clear_goals().add_prune(CLIENTS_DONE)
    settings.set_max_depth(joined.depth + 6)
    results = bfs(joined, settings)
    assert results.end_condition in (EndCondition.SPACE_EXHAUSTED,
                                     EndCondition.TIME_EXHAUSTED), results


@lab_test("4", 11, "Single client, multi-group", points=20, part=2, categories=(SEARCH_TESTS,))
def test11_single_client_multi_group_search():
    """ShardStorePart1Test.test11: the workload spans both groups' shards."""
    from dslabs_tpu.search.search import bfs
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    state = make_search(2, 1, 1, 10)
    joined = _joined_state(state, 2)
    joined.add_client_worker(
        LocalAddress("client1"),
        kv_workload(["PUT:key-1:v1", "PUT:key-6:v6", "GET:key-1"],
                    ["PutOk", "PutOk", "v1"]))

    # Full goal-finding over two groups is beyond the Python oracle's
    # budget (the tensor backend is the scaling path); ungated CI checks
    # bounded-depth safety of the same space, goal-finding runs under
    # DSLABS_SLOW_TESTS with a long budget.
    import os as _os

    settings = SearchSettings()
    settings.add_invariant(RESULTS_OK)
    settings.node_active(CCA, False)
    settings.deliver_timers(CCA, False)
    settings.deliver_timers(shard_master(1), False)
    if _os.environ.get("DSLABS_SLOW_TESTS"):
        settings.max_time(900).add_goal(CLIENTS_DONE)
        results = bfs(joined, settings)
        assert results.end_condition == EndCondition.GOAL_FOUND, results
    else:
        settings.max_time(120).set_max_depth(joined.depth + 6)
        results = bfs(joined, settings)
        assert results.end_condition in (EndCondition.SPACE_EXHAUSTED,
                                         EndCondition.TIME_EXHAUSTED), results


@lab_test("4", 12, "Multi-client, multi-group", points=20, part=2, categories=(SEARCH_TESTS,))
def test12_multi_client_multi_group_search():
    """ShardStorePart1Test.test12: two clients appending to keys in
    different groups; both orders linearize."""
    from dslabs_tpu.search.search import bfs
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    state = make_search(2, 1, 1, 2)
    joined = _joined_state(state, 2)
    joined.add_client_worker(LocalAddress("client1"),
                             kv_workload(["APPEND:foo-1:X1"], ["X1"]))
    joined.add_client_worker(
        LocalAddress("client2"),
        kv_workload(["APPEND:foo-2:Y2"], ["Y2"]))

    import os as _os

    settings = SearchSettings()
    settings.add_invariant(RESULTS_OK)
    settings.node_active(CCA, False)
    settings.deliver_timers(CCA, False)
    settings.deliver_timers(shard_master(1), False)
    if _os.environ.get("DSLABS_SLOW_TESTS"):
        settings.max_time(900).add_goal(CLIENTS_DONE)
        results = bfs(joined, settings)
        assert results.end_condition == EndCondition.GOAL_FOUND, results
    else:
        settings.max_time(120).set_max_depth(joined.depth + 6)
        results = bfs(joined, settings)
        assert results.end_condition in (EndCondition.SPACE_EXHAUSTED,
                                         EndCondition.TIME_EXHAUSTED), results


def _random_search(servers_per_group):
    from dslabs_tpu.search.search import dfs
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    state = make_search(2, servers_per_group, 1, 2)
    joined = _joined_state(state, 2, servers_per_group)
    joined.add_client_worker(LocalAddress("client1"),
                             kv_workload(["APPEND:foo-1:x"]))
    joined.add_client_worker(LocalAddress("client2"),
                             kv_workload(["APPEND:foo-2:y"]))

    settings = SearchSettings()
    settings.set_max_depth(1000).max_time(8)
    settings.add_invariant(RESULTS_OK)
    settings.add_prune(CLIENTS_DONE)
    results = dfs(joined, settings)
    assert not results.terminal_found()


@lab_test("4", 13, "One server per group random search", points=20, part=2, categories=(SEARCH_TESTS,))
def test13_single_server_random_search():
    _random_search(1)


@lab_test("4", 14, "Multiple servers per group random search", points=20, part=2, categories=(SEARCH_TESTS,))
def test14_multi_server_random_search():
    _random_search(2)
