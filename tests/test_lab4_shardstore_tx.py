"""Lab 4 tests, part 3 — behavioural port of ShardStorePart2Test's run
and search tests (no progress across a partition, MultiPut/MultiGet
isolation, repeated transactions under movement, the 2PC searches) and
the 2PC vote-pinning unit test.  Moved verbatim out of
``tests/test_lab4_shardstore.py`` (PR 45), whose fixtures they share, so
that ``--dist loadfile`` no longer gives one worker both halves."""

import time

from dslabs_tpu.harness import (RUN_TESTS, SEARCH_TESTS,
                                UNRELIABLE_TESTS, lab_test)
from dslabs_tpu.core.address import LocalAddress
from dslabs_tpu.labs.shardedstore.shardmaster import (Join, Leave, Move, Ok,
                                                      ShardConfig)
from dslabs_tpu.labs.shardedstore.shardstore import (ShardStoreServer,
                                                     key_to_shard)
from dslabs_tpu.labs.shardedstore.txkvstore import (MultiGet, MultiGetResult,
                                                    MultiPut, MultiPutOk,
                                                    Swap, SwapOk,
                                                    KEY_NOT_FOUND)
from dslabs_tpu.runner.run_settings import RunSettings
from dslabs_tpu.testing.predicates import CLIENTS_DONE, RESULTS_OK
from tests.test_lab4_shardstore import (CCA, MOVER, NUM_SHARDS,
                                        _joined_state, group, make_search,
                                        make_state, send_check, server,
                                        shard_master)

# ------------------------------------------- additional reference ports (p3)

@lab_test("4", 3, "No progress when groups can't communicate", points=10, part=3, categories=(RUN_TESTS,))
def test03_no_progress():
    """ShardStorePart2Test.test03NoProgress: with the groups partitioned
    from each other (client still sees both), single-group transactions
    commit but a cross-group 2PC transaction must block."""
    state = make_state(2, num_shards=2)
    settings = RunSettings().max_time(30)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())
    send_check(cc, Join(2, group(2)), Ok())
    c = state.add_client(LocalAddress("client1"))
    send_check(c, MultiPut({"key1-1": "foo1", "key1-2": "foo2"}),
               MultiPutOk(), timeout=15)
    time.sleep(1)

    g1 = [server(1, i) for i in range(1, 4)]
    g2 = [server(2, i) for i in range(1, 4)]
    # Groups in separate partitions; the client keeps links to every server.
    settings.partition(*g1)
    for s in g2:
        for s2 in g2:
            settings.link_active(s, s2, True)
    for s in g1 + g2:
        settings.link_active(LocalAddress("client1"), s, True)
        settings.link_active(s, LocalAddress("client1"), True)

    send_check(c, MultiPut({"key2-1": "foo1", "key3-1": "foo2"}),
               MultiPutOk(), timeout=15)
    send_check(c, MultiPut({"key2-2": "foo1", "key3-2": "foo2"}),
               MultiPutOk(), timeout=15)

    c.send_command(MultiPut({"key4-1": "foo1", "key4-2": "foo2"}))
    time.sleep(4)
    assert not c.has_result(), "cross-group 2PC committed without comms"
    state.stop()


def _multi_gets_match(state):
    for w in state.client_workers().values():
        for r in w.results:
            if isinstance(r, MultiGetResult):
                vals = set(r.as_dict().values())
                if len(vals) > 1:
                    return False
    return True


@lab_test("4", 4, "Isolation between MultiPuts and MultiGets", points=10, part=3, categories=(RUN_TESTS,))
def test04_put_get_isolation():
    """ShardStorePart2Test.test04 (scaled 100 -> 25 rounds): a MultiGet
    concurrent with atomic MultiPuts over the same two cross-group keys
    must never observe a torn write."""
    from dslabs_tpu.testing.predicates import StatePredicate
    from dslabs_tpu.testing.workload import Workload

    n_rounds = 25
    state = make_state(2, num_shards=2)
    settings = RunSettings().max_time(90)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok())
    send_check(cc, Join(2, group(2)), Ok())

    put_cmds = [MultiPut({f"key{i}-1": f"foo{i}", f"key{i}-2": f"foo{i}"})
                for i in range(n_rounds)]
    get_cmds = [MultiGet({f"key{i}-1", f"key{i}-2"}) for i in range(n_rounds)]
    state.add_client_worker(LocalAddress("client1"),
                            Workload(commands=put_cmds,
                                     results=[MultiPutOk()] * n_rounds))
    state.add_client_worker(LocalAddress("client2"),
                            Workload(commands=get_cmds))
    state.wait_for()
    state.stop()
    assert _multi_gets_match(state), "torn MultiGet observed"
    r = RESULTS_OK.check(state)
    assert r.value, r.error_message()


def _repeated_puts_gets(deliver_rate=None, with_movement=False,
                        n_rounds=12):
    """test05/06/07 (scaled): repeated cross-group MultiPut/MultiGet with
    matching expectations; optionally unreliable and/or under movement."""
    import random as _random
    import threading

    from dslabs_tpu.testing.workload import Workload

    state = make_state(2, num_shards=2)
    # Generous budget: wait_for returns as soon as the workers finish
    # (seconds when healthy); the margin only matters when the host is
    # heavily loaded and the real-time emulation is starved for cycles.
    settings = RunSettings().max_time(300)
    if deliver_rate is not None:
        settings.network_deliver_rate(deliver_rate)
    state.start(settings)
    cc = state.add_client(CCA)
    send_check(cc, Join(1, group(1)), Ok(), timeout=20)
    send_check(cc, Join(2, group(2)), Ok(), timeout=20)

    put_cmds, put_res, get_cmds, get_res = [], [], [], []
    for i in range(n_rounds):
        put_cmds.append(MultiPut({f"key{i}-1": f"v{i}", f"key{i}-2": f"v{i}"}))
        put_res.append(MultiPutOk())
    state.add_client_worker(LocalAddress("client1"),
                            Workload(commands=put_cmds, results=put_res))

    stop = threading.Event()
    th = None
    if with_movement:
        def mover():
            rng = _random.Random(13)
            mc = state.add_client(MOVER)
            while not stop.is_set():
                try:
                    mc.send_command(Move(rng.randrange(1, 3),
                                         rng.randrange(1, 3)))
                    mc.get_result(timeout=5)
                except TimeoutError:
                    pass
                if stop.wait(0.4):
                    break

        th = threading.Thread(target=mover, daemon=True)
        th.start()

    state.wait_for()
    # Now read everything back atomically.
    for i in range(n_rounds):
        get_cmds.append(MultiGet({f"key{i}-1", f"key{i}-2"}))
        get_res.append(MultiGetResult({f"key{i}-1": f"v{i}",
                                       f"key{i}-2": f"v{i}"}))
    state.add_client_worker(LocalAddress("client2"),
                            Workload(commands=get_cmds, results=get_res))
    state.wait_for()
    stop.set()
    if th is not None:
        th.join(8)
    state.stop()
    r = RESULTS_OK.check(state)
    assert r.value, r.error_message()
    assert _multi_gets_match(state)


@lab_test("4", 5, "Repeated MultiPuts and MultiGets, different keys", points=20, part=3, categories=(RUN_TESTS,))
def test05_repeated_puts_gets():
    _repeated_puts_gets()


@lab_test("4", 6, "Repeated MultiPuts and MultiGets, different keys", points=20, part=3, categories=(RUN_TESTS, UNRELIABLE_TESTS,))
def test06_repeated_puts_gets_unreliable():
    _repeated_puts_gets(deliver_rate=0.8, n_rounds=8)


@lab_test("4", 7, "Repeated MultiPuts and MultiGets; constant movement", points=20, part=3, categories=(RUN_TESTS, UNRELIABLE_TESTS,))
def test07_constant_movement_tx():
    _repeated_puts_gets(deliver_rate=0.8, with_movement=True, n_rounds=8)


@lab_test("4", 8, "Single client, single group; MultiPut, MultiGet", points=20, part=3, categories=(SEARCH_TESTS,))
def test08_single_client_single_group_tx_search():
    """ShardStorePart2Test.test08: transactional workload search in one
    single-server group."""
    from dslabs_tpu.search.search import bfs
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.workload import Workload

    state = make_search(1, 1, 1, 2)
    joined = _joined_state(state, 1)
    joined.add_client_worker(
        LocalAddress("client1"),
        Workload(commands=[MultiPut({"key-1": "x", "key-2": "y"}),
                           MultiGet({"key-1", "key-2"})],
                 results=[MultiPutOk(),
                          MultiGetResult({"key-1": "x", "key-2": "y"})]))

    settings = SearchSettings().max_time(240)
    settings.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    settings.node_active(CCA, False)
    settings.deliver_timers(CCA, False)
    settings.deliver_timers(shard_master(1), False)
    results = bfs(joined, settings)
    assert results.end_condition == EndCondition.GOAL_FOUND, results

    settings.clear_goals().add_prune(CLIENTS_DONE)
    settings.set_max_depth(joined.depth + 6)
    results = bfs(joined, settings)
    assert results.end_condition in (EndCondition.SPACE_EXHAUSTED,
                                     EndCondition.TIME_EXHAUSTED), results


@lab_test("4", 9, "Single client, multi-group; MultiPut, MultiGet", points=20, part=3, categories=(SEARCH_TESTS,))
def test09_single_client_multi_group_tx_search():
    """ShardStorePart2Test.test09: the transaction spans both groups
    (cross-group 2PC searched to completion)."""
    from dslabs_tpu.search.search import bfs
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.workload import Workload

    state = make_search(2, 1, 1, 2)
    joined = _joined_state(state, 2)
    joined.add_client_worker(
        LocalAddress("client1"),
        Workload(commands=[MultiPut({"key-1": "x", "key-2": "y"})],
                 results=[MultiPutOk()]))

    settings = SearchSettings().max_time(300)
    settings.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    settings.node_active(CCA, False)
    settings.deliver_timers(CCA, False)
    settings.deliver_timers(shard_master(1), False)
    results = bfs(joined, settings)
    assert results.end_condition == EndCondition.GOAL_FOUND, results


@lab_test("4", 10, "Multi-client, multi-group; MultiPut, Swap, MultiGet", points=20, part=3, categories=(SEARCH_TESTS,))
def test10_multi_client_multi_group_tx_search():
    """ShardStorePart2Test.java:255 test10MultiClientMultiGroupSearch:
    client1 runs MultiPut{foo-1: X, foo-2: Y} then Swap(foo-1, foo-2)
    across both groups while client2's MultiGet must observe the swapped
    pair atomically ({foo-1: Y, foo-2: X} under the expected-results
    serialization)."""
    from dslabs_tpu.search.search import bfs
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.workload import Workload

    import os as _os

    state = make_search(2, 1, 1, 2)
    joined = _joined_state(state, 2)
    joined.add_client_worker(
        LocalAddress("client1"),
        Workload(commands=[MultiPut({"foo-1": "X", "foo-2": "Y"}),
                           Swap("foo-1", "foo-2")],
                 results=[MultiPutOk(), SwapOk()]))
    joined.add_client_worker(
        LocalAddress("client2"),
        Workload(commands=[MultiGet({"foo-1", "foo-2"})],
                 results=[MultiGetResult({"foo-1": "Y", "foo-2": "X"})]))

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
        # Bounded-depth safety of the same space on the fast path (the
        # goal lies beyond the Python oracle's ungated budget, exactly
        # like test11/test12 of Part 1).
        settings.max_time(120).set_max_depth(joined.depth + 5)
        results = bfs(joined, settings)
        assert results.end_condition in (EndCondition.SPACE_EXHAUSTED,
                                         EndCondition.TIME_EXHAUSTED), results


def _tx_random_search(servers_per_group, max_secs=20):
    """ShardStorePart2Test.java:275-334 randomSearch: the Join, Join,
    Leave(1) reconfiguration happens DURING the search (no staged join),
    transactional clients race it, and the MultiGet-atomicity invariant
    pins that client2 sees either both puts or neither — a torn
    {X, KEY_NOT_FOUND} read is the classic non-atomic-commit bug."""
    from dslabs_tpu.search.search import dfs
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.predicates import StatePredicate
    from dslabs_tpu.testing.workload import Workload

    state = make_search(2, servers_per_group, 1, 2)
    cmds = [Join(1, group(1, servers_per_group)),
            Join(2, group(2, servers_per_group)),
            Leave(1)]
    state.add_client_worker(CCA, Workload(commands=cmds,
                                          results=[Ok()] * len(cmds)))
    state.add_client_worker(
        LocalAddress("client1"),
        Workload(commands=[MultiPut({"foo-1": "X", "foo-2": "Y"})],
                 results=[MultiPutOk()]))
    state.add_client_worker(
        LocalAddress("client2"),
        Workload(commands=[MultiGet({"foo-1", "foo-2"})]))

    ok_full = MultiGetResult({"foo-1": "X", "foo-2": "Y"})
    ok_none = MultiGetResult({"foo-1": KEY_NOT_FOUND,
                              "foo-2": KEY_NOT_FOUND})

    def multi_get_atomic(s):
        results = s.client_workers()[LocalAddress("client2")].results
        if not results:
            return True
        if len(results) > 1:
            return False, "client2 received multiple MultiGetResults"
        r = results[0]
        if r != ok_full and r != ok_none:
            return False, (f"{r} matches neither {ok_none} nor "
                           f"{ok_full}")
        return True

    settings = SearchSettings()
    settings.set_max_depth(1000).max_time(max_secs)
    settings.add_invariant(StatePredicate(
        "MultiGet returns correct results", multi_get_atomic))
    settings.add_invariant(RESULTS_OK)
    settings.add_prune(CLIENTS_DONE)
    results = dfs(state, settings)
    assert not results.terminal_found(), results


@lab_test("4", 12, "Multiple servers per group random search", points=20, part=3, categories=(SEARCH_TESTS,))
def test12_multi_server_tx_random_search():
    """ShardStorePart2Test.java:346 test12MultiServerRandomSearch: the
    randomSearch shape with REAL 3-server Paxos groups."""
    _tx_random_search(3)


@lab_test("4", 11, "One server per group random search", points=20, part=3, categories=(SEARCH_TESTS,))
def test11_tx_random_search():
    """ShardStorePart2Test.test11: random probes over transactional
    workloads (MultiPut, Swap, MultiGet)."""
    from dslabs_tpu.search.search import dfs
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.workload import Workload

    state = make_search(2, 1, 1, 2)
    joined = _joined_state(state, 2)
    joined.add_client_worker(
        LocalAddress("client1"),
        Workload(commands=[MultiPut({"key-1": "x", "key-2": "y"}),
                           Swap("key-1", "key-2")]))
    joined.add_client_worker(
        LocalAddress("client2"),
        Workload(commands=[MultiGet({"key-1", "key-2"})]))

    settings = SearchSettings()
    settings.set_max_depth(1000).max_time(8)
    settings.add_invariant(RESULTS_OK)
    settings.add_prune(CLIENTS_DONE)
    results = dfs(joined, settings)
    assert not results.terminal_found()


# ------------------------------------------------- unit: 2PC vote pinning

@lab_test("4", 38, "coordinator ignores same-round votes after decision",
          part=3, categories=(RUN_TESTS,))
def test_yes_then_abort_same_round_duplicate():
    """Pins the `entry[2] is not None` guard in _apply_tx_vote: a
    participant that voted YES for round r can later vote ABORT for the
    SAME round (duplicate TxPrepare delivered after it installed a newer
    config — the config-mismatch abort in _apply_tx_prepare).  Once the
    coordinator fixed the round's decision, the late vote must be
    ignored, or a committed transaction would flip to aborted after the
    client already got its reply (round-2 advisor finding)."""
    from dslabs_tpu.core.node import NodeConfig
    from dslabs_tpu.labs.clientserver.amo import AMOCommand
    from dslabs_tpu.labs.shardedstore.shardstore import TxVote

    node = ShardStoreServer(server(1, 1), (shard_master(1),), NUM_SHARDS,
                            tuple(group(1)), 1)
    sent = []
    node.config(NodeConfig(
        message_adder=lambda frm, to, m: sent.append((to, m)),
        timer_adder=lambda frm, t, mn, mx: None,
    ))
    node.init()
    # Two groups, each owning one of the tx's shards.
    node.current_config = ShardConfig(1, {
        1: (group(1), frozenset({key_to_shard("key-1", NUM_SHARDS)})),
        2: (group(2), frozenset({key_to_shard("key-2", NUM_SHARDS)})),
    })
    client = LocalAddress("client1")
    tx = AMOCommand(MultiPut({"key-1": "x", "key-2": "y"}), client, 1)
    tx_id = (client, 1)
    node.tx_round[tx_id] = 1
    node.coord[tx_id] = [tx, {}, None, (), frozenset(), 1]

    node._apply_tx_vote(TxVote(tx_id, 1, 1, True, (("key-1", "a"),)))
    assert node.coord[tx_id][2] is None  # one vote: undecided
    node._apply_tx_vote(TxVote(tx_id, 1, 2, True, (("key-2", "b"),)))
    entry = node.coord[tx_id]
    assert entry[2] is True              # all yes: committed
    writes = entry[3]
    assert dict(writes) == {"key-1": "x", "key-2": "y"}

    # The duplicate-delivery interleaving: group 2 re-votes ABORT for the
    # SAME round.  Must be a no-op.
    node._apply_tx_vote(TxVote(tx_id, 1, 2, False, ()))
    assert node.coord[tx_id][2] is True
    assert node.coord[tx_id][3] == writes

    # Contrast (documents current semantics): BEFORE the decision, a
    # same-round re-vote does overwrite — an abort then wins.
    tx2 = AMOCommand(MultiPut({"key-1": "x2", "key-2": "y2"}), client, 2)
    tx2_id = (client, 2)
    node.tx_round[tx2_id] = 1
    node.coord[tx2_id] = [tx2, {}, None, (), frozenset(), 1]
    node._apply_tx_vote(TxVote(tx2_id, 1, 2, True, (("key-2", "b"),)))
    node._apply_tx_vote(TxVote(tx2_id, 1, 2, False, ()))
    node._apply_tx_vote(TxVote(tx2_id, 1, 1, True, (("key-1", "a"),)))
    assert node.coord[tx2_id][2] is False
