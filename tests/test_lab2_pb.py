"""Lab 2 part 2 tests — behavioural port of PrimaryBackupTest.java:75-905
(run tests: basic ops, backup takeover, failover reads, at-most-once under
loss, all-servers-dead liveness; search tests: single-client BFS with
RESULTS_OK, linearizable appends)."""

import time

import pytest

from dslabs_tpu.harness import (RUN_TESTS, SEARCH_TESTS, UNRELIABLE_TESTS,
                                lab_test)
from dslabs_tpu.core.address import LocalAddress
from dslabs_tpu.labs.clientserver.kv_workload import (
    APPENDS_LINEARIZABLE, append_different_key_workload,
    append_same_key_workload, kv_workload, put_get_workload, simple_workload)
from dslabs_tpu.labs.clientserver.kvstore import KVStore
from dslabs_tpu.labs.primarybackup.pb import PBClient, PBServer
from dslabs_tpu.labs.primarybackup.pb import PING_MILLIS
from dslabs_tpu.labs.primarybackup.viewserver import (PING_CHECK_MILLIS,
                                                      ViewServer)
from dslabs_tpu.labs.clientserver.kv_workload import (
    different_keys_infinite_workload, get, put, get_result, put_ok)
from dslabs_tpu.runner.run_settings import RunSettings
from dslabs_tpu.runner.run_state import RunState
from dslabs_tpu.search.results import EndCondition
from dslabs_tpu.search.search import bfs, dfs
from dslabs_tpu.search.search_state import SearchState
from dslabs_tpu.search.settings import SearchSettings
from dslabs_tpu.testing.generator import NodeGenerator
from dslabs_tpu.testing.predicates import ALL_RESULTS_SAME, CLIENTS_DONE, RESULTS_OK

VSA = LocalAddress("viewserver")


def server(i):
    return LocalAddress(f"server{i}")


def client(i):
    return LocalAddress(f"client{i}")


def generator(workload_factory=put_get_workload):
    def server_supplier(a):
        if a == VSA:
            return ViewServer(a)
        return PBServer(a, VSA, KVStore())

    return NodeGenerator(
        server_supplier=server_supplier,
        client_supplier=lambda a: PBClient(a, VSA),
        workload_supplier=lambda a: workload_factory())


def make_run_state(workload_factory=put_get_workload):
    state = RunState(generator(workload_factory))
    state.add_server(VSA)
    return state


def assert_ok(state):
    r = RESULTS_OK.check(state)
    assert r.value, r.error_message()


def settle(state, settings, secs):
    """Run the live system for a bit so views form / heal."""
    state.start(settings)
    time.sleep(secs)
    state.stop()


# ------------------------------------------------------------------ run tests

@lab_test("2", 2, "Single client, single server, simple operations", points=5, part=2, categories=(RUN_TESTS,))
def test02_basic():
    state = make_run_state(simple_workload)
    state.add_server(server(1))
    state.add_client_worker(client(1))
    state.run(RunSettings().max_time(10))
    assert_ok(state)


@lab_test("2", 4, "Backup is chosen", points=5, part=2, categories=(RUN_TESTS,))
def test04_backup_chosen_and_replicates():
    state = make_run_state(simple_workload)
    settings = RunSettings().max_time(15)
    state.add_server(server(1))
    state.add_server(server(2))
    settle(state, settings, PING_CHECK_MILLIS * 6 / 1000)
    state.add_client_worker(client(1))
    state.run(settings)
    assert_ok(state)


@lab_test("2", 6, "Backup takes over", points=10, part=2, categories=(RUN_TESTS,))
def test06_backup_takes_over():
    state = make_run_state()
    settings = RunSettings().max_time(15)
    state.add_server(server(1))
    c = state.add_client(client(1))
    state.start(settings)

    c.send_command(put("foo1", "bar1"))
    assert c.get_result(timeout=5) == put_ok()

    state.add_server(server(2))
    # Wait for the backup view to form and sync.
    time.sleep(PING_CHECK_MILLIS * 8 / 1000)

    c.send_command(put("foo2", "bar2"))
    assert c.get_result(timeout=5) == put_ok()

    state.remove_node(server(1))
    c.send_command(get("foo1"))
    assert c.get_result(timeout=5) == get_result("bar1")
    c.send_command(get("foo2"))
    assert c.get_result(timeout=5) == get_result("bar2")
    state.stop()


@lab_test("2", 7, "Kill all servers", points=10, part=2, categories=(RUN_TESTS,))
def test07_kill_all_servers():
    state = make_run_state()
    settings = RunSettings().max_time(15)
    state.add_server(server(1))
    state.add_server(server(2))
    c = state.add_client(client(1))
    state.start(settings)

    c.send_command(put("foo", "bar"))
    assert c.get_result(timeout=5) == put_ok()

    # Kill every server holding state; a fresh server must NOT serve.
    state.stop()
    state.remove_node(server(1))
    state.remove_node(server(2))
    state.add_server(server(3))
    state.start(settings)

    c.send_command(get("foo"))
    time.sleep(PING_CHECK_MILLIS * 4 / 1000)
    assert not c.has_result()
    state.stop()


@lab_test("2", 8, "At-most-once append", points=15, part=2, categories=(RUN_TESTS, UNRELIABLE_TESTS,))
def test08_at_most_once_unreliable():
    state = make_run_state(lambda: append_different_key_workload(10))
    settings = RunSettings().max_time(30)
    state.add_server(server(1))
    state.add_server(server(2))
    settle(state, settings, PING_CHECK_MILLIS * 6 / 1000)
    state.add_client_worker(client(1))
    settings.network_deliver_rate(0.8).node_unreliable(VSA, False)
    state.run(settings)
    assert_ok(state)


@lab_test("2", 11, "Concurrent appends, same key, fail to backup", points=15, part=2, categories=(RUN_TESTS,))
def test11_concurrent_appends_linearizable_failover():
    state = make_run_state(lambda: append_same_key_workload(5))
    settings = RunSettings().max_time(30)
    state.add_server(server(1))
    state.add_server(server(2))
    settle(state, settings, PING_CHECK_MILLIS * 6 / 1000)
    for i in range(1, 4):
        state.add_client_worker(client(i))
    state.run(settings)
    r = APPENDS_LINEARIZABLE.check(state)
    assert r.value, r.error_message()

    for a in list(state.client_workers()):
        state.remove_node(a)
    # Heal, then read from the primary and (after failover) the old backup.
    settle(state, settings, PING_CHECK_MILLIS * 6 / 1000)

    read = kv_workload(["GET:the-key"])
    state.add_client_worker(LocalAddress("client-readprimary"), read)
    state.run(settings)

    state.remove_node(server(1))
    settle(state, settings, PING_CHECK_MILLIS * 6 / 1000)
    state.add_client_worker(LocalAddress("client-readbackup"), read)
    settings.add_invariant(ALL_RESULTS_SAME)
    state.run(settings)
    r = ALL_RESULTS_SAME.check(state)
    assert r.value, r.error_message()


# --------------------------------------------------------------- search tests

def make_search_state(workload):
    state = SearchState(generator(lambda: workload))
    state.add_server(VSA)
    return state


@lab_test("2", 16, "Single client, single server", points=15, part=2, categories=(SEARCH_TESTS,))
def test16_single_client_search():
    workload = kv_workload(["PUT:foo:bar", "GET:foo"], ["PutOk", "bar"])
    state = make_search_state(workload)
    state.add_server(server(1))
    state.add_client_worker(client(1))

    settings = (SearchSettings().add_invariant(RESULTS_OK)
                .add_goal(CLIENTS_DONE))
    settings.max_time(60)
    results = bfs(state, settings)
    assert results.end_condition == EndCondition.GOAL_FOUND, results

    # The done-pruned subspace never violates RESULTS_OK.  (The state
    # is rebuilt with the SAME topology — an earlier port slip searched
    # a ViewServer-only state here, which exhausts vacuously.)
    settings2 = (SearchSettings().add_invariant(RESULTS_OK)
                 .add_prune(CLIENTS_DONE))
    settings2.max_time(60).set_max_depth(22)
    state2 = make_search_state(workload)
    state2.add_server(server(1))
    state2.add_client_worker(client(1))
    results2 = bfs(state2, settings2)
    assert results2.end_condition in (EndCondition.SPACE_EXHAUSTED,
                                      EndCondition.TIME_EXHAUSTED), results2


@lab_test("2", 18, "Multi-client, multi-server; writes visible", points=20, part=2, categories=(SEARCH_TESTS,))
def test18_two_client_appends_linearizable_search():
    """Staged search in the reference's initView style
    (PrimaryBackupTest.java:124-187): first reach the synced two-server view
    with the clients gated off, then search client completion with the ping
    machinery frozen (settings gate events, never mutate states — SURVEY
    §7.7)."""
    from dslabs_tpu.testing.predicates import StatePredicate

    workload = append_same_key_workload(1)
    state = make_search_state(workload)
    state.add_server(server(1))
    state.add_server(server(2))
    state.add_client_worker(client(1))
    state.add_client_worker(client(2))

    def view2_synced(s):
        s1, s2 = s.node(server(1)), s.node(server(2))
        return (s1.view is not None and s1.view.view_num == 2
                and s1.view.primary == server(1) and s1.view.backup == server(2)
                and s1.synced and s2.view is not None
                and s2.view.view_num == 2 and s2.synced)

    stage1 = (SearchSettings()
              .add_goal(StatePredicate("view 2 formed and synced", view2_synced,
                                       tkey=("PB_VIEW_SYNCED", 2,
                                             "server1", "server2"))))
    stage1.max_time(60)
    stage1.sender_active(client(1), False).sender_active(client(2), False)
    stage1.deliver_timers(client(1), False).deliver_timers(client(2), False)
    results = bfs(state, stage1)
    assert results.end_condition == EndCondition.GOAL_FOUND, results
    synced_state = results.goal_matching_state

    stage2 = (SearchSettings().add_invariant(APPENDS_LINEARIZABLE)
              .add_goal(CLIENTS_DONE))
    stage2.max_time(120)
    stage2.deliver_timers(VSA, False)
    stage2.deliver_timers(server(1), False).deliver_timers(server(2), False)
    results = bfs(synced_state, stage2)
    assert results.end_condition == EndCondition.GOAL_FOUND, results


# ------------------------------------------------ additional reference ports

def current_view(state):
    return state.servers[VSA].view


def wait_for_view(state, primary, backup, ticks=8):
    """waitForView (PrimaryBackupTest.java:233-247): poll until the
    expected (primary, backup) view is active."""
    for _ in range(ticks):
        v = current_view(state)
        if v.primary == primary and v.backup == backup:
            return v
        time.sleep(PING_CHECK_MILLIS / 1000)
    v = current_view(state)
    assert v.primary == primary and v.backup == backup, \
        f"expected ({primary},{backup}), got {v}"
    return v


def wait_for_synced_pair(state, a, b, ticks):
    """Poll until the view holds BOTH servers, in either role, and its
    primary has acknowledged it — it pings a view's number only once its
    backup holds its state.  A loaded host can cost either server its
    place for a while; the pair comes back as the servers ping."""
    def reached():
        v = current_view(state)
        return {v.primary, v.backup} == {a, b} and state.servers[VSA].acked

    for _ in range(ticks):
        if reached():
            break
        time.sleep(PING_CHECK_MILLIS / 1000)
    assert reached(), (f"expected {a} and {b} in one acknowledged view, "
                       f"got {current_view(state)}, acked "
                       f"{state.servers[VSA].acked}")
    return current_view(state)


def setup_run_view(state, settings, primary, backup):
    """setupRunView (PrimaryBackupTest.java:249-264)."""
    state.start(settings)
    state.add_server(primary)
    wait_for_view(state, primary, None)
    if backup is not None:
        state.add_server(backup)
        wait_for_view(state, primary, backup)
        time.sleep(PING_CHECK_MILLIS * 4 / 1000)  # let the backup sync
    state.stop()


@lab_test("2", 1, "Client throws InterruptedException", points=5, part=2, categories=(RUN_TESTS,))
def test01_throws_exception():
    state = make_run_state()
    c = state.add_client(client(1))
    c.send_command(get("foo"))
    with pytest.raises(TimeoutError):
        c.get_result(timeout=0.5)


@lab_test("2", 3, "Primary chosen", points=5, part=2, categories=(RUN_TESTS,))
def test03_primary_chosen():
    state = make_run_state()
    settings = RunSettings().max_time(10)
    setup_run_view(state, settings, server(1), None)


@lab_test("2", 5, "Count number of ViewServer requests", points=10, part=2, categories=(RUN_TESTS,))
def test05_max_viewserver_pings_count():
    """test05MaxViewServerPingsCount (scaled 500 -> 60 rounds): servers may
    not spam the ViewServer beyond the ping-interval budget."""
    state = make_run_state()
    settings = RunSettings().max_time(60)
    state.add_server(server(1))
    state.add_server(server(2))
    c = state.add_client(client(1))
    state.start(settings)

    t1 = time.time()
    for i in range(60):
        c.send_command(put(f"xk{i}", str(i)))
        assert c.get_result(timeout=5) == put_ok()
        c.send_command(get(f"xk{i}"))
        assert c.get_result(timeout=5) == get_result(str(i))
        time.sleep(PING_MILLIS / 10 / 1000)
    elapsed_ms = (time.time() - t1) * 1000
    state.stop()

    received = state.network.num_messages_received(VSA)
    # numNodes x 2 pings per PING_MILLIS (PrimaryBackupTest.java:341)
    allowed = elapsed_ms / PING_MILLIS * (len(state.servers)
                                          + len(state.clients)) * 2
    assert received <= allowed, \
        f"Too many ViewServer messages: {received} (allowed {allowed:.0f})"


@lab_test("2", 9, "Fail to new backup", points=10, part=2, categories=(RUN_TESTS,))
def test09_fail_put():
    """test09FailPut: acknowledged writes survive a backup death, a
    promotion to a fresh backup, and then a primary death."""
    state = make_run_state()
    settings = RunSettings().max_time(30)
    setup_run_view(state, settings, server(1), server(2))
    state.add_server(server(3))
    c = state.add_client(client(1))
    state.start(settings)

    for k, v in (("a", "aa"), ("b", "bb"), ("c", "cc")):
        c.send_command(put(k, v))
        assert c.get_result(timeout=5) == put_ok()
        c.send_command(get(k))
        assert c.get_result(timeout=5) == get_result(v)

    state.remove_node(server(2))
    c.send_command(put("a", "aaa"))
    assert c.get_result(timeout=5) == put_ok()
    c.send_command(get("a"))
    assert c.get_result(timeout=5) == get_result("aaa")
    wait_for_view(state, server(1), server(3))
    time.sleep(PING_CHECK_MILLIS * 4 / 1000)
    c.send_command(get("a"))
    assert c.get_result(timeout=5) == get_result("aaa")

    state.remove_node(server(1))
    c.send_command(put("b", "bbb"))
    assert c.get_result(timeout=10) == put_ok()
    wait_for_view(state, server(3), None)
    for k, v in (("a", "aaa"), ("b", "bbb"), ("c", "cc")):
        c.send_command(get(k))
        assert c.get_result(timeout=5) == get_result(v)
    state.stop()


def _concurrent_fail_to_backup(workload_factory, read_cmds, deliver_rate=None):
    """Shared body of test10/test11 (PrimaryBackupTest.java:455-563): run
    concurrent writers, heal, read from the primary, kill it, read from the
    promoted backup — both reads must agree (ALL_RESULTS_SAME)."""
    state = make_run_state(workload_factory)
    settings = RunSettings().max_time(60)
    if deliver_rate is not None:
        settings.network_deliver_rate(deliver_rate)
    setup_run_view(state, settings, server(1), server(2))
    for i in range(1, 4):
        state.add_client_worker(client(i))
    state.run(settings)

    for a in list(state.client_workers()):
        state.remove_node(a)

    # Heal fully — wait, within the test's own time limit, until both
    # servers share a synced view again — then read the keys from the
    # primary.
    limit = int(settings.max_time_secs * 1000 / PING_CHECK_MILLIS)
    settings.reset_network()
    state.start(settings)
    wait_for_synced_pair(state, server(1), server(2), limit)
    state.stop()

    state.add_client_worker(LocalAddress("client-readprimary"),
                            kv_workload(read_cmds))
    state.run(settings)

    # Kill the primary of a synced pair (the read was a loaded stretch
    # too); its backup takes over.
    state.start(settings)
    view = wait_for_synced_pair(state, server(1), server(2), limit)
    state.remove_node(view.primary)
    wait_for_view(state, view.backup, None, ticks=limit)
    state.stop()

    state.add_client_worker(LocalAddress("client-readbackup"),
                            kv_workload(read_cmds))
    state.run(settings)
    r = ALL_RESULTS_SAME.check(state)
    assert r.value, r.error_message()


@lab_test("2", 10, "Concurrent puts, same keys, fail to backup", points=15, part=2, categories=(RUN_TESTS,))
def test10_concurrent_put():
    import random as _random

    rng = _random.Random(7)

    def puts():
        return kv_workload([f"PUT:k{rng.randrange(2)}:{rng.randrange(1000)}"
                            for _ in range(30)])

    _concurrent_fail_to_backup(puts, ["GET:k0", "GET:k1"])


@lab_test("2", 21, "Concurrent appends failover read-back (extended)", points=0, part=2, categories=(RUN_TESTS,))
def test11b_concurrent_append_fail_to_backup():
    _concurrent_fail_to_backup(lambda: append_same_key_workload(20),
                               ["GET:the-key"])


@lab_test("2", 12, "Concurrent puts, same keys, fail to backup", points=20, part=2, categories=(RUN_TESTS, UNRELIABLE_TESTS,))
def test12_concurrent_put_unreliable():
    import random as _random

    rng = _random.Random(11)

    def puts():
        return kv_workload([f"PUT:k{rng.randrange(2)}:{rng.randrange(1000)}"
                            for _ in range(15)])

    _concurrent_fail_to_backup(puts, ["GET:k0", "GET:k1"], deliver_rate=0.8)


@lab_test("2", 13, "Concurrent appends, same key, fail to backup", points=20, part=2, categories=(RUN_TESTS, UNRELIABLE_TESTS,))
def test13_concurrent_append_unreliable():
    _concurrent_fail_to_backup(lambda: append_same_key_workload(10),
                               ["GET:the-key"], deliver_rate=0.8)


def _repeated_crashes(deliver_rate=None, length_secs=10):
    """test14/test15 (PrimaryBackupTest.java:565-635, scaled 30s -> 10s):
    randomly crash a server and add a fresh one while infinite-workload
    clients keep running."""
    import random as _random
    import threading

    state = make_run_state(lambda: different_keys_infinite_workload(10))
    settings = RunSettings().max_time(length_secs + 30)
    if deliver_rate is not None:
        settings.network_deliver_rate(deliver_rate)
        settings.node_unreliable(VSA, False)
    servers = [server(i) for i in range(1, 4)]
    for a in servers:
        state.add_server(a)
    state.start(settings)
    stop = threading.Event()
    total = [3]

    def crasher():
        rng = _random.Random(5)
        stop.wait(PING_CHECK_MILLIS * 10 / 1000)
        while not stop.is_set():
            to_kill = servers[rng.randrange(len(servers))]
            total[0] += 1
            to_add = server(total[0])
            servers.append(to_add)
            state.add_server(to_add)
            servers.remove(to_kill)
            state.remove_node(to_kill)
            if stop.wait(PING_CHECK_MILLIS * 10 / 1000):
                return

    th = threading.Thread(target=crasher, daemon=True)
    th.start()
    for i in range(1, 4):
        state.add_client_worker(client(i))
    time.sleep(length_secs)
    stop.set()
    th.join(5)
    state.stop()
    assert_ok(state)
    for w in state.client_workers().values():
        mw = w.max_wait(state.stop_time)
        assert mw is not None and mw[0] < 5.0, f"max wait {mw}"


@lab_test("2", 14, "Repeated crashes", points=15, part=2, categories=(RUN_TESTS,))
def test14_repeated_crashes():
    _repeated_crashes()


@lab_test("2", 15, "Repeated crashes", points=20, part=2, categories=(RUN_TESTS, UNRELIABLE_TESTS,))
def test15_repeated_crashes_unreliable():
    _repeated_crashes(deliver_rate=0.8)


@lab_test("2", 17, "Single client, multi-server", points=15, part=2, categories=(SEARCH_TESTS,))
def test17_single_client_multi_server_search():
    """test17SingleClientMultiServerSearch: from the synced two-server
    view, the client can finish, and the done-pruned subspace stays clean
    (third server gated off, as the reference does)."""
    workload = kv_workload(["PUT:foo:bar", "GET:foo"], ["PutOk", "bar"])
    state = make_search_state(workload)
    for i in (1, 2, 3):
        state.add_server(server(i))
    state.add_client_worker(client(1))

    def view2_synced(s):
        s1, s2 = s.node(server(1)), s.node(server(2))
        return (s1.view is not None and s1.view.view_num == 2
                and s1.view.primary == server(1)
                and s1.view.backup == server(2)
                and s1.synced and s2.view is not None
                and s2.view.view_num == 2 and s2.synced)

    from dslabs_tpu.testing.predicates import StatePredicate

    init_settings = SearchSettings().max_time(60)
    init_settings.node_active(client(1), False)
    init_settings.node_active(server(3), False)
    init_settings.deliver_timers(client(1), False)
    init_settings.deliver_timers(server(3), False)
    init_settings.add_goal(StatePredicate(
        "view 2 synced", view2_synced,
        tkey=("PB_VIEW_SYNCED", 2, "server1", "server2")))
    results = bfs(state, init_settings)
    assert results.end_condition == EndCondition.GOAL_FOUND, results
    view_ready = results.goal_matching_state

    settings = SearchSettings().max_time(120)
    settings.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    settings.node_active(server(3), False)
    settings.deliver_timers(server(3), False)
    # Freeze the ping machinery so the search explores the replication
    # protocol, not the view-change interleavings (the reference prunes
    # later views the same way, PrimaryBackupTest.java:688-696).
    settings.deliver_timers(VSA, False)
    settings.deliver_timers(server(1), False)
    settings.deliver_timers(server(2), False)
    results = bfs(view_ready, settings)
    assert results.end_condition == EndCondition.GOAL_FOUND, results

    settings.clear_goals().add_prune(CLIENTS_DONE)
    settings.set_max_depth(view_ready.depth + 6)
    results = bfs(view_ready, settings)
    assert results.end_condition in (EndCondition.SPACE_EXHAUSTED,
                                     EndCondition.TIME_EXHAUSTED), results


@lab_test("2", 19, "Multi-client, multi-server; multiple failures to backup", points=20, part=2, categories=(SEARCH_TESTS,))
def test19_multiple_failures_search():
    """test19MultipleFailuresSearch (simplified): from the synced view, an
    acknowledged write must remain visible after the primary fails and the
    backup serves alone — searched over the narrowed failover space."""
    from dslabs_tpu.testing.predicates import StatePredicate

    workload = kv_workload(["PUT:foo:bar", "GET:foo"], ["PutOk", "bar"])
    state = make_search_state(workload)
    state.add_server(server(1))
    state.add_server(server(2))
    state.add_client_worker(client(1))

    def view2_synced(s):
        s1, s2 = s.node(server(1)), s.node(server(2))
        return (s1.view is not None and s1.view.view_num == 2
                and s1.view.primary == server(1)
                and s1.view.backup == server(2)
                and s1.synced and s2.view is not None
                and s2.view.view_num == 2 and s2.synced
                # the ViewServer must have the view ACKED, or it can
                # never change views again (viewserver.py:125-126)
                and s.node(VSA).acked)

    init_settings = SearchSettings().max_time(60)
    init_settings.node_active(client(1), False)
    init_settings.deliver_timers(client(1), False)
    init_settings.add_goal(StatePredicate(
        "view 2 synced", view2_synced,
        tkey=("PB_VIEW_SYNCED", 2, "server1", "server2", "acked")))
    results = bfs(state, init_settings)
    assert results.end_condition == EndCondition.GOAL_FOUND, results
    view_ready = results.goal_matching_state

    # Find a state where the first write is acknowledged.
    from dslabs_tpu.testing.predicates import client_has_results

    s2 = SearchSettings().max_time(120)
    s2.add_invariant(RESULTS_OK)
    s2.deliver_timers(VSA, False)
    s2.deliver_timers(server(1), False).deliver_timers(server(2), False)
    s2.add_goal(client_has_results(client(1), 1))
    results = bfs(view_ready, s2)
    assert results.end_condition == EndCondition.GOAL_FOUND, results
    acked = results.goal_matching_state

    # Primary partitioned away.  Stage the failover the way the
    # reference's initView does (PrimaryBackupTest.java:124-187): first
    # reach the promoted view with the client gated off, then let the
    # client finish with the ping machinery frozen.
    acked.drop_pending_messages()

    def promoted(s):
        s2n = s.node(server(2))
        return (s2n.view is not None and s2n.view.primary == server(2)
                and s2n.view.backup is None and s2n.synced)

    s3 = SearchSettings().max_time(180)
    s3.add_invariant(RESULTS_OK)
    s3.partition(VSA, server(2), client(1))
    s3.node_active(client(1), False).deliver_timers(client(1), False)
    s3.deliver_timers(server(1), False)   # dead primary's timers are noise
    s3.set_max_depth(acked.depth + 10)    # promotion takes ~8 events
    s3.add_goal(StatePredicate("backup promoted", promoted,
                               tkey=("PB_PROMOTED", "server2")))
    results = bfs(acked, s3)
    assert results.end_condition == EndCondition.GOAL_FOUND, results
    failed_over = results.goal_matching_state

    s4 = SearchSettings().max_time(120)
    s4.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    s4.partition(VSA, server(2), client(1))
    s4.deliver_timers(VSA, False).deliver_timers(server(2), False)
    results = bfs(failed_over, s4)
    assert results.end_condition == EndCondition.GOAL_FOUND, results


@lab_test("2", 20, "Multi-client, multi-server random depth-first search", points=20, part=2, categories=(SEARCH_TESTS,))
def test20_random_search():
    state = make_search_state(append_same_key_workload(1))
    state.add_server(server(1))
    state.add_server(server(2))
    state.add_client_worker(client(1))
    state.add_client_worker(client(2))

    settings = SearchSettings()
    settings.set_max_depth(1000).max_time(8)
    settings.add_invariant(APPENDS_LINEARIZABLE)
    settings.add_prune(CLIENTS_DONE)
    results = dfs(state, settings)
    assert not results.terminal_found()
