"""Lab 2's search tests through the lab entry on the COMPILED twin:
PrimaryBackupTest test17, test18 and test19 as ``tests/test_lab2_pb.py``
ports them, every phase through ``search.bfs`` under the tensor backend
AND under the object checker, compared phase by phase — end condition,
minimal goal depth, the goal predicate true on the object state the
tensor backend replays, and the discovered count where a phase
exhausts.  (test16 is ``tests/test_search_backend.py``'s.)

A test's phases depend on one another — a phase starts from the state
the one before it found — so each test runs once a backend, on first
use, and every phase is a case of its own.  test18 binds the
``shared_key`` twin (two clients, one APPEND each to one key) and adds
the frozen-timer exhaust from its synced view, which is where the
twin's counts used to part from the object checker's (118 for 134);
test19 is lab 2's one path through ``derive_root``'s ``drop`` op and a
partition, and the only user of ``PB_PROMOTED``."""

import functools

import pytest

jax = pytest.importorskip("jax")

import tests.test_lab2_pb as L2  # noqa: E402
from dslabs_tpu.labs.clientserver.kv_workload import (  # noqa: E402
    APPENDS_LINEARIZABLE, append_same_key_workload, kv_workload)
from dslabs_tpu.search.search import bfs  # noqa: E402
from dslabs_tpu.search.settings import SearchSettings  # noqa: E402
from dslabs_tpu.testing.predicates import (CLIENTS_DONE,  # noqa: E402
                                           RESULTS_OK, StatePredicate,
                                           client_has_results)
from dslabs_tpu.utils.flags import GlobalSettings  # noqa: E402

VSA, server, client = L2.VSA, L2.server, L2.client
PUT_GET = (["PUT:foo:bar", "GET:foo"], ["PutOk", "bar"])


def _state(workload, servers, clients):
    state = L2.make_search_state(workload)
    for i in range(1, servers + 1):
        state.add_server(server(i))
    for i in range(1, clients + 1):
        state.add_client_worker(client(i))
    return state


def _synced(acked):
    def view2_synced(s):
        s1, s2 = s.node(server(1)), s.node(server(2))
        return (s1.view is not None and s1.view.view_num == 2
                and s1.view.primary == server(1)
                and s1.view.backup == server(2) and s1.synced
                and s2.view is not None and s2.view.view_num == 2
                and s2.synced and (not acked or s.node(VSA).acked))

    return StatePredicate(
        "view 2 synced", view2_synced,
        tkey=("PB_VIEW_SYNCED", 2, "server1", "server2")
        + (("acked",) if acked else ()))


def _promoted():
    def promoted(s):
        n = s.node(server(2))
        return (n.view is not None and n.view.primary == server(2)
                and n.view.backup is None and n.synced)

    return StatePredicate("backup promoted", promoted,
                          tkey=("PB_PROMOTED", "server2"))


def _frozen(settings, *servers):
    """The ping machinery off: the view server's timer and the named
    servers'."""
    settings.deliver_timers(VSA, False)
    for i in servers:
        settings.deliver_timers(server(i), False)
    return settings


class _Run:
    """One test's phases on one backend, in order."""

    def __init__(self):
        self.phases = {}

    def phase(self, name, state, settings):
        res = bfs(state, settings)
        goal = res.goal_matching_state
        self.phases[name] = {
            "end": res.end_condition.name,
            "goal_depth": None if goal is None else goal.depth,
            "goal_holds": None if goal is None else all(
                g.check(goal).value for g in settings.goals),
            "discovered": res.discovered_count}
        return goal


def _test17(run):
    state = _state(kv_workload(*PUT_GET), 3, 1)
    init = SearchSettings().max_time(60).add_goal(_synced(False))
    for node in (client(1), server(3)):
        init.node_active(node, False).deliver_timers(node, False)
    ready = run.phase("view_ready", state, init)
    settings = SearchSettings().max_time(120)
    settings.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    settings.node_active(server(3), False).deliver_timers(server(3), False)
    _frozen(settings, 1, 2)
    run.phase("done", ready, settings)
    settings.clear_goals().add_prune(CLIENTS_DONE)
    settings.set_max_depth(ready.depth + 6)
    run.phase("exhaust6", ready, settings)


def _test18(run):
    state = _state(append_same_key_workload(1), 2, 2)
    stage1 = SearchSettings().max_time(60).add_goal(_synced(False))
    for c in (client(1), client(2)):
        stage1.sender_active(c, False).deliver_timers(c, False)
    synced = run.phase("view_ready", state, stage1)
    stage2 = _frozen(SearchSettings().max_time(120), 1, 2)
    stage2.add_invariant(APPENDS_LINEARIZABLE).add_goal(CLIENTS_DONE)
    run.phase("done", synced, stage2)
    exhaust = _frozen(SearchSettings().max_time(120), 1, 2)
    exhaust.add_invariant(APPENDS_LINEARIZABLE).add_prune(CLIENTS_DONE)
    run.phase("exhaust", synced, exhaust)


def _test19(run):
    state = _state(kv_workload(*PUT_GET), 2, 1)
    init = SearchSettings().max_time(60).add_goal(_synced(True))
    init.node_active(client(1), False).deliver_timers(client(1), False)
    ready = run.phase("view_ready", state, init)
    s2 = _frozen(SearchSettings().max_time(120), 1, 2)
    s2.add_invariant(RESULTS_OK).add_goal(client_has_results(client(1), 1))
    acked = run.phase("acked", ready, s2)
    acked.drop_pending_messages()
    s3 = SearchSettings().max_time(180).add_invariant(RESULTS_OK)
    s3.partition(VSA, server(2), client(1))
    s3.node_active(client(1), False).deliver_timers(client(1), False)
    s3.deliver_timers(server(1), False)
    s3.set_max_depth(acked.depth + 10)
    s3.add_goal(_promoted())
    failed_over = run.phase("promoted", acked, s3)
    s4 = SearchSettings().max_time(120)
    s4.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    s4.partition(VSA, server(2), client(1))
    _frozen(s4, 2)
    run.phase("done", failed_over, s4)


TESTS = {"test17": _test17, "test18": _test18, "test19": _test19}
# (test, phase, goal depth or None, the object checker's discovered
# count where the phase exhausts)
CASES = [("test17", "view_ready", 8, None), ("test17", "done", 18, None),
         ("test17", "exhaust6", None, 13),
         ("test18", "view_ready", 8, None), ("test18", "done", 20, None),
         ("test18", "exhaust", None, 134),
         ("test19", "view_ready", 10, None), ("test19", "acked", 16, None),
         ("test19", "promoted", 21, None), ("test19", "done", 27, None)]
# test17 is test18's shape with one client (and a third server gated
# off): half a minute of compiles for a path the other two cover
SLOW = {"test17"}


@functools.lru_cache(maxsize=None)
def _phases(test, backend):
    was = GlobalSettings.search_backend
    GlobalSettings.search_backend = backend
    try:
        run = _Run()
        TESTS[test](run)
        return run.phases
    finally:
        GlobalSettings.search_backend = was


@pytest.mark.parametrize("test,phase,goal_depth,discovered", [
    pytest.param(*case, id=f"{case[0]}-{case[1]}",
                 marks=[pytest.mark.slow] if case[0] in SLOW else [])
    for case in CASES])
def test_a_phase_agrees_with_the_object_checker(test, phase, goal_depth,
                                                discovered):
    ten, obj = _phases(test, "tensor")[phase], _phases(test, "object")[phase]
    assert ten["end"] == obj["end"] == (
        "GOAL_FOUND" if goal_depth else "SPACE_EXHAUSTED")
    assert ten["goal_depth"] == obj["goal_depth"] == goal_depth
    if goal_depth:
        # the object state the tensor backend replayed satisfies the
        # test's own predicate, not the twin's lanes alone
        assert ten["goal_holds"] is obj["goal_holds"] is True
    else:
        assert ten["discovered"] == obj["discovered"] == discovered


def test_the_lab_entry_binds_the_compiled_twin():
    """test18's state binds the ``shared_key`` twin, test19's the plain
    one; both are ``pb_spec``'s, at the ladder's caps, and the adapter
    no longer builds the hand twin."""
    import inspect

    from dslabs_tpu.tpu import backend
    from dslabs_tpu.tpu.adapters import simple

    shared = backend.resolve_binding(
        _state(append_same_key_workload(1), 2, 2))
    plain = backend.resolve_binding(_state(kv_workload(*PUT_GET), 2, 1))
    assert (shared.shared_key, plain.shared_key) == (True, False)
    assert shared.twin_key() != plain.twin_key()
    twin = shared.build_protocol(64, 6)
    assert (twin.name, twin.net_cap, twin.timer_cap) == (
        "pb-gen-shared", 64, 6)
    assert plain.build_protocol(*plain.initial_caps()).name == "pb-gen"
    # two clients on keys of their own keep the plain twin
    own = L2.make_search_state(None)
    for i in (1, 2):
        own.add_server(server(i))
        own.add_client_worker(client(i),
                              kv_workload([f"APPEND:key-{i}:x"], ["x"]))
    assert backend.resolve_binding(own).shared_key is False
    assert "make_pb_protocol" not in inspect.getsource(simple)
