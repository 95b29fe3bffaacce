"""Lab 4 with REAL replica groups through the lab entry point: a staged
``setupStates(2, n, 1, 10)`` state with one own-key PUT client binds
``ShardStoreMultiBinding`` (``tpu/adapters/shardstore.py``), is
VALIDATED as the multi-server twin's root, searched by
``backend.tensor_bfs`` and re-checked on the object side through the
binding's decoders — at n = 2 here (the chip runs n = 3); what lies
outside the twin's scope still raises ``NoTensorTwin`` and says what
binds; the states of the accepted lab cells resolve to the bindings and
``twin_key``\\ s they resolved before; and the benchmark driver's
``verify`` on recorded level counts.

The twin itself under ``ShardedTensorSearch``:
``tests/test_lab4_multi_deep.py``.
"""

import dataclasses
import hashlib
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.search.search import BFS  # noqa: E402
from dslabs_tpu.testing.predicates import (CLIENTS_DONE,  # noqa: E402
                                           StatePredicate)
from dslabs_tpu.tpu import backend  # noqa: E402
from dslabs_tpu.tpu import telemetry as tel_mod  # noqa: E402
from dslabs_tpu.tpu.adapters.shardstore import (  # noqa: E402
    MULTI_SCOPE, ShardStoreMultiBinding)
from dslabs_tpu.tpu.trace import MessageTemplate  # noqa: E402
from tests.fixtures.lab4_multi_small import at_small_size  # noqa: E402

import os  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 40
CELL = "shardkv-n3-deep"


def _load(name):
    from benchmark.harness import manifest

    return manifest.load_cell(ROOT, name)


@pytest.fixture(scope="module", autouse=True)
def two_devices():
    """The lab entry spreads a search over every device it sees, and on
    the CPU a level of this twin costs 2.7 s a virtual device whatever
    it holds (22 s on the suite's eight): two keep the exchange between
    devices and make the 12 levels down to a follower's catch-up a
    minute's work."""
    devices = jax.devices()[:2]
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "devices", lambda *args, **kwargs: devices)
    yield
    patch.undo()


@pytest.fixture(scope="module")
def small():
    """The cell at two servers a group."""
    cell = _load(CELL)
    return dataclasses.replace(
        cell, config=at_small_size(cell.config))


def _ctx(cell, seed=SEED, **config):
    return types.SimpleNamespace(
        cell=dataclasses.replace(cell, config=dict(cell.config, **config)),
        seed=seed, dev={"platform": "cpu"}, note=lambda msg: None)


@pytest.fixture(scope="module")
def joined(small):
    return small.driver.joined_state(_ctx(small))


def _settings(small, state, **over):
    return small.driver.lab4_phases.build_settings(
        dict(small.config["search"], **over), state)


def _phases(tel):
    return [r for r in tel.ring if r["t"] == "phase"]


# ------------------------------------------------------------ the binding

def test_the_staged_state_binds_the_multi_server_twin(small, joined):
    binding = backend.resolve_binding(joined)
    assert type(binding) is ShardStoreMultiBinding
    assert binding.key[0] == "shardstore-multi"
    assert binding.shape == (2, 2, 10, 1)
    assert binding.server_names == [["server1-1", "server1-2"],
                                    ["server2-1", "server2-2"]]
    assert binding.addr_index == {
        "shardmaster1": 0, "server1-1": 1, "server1-2": 2, "server2-1": 3,
        "server2-2": 4, "client1": 5}
    binding.check_settings(_settings(small, joined))
    tel = tel_mod.Telemetry(ring=64)
    with tel_mod.use(tel):
        # no replay under it: the search is never asked for anything
        assert binding.derive_root(None, joined) == (None, [])
    assert [(r["name"], r["cached"]) for r in _phases(tel)] == [
        ("entry.root.validate", 1)]
    assert small.driver.root_is_the_twins(_ctx(small), joined).ok


def test_every_record_of_the_twin_decodes(joined):
    """All 16 message tags and 4 timer tags, by the spec's own tables:
    addresses (a server's ``paxos`` sub-address for the group's log),
    ballots as ``(round, index)``, log commands from the twin's ids."""
    from dslabs_tpu.core.address import LocalAddress, SubAddress
    from dslabs_tpu.labs.paxos import paxos as P
    from dslabs_tpu.labs.shardedstore import shardstore as S

    binding = backend.resolve_binding(joined)
    spec = binding._spec()
    p = binding.build_protocol(48, 6)

    def msg(name, frm, to, **fields):
        rec = np.zeros((spec._mw,), np.int32)
        rec[:3] = [spec._mtag[name], frm, to]
        for j, f in enumerate(spec._mspec[name].fields):
            rec[3 + j] = fields[f]
        return p.decode_message(rec)

    def sub(name):
        return SubAddress(LocalAddress(name), "paxos")

    master, client = LocalAddress("shardmaster1"), LocalAddress("client1")
    s11, s12, s21 = (LocalAddress(n) for n in
                     ("server1-1", "server1-2", "server2-1"))
    assert len(spec._mtag) == 16 and len(spec._ttag) == 4
    frm, to, m = msg("Query", 5, 0, seq=2, arg=-1)
    assert (frm, to) == (client, master)
    assert m.command.sequence_num == 2 and m.command.command.config_num == -1
    frm, to, m = msg("Query", 3, 0, seq=1, arg=1)
    assert frm == s21 and m.command.client_address == s21
    frm, to, m = msg("QueryReply", 0, 2, seq=1, kind=0)
    assert (frm, to, m.cls) == (master, s12, P.PaxosReply)
    frm, to, m = msg("ShardStoreRequest", 5, 1, k=1)
    assert (frm, to) == (client, s11) and m == S.ShardStoreRequest(
        binding._amo(1))
    frm, to, m = msg("ShardStoreReply", 1, 5, k=1)
    assert (frm, to, m.cls) == (s11, client, S.ShardStoreReply)
    assert m.fallback.result.sequence_num == 1
    assert msg("WrongGroup", 3, 5, k=1) == (s21, client, S.WrongGroup(1))
    frm, to, m = msg("ShardMove", 1, 3, g=1, v=1)
    assert (frm, to, m.cls, m.fallback) == (s11, s21, S.ShardMove, None)
    frm, to, m = msg("ShardMoveAck", 3, 1, g=1)
    assert (frm, to, m.cls) == (s21, s11, S.ShardMoveAck)
    # ---- the group's log: sub-node to sub-node
    a, b = sub("server1-1"), sub("server1-2")
    frm, to, m = msg("PaxosRequest", 1, 2, cmd=binding.CMD_NC0 + 1)
    assert (frm, to, m.cls) == (a, b, P.PaxosRequest)
    assert m.fallback == P.PaxosRequest(S.NewConfig(binding.configs[1]))
    assert m.match(m.fallback)
    assert msg("P1a", 2, 1, b=3) == (b, a, P.P1a((1, 1)))
    frm, to, m = msg("P1b", 1, 2, b=3, e1=1 | (2 << 2) | (1 << 14),
                     e2=0, e3=0, e4=0, e5=0)
    assert (frm, to, m.cls) == (a, b, P.P1b)
    vote = P.P1b((1, 1), ((1, ((1, 0), binding._amo(1), False)),))
    assert m.match(vote)
    assert not m.match(P.P1b((1, 1), ()))
    assert not m.match(P.P1b((1, 1), ((1, ((1, 0), binding._amo(1),
                                           True)),)))
    frm, to, m = msg("P2a", 1, 2, b=2, slot=1, cmd=0)
    assert m.fallback == P.P2a((1, 0), 1, None) and m.match(m.fallback)
    frm, to, m = msg("P2a", 3, 4, b=2, slot=2, cmd=binding.CMD_IS0 + 1)
    assert (frm, to, m.fallback) == (sub("server2-1"), sub("server2-2"),
                                     None)
    install = S.InstallShards(1, 1, frozenset({6}), (), (
        (client, (1, None)),))
    assert m.match(P.P2a((1, 0), 2, install))
    assert not m.match(P.P2a((1, 0), 2, dataclasses.replace(install,
                                                            amo=())))
    frm, to, m = msg("P2a", 1, 2, b=2, slot=3, cmd=binding.CMD_MD)
    assert m.fallback.command == S.MoveDone(1, 2, frozenset(range(6, 11)))
    assert msg("P2b", 2, 1, b=2, slot=1) == (b, a, P.P2b((1, 0), 1))
    assert msg("Heartbeat", 1, 2, b=2, commit=1, gc=0) == (
        a, b, P.Heartbeat((1, 0), 1, 0))
    assert msg("HeartbeatReply", 2, 1, b=2, exec=1) == (
        b, a, P.HeartbeatReply((1, 0), 1))
    assert msg("CatchupRequest", 2, 1, slot=2) == (
        b, a, P.CatchupRequest(2))
    # entries from slot 2 up: c{k} is 1 + the command's id, 0 = no more
    frm, to, m = msg("CatchupReply", 1, 2, base=2, c1=1,
                     c2=binding.CMD_NC0 + 2, c3=0, c4=0, c5=0)
    assert (frm, to, m.cls) == (a, b, P.CatchupReply)
    assert m.fallback == P.CatchupReply((
        (2, None), (3, S.NewConfig(binding.configs[1]))))
    assert m.match(m.fallback)
    assert not m.match(P.CatchupReply(((2, None),)))
    assert not m.match(P.CatchupReply((
        (1, None), (2, S.NewConfig(binding.configs[1])))))
    frm, to, m = msg("CatchupReply", 3, 4, base=1,
                     c1=binding.CMD_IS0 + 2, c2=0, c3=0, c4=0, c5=0)
    assert m.fallback is None and m.match(P.CatchupReply(((1, install),)))

    def tmr(name, node, p0=0):
        return p.decode_timer(node, np.asarray(
            [spec._ttag[name], 0, 0, p0], np.int32))

    assert tmr("Election", 2)[:2] == (b, P.ElectionTimer())
    assert tmr("Heartbeat", 1, p0=2)[:2] == (a, P.HeartbeatTimer((1, 0)))
    assert tmr("Query", 3)[:2] == (s21, S.QueryTimer())
    assert tmr("Client", 5, p0=1)[:2] == (client, S.ClientTimer(1))
    with pytest.raises(backend.NoTensorTwin, match="message tag 99"):
        p.decode_message(np.asarray([99, 0, 0, 0], np.int32))
    assert isinstance(msg("QueryReply", 0, 5, seq=1, kind=1)[2],
                      MessageTemplate)


# -------------------------------------------------------- the lab entry

def test_the_pruned_exhaust_through_tensor_bfs(small, joined):
    """test10's second phase on this state (``RESULTS_OK``, prune
    ``CLIENTS_DONE``, depth + 3): bound to ``shardstore-multi``, the
    root validated and not replayed, the object checker's count, and
    the sampled re-check replaying its deepest states on the object
    side through the binding's decoders.  (The goal itself,
    ``CLIENTS_DONE``, lies 19 levels down — an election, two configs
    through the log, the handoff's start, then the PUT — past any BFS:
    upstream searches such groups by random DFS only.)"""
    settings = _settings(small, joined, max_depth=3)
    settings.add_prune(CLIENTS_DONE)
    assert settings.max_depth == joined.depth + 3
    tel = tel_mod.Telemetry(ring=1 << 12)
    with tel_mod.use(tel):
        results = backend.tensor_bfs(joined, settings)
    obj = BFS(settings).run(joined)
    assert (results.end_condition.name == obj.end_condition.name
            == "SPACE_EXHAUSTED")
    assert results.discovered_count == obj.discovered_count == 180
    out = results.tensor_outcome
    assert (out.dropped, out.visited_overflow, out.retries) == (0, 0, 0)
    phases = _phases(tel)
    assert [r["twin"] for r in phases if r["name"] == "entry.bind"] == [
        "shardstore-multi"]
    assert [r["parent"] for r in phases
            if r["name"] == "entry.root.validate"] == ["entry.derive_root"]
    assert not [r for r in phases if r["name"] == "entry.root.replay"]
    assert [r["name"] for r in phases if r["name"] == "entry.recheck"]


# What a follower needs to execute at all: nobody broadcasts a decision,
# so it learns that a slot was chosen from the leader's heartbeat
# (``commit`` past its executed prefix -> CatchupRequest ->
# CatchupReply) or from a later phase 1.  From the joined root that is 10
# events down (an election, P1a / P1b, QueryTimer, Query, QueryReply,
# P2a, P2b, HeartbeatTimer, Heartbeat): about 4 M states at n = 2, past
# the object checker.  So the space is NARROWED to group 1 and the master
# with server1-1's timers alone (the settings' partition and timer gates
# are runtime masks of the kept engine: nothing compiles), where the
# object checker reaches the exchange in seconds: the request is in the
# network at depth 10, the reply at 11, and server1-2 has executed the
# first config at 12 (found by goal searches of the object checker,
# PR 40; depths 13 and 14, 2,842 and 4,842, agree as well).  Cumulative
# unique counts by the object checker.  (With the request's send taken
# out, the twin reads 1,617 at depth 12.)
_NARROW = dict(partition=["shardmaster1", "server1-1", "server1-2"],
               timers_off=["configController", "shardmaster1", "server1-2",
                           "server2-1", "server2-2", "client1"])
_NARROW_COUNTS = {9: 295, 10: 531, 11: 943, 12: 1649}


def test_a_follower_catches_up_as_the_object_servers_do(small, joined):
    from dslabs_tpu.labs.paxos import paxos as P

    depth = max(_NARROW_COUNTS)
    settings = _settings(small, joined, max_depth=depth, **_NARROW)
    # the pruned exhaust's predicates, so that its kept engine answers
    # (the goal is out of this space's reach: the prune cuts nothing)
    settings.add_prune(CLIENTS_DONE)
    results = backend.tensor_bfs(joined, settings)
    obj = BFS(settings).run(joined)
    assert (results.end_condition.name == obj.end_condition.name
            == "SPACE_EXHAUSTED")
    assert (results.discovered_count == obj.discovered_count
            == _NARROW_COUNTS[depth])
    out = results.tensor_outcome
    assert (out.dropped, out.visited_overflow, out.retries) == (0, 0, 0)
    got = {lv["depth"]: lv["unique"] for lv in out.levels}
    assert {d: got[d] for d in _NARROW_COUNTS} == _NARROW_COUNTS

    # and the space does hold the exchange, to its end
    def reached(what, pred):
        goal = _settings(small, joined, max_depth=depth, **_NARROW)
        goal.add_goal(StatePredicate(what, pred))
        state = BFS(goal).run(joined).goal_matching_state
        return None if state is None else state.depth - joined.depth

    def in_network(cls):
        return lambda s: any(isinstance(m.message, cls)
                             for m in s.network())

    def follower_executed(s):
        return next(srv for a, srv in s.servers.items()
                    if str(a) == "server1-2").paxos.executed_through >= 1

    assert reached("reply", in_network(P.CatchupReply)) == 11
    assert reached("executed", follower_executed) == 12


# ------------------------------------------------ outside the twin's scope

def _staged(small, groups=2, clients=("PUT:key-1:v1",), results=None):
    """A joined state of ``groups`` groups of two servers with one store
    client a command string."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    d = small.driver.lab4_phases
    spec = dict(small.config["deployment"]["object_state"], groups=groups)
    root = d.build_state(spec, SEED)
    join = dict(small.config["join"], timers_off=[
        f"server{g}-{i}" for g in range(1, groups + 1) for i in (1, 2)])
    state = BFS(d.build_settings(join, root)).run(root).goal_matching_state
    for i, cmd in enumerate(clients, start=1):
        state.add_client_worker(
            LocalAddress(f"client{i}"),
            kv_workload([cmd], [(results or {}).get(cmd, "PutOk")]))
    return state


@pytest.mark.parametrize("kwargs, why", [
    (dict(clients=("PUT:key-1:v1", "PUT:key-1:v2")), "2 store clients"),
    (dict(groups=3), r"group ids \[1, 2, 3\]"),
    (dict(clients=("GET:key-1",), results={"GET:key-1": "KeyNotFound"}),
     "client command 1 is Get"),
    (dict(clients=("PUT:key-6:v6",)), "client command 1 is Put"),
], ids=["two-clients", "three-groups", "a-get", "another-key"])
def test_outside_the_scope_still_raises_and_says_what_binds(small, kwargs,
                                                            why):
    with pytest.raises(backend.NoTensorTwin, match=why) as e:
        backend.resolve_binding(_staged(small, **kwargs))
    assert MULTI_SCOPE in str(e.value)


def test_live_master_timers_or_a_live_controller_are_refused(small, joined):
    from dslabs_tpu.core.address import LocalAddress

    binding = backend.resolve_binding(joined)
    live_master = _settings(small, joined, timers_off=["configController"])
    with pytest.raises(backend.NoTensorTwin, match="master's timers"):
        binding.check_settings(live_master)
    live_ctl = _settings(small, joined, nodes_off=[])
    live_ctl.deliver_timers(LocalAddress("configController"), False)
    with pytest.raises(backend.NoTensorTwin, match="fully suppressed"):
        binding.check_settings(live_ctl)


# --------------------------------- what the accepted cells resolved before

# (binding class, key[0], sha256(repr(twin_key()))[:16]) at seed 2^31 + 40,
# computed on 34793b0 (the parent of PR 40) by the same lines as below.
_BEFORE = {
    "lab1-entry": ("ClientServerBinding", "clientserver",
                   "43f504fc0b93b38e"),
    "paxos3-suite": ("PaxosBinding", "paxos", "0d94c846b72941a7"),
    "shardtx-suite.join": ("JoinBinding", "ss-join", "d55f824f53cfa0dd"),
    "shardtx-suite.commit": ("ShardStoreTxBinding", "shardstore-tx",
                             "06c61d5393d141af"),
    "shardkv-deep": ("ShardStoreBinding", "shardstore",
                     "a3c53608c1deed76"),
}


def _given(name):
    """``(state, settings)`` a call of the accepted cell is handed."""
    from benchmark.harness import states

    cell_name, _, phase = name.partition(".")
    cell = _load(cell_name)
    cfg, d = cell.config, cell.driver
    spec = cfg["deployment"]["object_state"]
    if cell_name == "lab1-entry":
        return (states.build(spec, SEED),
                states.settings(cfg["calls"]["goal"]))
    if cell_name == "shardkv-deep":
        state = d.joined_state(_ctx(cell))
        return state, d.lab4_phases.build_settings(cfg["search"], state)
    state = d.build_state(spec, SEED)
    first = "decide" if cell_name == "paxos3-suite" else "join"
    settings = d.build_settings(cfg["phases"][first], state)
    if phase == "commit":
        state = BFS(settings).run(state).goal_matching_state
        d.add_client(state, spec, SEED)
        settings = d.build_settings(cfg["phases"]["commit"], state)
    return state, settings


@pytest.mark.parametrize("name", sorted(_BEFORE))
def test_an_accepted_cells_state_resolves_what_it_resolved_before(name):
    state, settings = _given(name)
    binding = backend.resolve_binding(state)
    binding.check_settings(settings)
    key = binding.twin_key()
    assert (type(binding).__name__, key[0], hashlib.sha256(
        repr(key).encode()).hexdigest()[:16]) == _BEFORE[name]


# ------------------------------------------------------ the driver's verify

def _measured(levels):
    return {"outcome": dict(platform="cpu", mesh_width=1, dropped=0,
                            visited_overflow=0, retries=0, failovers=0,
                            knob_retries=0, end_condition="DEPTH_EXHAUSTED",
                            depth=max(levels), elapsed_secs=1.0,
                            unique_states=levels[max(levels)],
                            states_explored=4 * levels[max(levels)]),
            "levels": [{"depth": d, "unique": n}
                       for d, n in sorted(levels.items())]}


def _verdict(small, levels, monkeypatch=None, state=None):
    """``(failed check names, all check names)`` of the driver's
    ``verify`` on recorded level counts."""
    if state is not None:
        monkeypatch.setattr(small.driver, "joined_state",
                            lambda ctx: state)
    checks = small.driver.verify(_ctx(small), _measured(levels))
    return [c.name for c in checks if not c.ok], [c.name for c in checks]


def _true_levels(small, upto=5):
    return {int(d): n
            for d, n in small.config["reference_counts"].items()
            if int(d) <= upto}


def test_verify_passes_the_true_counts(small):
    assert (small.config["reference_live_depth"],
            small.config["must_pass_depth"]) == (3, 5)
    failed, names = _verdict(small, _true_levels(small))
    assert failed == []
    assert {"reference.root_is_the_twins", "unique.depth3", "unique.depth5",
            "reference.live_vs_pinned.depth3", "completed_depth",
            "dropped"} <= set(names)
    assert "reference.live_vs_pinned.depth4" not in names


@pytest.mark.parametrize("depth", [2, 5], ids=["live-depth", "pinned-depth"])
def test_verify_fails_a_count_that_is_off_by_one(small, depth):
    levels = _true_levels(small)
    levels[depth] -= 1
    assert _verdict(small, levels)[0] == [f"unique.depth{depth}"]


def test_verify_fails_a_run_that_stopped_early(small):
    assert _verdict(small, _true_levels(small, upto=4))[0] == [
        "completed_depth"]


def test_verify_fails_a_state_that_is_not_the_twins_root(small, joined,
                                                         monkeypatch):
    """The reference started one step below the joined state (an election
    timer fired, or a query was delivered): the adapter's validation
    refuses it, and the counts — of another space — are off as well."""
    from dslabs_tpu.testing.predicates import StatePredicate

    settings = _settings(small, joined, max_depth=1)
    settings.add_goal(StatePredicate("below the root",
                                     lambda s: s.depth > joined.depth))
    state = BFS(settings).run(joined).goal_matching_state
    assert state.depth == joined.depth + 1
    failed, _names = _verdict(small, _true_levels(small), monkeypatch,
                              state)
    assert "reference.root_is_the_twins" in failed
    assert any(name.startswith("unique.depth") for name in failed)
