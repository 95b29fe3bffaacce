"""The parts of the lab entry point (tpu/backend.py) that lab 4's STAGED
cross-group transaction search stands on — ShardStorePart2Test test09 as
the benchmark's configuration ``lab4-shardstore-tx-g2`` states it and
its driver ``lab4_phases`` builds it — each small enough for the tier-1
run (the full ``commit`` goal parity, a minute of object checker, is
``tests/test_search_backend.py``'s, behind ``DSLABS_SLOW_TESTS``):

* the configuration's phases build the very states and settings the
  port (tests/test_lab4_shardstore.py) builds;
* ``join`` through ``tensor_bfs`` against the object checker, and from
  ITS goal state plus the client the done-pruned exhausts at depth + 3
  and + 4 against the object checker and the configuration's pinned
  counts — two phases, two twins, the second's root validated, not
  replayed;
* a climbed ladder answers the same, and ``attempt`` tells the doomed
  search from the answering one;
* the cell's data files hold together.
"""

import json
import os
import types

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.core.address import LocalAddress  # noqa: E402
from dslabs_tpu.search.search import BFS  # noqa: E402
from dslabs_tpu.tpu import backend  # noqa: E402
from dslabs_tpu.tpu import telemetry as tel_mod  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 33
NODES = ["shardmaster1", "server1-1", "server2-1", "configController",
         "client1"]


@pytest.fixture(scope="module")
def cell():
    from benchmark.harness import manifest

    return manifest.load_cell(ROOT, "shardtx-suite")


def _phases(recorder):
    return [r for r in recorder.ring if r["t"] == "phase"]


# ------------------------------------------- the port's states and settings

def _port_root():
    """test09's Join-phase state as ``_joined_state`` builds it."""
    import tests.test_lab4_shardstore as lab4
    from dslabs_tpu.testing.workload import Workload

    state = lab4.make_search(2, 1, 1, 2)
    cmds = [lab4.Join(g, lab4.group(g, 1)) for g in (1, 2)]
    state.add_client_worker(lab4.CCA, Workload(
        commands=cmds, results=[lab4.Ok()] * 2))
    return state


def _port_settings(phase, start):
    """The port's settings, line for line
    (tests/test_lab4_shardstore.py:484-492, :838-842, :817-820)."""
    import tests.test_lab4_shardstore as lab4
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, RESULTS_OK,
                                               client_done)

    if phase == "join":
        s = SearchSettings().max_time(420)
        s.add_invariant(RESULTS_OK)
        s.partition(lab4.CCA, lab4.shard_master(1))
        for a in list(start.servers):
            if "server" in str(a):
                s.deliver_timers(a, False)
        return s.add_goal(client_done(lab4.CCA))
    s = SearchSettings().max_time(300)
    s.add_invariant(RESULTS_OK).add_goal(CLIENTS_DONE)
    s.node_active(lab4.CCA, False)
    s.deliver_timers(lab4.CCA, False)
    s.deliver_timers(lab4.shard_master(1), False)
    if phase == "exhaust6":
        s.clear_goals().add_prune(CLIENTS_DONE)
        s.set_max_depth(start.depth + 6)
    return s


def _workloads(state):
    return {str(a): [(type(c).__name__, sorted(getattr(c, "key_set",
                                                       lambda: ())()),
                      repr(r))
                     for c, r in zip(w.workload._commands,
                                     w.workload._results)]
            for a, w in state.client_workers().items()}


def _gating(settings):
    """Every ``should_deliver`` and ``should_deliver_timer`` answer."""
    nodes = [LocalAddress(n) for n in NODES]
    return ([settings.should_deliver(types.SimpleNamespace(frm=f, to=t))
             for f in nodes for t in nodes],
            [settings.should_deliver_timer(n) for n in nodes])


@pytest.mark.parametrize("phase", ["join", "commit", "exhaust6"])
def test_phases_build_the_ports_states_and_settings(cell, phase):
    import tests.test_lab4_shardstore as lab4
    from dslabs_tpu.labs.shardedstore.txkvstore import MultiPut, MultiPutOk
    from dslabs_tpu.testing.workload import Workload

    drv, spec = cell.driver, cell.config["deployment"]["object_state"]
    mine, port = drv.build_state(spec, SEED), _port_root()
    if phase != "join":
        # the joined state, by the object checker on either side
        mine = BFS(drv.build_settings(cell.config["phases"]["join"],
                                      mine)).run(mine).goal_matching_state
        port = BFS(_port_settings("join", port)).run(
            port).goal_matching_state
        assert mine.depth == port.depth == 4
        drv.add_client(mine, spec, SEED)
        port.add_client_worker(LocalAddress("client1"), Workload(
            commands=[MultiPut({"key-1": "x", "key-2": "y"})],
            results=[MultiPutOk()]))
    assert (sorted(map(str, mine.servers)) == sorted(map(str, port.servers))
            == sorted(NODES[:3]))
    assert _workloads(mine) == _workloads(port)
    assert set(_workloads(mine)) == set(NODES[3:4 if phase == "join"
                                              else 5])
    for a, s in mine.servers.items():
        if isinstance(s, lab4.ShardStoreServer):
            other = port.servers[a]
            assert (s.group_id, s.num_shards) == (other.group_id,
                                                  other.num_shards)
    got = drv.build_settings(cell.config["phases"][phase], mine)
    want = _port_settings(phase, port)
    assert _gating(got) == _gating(want)
    assert (got.max_time_secs, got.max_depth) == (want.max_time_secs,
                                                  want.max_depth)
    for group in ("invariants", "goals", "prunes"):
        assert ([p.name for p in getattr(got, group)]
                == [p.name for p in getattr(want, group)])
    assert backend._predicates_key(got) == backend._predicates_key(want)


def test_the_seed_draws_the_values_and_nothing_else(cell):
    drv, spec = cell.driver, cell.config["deployment"]["object_state"]

    def puts(seed):
        state = drv.add_client(drv.build_state(spec, seed), spec, seed)
        (cmd,) = state.client_workers()[
            LocalAddress("client1")].workload._commands
        return dict(cmd.values)

    a, b = puts(SEED), puts(SEED + 1)
    assert sorted(a) == sorted(b) == ["key-1", "key-2"]
    assert a != b and puts(SEED) == a


# ----------------------------------------- the phases through tensor_bfs

@pytest.fixture(scope="module")
def joined(cell):
    """``join`` through ``tensor_bfs`` from the driver's root: ``(results,
    the call's phases, the goal state with client1's worker added)``."""
    drv, spec = cell.driver, cell.config["deployment"]["object_state"]
    root = drv.build_state(spec, SEED)
    settings = drv.build_settings(cell.config["phases"]["join"], root)
    tel = tel_mod.Telemetry(ring=1 << 12)
    with tel_mod.use(tel):
        results = backend.tensor_bfs(root, settings)
    state = results.goal_matching_state
    return results, _phases(tel), (None if state is None else
                                   drv.add_client(state, spec, SEED))


def test_join_through_tensor_bfs_against_the_object_checker(cell, joined):
    results, phases, state = joined
    drv, spec = cell.driver, cell.config["deployment"]["object_state"]
    root = drv.build_state(spec, SEED)
    obj = BFS(drv.build_settings(cell.config["phases"]["join"],
                                 root)).run(root)
    want = cell.config["reference"]["join"]
    assert (results.end_condition.name == obj.end_condition.name
            == want["end_condition"])
    assert (results.goal_matching_state.depth
            == obj.goal_matching_state.depth == want["terminal_depth"])
    assert state._tensor_provenance.key[0] == "ss-join"
    assert len(state._tensor_provenance.history) == 4
    binds = [r for r in phases if r["name"] == "entry.bind"]
    assert [(r["attempt"], r["twin"]) for r in binds] == [(0, "ss-join")]
    # a root state: nothing to replay, nothing to validate
    assert not [r for r in phases if r["name"].startswith("entry.root.")
                and r["name"] != "entry.root.build"]


def test_a_kept_engine_names_itself_and_gives_its_supersteps_text(joined):
    """``entry.build_engine`` says which kept engine the attempt leased,
    and that engine, found under ``telemetry.KEPT_SUPERSTEP``, lowers
    its superstep again when asked: the reader of a traced call gets
    the scopes of the very program the attempt ran."""
    _results, phases, _state = joined
    (build,) = [r for r in phases if r["name"] == "entry.build_engine"]
    (engine,) = [e for e in tel_mod.registered_programs(
        tel_mod.KEPT_SUPERSTEP) if e.serial == build["engine"]]
    assert engine.search.p.name == "shardmaster-join-w2"
    # the bytes a row are the engine's, as its outcomes report them: a
    # reader prices a doomed rung's states at that rung's own width
    assert (engine.search.bytes_per_state
            == _results.tensor_outcome.bytes_per_state > 0)
    text = engine.as_text()
    assert engine.as_text() is text         # lowered once
    scopes = {scope for scope, _named in
              tel_mod.scopes_of_hlo(text).values()}
    assert {"expand.handlers", "visited_insert", "fingerprint"} <= scopes


def test_the_joined_state_binds_the_2pc_twin_and_is_validated(joined):
    from dslabs_tpu.tpu.adapters.shardstore import ShardStoreTxBinding

    binding = backend.resolve_binding(joined[2])
    assert type(binding) is ShardStoreTxBinding
    assert binding.key[0] == "shardstore-tx" and binding.W == 1
    tel = tel_mod.Telemetry(ring=64)
    with tel_mod.use(tel):
        # no replay under it: the search is never asked for anything
        assert binding.derive_root(None, joined[2]) == (None, [])
    assert [(r["name"], r["cached"]) for r in _phases(tel)] == [
        ("entry.root.validate", 1)]
    assert "entry.root.validate" in tel_mod.PHASES


@pytest.mark.parametrize("depth", [3, 4])
def test_done_pruned_exhaust_from_the_joined_state(cell, joined, depth):
    state = joined[2]
    settings = cell.driver.build_settings(
        dict(cell.config["phases"]["exhaust6"], max_depth=depth), state)
    assert settings.max_depth == state.depth + depth
    tel = tel_mod.Telemetry(ring=1 << 12)
    with tel_mod.use(tel):
        results = backend.tensor_bfs(state, settings)
    obj = BFS(settings).run(state)
    assert (results.end_condition.name == obj.end_condition.name
            == "SPACE_EXHAUSTED")
    pinned = cell.config["exhaust_counts"]["by_max_depth"][str(depth)]
    assert results.discovered_count == obj.discovered_count == pinned
    out = results.tensor_outcome
    assert (out.dropped, out.visited_overflow, out.retries) == (0, 0, 0)
    phases = _phases(tel)
    validate = [r for r in phases if r["name"] == "entry.root.validate"]
    assert [r["parent"] for r in validate] == ["entry.derive_root"]
    assert [r["twin"] for r in phases if r["name"] == "entry.bind"] == [
        "shardstore-tx"]
    assert not [r for r in phases if r["name"] == "entry.root.replay"]
    assert [r["attempt"] for r in phases
            if r["name"] == "entry.search"] == [0]


def test_a_climbed_ladder_answers_the_same(cell, joined, monkeypatch):
    """The depth + 3 exhaust on a ladder whose first rung's visited
    table holds 16 slots a device: the warm run fits, the search
    overflows, the entry point climbs, and the call's record tells the
    doomed search from the answering one."""
    monkeypatch.setattr(backend, "_LADDER",
                        [(1 << 9, 1 << 4), (1 << 9, 1 << 12)])
    state = joined[2]
    settings = cell.driver.build_settings(
        dict(cell.config["phases"]["exhaust6"], max_depth=3), state)
    tel = tel_mod.Telemetry(ring=1 << 12)
    with tel_mod.use(tel), pytest.warns(RuntimeWarning,
                                        match="capacity pressure"):
        results = backend.tensor_bfs(state, settings)
    assert results.end_condition.name == "SPACE_EXHAUSTED"
    assert results.discovered_count == 142
    phases = _phases(tel)
    marks = [r for r in phases if r["name"] == "entry.capacity_retry"]
    assert [m["attempt"] for m in marks] == [0]
    assert "visited" in marks[0]["overflow"]
    searches = [r for r in phases if r["name"] == "entry.search"]
    assert [r["attempt"] for r in searches] == [0, 1]
    # what the doomed search had explored when it was thrown away: some
    # of what the answering one explored in all
    assert 0 < marks[0]["explored"] <= results.tensor_outcome.states_explored
    for name in ("entry.bind", "entry.derive_root", "entry.warm_run"):
        assert [r["attempt"] for r in phases if r["name"] == name] == [0, 1]
    assert len([r for r in phases
                if r["name"] == "entry.root.validate"]) == 2


# ------------------------------------------------- the cell's data files

def _cell_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        man = json.load(fh)
    entry = next(w for w in man["workloads"] if w["name"] == "shardtx-suite")
    cfg_entry = next(c for c in man["configs"]
                     if c["name"] == entry["config"])
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "shardtx-suite.json")) as fh:
        traffic = json.load(fh)
    with open(os.path.join(ROOT, cfg_entry["file"])) as fh:
        return man, traffic["params"], cfg_entry, json.load(fh)


def _the_cycle_is_made_of_phases_that_start_earlier(man, params, entry,
                                                    config):
    seen = []
    for name in params["cycle"]:
        start = config["phases"][name]["start"]
        assert start == "root" or (
            start.split(" of ")[0] in ("goal", "start")
            and start.split(" of ")[1] in seen), (name, start, seen)
        seen.append(name)
    # the client is added once, to the Join phase's goal state
    assert [n for n in params["cycle"]
            if config["phases"][n]["adds"]] == ["commit"]


def _the_traced_phase_is_the_staged_goal_search(man, params, entry, config):
    assert params["traced_phases"] == ["commit"]
    assert set(params["traced_phases"]) <= set(params["cycle"])
    assert config["phases"]["commit"]["start"] == "goal of join"


def _reduced_is_what_the_cycle_leaves_out(man, params, entry, config):
    assert set(config["reduced"]) == (set(config["phases"])
                                      - set(params["cycle"]))
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    # join and commit are the deployment: never cut
    assert {"join", "commit"} <= set(params["cycle"])


def _every_phase_has_its_pinned_answer(man, params, entry, config):
    assert set(config["reference"]) == set(config["phases"])
    for name in params["cycle"]:
        want = config["reference"][name]
        if want["end_condition"] == "GOAL_FOUND":
            assert want["terminal_depth"] > 0
        else:
            depth = config["phases"][name]["max_depth"]
            assert (want["discovered_count"] == config["exhaust_counts"][
                "by_max_depth"][str(depth)])
    # an exact count in the cycle: what a narrowed search cannot match
    assert any(config["reference"][n]["end_condition"] == "SPACE_EXHAUSTED"
               for n in params["cycle"])


def _the_manifest_reads_the_cell_where_the_issue_says(man, params, entry,
                                                      config):
    assert entry["source"] == config["source"]
    cell = next(w for w in man["workloads"] if w["name"] == "shardtx-suite")
    # what the driver refuses before any run: a line over 200 characters
    assert max(map(len, (entry["source"], entry["why"], cell["why"]))) <= 200
    reads = {m["name"] for m in man["per_layer"]
             if "shardtx-suite" in m.get("workloads", ())}
    assert {"ladder_wasted_s.lab4", "root_validate_s.lab4",
            "expand_us_per_state.lab4", "superstep_roofline.lab4",
            "ladder_attempts_per_call.suite", "engine_cache_hit_pct.lab",
            "dispatches_per_call.lab"} <= reads
    assert all(m["moves"] == "verdict_s"
               or m["name"] in ("warmup_s.lab", "exe_store_hit_pct",
                                "twin_build_s")
               for m in man["per_layer"] if m["name"] in reads)
    assert not {m for m in reads if m.endswith(".deep")}


_CELL_CHECKS = [_the_cycle_is_made_of_phases_that_start_earlier,
                _the_traced_phase_is_the_staged_goal_search,
                _reduced_is_what_the_cycle_leaves_out,
                _every_phase_has_its_pinned_answer,
                _the_manifest_reads_the_cell_where_the_issue_says]


@pytest.mark.parametrize("check", _CELL_CHECKS,
                         ids=[c.__name__.lstrip("_") for c in _CELL_CHECKS])
def test_shardtx_suites_data(check):
    check(*_cell_files())
