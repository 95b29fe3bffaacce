"""The parts of the lab entry point (tpu/backend.py) that a STAGED lab 3
search stands on, each without a lab 3 search (those are minutes on the
CPU: tests/test_search_backend.py, ``DSLABS_SLOW_TESTS``):

* ``compile_masks`` turns each of PaxosTest test22's five settings — as
  the benchmark's configuration ``lab3-paxos-test22-staged`` states them
  and its driver builds them — into the expected delivery matrix and
  timer vector on the 3-server 2-client binding (no engine built);
* ``derive_root`` replays a staged state's provenance to the very row
  the recording search reached, also after ``drop`` / ``undrop_from``,
  and a history that needs a higher rung of the capacity ladder raises
  ``CapacityOverflow`` (which the ladder retries), never ``NoTensorTwin``
  (which fails the test);
* the ladder's retry leaves an ``entry.capacity_retry`` mark, and the
  names ISSUE 27 added are in ``telemetry.PHASES``;
* a lab 3 spec with a ONE-slot log traces (tpu/compiler.py ``repack``).
"""

import os
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import backend  # noqa: E402
from dslabs_tpu.tpu import telemetry as tel_mod  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- compile_masks

@pytest.fixture(scope="module")
def suite():
    """``(configuration, driver, binding, state)`` of the benchmark's
    ``paxos3-suite`` cell: the phases as data, the functions that build
    test22's state and settings from them, the twin binding and the
    seeded state it was resolved from."""
    from benchmark.harness import manifest

    cell = manifest.load_cell(ROOT, "paxos3-suite")
    state = cell.driver.build_state(
        cell.config["deployment"]["object_state"], 2**31 + 5)
    return cell.config, cell.driver, backend.resolve_binding(state), state


NODES = ["server1", "server2", "server3", "client1", "client2"]
# phase -> (the partition's nodes, the nodes whose timers stay on)
EXPECTED_MASKS = {
    "decide": ("server1 server2 client1", NODES),
    "finish13": ("server1 server3 client2", ["server1", "server3"]),
    "finish23": ("server2 server3 client2", ["server2", "server3"]),
    "exhaust13": ("server1 server3 client2", []),
    "exhaust23": ("server2 server3 client2", []),
}


@pytest.mark.parametrize("phase", list(EXPECTED_MASKS))
def test_compile_masks_of_test22s_settings(suite, phase):
    config, driver, binding, state = suite
    assert [binding.addr_index[n] for n in NODES] == [0, 1, 2, 3, 4]
    settings = driver.build_settings(config["phases"][phase], state)
    mat, tvec = backend.compile_masks(binding, settings)
    part, timers_on = EXPECTED_MASKS[phase]
    want = np.zeros((5, 5), bool)
    for f in part.split():
        for t in part.split():
            want[NODES.index(f), NODES.index(t)] = f != t
    assert mat.dtype == bool and mat.shape == (25,)
    assert (mat.reshape(5, 5) == want).all(), mat.reshape(5, 5)
    assert tvec.tolist() == [n in timers_on for n in NODES]
    # 3 nodes inside: 6 directed links, nothing to or from an outsider
    assert int(mat.sum()) == 6


def test_the_suites_state_is_test22s(suite):
    """Both clients APPEND to one key; client 2 expects client 1's value
    before its own; the same seed gives the same commands."""
    config, driver, binding, state = suite
    assert (binding.n, binding.nc, binding.w, binding.S) == (3, 2, 1, 2)
    (c1, r1), (c2, r2) = [(binding.cmd_objs[i], binding.results[i])
                          for i in (1, 2)]
    assert c1.key == c2.key and c1.value != c2.value
    assert (r1.value, r2.value) == (c1.value, c1.value + c2.value)
    again = backend.resolve_binding(driver.build_state(
        config["deployment"]["object_state"], 2**31 + 5))
    assert again.key == binding.key
    assert sorted(config["phases"]) == sorted(EXPECTED_MASKS)


# ------------------------------------------------------------- derive_root

class _GenBinding(backend.TwinBinding):
    """The single-decree ``paxos_spec``'s nodes, as a binding."""
    key = ("paxos-gen", 3)
    addr_index = {"proposer": 0, "acceptor1": 1, "acceptor2": 2,
                  "acceptor3": 3}


def _gen_search(net_cap=None):
    """A recording search of single-decree Paxos whose goal is the state
    in which all three acceptors have promised and no PROMISE has been
    delivered yet: three events deep, with the three PREPAREs (a
    delivered message stays in the network) and a PROMISE from each
    acceptor in flight."""
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
    from dslabs_tpu.tpu.specs import paxos_spec

    spec = paxos_spec(3)
    if net_cap is not None:
        spec.net_cap = net_cap
    spec.goals = {"ALL_PROMISED": lambda v: (
        (v.get("acceptor", 0, "bal") == 1)
        & (v.get("acceptor", 1, "bal") == 1)
        & (v.get("acceptor", 2, "bal") == 1)
        & (v.get("proposer", 0, "ph") == 0))}
    return ShardedTensorSearch(
        spec.compile(), make_mesh(1), chunk_per_device=16,
        frontier_cap=1 << 8, visited_cap=1 << 10, strict=True,
        record_trace=True)


def _row(state):
    from dslabs_tpu.tpu.engine import flatten_state

    return np.asarray(flatten_state(
        jax.tree.map(jax.numpy.asarray, state)))[0]


def _staged(history):
    return types.SimpleNamespace(
        depth=len(history),
        _tensor_provenance=backend.TensorProvenance(_GenBinding.key,
                                                    list(history)))


@pytest.fixture(scope="module")
def recorded():
    """``(search, outcome, history)``: the recording search, its goal
    outcome, and the goal's provenance as ``_materialize`` writes it."""
    search = _gen_search()
    outcome = search.run()
    assert outcome.end_condition == "GOAL_FOUND" and outcome.depth == 3
    return search, outcome, [backend._norm_event(search.p, e)
                             for e in outcome.trace]


def test_derive_root_reproduces_the_row_the_search_reached(recorded):
    search, outcome, history = recorded
    assert [op[0] for op in history] == ["ev_msg"] * 3
    root, got = backend.derive_root(_GenBinding(), search,
                                    _staged(history))
    assert got == history
    assert (_row(root) == _row(outcome.goal_state)).all()


def test_derive_root_after_drop_and_undrop_from(recorded):
    from dslabs_tpu.tpu.engine import SENTINEL

    search, outcome, history = recorded
    p = search.p
    o0, o1 = search._off[0], search._off[1]
    reached = _row(outcome.goal_state)
    net = reached[o0:o1].reshape(p.net_cap, p.msg_width)
    live = net[net[:, 0] != SENTINEL]
    # three PREPAREs from the proposer, a PROMISE from each acceptor
    assert sorted(live[:, 1].tolist()) == [0, 0, 0, 1, 2, 3]

    root, _ = backend.derive_root(
        _GenBinding(), search, _staged(history + [("drop",)]))
    dropped = _row(root)
    assert (dropped[o0:o1] == SENTINEL).all()
    assert (np.delete(dropped, np.s_[o0:o1])
            == np.delete(reached, np.s_[o0:o1])).all()

    root, _ = backend.derive_root(
        _GenBinding(), search,
        _staged(history + [("drop",), ("undrop_from", "acceptor2")]))
    back = _row(root)
    want = np.full_like(net, SENTINEL)
    want[0] = live[live[:, 1] == 2][0]
    assert (back[o0:o1].reshape(net.shape) == want).all()
    assert (np.delete(back, np.s_[o0:o1])
            == np.delete(reached, np.s_[o0:o1])).all()


def test_a_history_beyond_this_rungs_caps_is_a_capacity_overflow(recorded):
    from dslabs_tpu.tpu.engine import CapacityOverflow

    search, _outcome, history = recorded
    # a slot no net of this rung has: the recording phase stood higher
    with pytest.raises(CapacityOverflow, match="beyond net_cap"):
        backend.derive_root(
            _GenBinding(), search,
            _staged(history + [("ev_msg", search.p.net_cap)]))
    # a transition that overflows this rung's net: the second PROMISE
    # (slots 3 and 4, after the PREPAREs) makes the proposer send three
    # ACCEPTs beside the six messages there are
    more = history + [("ev_msg", 3), ("ev_msg", 4)]
    backend.derive_root(_GenBinding(), search, _staged(more))   # fits here
    with pytest.raises(CapacityOverflow, match="overflowed caps"):
        backend.derive_root(_GenBinding(), _gen_search(net_cap=8),
                            _staged(more))
    # what no rung can cure stays a NoTensorTwin
    with pytest.raises(backend.NoTensorTwin, match="undeliverable"):
        backend.derive_root(
            _GenBinding(), search,
            _staged(history + [("ev_msg", search.p.net_cap - 1)]))


# ------------------------------------------------------------- the ladder

def test_new_names_are_in_the_table():
    assert {"entry.root.build", "entry.root.replay",
            "entry.capacity_retry"} <= set(tel_mod.PHASES)


def test_derive_root_writes_its_phases(recorded):
    """``entry.root.build`` where the trace step had to be built, and
    not where the lab entry had kept it; ``entry.root.replay`` always,
    between the two halves of the eager work around it (the twin's
    initial row read back, the replayed row put back: ISSUE 38)."""
    search, _outcome, history = recorded
    backend.clear_cache()
    around = ["entry.root.eager", "entry.root.replay", "entry.root.eager"]
    for names in (["entry.root.build"] + around, around):
        tel = tel_mod.Telemetry(ring=256)
        with tel_mod.use(tel):
            backend.derive_root(_GenBinding(), search,
                                _staged(history + [("drop",)]))
        phases = [r for r in tel.ring if r["t"] == "phase"]
        # the executable store (ISSUE 41) is asked where the step is
        # built, inside ``entry.root.build``, and nowhere else
        store = [r["name"] for r in phases
                 if r["name"].startswith("compile.store.")]
        phases = [r for r in phases
                  if not r["name"].startswith("compile.store.")]
        assert [r["name"] for r in phases] == names
        assert store in ([], ["compile.store.key", "compile.store.load"],
                         ["compile.store.key", "compile.store.load",
                          "compile.store.write"])
        assert bool(store) == (names[0] == "entry.root.build")
        assert (phases[-2]["events"], phases[-2]["staged_ops"]) == (3, 1)
    assert backend.cache_info()["step"] == 1


def test_a_ladder_retry_leaves_a_mark(monkeypatch):
    """Lab 1 (2 clients, 2 appends each: 80 states) on a ladder whose
    first rung's visited table holds 8 slots a device: the strict search
    overflows, the entry point climbs one rung, and the call's record
    says so."""
    from benchmark.harness import states
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.predicates import CLIENTS_DONE, RESULTS_OK

    monkeypatch.setattr(backend, "_LADDER",
                        [(1 << 9, 1 << 3), (1 << 9, 1 << 12)])
    state = states.build({"kind": "clientserver", "clients": 2,
                          "commands_per_client": 2}, 5)
    settings = (SearchSettings().add_invariant(RESULTS_OK)
                .add_prune(CLIENTS_DONE))
    tel = tel_mod.Telemetry(ring=1 << 12)
    with tel_mod.use(tel), pytest.warns(RuntimeWarning,
                                        match="capacity pressure"):
        results = backend.tensor_bfs(state, settings)
    assert results.end_condition.name == "SPACE_EXHAUSTED"
    assert results.discovered_count == 80
    phases = [r for r in tel.ring if r["t"] == "phase"]
    marks = [r for r in phases if r["name"] == "entry.capacity_retry"]
    assert [m["attempt"] for m in marks] == [0]
    assert "visited" in marks[0]["overflow"] and marks[0]["wall"] < 0.01
    binds = [r for r in phases if r["name"] == "entry.bind"]
    assert [r["attempt"] for r in binds] == [0, 1]
    assert {r["call"] for r in marks + binds} == {binds[0]["call"]}


def test_a_ladder_climb_keeps_both_rungs(monkeypatch):
    """The call above again, twice: the first keeps the engine of the
    rung that overflowed and of the rung that answered (and each rung's
    twin); the second still starts on rung 0, overflows there as the
    first did, climbs, and builds nothing on either rung."""
    from benchmark.harness import states
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.predicates import CLIENTS_DONE, RESULTS_OK

    monkeypatch.setattr(backend, "_LADDER",
                        [(1 << 9, 1 << 3), (1 << 9, 1 << 12)])
    backend.clear_cache()
    seen = []
    for _ in range(2):
        state = states.build({"kind": "clientserver", "clients": 2,
                              "commands_per_client": 2}, 5)
        settings = (SearchSettings().add_invariant(RESULTS_OK)
                    .add_prune(CLIENTS_DONE))
        tel = tel_mod.Telemetry(ring=1 << 12)
        with tel_mod.use(tel), pytest.warns(RuntimeWarning,
                                            match="capacity pressure"):
            results = backend.tensor_bfs(state, settings)
        assert results.discovered_count == 80
        phases = [r for r in tel.ring if r["t"] == "phase"]
        seen.append({name: [(r["attempt"], r["cached"]) for r in phases
                            if r["name"] == name]
                     for name in ("entry.bind", "entry.build_engine")})
        assert [r["attempt"] for r in phases
                if r["name"] == "entry.capacity_retry"] == [0]
        info = backend.cache_info()
        assert (info["twin"], info["engine"]) == (2, 2)
    assert seen[0] == {"entry.bind": [(0, 0), (1, 0)],
                       "entry.build_engine": [(0, 0), (1, 0)]}
    assert seen[1] == {"entry.bind": [(0, 1), (1, 1)],
                       "entry.build_engine": [(0, 1), (1, 1)]}


# ------------------------------------------- the staged cells' data files
# What ISSUE 29 asked of a cycle cut to fit the run budget
# (benchmark/README.md), held here, where the tier-1 run sees it; the
# checks read BENCHMARK.json, the cell's traffic file and the
# configuration's file, and run nothing.

def _staged_cells():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        man = json.load(fh)
    out = []
    for entry in man["workloads"]:
        with open(os.path.join(ROOT, man["paths"][0], "workloads",
                               entry["name"] + ".json")) as fh:
            traffic = json.load(fh)
        if traffic["driver"] != "lab_phases":
            continue
        cfg_entry = next(c for c in man["configs"]
                         if c["name"] == entry["config"])
        with open(os.path.join(ROOT, cfg_entry["file"])) as fh:
            out.append((entry["name"], traffic["params"], cfg_entry,
                        json.load(fh)))
    return out


def _starts_are_earlier_in_the_cycle(params, cfg_entry, config):
    """A phase starts from ``root`` or from the goal state of a phase
    EARLIER in the cycle: the driver has no other state to give it."""
    seen = []
    for name in params["cycle"]:
        start = config["phases"][name]["start"]
        assert start == "root" or (
            start.startswith("goal of ")
            and start[len("goal of "):] in seen), (name, start, seen)
        seen.append(name)


def _traced_phases_are_staged_calls_of_the_cycle(params, cfg_entry, config):
    """``derive_root_s.suite`` and ``root_replay_events.suite`` read the
    provenance replay that only a call from a goal state makes."""
    assert params["traced_phases"]
    assert set(params["traced_phases"]) <= set(params["cycle"])
    for name in params["traced_phases"]:
        assert config["phases"][name]["start"].startswith("goal of ")


def _reduced_is_what_the_cycle_leaves_out(params, cfg_entry, config):
    assert len(set(config["reduced"])) == len(config["reduced"])
    assert set(config["reduced"]) == (set(config["phases"])
                                      - set(params["cycle"]))
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"])
    # every phase keeps its pinned answer, cut or not: the CPU tests
    # (test_search_backend.py) run them all
    assert set(config["reference"]) == set(config["phases"])


def _the_cycle_keeps_an_exact_count(params, cfg_entry, config):
    """``correct`` compares discovered counts only where the space was
    exhausted, and the control fails by that count alone (PERF.md §6)."""
    assert any(config["reference"][name]["end_condition"]
               == "SPACE_EXHAUSTED" for name in params["cycle"])


_CELL_CHECKS = [_starts_are_earlier_in_the_cycle,
                _traced_phases_are_staged_calls_of_the_cycle,
                _reduced_is_what_the_cycle_leaves_out,
                _the_cycle_keeps_an_exact_count]


@pytest.mark.parametrize("check", _CELL_CHECKS,
                         ids=[c.__name__.lstrip("_") for c in _CELL_CHECKS])
@pytest.mark.parametrize("cell", _staged_cells(), ids=lambda c: c[0])
def test_staged_cell_data(cell, check):
    check(*cell[1:])


def test_paxos3_suite_is_a_staged_cell():
    assert [c[0] for c in _staged_cells()] == ["paxos3-suite"]


# ------------------------------------------------------ the one-slot log

def test_a_one_slot_lab3_spec_traces():
    """One client, one command: the lab 3 twin's log has ONE slot, a
    size-1 field that the slot ops hand back as a [1] vector."""
    import jax.numpy as jnp

    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    p = make_paxos_protocol(n=3, n_clients=1, w=1, max_slots=1,
                            net_cap=8, timer_cap=4)
    nodes = jax.ShapeDtypeStruct((p.node_width,), jnp.int32)
    msg = jax.ShapeDtypeStruct((p.msg_width,), jnp.int32)
    timer = jax.ShapeDtypeStruct((p.timer_width,), jnp.int32)
    for out in (jax.eval_shape(p.step_message, nodes, msg),
                jax.eval_shape(p.step_timer, nodes, jnp.int32(0), timer)):
        assert out[0].shape == (p.node_width,) and out[0].dtype == jnp.int32
    # and batched, as the engine's flat vmap traces it
    batched = jax.eval_shape(
        jax.vmap(p.step_message),
        jax.ShapeDtypeStruct((4, p.node_width), jnp.int32),
        jax.ShapeDtypeStruct((4, p.msg_width), jnp.int32))
    assert batched[0].shape == (4, p.node_width)
