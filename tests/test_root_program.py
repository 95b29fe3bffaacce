"""A run's root is prepared by ONE compiled program (ISSUE 39,
``TensorSearch._root_program``, ``ShardedTensorSearch._root``).

What is held here:

* the program's row, key and hits equal what the eager helpers give
  (``flatten_state``, ``_canonical_root_fp``, one ``vmap`` a predicate),
  bit for bit, on the lab 1, lab 3 and lab 4 twins and on the twin that
  declares a symmetry group (reduction off and on), for the twin's own
  initial state and for a staged root handed in as host numpy and as
  device arrays;
* a root that violates an invariant, or meets a goal, ends the run with
  the outcome the eager check gives, invariant before goal;
* a second ``run()`` on a warm engine traces and compiles nothing and
  binds no eager primitive before ``dispatch.init``, and the only module
  that runs before it is ``jit_root_program``;
* ``initial_state()`` is built once an engine, and an over-cap initial
  timer set still raises ``CapacityOverflow``.
"""

import dataclasses
import glob

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dslabs_tpu.tpu import compile_cache, engine  # noqa: E402
from dslabs_tpu.tpu import telemetry as tel_mod  # noqa: E402
from dslabs_tpu.tpu.engine import (CapacityOverflow, TensorSearch,  # noqa: E402
                                   flatten_state)
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh  # noqa: E402


def _lab1():
    from dslabs_tpu.tpu.protocols.clientserver import \
        make_clientserver_protocol

    return make_clientserver_protocol(n_clients=2, w=3)


def _lab3():
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    return make_paxos_protocol(n=3, n_clients=2, w=1, max_slots=3,
                               net_cap=32, timer_cap=6)


def _lab4():
    from dslabs_tpu.tpu.specs_lab4 import make_shardstore_protocol

    return make_shardstore_protocol([[1], [2]], net_cap=48, timer_cap=6)


def _acceptors():
    from dslabs_tpu.tpu.specs import paxos_spec

    return paxos_spec(3).compile()


TWINS = {"lab1": (_lab1, None), "lab3-paxos": (_lab3, None),
         "lab4-shardstore": (_lab4, None),
         "acceptors-sym-off": (_acceptors, False),
         "acceptors-sym-on": (_acceptors, True)}


def _engine(protocol, symmetry=None, **kw):
    return ShardedTensorSearch(
        protocol, make_mesh(1), chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, symmetry=symmetry, **kw)


@pytest.fixture(scope="module", params=list(TWINS))
def twin(request):
    """An engine on the twin, and a staged root of it."""
    make, symmetry = TWINS[request.param]
    eng = _engine(make(), symmetry)
    assert (eng._canon is not None) == bool(symmetry)
    return eng, _first_successor(eng)


def _first_successor(eng):
    """The twin's initial row stepped by its first deliverable event
    that changes it."""
    row = np.asarray(flatten_state(eng.initial_state()))[0]
    step = jax.jit(eng._step_one)
    for ev in range(eng._grid_events(eng.p)):
        nxt, ok, over = step(jnp.asarray(row), jnp.int32(ev))
        if bool(ok) and not (np.asarray(nxt) == row).all():
            assert not int(over)
            return np.asarray(nxt)
    raise AssertionError("no event changes the root")


def _eager(eng, state):
    """What the eager helpers give for a batch-1 device state."""
    p = eng.p
    return (np.asarray(flatten_state(state)),
            np.asarray(eng._canonical_root_fp(state)),
            [bool(jax.vmap(fn)(state)[0]) for fn in p.invariants.values()],
            [bool(jax.vmap(fn)(state)[0]) for fn in p.goals.values()])


@pytest.mark.parametrize("root", ["initial", "staged-host", "staged-device"])
def test_root_program_equals_the_eager_helpers(twin, root):
    eng, staged = twin
    if root == "initial":
        state = eng.initial_state()
    elif root == "staged-host":
        state = eng.unflatten_rows(staged[None])
        assert all(isinstance(v, np.ndarray) for v in state.values())
    else:
        state = eng.unflatten_rows(jnp.asarray(staged[None]))
        assert all(isinstance(v, jax.Array) for v in state.values())
    row0, fp0, inv, goal = eng._root(state)
    want = _eager(eng, jax.tree.map(jnp.asarray, state))
    assert row0.dtype == np.int32 and row0.shape == (1, eng.lanes)
    assert fp0.dtype == np.uint32 and fp0.shape == (1, 4)
    assert inv.dtype == goal.dtype == np.bool_
    assert (row0 == want[0]).all() and (fp0 == want[1]).all()
    assert (inv.tolist(), goal.tolist()) == want[2:]
    # the warm run's call skips the hits, not the program
    short = eng._root(state, hits=False)
    assert len(short) == 2
    assert (short[0] == row0).all() and (short[1] == fp0).all()
    # and the carry's address of the root is read from that key
    (row, key0), owner, home = eng._root_ids(row0, fp0)
    assert (row == row0[0]).all() and key0.dtype == np.uint32
    assert owner == int(fp0[0, 0]) % eng.n_devices
    assert 0 <= home < eng.v_cap


def test_roots_of_one_orbit_share_a_key_only_under_the_reduction():
    """The root's PREPARE delivered to the first acceptor or to the last
    gives two states of one orbit: as roots their rows differ, their
    keys differ with the reduction off and are one key with it on."""
    make, _ = TWINS["acceptors-sym-on"]
    p = make()
    off, on = _engine(p, False), _engine(p, True)
    row = np.asarray(flatten_state(off.initial_state()))
    net = off.unflatten_rows(row)["net"][0]
    occupied = [i for i in range(net.shape[0]) if net[i][0] != 2**31 - 1]
    roots = []
    for slot in (occupied[0], occupied[-1]):
        nxt, ok, _ = off._step_one(jnp.asarray(row[0]), jnp.int32(slot))
        assert bool(ok)
        state = off.unflatten_rows(np.asarray(nxt)[None])
        roots.append((off._root(state), on._root(state)))
    (a_off, a_on), (b_off, b_on) = roots
    assert (a_off[0] == a_on[0]).all() and (b_off[0] == b_on[0]).all()
    assert not (a_off[0] == b_off[0]).all()
    assert not (a_off[1] == b_off[1]).all()
    assert (a_on[1] == b_on[1]).all()


# ------------------------------------------------ the verdict at the root

def _pingpong(invariants=None, goals=None):
    pp = make_pingpong_protocol(workload_size=2)
    return dataclasses.replace(
        pp, invariants=pp.invariants if invariants is None else invariants,
        goals=pp.goals if goals is None else goals)


def _holds(s):
    return s["exc"] == 0


def _fails(s):
    return s["exc"] != 0


@pytest.mark.parametrize("invariants,goals,want", [
    ({"HOLDS": _holds, "BROKEN": _fails, "BROKEN_TOO": _fails},
     {"MET": _holds},
     ("INVARIANT_VIOLATED", "BROKEN")),
    ({"HOLDS": _holds}, {"UNMET": _fails, "MET": _holds, "MET_TOO": _holds},
     ("GOAL_FOUND", "MET")),
    ({}, {"MET": _holds}, ("GOAL_FOUND", "MET")),
], ids=["invariant-before-goal", "first-goal-hit", "no-invariants"])
def test_a_root_that_decides_ends_the_run_as_the_eager_check_does(
        invariants, goals, want):
    eng = _engine(_pingpong(invariants, goals), record_trace=True)
    out = eng.run()
    assert (out.end_condition, out.predicate_name) == want
    assert (out.states_explored, out.unique_states, out.depth) == (1, 1, 0)
    assert out.levels is None and out.trace is None
    eager = eng._check_initial(eng.initial_state(), 0.0)
    assert (eager.end_condition, eager.predicate_name) == want
    for mine, theirs in ((out.violating_state, eager.violating_state),
                         (out.goal_state, eager.goal_state)):
        assert (mine is None) == (theirs is None)
        if mine is not None:
            assert set(mine) == set(theirs)
            for k in mine:
                assert isinstance(mine[k], np.ndarray)
                assert mine[k].shape == np.shape(theirs[k])
                assert (mine[k] == np.asarray(theirs[k])).all()
    # the state returned is the root the trace replays from
    assert (flatten_state(eng._trace_root)
            == flatten_state(eng.initial_state())).all()
    # a warm run does not ask: the search goes on past the root
    assert eng.run(check_initial=False).depth >= 1


def test_a_staged_root_is_checked_too():
    """The goal holds at a staged root (depth 0 from there) and not at
    the twin's own (depth 1 from there)."""
    pp = make_pingpong_protocol(workload_size=2)
    eng = _engine(dataclasses.replace(pp, invariants={}, goals={}))
    row = np.asarray(flatten_state(eng.initial_state()))[0]
    staged = _first_successor(eng)
    lane = int(np.nonzero(staged != row)[0][0])
    value = int(staged[lane])

    def reached(s):
        return flatten_state(jax.tree.map(lambda x: x[None], s))[
            0, lane] == value

    eng = _engine(dataclasses.replace(pp, invariants={},
                                      goals={"REACHED": reached}))
    out = eng.run()
    assert (out.end_condition, out.depth) == ("GOAL_FOUND", 1)
    out = eng.run(initial=eng.unflatten_rows(staged[None]))
    assert (out.end_condition, out.predicate_name) == ("GOAL_FOUND",
                                                       "REACHED")
    assert (out.states_explored, out.unique_states, out.depth) == (1, 1, 0)
    assert (flatten_state(out.goal_state)[0] == staged).all()
    assert eng._root_fp == tuple(np.asarray(eng._canonical_root_fp(
        eng.unflatten_rows(jnp.asarray(staged[None]))))[0].tolist())


# ------------------------------------------------------- a warm engine

def _pruned_pingpong():
    pp = make_pingpong_protocol(workload_size=2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


@pytest.fixture(scope="module")
def warm():
    eng = _engine(_pruned_pingpong(), max_depth=8, record_trace=True)
    first = eng.run()
    assert first.end_condition == "SPACE_EXHAUSTED"
    return eng, first


@pytest.mark.parametrize("aot", [False, True], ids=["lazy-jit", "aot"])
def test_a_second_run_compiles_nothing_and_binds_no_eager_primitive(
        monkeypatch, warm, aot):
    """JAX's eager-dispatch seam is ``EvalTrace.process_primitive``:
    every primitive bound outside a trace, a jitted function's own
    ``jit`` bind on a call its C++ cache misses included, passes it."""
    from jax._src import core

    eng, first = warm
    if aot:
        eng = _engine(_pruned_pingpong(), max_depth=8, record_trace=True)
        eng.aot_warmup()
    bound, before_init = [], [True]
    real = core.EvalTrace.process_primitive

    def spy(self, primitive, args, params):
        if before_init[0]:
            bound.append(primitive.name)
        return real(self, primitive, args, params)

    def hook(tag, fn, *args):
        if tag == "sharded.init":
            before_init[0] = False
        return fn(*args)

    monkeypatch.setattr(core.EvalTrace, "process_primitive", spy)
    monkeypatch.setattr(eng, "_dispatch_hook", hook, raising=False)
    before = compile_cache.totals()
    again = eng.run()
    assert compile_cache.totals() == before
    assert not before_init[0] and bound == []
    assert (again.end_condition, again.states_explored,
            again.unique_states, again.depth) == (
        first.end_condition, first.states_explored, first.unique_states,
        first.depth)


def test_only_the_root_program_runs_before_the_carry_is_built(
        warm, tmp_path):
    """The same from the profile's side: of the modules that ran before
    ``dispatch.init`` opened, every one is the root program."""
    eng, _first = warm
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    notes, modules = {}, []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                name = str(ev.name)
                if name.startswith(tel_mod.ANNOTATION_PREFIX):
                    notes.setdefault(
                        name[len(tel_mod.ANNOTATION_PREFIX):],
                        float(ev.start_ns))
                else:
                    stats = dict(ev.stats)
                    if "hlo_module" in stats:
                        modules.append((float(ev.start_ns),
                                        str(stats["hlo_module"])))
    assert notes["search.start"] < notes["search.carry"] \
        < notes["dispatch.init"]
    early = {m for t, m in modules if t < notes["dispatch.init"]}
    assert early == {"jit_root_program"}
    assert {"jit_init_carry", "jit_superstep"} <= {m for _t, m in modules}


# -------------------------------------------------- the twin's own root

@pytest.mark.parametrize("make", [
    lambda p: TensorSearch(p, chunk=16, frontier_cap=1 << 8,
                           visited_cap=1 << 10),
    _engine], ids=["device-engine", "sharded-engine"])
def test_initial_state_is_built_once_an_engine(monkeypatch, make):
    eng = make(_pruned_pingpong())
    builds = []
    real = type(eng)._build_initial_state

    def counted(self):
        builds.append(1)
        return real(self)

    monkeypatch.setattr(type(eng), "_build_initial_state", counted)
    a, b = eng.initial_state(), eng.initial_state()
    assert builds == [1]
    assert a is not b and set(a) == set(b) == {"nodes", "net", "timers",
                                               "exc"}
    assert all((np.asarray(a[k]) == np.asarray(b[k])).all() for k in a)
    # a caller that rebinds a leaf of what it was handed leaves the
    # engine's own alone
    a["net"] = engine.drop_pending_messages(a)["net"]
    assert (np.asarray(eng.initial_state()["net"])
            == np.asarray(b["net"])).all()
    other = make(_pruned_pingpong())
    other.initial_state()
    assert builds == [1, 1]


def test_an_over_cap_initial_timer_set_still_raises():
    pp = _pruned_pingpong()
    assert len(pp.init_timers()) == 1
    crowded = dataclasses.replace(
        pp, init_timers=lambda: np.concatenate(
            [np.asarray(pp.init_timers(), np.int32)] * (pp.timer_cap + 1)))
    eng = _engine(crowded)
    for _ in range(2):          # nothing half-built is kept
        with pytest.raises(CapacityOverflow, match="initial timers"):
            eng.initial_state()
    with pytest.raises(CapacityOverflow, match="initial timers"):
        eng.run()
