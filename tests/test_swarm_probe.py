"""The dfs entry's swarm probe (ISSUE 43): ``backend.probe_fleet``, the
ONE builder of ``tensor_dfs``'s fleet and of the benchmark cell
``paxos5-random``'s, held to the object checker on the CPU.

* the fleet at test25's shape — three servers under test26's two
  clients, bounds to 32 — through ``benchmark/harness/swarm_reference``:
  recorded histories replay event by event on the object state, the
  cumulative fresh count by walk depth EQUALS the object BFS's at depths
  1-2 and never exceeds it to depth 4, the guarantee counters read 0 and
  the accounting is exact; and ``fresh`` is what it says: the distinct
  rows the walkers stood on, recounted from the rows themselves;
* ``PaxosBinding`` at n = 5 binds test26's state at the probe's caps,
  takes its root as the twin's own, and decodes every message and timer
  tag (no search); the probe's twin refuses a proposal past its last
  log slot LOUDLY, and the fleet counts it or raises;
* a probe that HITS through ``dfs()`` is decoded by the probe's binding,
  and its trace step does not stand in for the caller's twin's;
* ``_rollout_probe`` and the cell's driver reach ``probe_fleet``, and
  through it ``SwarmSearch``, with the same arguments for test26's
  settings — but the fleet's stated size;
* the builder's sizes and caps redo the configuration's ``sizing``;
* the round's byte function against a hand-counted step.

The fleet is 4,096 walkers wide, not the few hundred a CPU's probe has:
the depth-2 equality holds where the first two steps, made in lock step
from the root, see all 38 states (2,048 walkers see 37, 256 see 29).
The n = 5 fleet itself (19 s to build, 85 s to compile here) is marked
``slow``."""

import dataclasses
import hashlib
import json
import os
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.harness import manifest, roofline_swarm  # noqa: E402
from benchmark.harness import swarm_reference as ref  # noqa: E402
from dslabs_tpu.tpu import backend, swarm  # noqa: E402
from dslabs_tpu.tpu.adapters.paxos import PaxosBinding  # noqa: E402
from dslabs_tpu.tpu.engine import flatten_state  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 31 + 43
CELL = "paxos5-random"
SEARCH = {"invariants": ["APPENDS_LINEARIZABLE", "LOGS_CONSISTENT"],
          "prunes": ["CLIENTS_DONE"], "max_time": None}
N3 = {"kind": "paxos", "servers": 3, "clients": 2,
      "commands_per_client": 1, "key": "foo"}
N5 = dict(N3, servers=5)


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(ROOT, CELL)


@pytest.fixture(scope="module")
def fleet():
    """The probe's fleet at the small shape, run for two rounds."""
    state = ref.build_state(N3, SEED)
    settings = ref.build_settings(dict(SEARCH, max_depth=32))
    binding = backend.resolve_binding(state).probe_binding()
    search, root, history, bound = backend.probe_fleet(
        binding, settings, state, walkers=4096, steps_per_round=8,
        strict=True)
    assert root is None and history == [] and bound is binding
    assert search.p.name == "paxos-n3-c2-w1-s3"
    assert search.p.capacity_exc == 1
    search.max_rounds = 2
    out = search.run(check_initial=False)
    return types.SimpleNamespace(settings=settings, binding=binding,
                                 search=search, out=out)


# ------------------------------------------------ against the object checker

def test_the_fleet_ends_with_its_guarantees(fleet):
    out, sd = fleet.out, fleet.out.swarm
    assert out.end_condition == "TIME_EXHAUSTED" and out.witness is None
    assert (fleet.search.p.net_cap, fleet.search.p.timer_cap) == (128, 10)
    assert (fleet.search.min_steps, fleet.search.max_steps) == (8, 32)
    assert sd["walkers"] == 4096 and sd["rounds"] == 2
    assert sd["overflow_restarts"] == sd["vis_over"] == sd["ev_rem"] == 0
    assert sd["refused"] == 0
    assert sd["explored"] == sd["unique"] + sd["revisits"] == 16 * 4096
    assert sd["probes"] == sd["restarts"] > 0
    assert out.unique_states == sd["unique"] == sum(sd["fresh_by_depth"])
    assert 0 < sd["net_peak"] <= 128 and 0 < sd["tmr_peak"] <= 10


def test_cumulative_fresh_by_depth_against_the_object_bfs(fleet):
    counts = ref.bfs_counts(N3, SEED, 4)
    assert counts == {1: 8, 2: 38, 3: 162, 4: 713}
    got = ref.cumulative(fleet.out.swarm["fresh_by_depth"])
    assert (got[1], got[2]) == (counts[1], counts[2])
    assert got[3] <= counts[3] and got[4] <= counts[4]
    assert got[16] == fleet.out.swarm["unique"] + 1


def test_recorded_histories_replay_on_the_object_checker(fleet):
    drawn = ref.walker_sample(SEED, 4096, 32)
    snap = fleet.search.walker_snapshot(drawn)
    deepest = sorted(range(32), key=lambda i: -len(snap[i][1]))[:6]
    assert len(snap[deepest[0]][1]) >= 8
    rows, keys = set(), set()
    for i in deepest:
        row, events = snap[i]
        got = ref.replay_walker(fleet.binding, fleet.search,
                                ref.build_state(N3, SEED), row, events,
                                fleet.settings.invariants)
        assert got["applied"] == got["events"] == len(events)
        assert got["violated"] is None and got["row_equal"]
        assert got["decoded"] == []
        rows |= got["rows"]
        keys |= got["keys"]
    assert len(rows) == len(keys) > 8


def test_fresh_counts_the_distinct_rows_the_walkers_stood_on(fleet):
    """Seven single steps from the root — under every walker's bound,
    before any client is done — read back row by row: the fleet's
    ``fresh`` is the number of distinct rows but the root, by the rows'
    own bytes and no fingerprint — and the harness's plain digests
    (``swarm_reference.row_digest_fn``, what the cell counts by at the
    timed size) tell apart exactly the rows their bytes do."""
    search = fleet.search
    root = np.asarray(flatten_state(search.initial_state()))[0]
    digest = ref.row_digest_fn(search.lanes)
    seen, digests = set(), [np.asarray(digest(jnp.asarray(root[None])))]
    with search.mesh:
        carry = search._init_carry(search.initial_state())
        for _ in range(7):
            carry, stats = search._round_call(carry, 1)
            rows = np.asarray(carry["rows"])
            assert np.asarray(carry["depths"]).min() >= 0
            seen |= {hashlib.blake2b(r.tobytes(), digest_size=16).digest()
                     for r in rows}
            digests.append(np.asarray(digest(carry["rows"])))
    sd = search._stats_dict(np.asarray(stats), 7, 1.0)
    assert sd["restarts"] == 0 and sd["explored"] == 7 * 4096
    seen.discard(hashlib.blake2b(root.tobytes(), digest_size=16).digest())
    assert sd["unique"] == len(seen) == ref.distinct_rows(digests) - 1


def test_the_drivers_warm_up_counts_the_rows_itself(fleet):
    from benchmark.drivers import timeboxed_swarm

    search = fleet.search
    kept = search.steps_per_round, search.max_rounds
    warm = timeboxed_swarm.warm_up(search, 7)
    assert (search.steps_per_round, search._telemetry) == (kept[0], None)
    search.max_rounds = kept[1]
    assert warm["rounds"] == 7 and warm["explored"] == 7 * 4096
    assert warm["restarts"] == 0
    assert warm["unique"] == warm["distinct_rows"] > 500


# ------------------------------------------------------ five servers, bound

@pytest.fixture(scope="module")
def five():
    state = ref.build_state(N5, SEED)
    settings = ref.build_settings(dict(SEARCH, max_depth=1000,
                                       max_time=8))
    return types.SimpleNamespace(state=state, settings=settings,
                                 binding=backend.resolve_binding(state))


def test_paxos_binding_at_five_servers(five):
    from dslabs_tpu.labs.paxos import paxos as P
    from dslabs_tpu.tpu import specs_lab3 as L3
    from dslabs_tpu.tpu.trace import MessageTemplate

    base = five.binding
    assert type(base) is PaxosBinding
    assert (base.n, base.nc, base.w, base.S) == (5, 2, 1, 2)
    assert base.build_protocol(*base.initial_caps()).name == \
        "paxos-n5-c2-w1-s2"
    # the probe's twin has a spare log slot (a hole, a duplicate)
    b = base.probe_binding()
    assert type(b) is PaxosBinding and b is not base and b.S == 3
    assert (b.spare_slots, base.spare_slots) == (1, 0)
    assert b.probe_binding() is b and b.key[:5] == ("paxos", 5, 2, 1, 3)
    assert b.key[5:] == base.key[5:] and b.twin_key() != base.twin_key()
    assert b.L != base.L and b.cmd_ids == base.cmd_ids
    b.check_settings(five.settings)
    assert b.initial_caps() == (32, 6) and b.probe_caps() == (2048, 10)
    assert b.derive_root(None, five.state) == (None, [])
    p = b.build_protocol(*b.probe_caps())
    assert (p.name, p.n_nodes, p.net_cap, p.timer_cap) == (
        "paxos-n5-c2-w1-s3", 7, 2048, 10)
    # the probe's twin refuses loudly, the strict BFS's as it always did
    assert p.capacity_exc == L3.EXC_LOG_FULL == 1
    assert base.build_protocol(*base.initial_caps()).capacity_exc == 0
    for pred in five.settings.invariants + five.settings.prunes:
        assert callable(backend.translate_predicate(b, pred))
    pad = [0] * p.msg_width

    def msg(tag, frm, to, *payload):
        rec = [tag, frm, to, *payload]
        return b._decode_message(rec + pad[:p.msg_width - len(rec)])

    want = {L3.REQ: P.PaxosRequest, L3.P1A: P.P1a, L3.P1B: P.P1b,
            L3.P2A: P.P2a, L3.P2B: P.P2b, L3.HB: P.Heartbeat,
            L3.HBR: P.HeartbeatReply, L3.CREQ: P.CatchupRequest,
            L3.CREP: P.CatchupReply}
    for tag, cls in want.items():
        payload = (1, 1) if tag == L3.REQ else (7, 1, 1)
        frm, to, m = msg(tag, 5 if tag == L3.REQ else 4, 0, *payload)
        assert isinstance(m, cls), tag
        assert (str(frm), str(to)) == (
            "client1" if tag == L3.REQ else "server5", "server1")
    frm, to, m = msg(L3.REPLY, 2, 6, 1, 1)
    assert isinstance(m, MessageTemplate) and m.cls is P.PaxosReply
    assert (str(frm), str(to)) == ("server3", "client2")
    for tag, cls in ((L3.T_ELECTION, P.ElectionTimer),
                     (L3.T_HEARTBEAT, P.HeartbeatTimer)):
        to, timer, lo, hi = b._decode_timer(4, [tag, 0, 0, 7])
        assert isinstance(timer, cls) and str(to) == "server5" and lo <= hi
    to, timer, _lo, _hi = b._decode_timer(6, [L3.T_CLIENT, 0, 0, 1])
    assert isinstance(timer, P.ClientTimer) and str(to) == "client2"


class _Built(Exception):
    pass


def _constructor_args(monkeypatch, call):
    """What ``call()`` hands ``SwarmSearch``, the constructor stopped."""
    seen = {}

    def stop(protocol, **kw):
        seen.update(kw, protocol=protocol)
        raise _Built

    monkeypatch.setattr(swarm, "SwarmSearch", stop)
    with pytest.raises(_Built):
        call()
    return seen


def test_the_lab_entry_and_the_cell_build_one_fleet(five, cell, monkeypatch):
    from benchmark.drivers import timeboxed_swarm
    from benchmark.harness.runner import Context

    assert timeboxed_swarm.probe_fleet is backend.probe_fleet
    lab = _constructor_args(monkeypatch, lambda: backend.probe_fleet(
        five.binding, five.settings, five.state))
    ctx = Context(cell=cell, dev={}, seed=SEED, trace=False, events=None,
                  tracer=None)
    bench = _constructor_args(monkeypatch,
                              lambda: timeboxed_swarm.build_fleet(ctx))
    fleet_cfg = cell.config["fleet"]
    assert bench.pop("walkers_per_device") == fleet_cfg["walkers"]
    assert bench.pop("steps_per_round") == fleet_cfg["steps_per_round"]
    assert bench.pop("visited_cap") == fleet_cfg["visited_cap"]
    assert bench.pop("strict") is True and lab.pop("strict") is False
    # the lab's fleet follows its budget: a third of max_time(8) at a
    # CPU's rate pays for 128 walkers of 1,000 steps and no more
    budget = backend.probe_walker_steps(backend.probe_secs(five.settings))
    assert backend.probe_secs(five.settings) == pytest.approx(8 / 3)
    assert budget == 8000
    assert lab.pop("walkers_per_device") == backend.probe_walkers(
        budget, 1000) == 128
    assert lab.pop("steps_per_round") == backend.probe_round(128) == 64
    assert lab.pop("visited_cap") == backend.probe_table(budget) == 1 << 18
    # the walk step's blocks follow the twin's caps and the width alone:
    # at the cell's width the lab entry's fleet walks in the cell's
    # blocks, and the cell's fleet is wide enough to have any
    blocks = []
    for args in (lab, bench):
        p = args.pop("protocol")
        assert (p.name, p.net_cap, p.timer_cap) == (
            "paxos-n5-c2-w1-s3", 2048, 10)
        blocks.append(fleet_cfg["walkers"] // swarm.step_block_rows(
            fleet_cfg["walkers"], p.net_cap, p.msg_width))
        assert list(p.invariants) == [q.name for q
                                      in five.settings.invariants]
        assert not p.goals and len(p.prunes) == 1
        assert args.pop("mesh").devices.size == 1
    assert blocks[0] == blocks[1] > 1
    assert lab == bench == {"max_steps": 1000, "seed": 0}


def test_rollout_probe_builds_through_probe_fleet(five, monkeypatch):
    seen = []

    def builder(binding, settings, state, **kw):
        seen.append((binding, settings, state, kw))
        return None

    monkeypatch.setattr(backend, "probe_fleet", builder)
    trip, secs = backend._rollout_probe(five.binding, five.settings,
                                        five.state)
    assert trip is None and secs >= 0
    assert seen == [(five.binding, five.settings, five.state, {})]


def test_the_probes_size_follows_its_budget():
    """Width and table from the walker steps the budget pays for: a
    CPU's rate never pays for more than the least fleet; a chip's gives
    test26's ``max_time(8)`` 256 walkers of 1,000 steps, a ten-second
    probe of 192 steps 4,096, and never more than 8,192."""
    from dslabs_tpu.search.settings import SearchSettings

    cpu, chip = (backend.PROBE_WALKER_STEPS_PER_SEC[k]
                 for k in ("cpu", "tpu"))
    assert backend.probe_secs(SearchSettings()) == backend.PROBE_SECS == 10
    assert backend.probe_secs(SearchSettings().max_time(60)) == 10
    for depth in (1, 8, 192, 1000):
        assert backend.probe_walkers(int(10 * cpu), depth) == 128
    assert backend.probe_walkers(int(8 / 3 * chip), 1000) == 256
    assert backend.probe_walkers(int(10 * chip), 192) == 4096
    assert backend.probe_walkers(int(10 * chip), 8) == 4096
    assert backend.probe_walkers(10 ** 9, 192) == 8192
    assert [backend.probe_round(k) for k in (128, 256, 4096, 8192)] == [
        64, 64, 8, 4]
    assert backend.probe_table(int(10 * cpu)) == 1 << 18
    assert backend.probe_table(int(8 / 3 * chip)) == 1 << 20
    assert backend.probe_table(int(10 * chip)) == 1 << 22


# ------------------------------------------------ a log with no slot left

def test_a_refused_proposal_is_counted_and_a_strict_fleet_raises():
    """A twin of ONE log slot under two clients: the leader that has
    chosen the first command is handed the second and has no slot for
    it.  Refusing loudly, the step is a truncated one — the walker
    restarts, ``refused`` counts it, nothing is inserted for it — and a
    strict fleet raises; the silent twin's fleet sees nothing."""
    from dslabs_tpu.tpu.engine import CapacityOverflow
    from dslabs_tpu.tpu.sharded import make_mesh
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    def fleet_of(loud):
        p = make_paxos_protocol(n=3, n_clients=2, w=1, max_slots=1,
                                net_cap=64, timer_cap=8,
                                loud_refusal=loud)
        # (the spec's own predicates are written for two slots and up)
        p = dataclasses.replace(p, invariants={}, goals={})
        s = swarm.SwarmSearch(p, mesh=make_mesh(1), walkers_per_device=256,
                              max_steps=48, seed=0, steps_per_round=48)
        s.max_rounds = 2
        return s

    loud = fleet_of(True)
    assert loud.p.capacity_exc == 1
    sd = loud.run(check_initial=False).swarm
    assert sd["refused"] > 0 and sd["overflow_restarts"] == 0
    assert sd["explored"] == sd["unique"] + sd["revisits"]
    assert sd["restarts"] == sd["probes"] + sd["refused"]
    loud.strict = True
    with pytest.raises(CapacityOverflow, match="refused by the twin"):
        loud.run(check_initial=False)
    quiet = fleet_of(False)
    assert quiet.p.capacity_exc == 0
    out = quiet.run(check_initial=False)
    assert out.swarm["refused"] == 0
    assert out.end_condition == "TIME_EXHAUSTED"


# ------------------------------------------------- a probe that hits

def test_a_probe_hit_is_decoded_by_the_probes_binding(monkeypatch):
    """``dfs()`` on three servers under an invariant a walk breaks ten
    events down: the probe hits, on the three-slot twin, and the
    witness is decoded, replayed and minimised through the PROBE's
    binding (the caller's reads a two-slot layout).  The trace step the
    probe kept for its twin at (128, 10) is not the one a BFS of the
    caller's binding gets at that rung, the ladder's top."""
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.search import dfs
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.predicates import NONE_DECIDED
    from dslabs_tpu.utils.flags import GlobalSettings

    monkeypatch.setattr(GlobalSettings, "search_backend", "tensor")
    backend.clear_cache()
    state = ref.build_state(N3, SEED)
    settings = (SearchSettings().add_invariant(NONE_DECIDED)
                .set_max_depth(40).max_time(30))
    res = dfs(state, settings)
    assert res.end_condition == EndCondition.INVARIANT_VIOLATED
    assert res.probe_secs is not None
    out = res.tensor_outcome
    assert out.swarm["walkers"] == 128 and out.witness.object_verified
    bad = res.invariant_violating_state
    assert any(w.results for w in bad.client_workers().values())
    assert NONE_DECIDED.check(bad).value is False
    base = backend.resolve_binding(state)
    probe = base.probe_binding()
    assert base.probe_caps() == probe.probe_caps() == (128, 10)
    lanes = {}
    for b in (base, probe):
        p = b.build_protocol(128, 10)
        step = backend._trace_step(b, p)
        lanes[b.S] = step.in_avals[0][0].shape[0]
        assert lanes[b.S] == (p.node_width + p.net_cap * p.msg_width
                              + p.n_nodes * p.timer_cap * p.timer_width
                              + 1)
    assert lanes[2] < lanes[3]
    # the probe's own step was kept by the hit; the caller's was not it
    info = backend.cache_info()
    assert info["step"] == 2 and info["hits"] >= 1


# --------------------------------------------------------------- the sizing

def p_msg_width(binding):
    return binding.build_protocol(*binding.probe_caps()).msg_width


def test_the_configurations_sizing_is_the_builders(cell, five):
    cfg = cell.config
    proto, fleet_cfg, sizing = cfg["protocol"], cfg["fleet"], cfg["sizing"]
    binding = five.binding.probe_binding()
    assert [proto["net_cap"], proto["timer_cap"]] == list(
        binding.probe_caps())
    assert proto["log_slots"] == binding.S == 3
    assert p_msg_width(binding) == proto["msg_width"] == 8   # one tile
    p = binding.build_protocol(*binding.probe_caps())
    lanes = (p.node_width + p.net_cap * p.msg_width
             + p.n_nodes * p.timer_cap * p.timer_width + 1)
    assert (proto["name"], proto["nodes"], proto["node_width"]) == (
        p.name, p.n_nodes, p.node_width)
    assert (proto["lanes"], proto["row_bytes"]) == (lanes, 4 * lanes)
    K = fleet_cfg["walkers"]
    window = sizing["window"]
    # the cell states its width; its dispatch and its table are the lab
    # entry's rules for that width and for the window's walker steps
    assert K == backend.PROBE_WALKERS_MAX
    assert fleet_cfg["steps_per_round"] == backend.probe_round(K) == 4
    assert fleet_cfg["visited_cap"] == backend.probe_table(
        window["explored"]) == 1 << 23
    depth = cfg["search"]["max_depth"]
    assert cfg["walk_policy"]["depth_bounds"] == [depth // 4, depth]
    assert sizing["rows_bytes"] == K * 4 * lanes
    assert sizing["histories_bytes"] == K * depth * 4
    assert sizing["table_bytes"] == 16 * fleet_cfg["visited_cap"]
    # the table holds twice the window's fresh keys at a third full
    assert 2 * 3 * window["fresh"] <= fleet_cfg["visited_cap"]
    assert window["table_fill"] == round(
        window["fresh"] / fleet_cfg["visited_cap"], 4)
    # a dispatch ends in time, and a walker makes enough steps in the
    # window for the shortest bounds to bind (not for three probes a
    # walker: ``width`` says why)
    assert window["dispatch_s"] <= 0.3
    assert window["steps_per_walker"] == (
        window["rounds"] * fleet_cfg["steps_per_round"])
    assert window["steps_per_walker"] >= 1.5 * (depth // 4)
    assert window["explored"] <= K * window["steps_per_walker"]
    assert window["deepest"] <= window["steps_per_walker"]
    # the caps hold what the window's deepest walk held, with room
    assert window["net_peak"] <= 0.75 * proto["net_cap"]
    assert window["tmr_peak"] <= proto["timer_cap"]
    step = sizing["walker_step_us"]
    assert step["walkers_8192"] < 7 < 13 < step["walkers_16384_net_cap_4096"]
    assert step["walkers_8192"] < step["walkers_8192_three_slots"] < step[
        "walkers_8192_four_slots"]
    # the warm-up ends under the shortest bound: no walker restarts
    steps = cfg["reference"]["warmup_steps"]
    assert steps < depth // 4
    trip = cfg["reference"]["tripwire"]
    assert trip["steps"] == steps and trip["explored"] == K * steps
    assert 0 < trip["unique"] < trip["explored"]
    assert cfg["reference"]["counts"]["2"] == 117
    assert cfg["reduced"] == []


def test_the_manifest_holds_the_cell_and_its_metrics():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # the seventh configuration and the eighth cell (PR 47 appended its
    # own behind them)
    assert man["configs"][6]["name"] == man["workloads"][7]["config"]
    assert man["workloads"][7]["name"] == CELL
    new = {m["name"]: m for m in man["per_layer"]
           if m.get("workloads") == [CELL]}
    assert set(new) == {"walk_us_per_step.swarm", "fresh_pct.swarm",
                        "restarts_pct.swarm", "round_roofline.swarm",
                        "blocks_per_step.swarm"}
    assert {m["moves"] for m in new.values()} == {"states_per_s"}
    assert {m["layer"] for m in new.values()} == {"random walk", "kernels"}
    for name in ("states_per_s", "compile_s", "trace_lower_s",
                 "peak_hbm_gb", "exe_store_hit_pct"):
        entry = next(m for m in man["end_to_end"] + man["per_layer"]
                     if m["name"] == name)
        assert CELL in entry["workloads"][-2:]


# --------------------------------------------------------- the byte function

def test_round_roofline_against_a_hand_counted_step():
    # one walker at a 100-lane row: 400 bytes read, 400 written, one
    # history word, a 16-byte key, a bucket of eight 16-byte slots
    assert roofline_swarm.step_bytes(400) == 400 + 400 + 4 + 16 + 128
    assert roofline_swarm.necessary_bytes(10, 400) == 9480
    # 1,000 walker steps of 59,916-byte rows in 1 ms on one chip at
    # 819 GB/s: 119,980,000 bytes need 146.5 us
    pct = roofline_swarm.roofline_pct(1000, 59916, 1e-3, 819e9, 1)
    assert pct == pytest.approx(100 * 119_980_000 / 819e9 / 1e-3)
    assert 14.6 < pct < 14.7
    assert roofline_swarm.roofline_pct(1000, 59916, 1e-3, 819e9, 4) \
        == pytest.approx(pct / 4)


# ------------------------------------------------- five servers, the fleet

@pytest.mark.slow
def test_the_five_server_fleet_walks_and_replays(five):
    binding = five.binding.probe_binding()
    search, root, history, _ = backend.probe_fleet(
        binding, five.settings, five.state, walkers=512,
        steps_per_round=8, strict=True)
    assert root is None and (search.min_steps, search.max_steps) == (
        250, 1000)
    search.max_rounds = 4
    out = search.run(check_initial=False)
    sd = out.swarm
    assert out.end_condition == "TIME_EXHAUSTED"
    assert sd["overflow_restarts"] == sd["vis_over"] == sd["ev_rem"] == 0
    assert sd["refused"] == 0
    assert sd["explored"] == sd["unique"] + sd["revisits"]
    assert ref.cumulative(sd["fresh_by_depth"])[1] == 14
    row, events = search.walker_snapshot([5])[0]
    got = ref.replay_walker(binding, search,
                            ref.build_state(N5, SEED), row, events,
                            five.settings.invariants)
    assert got["applied"] == got["events"] == 32
    assert got["row_equal"] and got["decoded"] == []
