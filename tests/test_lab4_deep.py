"""Lab 4's part 1 store under the DEEP strict search, as the benchmark's
configuration ``lab4-shardstore-g2c2`` states it and its driver
``timeboxed_bfs_lab4`` builds it — ShardStorePart1Test test12's
deployment (two clients over two one-server groups, the config walk and
the shard handoff), each piece small enough for the tier-1 run:

* the twin through ``ShardedTensorSearch`` (strict, packed codec — what
  every cell and the lab entry run; ``tests/test_tpu_lab4.py`` holds it
  to the object checker through raw ``TensorSearch`` only) on 1 and 2
  virtual devices, against the object checker from the driver's joined
  state at depths 1-3 on two seeds, and against ``run_host`` at depth 4;
* the same state through ``backend.tensor_bfs``: the first lab-entry run
  of ``ShardStoreBinding`` with two clients;
* the driver's ``verify`` on recorded level counts: correct on the true
  ones, not correct when a count is off by one or when the state the
  reference starts from is not the twin's root;
* the cell's data files hold together and hold to ``BENCHMARK.json``.
"""

import dataclasses
import json
import os
import types

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.search.search import BFS  # noqa: E402
from dslabs_tpu.tpu import backend  # noqa: E402
from dslabs_tpu.tpu import telemetry as tel_mod  # noqa: E402
from dslabs_tpu.tpu.engine import TensorSearch  # noqa: E402
from dslabs_tpu.tpu.sharded import (ShardedTensorSearch,  # noqa: E402
                                    make_mesh)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [2**31 + 36, 36]
CELL = "shardkv-deep"


@pytest.fixture(scope="module")
def cell():
    from benchmark.harness import manifest

    return manifest.load_cell(ROOT, CELL)


def _ctx(cell, seed, **config):
    """What a driver is handed, as far as ``verify`` reads it."""
    return types.SimpleNamespace(
        cell=dataclasses.replace(cell, config=dict(cell.config, **config)),
        seed=seed, dev={"platform": "cpu"}, note=lambda msg: None)


@pytest.fixture(scope="module")
def joined(cell):
    """The driver's joined state a seed: the Join phase's goal state by
    the object checker, plus both clients."""
    return {seed: cell.driver.joined_state(_ctx(cell, seed))
            for seed in SEEDS}


@pytest.fixture(scope="module")
def object_counts(cell, joined):
    """The object checker's cumulative unique counts at depths 1-3 below
    the joined state, a seed."""
    return {seed: cell.driver.reference_counts(_ctx(cell, seed),
                                               joined[seed], 3)
            for seed in SEEDS}


@pytest.fixture(scope="module")
def protocol(cell):
    """The twin as the cell's supervisor builds it (goals stripped)."""
    from benchmark.drivers.timeboxed_bfs import build_protocol

    return build_protocol(cell.config["protocol"])


# ------------------------------------------------ the twin, sharded + packed

def test_the_configuration_names_the_twin_it_builds(cell, protocol):
    spec = cell.config["protocol"]
    search = ShardedTensorSearch(protocol, make_mesh(1),
                                 chunk_per_device=256, strict=True)
    assert search.p.name == spec["name"]
    assert search.lanes == spec["lanes"]
    assert search.bytes_per_state == spec["packed_bytes_per_state"]
    assert not search.p.goals
    # the packed codec is on: rows travel as words, not lanes
    assert search.plane * 4 == spec["packed_bytes_per_state"]
    assert (search.p.net_cap, search.p.timer_cap) == (
        spec["kwargs"]["net_cap"], spec["kwargs"]["timer_cap"])


@pytest.mark.parametrize("n_devices", [1, 2])
def test_sharded_packed_search_counts_what_the_object_checker_counts(
        cell, protocol, object_counts, n_devices):
    """Depths 1-3 relative to the joined state: 11 / 70 / 342, on both
    seeds (the twin is value-blind: one device run stands for both)."""
    eng = cell.config["engine"]
    out = ShardedTensorSearch(
        protocol, make_mesh(n_devices), chunk_per_device=256,
        frontier_cap=1 << 12, visited_cap=1 << 15, max_depth=3,
        strict=True, ev_budget=tuple(eng["ev_budget"])).run()
    got = {lv["depth"]: lv["unique"] for lv in out.levels}
    pinned = {int(d): n for d, n in cell.config["reference_counts"].items()}
    for seed in SEEDS:
        assert got == object_counts[seed] == {d: pinned[d] for d in got}
    assert got == {1: 11, 2: 70, 3: 342}
    assert (out.dropped, out.visited_overflow, out.retries) == (0, 0, 0)
    assert out.bytes_per_state == cell.config["protocol"][
        "packed_bytes_per_state"]


def test_sharded_packed_search_equals_run_host_at_depth_4(cell, protocol):
    eng = cell.config["engine"]
    out = ShardedTensorSearch(
        protocol, make_mesh(2), chunk_per_device=256,
        frontier_cap=1 << 12, visited_cap=1 << 15, max_depth=4,
        strict=True, ev_budget=tuple(eng["ev_budget"])).run()
    host = TensorSearch(protocol, chunk=256, max_depth=4).run_host()
    assert (out.unique_states == host.unique_states
            == cell.config["reference_counts"]["4"] == 1431)
    assert out.states_explored == host.states_explored
    # no chunk of these levels held a state with more valid events than
    # the configuration's window: no chunk step was re-run
    frontier = 1
    for lv in out.levels:
        assert lv["chunks"] == -(-frontier // 256), lv
        frontier = max(lv["per_device"]["frontier"])


# -------------------------------------------------------- the lab entry

def test_two_clients_through_tensor_bfs(cell, joined, object_counts):
    """The joined state and test12's settings through the lab entry
    point: ``ShardStoreBinding`` with two clients, its root validated,
    and the object checker's count at depth + 3."""
    state = joined[SEEDS[0]]
    settings = cell.driver.lab4_phases.build_settings(
        dict(cell.config["search"], max_depth=3), state)
    assert settings.max_depth == state.depth + 3
    tel = tel_mod.Telemetry(ring=1 << 12)
    with tel_mod.use(tel):
        results = backend.tensor_bfs(state, settings)
    obj = BFS(settings).run(state)
    assert (results.end_condition.name == obj.end_condition.name
            == "SPACE_EXHAUSTED")
    assert (results.discovered_count == obj.discovered_count
            == object_counts[SEEDS[0]][3] == 342)
    out = results.tensor_outcome
    assert (out.dropped, out.visited_overflow, out.retries) == (0, 0, 0)
    phases = [r for r in tel.ring if r["t"] == "phase"]
    assert [r["twin"] for r in phases if r["name"] == "entry.bind"] == [
        "shardstore"]
    assert [r["parent"] for r in phases
            if r["name"] == "entry.root.validate"] == ["entry.derive_root"]
    assert not [r for r in phases if r["name"] == "entry.root.replay"]


# ------------------------------------------------------ the driver's verify

def _measured(levels):
    return {"outcome": dict(platform="cpu", mesh_width=1, dropped=0,
                            visited_overflow=0, retries=0, failovers=0,
                            knob_retries=0),
            "levels": [{"depth": d, "unique": n}
                       for d, n in sorted(levels.items())]}


def _verdict(cell, levels, monkeypatch=None, state=None):
    """``(failed check names, all check names)`` of the driver's
    ``verify`` on recorded level counts, the live reference cut to
    depth 3 (1.5 s of object checker; the cell's 4 costs 7)."""
    ctx = _ctx(cell, SEEDS[0], reference_live_depth=3, must_pass_depth=6)
    if state is not None:
        monkeypatch.setattr(cell.driver, "joined_state", lambda ctx: state)
    checks = cell.driver.verify(ctx, _measured(levels))
    return [c.name for c in checks if not c.ok], [c.name for c in checks]


def _true_levels(cell, upto=6):
    return {int(d): n for d, n in cell.config["reference_counts"].items()
            if int(d) <= upto}


def test_verify_passes_the_true_counts(cell):
    failed, names = _verdict(cell, _true_levels(cell))
    assert failed == []
    assert {"reference.root_is_the_twins", "unique.depth3", "unique.depth6",
            "reference.live_vs_pinned.depth3", "completed_depth",
            "dropped"} <= set(names)
    assert "reference.live_vs_pinned.depth4" not in names


@pytest.mark.parametrize("depth", [2, 5], ids=["live-depth", "pinned-depth"])
def test_verify_fails_a_count_that_is_off_by_one(cell, depth):
    levels = _true_levels(cell)
    levels[depth] -= 1
    assert _verdict(cell, levels)[0] == [f"unique.depth{depth}"]


def test_verify_fails_a_run_that_stopped_early(cell):
    assert _verdict(cell, _true_levels(cell, upto=5))[0] == [
        "completed_depth"]


def test_verify_fails_a_state_that_is_not_the_twins_root(cell, joined,
                                                         monkeypatch):
    """The reference started one step below the joined state (a state
    the Join phase's goal search also reaches, with a client's request
    already delivered): the adapter's validation refuses it, and the
    counts — of another space — are off as well."""
    from dslabs_tpu.testing.predicates import StatePredicate

    root = joined[SEEDS[1]]
    settings = cell.driver.lab4_phases.build_settings(
        dict(cell.config["search"], max_depth=1), root)
    settings.add_goal(StatePredicate("below the root",
                                     lambda s: s.depth > root.depth))
    state = BFS(settings).run(root).goal_matching_state
    assert state.depth == root.depth + 1
    failed, _names = _verdict(cell, _true_levels(cell), monkeypatch, state)
    assert "reference.root_is_the_twins" in failed
    assert any(name.startswith("unique.depth") for name in failed)


# ------------------------------------------------- the cell's data files

def _cell_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        man = json.load(fh)
    entry = next(w for w in man["workloads"] if w["name"] == CELL)
    cfg_entry = next(c for c in man["configs"]
                     if c["name"] == entry["config"])
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as fh:
        traffic = json.load(fh)
    with open(os.path.join(ROOT, cfg_entry["file"])) as fh:
        return man, entry, traffic, cfg_entry, json.load(fh)


def _both_files_say_what_the_manifest_says(man, entry, traffic, cfg_entry,
                                           config):
    for key in ("name", "config", "traffic", "chips", "why"):
        assert traffic[key] == entry[key], key
    assert (config["name"], config["source"]) == (cfg_entry["name"],
                                                  cfg_entry["source"])
    assert config["reduced"] == cfg_entry["reduced"] == []
    # what the driver refuses before any run: a line over 200 characters
    assert max(map(len, (cfg_entry["source"], cfg_entry["why"],
                         entry["why"]))) <= 200
    assert (entry["chips"], entry["traffic"]) == (1, "timeboxed-strict-bfs")
    assert traffic["driver"] == "timeboxed_bfs_lab4"


def _the_deep_cells_differ_in_the_protocol_alone(man, entry, traffic,
                                                 cfg_entry, config):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "paxos3-deep.json")) as fh:
        paxos = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lab3-paxos-n3c2.json")) as fh:
        paxos_cfg = json.load(fh)
    assert traffic["params"] == paxos["params"]
    for key in ("chunk", "ev_budget"):
        assert config["engine"][key] == paxos_cfg["engine"][key], key
    assert config["guarantees"]["zero"] == paxos_cfg["guarantees"]["zero"]
    assert config["protocol"]["strip_goals"] is True


def _the_caps_hold_a_program_three_times_as_fast(man, entry, traffic,
                                                 cfg_entry, config):
    """The arithmetic of ``sizing``, redone from the level sizes and the
    window's end it records: what a program three times as fast as the
    measured one reaches in a window fits the frontier and leaves the
    table under half full."""
    sizing, eng = config["sizing"], config["engine"]
    levels = {int(d): lv for d, lv in sizing["levels"].items()}
    end, fast = sizing["window_end"], sizing["three_times_as_fast"]

    def reached(explored):
        """``(depth, rows appended, unique)`` when ``explored`` states
        are explored, rows and keys in proportion inside the level."""
        depth = min(d for d in levels if levels[d]["explored"] >= explored)
        lo, hi = levels[depth - 1], levels[depth]
        share = (explored - lo["explored"]) / (hi["explored"]
                                               - lo["explored"])
        return (depth, round(share * hi["next_frontier"]),
                round(lo["unique"] + share * (hi["unique"] - lo["unique"])))

    assert reached(end["explored"])[:2] == (end["depth"],
                                            end["frontier_rows"])
    assert fast["explored"] == 3 * end["explored"]
    assert reached(fast["explored"]) == (fast["depth"],
                                         fast["frontier_rows"],
                                         fast["unique"])
    # the level being read and the one being written both fit a buffer
    assert levels[fast["depth"] - 1]["next_frontier"] <= eng["frontier_cap"]
    assert fast["frontier_rows"] <= eng["frontier_cap"]
    assert fast["unique"] <= eng["visited_cap"] // 2
    assert reached(sizing["holds_until"]["explored"])[1] == pytest.approx(
        eng["frontier_cap"], abs=2)
    times = sizing["holds_until"]["explored"] / end["explored"]
    assert times >= 3
    assert sizing["holds_until"]["times_the_window"] == pytest.approx(
        times, abs=1e-3)
    row = config["protocol"]["packed_bytes_per_state"]
    assert sizing["bytes"]["frontier_buffers"] == 2 * row * eng[
        "frontier_cap"]
    assert sizing["bytes"]["visited_table"] == 16 * eng["visited_cap"]
    assert (sizing["bytes"]["frontier_buffers"]
            + sizing["bytes"]["visited_table"]
            < sizing["bytes"]["peak_on_the_chip"] < 15.75 * 2**30)


def _the_pinned_counts_reach_the_depth_they_are_checked_to(
        man, entry, traffic, cfg_entry, config):
    pinned = {int(d): n for d, n in config["reference_counts"].items()}
    assert sorted(pinned) == list(range(1, max(pinned) + 1))
    assert max(pinned) >= 6 and all(n > 0 for n in pinned.values())
    assert list(pinned.values()) == sorted(pinned.values())
    assert 1 <= config["reference_live_depth"] <= max(pinned)
    assert config["must_pass_depth"] >= max(pinned)
    assert config["join"]["goals"] == [
        {"client_done": config["deployment"]["object_state"][
            "controller"]["address"]}]
    assert config["search"]["goals"] == []       # goals stripped
    assert config["search"]["nodes_off"] == ["configController"]
    assert sorted(config["search"]["timers_off"]) == [
        "configController", "shardmaster1"]


def _the_manifest_reads_the_cell_where_the_issue_says(man, entry, traffic,
                                                      cfg_entry, config):
    reads = {m["name"] for m in man["per_layer"]
             if CELL in m.get("workloads", ())}
    assert reads == {
        "dispatches_per_level.deep", "useful_ratio.deep",
        "superstep_us_per_state.deep", "superstep_roofline.deep",
        "expand_us_per_state.deep", "insert_us_per_state.deep",
        "pack_us_per_state.deep", "scope_coverage_pct.deep",
        "write_blocks_per_step.deep", "compile_s", "peak_hbm_gb",
        "trace_lower_s", "exe_store_hit_pct", "event_resteps_pct.deep",
        "grid_fill_pct.deep",
        "probe_cols_per_step.deep", "twin_build_s",
        "kind_skips_pct.deep"}
    for m in man["per_layer"]:
        if m["name"] in ("event_resteps_pct.deep", "grid_fill_pct.deep"):
            # (PR 40 appended its own cell behind these two)
            assert m["workloads"][:2] == ["paxos3-deep", CELL]
            assert (m["layer"], m["moves"]) == ("expand", "states_per_s")
        if m["name"] in reads:
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    e2e = {m["name"] for m in man["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"states_per_s", "setup_s"}
    # appended where PR 36 left them, never moved since (a later cell
    # goes behind them)
    assert man["workloads"][5]["name"] == CELL
    assert man["configs"][4]["name"] == entry["config"]


_CELL_CHECKS = [_both_files_say_what_the_manifest_says,
                _the_deep_cells_differ_in_the_protocol_alone,
                _the_caps_hold_a_program_three_times_as_fast,
                _the_pinned_counts_reach_the_depth_they_are_checked_to,
                _the_manifest_reads_the_cell_where_the_issue_says]


@pytest.mark.parametrize("check", _CELL_CHECKS,
                         ids=[c.__name__.lstrip("_") for c in _CELL_CHECKS])
def test_shardkv_deeps_data(check):
    check(*_cell_files())
