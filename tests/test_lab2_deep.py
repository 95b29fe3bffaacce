"""Lab 2 under the DEEP strict search, as the benchmark's configuration
``lab2-primarybackup-s2c2`` states it and its driver
``timeboxed_bfs_lab2`` builds it — PrimaryBackupTest test18's
deployment from its root, every timer live — each piece small enough
for the tier-1 run:

* the configuration names the twin its factory builds (lanes, packed
  bytes, delta lanes, caps);
* the driver's ``verify`` on recorded level counts: correct on the true
  ones, not correct when a count is off by one, when the run stopped
  early, or when the state the reference counts is not the twin's root;
* the cell's data files hold together and hold to ``BENCHMARK.json``,
  and the ``sizing`` arithmetic, redone.

The counts themselves are ``tests/test_lab2_twin_counts.py``'s, the lab
entry ``tests/test_lab2_entry.py``'s, the engine under delta lanes
``tests/test_delta_rebase.py``'s."""

import dataclasses
import json
import os
import types

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu.sharded import (ShardedTensorSearch,  # noqa: E402
                                    make_mesh)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [2**31 + 47, 47]
CELL = "pb-deep"
# the cells the benchmark had when PR 47 appended this one
ACCEPTED = ("paxos3-deep", "lab1-entry", "paxos3-deep-mesh4", "paxos3-suite",
            "shardtx-suite", "shardkv-deep", "shardkv-n3-deep",
            "paxos5-random")


@pytest.fixture(scope="module")
def cell():
    from benchmark.harness import manifest

    return manifest.load_cell(ROOT, CELL)


def _ctx(cell, seed, **config):
    """What a driver is handed, as far as ``verify`` reads it."""
    return types.SimpleNamespace(
        cell=dataclasses.replace(cell, config=dict(cell.config, **config)),
        seed=seed, dev={"platform": "cpu"}, note=lambda msg: None)


def test_the_configuration_names_the_twin_it_builds(cell):
    from benchmark.drivers.timeboxed_bfs import build_protocol

    spec = cell.config["protocol"]
    search = ShardedTensorSearch(build_protocol(spec), make_mesh(1),
                                 chunk_per_device=64, strict=True)
    assert search.p.name == spec["name"] == "pb-gen-shared"
    assert search.lanes == spec["lanes"]
    assert search.bytes_per_state == spec["packed_bytes_per_state"]
    assert search.plane * 4 == spec["packed_bytes_per_state"]
    assert not search.p.goals
    assert search._mesh_delta
    assert len(search._delta_lanes) == spec["delta_lanes"] == 5
    assert set(search._pk.width[search._delta_lanes]) == {
        spec["delta_bits"]}
    assert (search.p.net_cap, search.p.timer_cap) == (
        spec["kwargs"]["net_cap"], spec["kwargs"]["timer_cap"])


@pytest.mark.parametrize("seed", SEEDS)
def test_the_seed_draws_the_key_and_the_values(cell, seed):
    """Equal seeds give equal commands; the two clients share the key
    and differ in the value; another seed gives others.  The adapter
    binds the shared-key twin whatever the seed drew."""
    from dslabs_tpu.tpu import backend

    spec = cell.config["deployment"]["object_state"]
    build = cell.driver.build_state

    def commands(s):
        return [backend.resolve_binding(build(spec, s)).pairs[c][0][0]
                for c in range(2)]

    one, again, other = commands(seed), commands(seed), commands(seed + 1)
    assert one == again != other
    assert one[0].key == one[1].key and one[0].value != one[1].value
    assert (len(one[0].key), len(one[0].value)) == (6, 4)
    assert cell.driver.root_is_the_twins(_ctx(cell, seed)).ok


# ------------------------------------------------------ the driver's verify

def _measured(levels, **outcome):
    return {"outcome": dict(dict(
        platform="cpu", mesh_width=1, bytes_per_state=304, dropped=0,
        visited_overflow=0, retries=0, failovers=0, knob_retries=0),
        **outcome),
            "levels": [{"depth": d, "unique": n}
                       for d, n in sorted(levels.items())]}


def _verdict(cell, levels, **outcome):
    """``(failed check names, all check names)`` of the driver's
    ``verify`` on recorded level counts, the live reference cut to
    depth 4 (0.5 s of object checker; the cell's 6 costs 5)."""
    ctx = _ctx(cell, SEEDS[0], reference_live_depth=4, must_pass_depth=9)
    checks = cell.driver.verify(ctx, _measured(levels, **outcome))
    return [c.name for c in checks if not c.ok], [c.name for c in checks]


def _true_levels(cell, upto=9):
    return {int(d): n for d, n in cell.config["reference_counts"].items()
            if int(d) <= upto}


def test_verify_passes_the_true_counts(cell):
    failed, names = _verdict(cell, _true_levels(cell))
    assert failed == []
    assert {"reference.root_is_the_twins", "unique.depth4", "unique.depth9",
            "reference.live_vs_pinned.depth4", "completed_depth",
            "bytes_per_state", "dropped"} <= set(names)
    assert "reference.live_vs_pinned.depth5" not in names


@pytest.mark.parametrize("depth", [3, 8], ids=["live-depth", "pinned-depth"])
def test_verify_fails_a_count_that_is_off_by_one(cell, depth):
    levels = _true_levels(cell)
    levels[depth] -= 1
    assert _verdict(cell, levels)[0] == [f"unique.depth{depth}"]


def test_verify_fails_the_default_twins_counts(cell):
    """What the lab entry's twin counted before PR 47 (one seq a client:
    8,133 and 16,753 at depths 8 and 9) is not correct."""
    levels = {**_true_levels(cell), 8: 8133, 9: 16753}
    assert _verdict(cell, levels)[0] == ["unique.depth8", "unique.depth9"]


def test_verify_fails_a_run_that_stopped_early_or_ran_raw(cell):
    assert _verdict(cell, _true_levels(cell, upto=8))[0] == [
        "completed_depth"]
    assert _verdict(cell, _true_levels(cell), bytes_per_state=1352)[0] == [
        "bytes_per_state"]
    assert _verdict(cell, _true_levels(cell), dropped=3)[0] == ["dropped"]


def test_verify_fails_a_state_that_is_not_the_twins_root(cell, monkeypatch):
    """Clients on keys of their own: the adapter binds the default twin,
    not the configuration's."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    build = cell.driver.build_state

    def own_keys(spec, seed):
        state = build(dict(spec, clients=0), seed)
        for i in (1, 2):
            state.add_client_worker(
                LocalAddress(f"client{i}"),
                kv_workload([f"APPEND:key-{i}:v{i}"], [f"v{i}"]))
        return state

    monkeypatch.setattr(cell.driver, "build_state", own_keys)
    failed, _names = _verdict(cell, _true_levels(cell))
    assert "reference.root_is_the_twins" in failed


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
    assert traffic["driver"] == "timeboxed_bfs_lab2"
    assert "test18" in config["source"]


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
    assert config["search"] == {
        "invariants": ["APPENDS_LINEARIZABLE"], "goals": [], "prunes": [],
        "max_time": 600, "max_depth": None}


def _the_caps_hold_a_program_three_times_as_fast(man, entry, traffic,
                                                 cfg_entry, config):
    """The arithmetic of ``sizing``, redone from the level sizes and the
    window's end it records: what a program three times as fast as the
    measured one reaches in a window fits the frontier, leaves the
    table under half full and the network under its cap."""
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
    # the network: about a row a level (search-mode delivery consumes
    # nothing), read off the deepest level a count run holds
    net = sizing["net_peak"]
    assert net["at_depth"] <= fast["depth"] + 1 or net["rows_per_level"] <= 1
    reach = net["rows"] + net["rows_per_level"] * max(
        0, fast["depth"] + 1 - net["at_depth"])
    assert reach <= config["protocol"]["kwargs"]["net_cap"]
    assert net["timers_per_node"] < config["protocol"]["kwargs"]["timer_cap"]
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
    assert max(pinned) >= 12 and all(n > 0 for n in pinned.values())
    assert list(pinned.values()) == sorted(pinned.values())
    assert 1 <= config["reference_live_depth"] <= max(pinned)
    assert config["must_pass_depth"] >= max(pinned)
    # the depth where the order of the APPENDs first shows is checked
    assert pinned[8] == 8135


def _the_manifest_reads_the_cell_where_the_issue_says(man, entry, traffic,
                                                      cfg_entry, config):
    reads = {m["name"] for m in man["per_layer"]
             if CELL in m.get("workloads", ())}
    assert reads == {
        "dispatches_per_level.deep", "useful_ratio.deep",
        "superstep_us_per_state.deep", "superstep_roofline.deep",
        "expand_us_per_state.deep", "insert_us_per_state.deep",
        "pack_us_per_state.deep", "scope_coverage_pct.deep",
        "write_blocks_per_step.deep", "probe_cols_per_step.deep",
        "event_resteps_pct.deep", "grid_fill_pct.deep", "compile_s",
        "trace_lower_s", "peak_hbm_gb", "exe_store_hit_pct",
        "promote_us_per_state.deep", "rebased_levels_pct.deep",
        "twin_build_s", "kind_skips_pct.deep"}
    for m in man["per_layer"]:
        if m["name"] in reads:
            # appended behind the cells that were there
            assert m["workloads"].index(CELL) == len(
                [w for w in m["workloads"] if w in ACCEPTED]), m["name"]
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        if m["name"] in ("promote_us_per_state.deep",
                         "rebased_levels_pct.deep"):
            assert (m["workloads"], m["layer"], m["moves"]) == (
                [CELL], "codec", "states_per_s")
    e2e = {m["name"] for m in man["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"states_per_s", "setup_s"}
    # appended where PR 47 left them (a later cell goes behind them)
    assert man["workloads"][8]["name"] == CELL
    assert man["configs"][7]["name"] == entry["config"]
    names = [m["name"] for m in man["per_layer"]]
    assert names.index("promote_us_per_state.deep") + 1 == names.index(
        "rebased_levels_pct.deep") == 47


_CELL_CHECKS = [_both_files_say_what_the_manifest_says,
                _the_deep_cells_differ_in_the_protocol_alone,
                _the_caps_hold_a_program_three_times_as_fast,
                _the_pinned_counts_reach_the_depth_they_are_checked_to,
                _the_manifest_reads_the_cell_where_the_issue_says]


@pytest.mark.parametrize("check", _CELL_CHECKS,
                         ids=[c.__name__.lstrip("_") for c in _CELL_CHECKS])
def test_pb_deeps_data(check):
    check(*_cell_files())
