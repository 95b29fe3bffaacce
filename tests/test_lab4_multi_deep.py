"""Lab 4 with REAL replica groups under the deep strict search, as the
benchmark's configuration ``lab4-shardstore-g2n3`` states it and its
driver ``timeboxed_bfs_lab4_multi`` builds it —
``ShardStoreBaseTest.setupStates(2, n, 1, 10)``: two groups of n
Paxos-replicated ShardStoreServers, one shard master, one client with
``PUT key-1`` pending.  The cell runs n = 3 on the chip; the tier-1 run
holds the same code at n = 2 (the second staged start of
``tests/test_lab4_multi.py``'s oracle table: 8 / 42 / 180), an engine's
compile being the cost of every piece:

* the twin through ``ShardedTensorSearch`` (strict, packed codec — what
  the cell and the lab entry run; ``tests/test_lab4_multi.py`` holds it
  to the object checker through raw ``TensorSearch`` behind
  ``DSLABS_SLOW_TESTS`` only) on 1 and 2 virtual devices, against the
  object checker from the driver's joined state at depths 1-3 on two
  seeds, and against ``run_host`` at depth 3;
* the handlers' operations name the fragment they came from
  (``tpu/compiler.py``'s one more ``dslabs.`` scope level), and a twin
  built of no fragment lowers as it did;
* the cell's data files hold together and hold to ``BENCHMARK.json``,
  and the arithmetic of ``sizing`` is redone.

The lab entry on the same state: ``tests/test_lab4_multi_entry.py``.
"""

import dataclasses
import json
import os
import types

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu.engine import TensorSearch  # noqa: E402
from dslabs_tpu.tpu.sharded import (ShardedTensorSearch,  # noqa: E402
                                    make_mesh)
from tests.fixtures.lab4_multi_small import at_small_size  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [2**31 + 40, 40]
CELL = "shardkv-n3-deep"


@pytest.fixture(scope="module")
def cell():
    from benchmark.harness import manifest

    return manifest.load_cell(ROOT, CELL)


@pytest.fixture(scope="module")
def small(cell):
    """The cell at two servers a group."""
    return dataclasses.replace(
        cell, config=at_small_size(cell.config))


def _ctx(cell, seed):
    return types.SimpleNamespace(cell=cell, seed=seed,
                                 dev={"platform": "cpu"},
                                 note=lambda msg: None)


@pytest.fixture(scope="module")
def object_counts(small):
    """The object checker's cumulative unique counts at depths 1-3 below
    the driver's joined state, a seed."""
    out = {}
    for seed in SEEDS:
        ctx = _ctx(small, seed)
        out[seed] = small.driver.reference_counts(
            ctx, small.driver.joined_state(ctx), 3)
    return out


@pytest.fixture(scope="module")
def protocol(small):
    """The n = 2 twin as the cell's supervisor builds it (goals
    stripped)."""
    from benchmark.drivers.timeboxed_bfs import build_protocol

    return build_protocol(small.config["protocol"])


@pytest.fixture(scope="module")
def runs(small, protocol):
    """``runs(n_devices)``: the search and its outcome at depth 3, one
    engine (a compile of two minutes) a mesh width for the module."""
    made = {}

    def run(n_devices):
        if n_devices not in made:
            eng = small.config["engine"]
            search = ShardedTensorSearch(
                protocol, make_mesh(n_devices), chunk_per_device=128,
                frontier_cap=1 << 11, visited_cap=1 << 14, max_depth=3,
                strict=True, ev_budget=tuple(eng["ev_budget"]))
            made[n_devices] = (search, search.run())
        return made[n_devices]

    return run


# ------------------------------------------------ the twin, sharded + packed

def test_the_configuration_names_the_twin_it_builds(cell):
    """At the cell's own size, n = 3 (the twin is built and laid out,
    nothing is compiled)."""
    from benchmark.drivers.timeboxed_bfs import build_protocol

    spec = cell.config["protocol"]
    search = ShardedTensorSearch(build_protocol(spec), make_mesh(1),
                                 chunk_per_device=128, strict=True)
    p = search.p
    assert (p.name, p.n_nodes, p.node_width) == (
        spec["name"], spec["nodes"], spec["node_width"])
    assert search.lanes == spec["lanes"]
    assert search.bytes_per_state == spec["packed_bytes_per_state"]
    assert search.plane * 4 == spec["packed_bytes_per_state"]
    assert not p.goals
    assert (p.net_cap, p.timer_cap) == (spec["kwargs"]["net_cap"],
                                        spec["kwargs"]["timer_cap"])


@pytest.mark.parametrize("n_devices", [1, 2])
def test_sharded_packed_search_counts_what_the_object_checker_counts(
        small, runs, object_counts, n_devices):
    """Depths 1-3 below the joined state: 8 / 42 / 180, on both seeds
    (the twin is value-blind: one device run stands for both)."""
    search, out = runs(n_devices)
    got = {lv["depth"]: lv["unique"] for lv in out.levels}
    pinned = {int(d): n
              for d, n in small.config["reference_counts"].items()}
    for seed in SEEDS:
        assert got == object_counts[seed] == {d: pinned[d] for d in got}
    assert got == {1: 8, 2: 42, 3: 180}
    assert (out.dropped, out.visited_overflow, out.retries) == (0, 0, 0)
    assert out.bytes_per_state == search.bytes_per_state > 0


def test_sharded_packed_search_equals_run_host_at_depth_3(protocol, runs):
    _search, out = runs(2)
    host = TensorSearch(protocol, chunk=128, max_depth=3).run_host()
    assert out.unique_states == host.unique_states == 180
    assert out.states_explored == host.states_explored


# ------------------------------------------- the fragments' device scopes

def _handler_text(p) -> str:
    """The lowered message step, locations and all (lowered, not
    compiled: no cache entry of another commit can answer)."""
    import jax.numpy as jnp

    args = (jax.ShapeDtypeStruct((p.node_width,), jnp.int32),
            jax.ShapeDtypeStruct((p.msg_width,), jnp.int32))
    return jax.jit(p.step_message).lower(*args).as_text(debug_info=True)


def test_handler_operations_name_their_fragment(protocol):
    text = _handler_text(protocol)
    assert "dslabs.expand.handlers.gpaxos" in text
    assert "dslabs.expand.handlers.spec" in text


def test_a_twin_of_no_fragment_names_none():
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_spec

    spec = make_paxos_spec(n=3, n_clients=1, w=1, max_slots=2)
    assert spec.fragments == []
    assert "dslabs.expand.handlers." not in _handler_text(spec.compile())


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
    assert traffic["driver"] == "timeboxed_bfs_lab4_multi"
    state = config["deployment"]["object_state"]
    kwargs = config["protocol"]["kwargs"]
    assert (state["groups"], state["servers_per_group"],
            state["shard_masters"], state["shards"]) == (2, 3, 1, 10) == (
        kwargs["n_groups"], kwargs["n"], 1, kwargs["num_shards"])
    assert len(state["clients"]) == 1 == kwargs["w"]
    assert sorted(config["join"]["timers_off"]) == sorted(
        f"server{g}-{i}" for g in (1, 2) for i in (1, 2, 3))


def _the_deep_cells_differ_in_the_protocol_and_the_caps(
        man, entry, traffic, cfg_entry, config):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "shardkv-deep.json")) as fh:
        other = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lab4-shardstore-g2c2.json")) as fh:
        other_cfg = json.load(fh)
    mine, theirs = dict(traffic["params"]), dict(other["params"])
    # the traced level is chosen for THIS search's level sizes
    assert mine.pop("trace_min_frontier_rows") < theirs.pop(
        "trace_min_frontier_rows")
    assert mine == theirs
    for key in ("chunk", "ev_budget"):
        assert config["engine"][key] == other_cfg["engine"][key], key
    assert config["guarantees"]["zero"] == other_cfg["guarantees"]["zero"]
    assert config["search"] == other_cfg["search"]
    assert config["protocol"]["strip_goals"] is True


def _the_caps_are_sized_from_the_chips_own_levels(man, entry, traffic,
                                                  cfg_entry, config):
    """The arithmetic of ``sizing``, redone: the four frontier-sized
    buffers of the compiler's plan and the table against the chip, the
    window's end against the frontier, and how much faster a program may
    get before a level's next frontier passes the buffer."""
    sizing, eng = config["sizing"], config["engine"]
    row = config["protocol"]["packed_bytes_per_state"]
    b = sizing["bytes"]
    assert b["one_frontier_buffer"] == row * eng["frontier_cap"]
    assert b["four_frontier_buffers"] == 4 * b["one_frontier_buffer"]
    assert b["visited_table"] == 16 * eng["visited_cap"]
    hbm = 15.75 * 2**30
    # The PLAN (both entry copies of ``nxt``: four frontier-sized
    # buffers) fills the chip, 90 %; before the group log carried its
    # catch-up handlers it read 92 %, and 7,168 bytes of plan a row more
    # passed the 14.6 GiB the issue allows within 65,536 rows.  What is
    # resident at once is less.
    live = b["superstep_live_by_memory_analysis"]
    before = b["superstep_live_before_the_catchup_handlers"]
    for plan in (live, before):
        assert (b["four_frontier_buffers"] + b["visited_table"] < plan
                < 14.6 * 2**30 < hbm)
        assert 0.88 * hbm < plan
    assert before + 4 * row * 65536 > 14.6 * 2**30
    assert (2 * b["one_frontier_buffer"] + b["visited_table"]
            < b["peak_on_the_chip"] < live)
    # one variant of the handlers compiled with ONE entry copy: the plan
    # is not a function of the cap alone, so the cap is sized for two
    one_copy = b["superstep_live_with_one_entry_copy"]
    assert 3 * b["one_frontier_buffer"] + b["visited_table"] < one_copy
    assert live - one_copy == pytest.approx(b["one_frontier_buffer"],
                                            rel=0.05)

    levels = {int(d): lv for d, lv in sizing["levels"].items()}
    pinned = {int(d): n for d, n in config["reference_counts"].items()}
    for d, lv in levels.items():
        if d in pinned:
            assert lv["unique"] == pinned[d]
        if d - 1 in levels:
            assert lv["next_frontier"] == (lv["unique"]
                                           - levels[d - 1]["unique"])
    end = sizing["window_end"]
    # the window ends inside a level whose read and written frontiers
    # both fit; every level the chip closed fits as well
    # (a traced run closes with level 7 open: must_pass_depth is 6)
    assert end["depth"] - 1 in levels
    assert end["depth"] - 1 >= config["must_pass_depth"] == 6
    assert all(lv["next_frontier"] <= eng["frontier_cap"]
               for lv in levels.values())
    assert end["frontier_rows"] == (end["unique"]
                                    - levels[end["depth"] - 1]["unique"])
    assert end["frontier_rows"] <= levels[end["depth"]]["next_frontier"]
    assert end["unique"] <= eng["visited_cap"] // 2
    # one row appended a new unique state: the buffer is full when the
    # level after the last one the chip closed has found frontier_cap
    holds, last = sizing["holds_until"], max(levels)
    assert holds["level"] == last + 1
    assert holds["unique"] == levels[last]["unique"] + eng["frontier_cap"]
    assert holds["unique"] <= eng["visited_cap"] // 2
    assert holds["times_the_window"] == pytest.approx(
        holds["unique"] / end["unique"], abs=1e-3)
    assert holds["times_the_window"] > 3     # the issue expected 2-3


def _the_pinned_counts_reach_the_depth_they_are_checked_to(
        man, entry, traffic, cfg_entry, config):
    pinned = {int(d): n for d, n in config["reference_counts"].items()}
    assert sorted(pinned) == list(range(1, max(pinned) + 1))
    assert pinned == {1: 10, 2: 69, 3: 392, 4: 1985, 5: 9304, 6: 41189,
                      7: 174362}
    assert list(pinned.values()) == sorted(pinned.values())
    assert config["reference_live_depth"] == 3
    assert config["must_pass_depth"] == 6 < max(pinned)
    assert config["join"]["goals"] == [
        {"client_done": config["deployment"]["object_state"][
            "controller"]["address"]}]
    assert config["search"]["goals"] == []       # goals stripped


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
        "probe_cols_per_step.deep", "gpaxos_handlers_pct.deep",
        "twin_build_s", "kind_skips_pct.deep"}
    for m in man["per_layer"]:
        if m["name"] == "gpaxos_handlers_pct.deep":
            assert m == {"name": m["name"], "unit": "%", "better": "lower",
                         "source": "device_trace", "layer": "expand",
                         "moves": "states_per_s", "workloads": [CELL]}
        if m["name"] in reads:
            # appended (PR 43's and PR 47's cells behind it where they
            # read the metric)
            assert CELL in m["workloads"][-3:]
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    e2e = {m["name"] for m in man["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"states_per_s", "setup_s"}
    # appended, never put first or in the middle: the seventh cell and
    # the sixth configuration (PR 43 appended its own behind them); one
    # cell on four chips
    assert man["workloads"][6]["name"] == CELL
    assert man["configs"][5]["name"] == entry["config"]
    # (PR 41 appended its one metric behind it, PR 43 its four, PR 44
    # its one, PR 47 its two, PR 48 its one, PR 49 its one)
    names = [m["name"] for m in man["per_layer"]]
    assert names[names.index("gpaxos_handlers_pct.deep"):] == [
        "gpaxos_handlers_pct.deep", "exe_store_hit_pct",
        "walk_us_per_step.swarm", "fresh_pct.swarm", "restarts_pct.swarm",
        "round_roofline.swarm", "blocks_per_step.swarm",
        "promote_us_per_state.deep", "rebased_levels_pct.deep",
        "twin_build_s", "kind_skips_pct.deep"]
    assert [w["chips"] for w in man["workloads"]].count(4) == 1
    assert len(man["workloads"]) == 9        # PRs 43 and 47: one each


_CELL_CHECKS = [_both_files_say_what_the_manifest_says,
                _the_deep_cells_differ_in_the_protocol_and_the_caps,
                _the_caps_are_sized_from_the_chips_own_levels,
                _the_pinned_counts_reach_the_depth_they_are_checked_to,
                _the_manifest_reads_the_cell_where_the_issue_says]


@pytest.mark.parametrize("check", _CELL_CHECKS,
                         ids=[c.__name__.lstrip("_") for c in _CELL_CHECKS])
def test_shardkv_n3_deeps_data(check):
    check(*_cell_files())
