"""On-device level supersteps + persistent compile cache (ISSUE 3).

The fused superstep (sharded.py ``_level_superstep``: one shard_map
program whose ``lax.while_loop`` drains every device's own frontier
shard) must match the legacy host-driven per-chunk driver
(``DSLABS_SHARDED_SUPERSTEP=0``, the parity oracle) EXACTLY — end
verdict, unique, explored, depth — while cutting host dispatches per
level from ``n_chunks + 1`` to at most 2 (superstep + promote; the
dispatch-counter tests assert it).  Mid-level time budgets keep their
contract under both drivers: TIME_EXHAUSTED never masks a violation
found in chunks already completed.  The persistent compile cache
(tpu/compile_cache.py) plus AOT warm-up makes a
second identical construction's compile near-zero.

The heavier paxos/shardstore parity cases are marked ``perf`` AND
``slow``: ``make perf-smoke`` (-m perf) runs them as the dry-run
8-virtual-device parity gate, while the tier-1 suite (-m 'not slow')
keeps only the cheap pingpong cases.
"""

import dataclasses
import os

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import sharded as sharded_mod  # noqa: E402
from dslabs_tpu.tpu.engine import TensorSearch  # noqa: E402
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh  # noqa: E402


def _pruned_pingpong():
    pp = make_pingpong_protocol(workload_size=2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def _run_pair(proto, max_depth=None, **kw):
    """The same config under the fused superstep and the legacy
    per-chunk driver; returns (superstep_outcome, legacy_outcome)."""
    mesh = make_mesh(8)
    kw.setdefault("chunk_per_device", 16)
    kw.setdefault("frontier_cap", 1 << 8)
    kw.setdefault("visited_cap", 1 << 10)
    fused = ShardedTensorSearch(proto, mesh, max_depth=max_depth,
                                superstep=True, **kw).run()
    legacy = ShardedTensorSearch(proto, mesh, max_depth=max_depth,
                                 superstep=False, **kw).run()
    return fused, legacy


def _assert_exact(fused, legacy):
    assert fused.end_condition == legacy.end_condition
    assert fused.unique_states == legacy.unique_states
    assert fused.states_explored == legacy.states_explored
    assert fused.depth == legacy.depth
    assert fused.dropped == legacy.dropped


# ------------------------------------------------------------- parity

@pytest.mark.perf
@pytest.mark.parametrize("strict", [True, False])
def test_superstep_vs_legacy_parity_pingpong(strict):
    fused, legacy = _run_pair(_pruned_pingpong(), strict=strict)
    assert fused.end_condition == "SPACE_EXHAUSTED"
    _assert_exact(fused, legacy)


@pytest.mark.perf
@pytest.mark.slow
def test_superstep_vs_legacy_parity_paxos_d5():
    """The dry-run 8-device paxos rung of the perf-smoke parity gate
    (acceptance: exact verdict/unique/explored match at depth 5)."""
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    proto = make_paxos_protocol(n=3, n_clients=1, w=1, max_slots=2,
                                net_cap=16, timer_cap=4)
    fused, legacy = _run_pair(proto, max_depth=5, chunk_per_device=64,
                              frontier_cap=1 << 12,
                              visited_cap=1 << 15)
    assert fused.end_condition == "DEPTH_EXHAUSTED"
    _assert_exact(fused, legacy)


@pytest.mark.perf
@pytest.mark.slow
def test_superstep_vs_legacy_parity_shardstore_d4():
    """Second protocol family (lab 4 shardstore lane layout) through
    the same superstep machinery."""
    from dslabs_tpu.tpu.specs_lab4 import \
        make_shardstore_protocol

    proto = make_shardstore_protocol([[1], [2]])
    fused, legacy = _run_pair(proto, max_depth=4, chunk_per_device=64,
                              frontier_cap=1 << 12,
                              visited_cap=1 << 15)
    assert fused.end_condition == "DEPTH_EXHAUSTED"
    _assert_exact(fused, legacy)


def test_superstep_ev_spill_parity():
    """Event-window spill inside the while_loop: a tiny budget re-steps
    spilled chunks (j held back keeps the drain condition true) with
    exact counts."""
    proto = _pruned_pingpong()
    mesh = make_mesh(8)
    full = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, superstep=True).run()
    tiny = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, superstep=True, ev_budget=(2, 1),
        ev_spill=True).run()
    _assert_exact(tiny, full)


# ---------------------------------------------------- dispatch counting

def _counted_run(proto, superstep, **kw):
    mesh = make_mesh(8)
    kw.setdefault("chunk_per_device", 16)
    kw.setdefault("frontier_cap", 1 << 8)
    kw.setdefault("visited_cap", 1 << 10)
    search = ShardedTensorSearch(proto, mesh, superstep=superstep, **kw)
    counts = {}

    def hook(tag, fn, *args):
        counts[tag] = counts.get(tag, 0) + 1
        return fn(*args)

    search._dispatch_hook = hook
    return search.run(), counts


def test_superstep_host_dispatches_per_level_at_most_two():
    """The acceptance bound: the superstep driver spends <= 2 host
    dispatches per level (superstep + promote; the stats vector rides
    inside the superstep program) vs the legacy driver's
    n_chunks + sync (+ promote)."""
    proto = _pruned_pingpong()
    out, counts = _counted_run(proto, superstep=True)
    levels = out.depth
    assert levels >= 3
    assert counts.get("sharded.step", 0) == 0
    assert counts.get("sharded.sync", 0) == 0
    assert counts["sharded.superstep"] + counts["sharded.promote"] <= (
        2 * levels)

    legacy_out, legacy_counts = _counted_run(proto, superstep=False)
    _assert_exact(out, legacy_out)
    # The legacy driver pays at least one chunk step AND one sync per
    # level on top of the promote — strictly more host dispatches.
    assert legacy_counts["sharded.step"] >= levels
    assert legacy_counts["sharded.sync"] >= levels
    legacy_total = sum(v for k, v in legacy_counts.items())
    fused_total = sum(v for k, v in counts.items())
    assert fused_total < legacy_total


def test_single_device_mesh_skips_chunk_grid_widening():
    """Satellite: on a 1-device mesh the level rebalance is an identity,
    so the legacy chunk grid must NOT be widened by the
    ``max_n + D - 1`` slack (no extra mostly-invalid chunk)."""
    proto = _pruned_pingpong()
    mesh = make_mesh(1)
    search = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, superstep=False)
    assert search._rebalance_slack() == 0
    counts = {}

    def hook(tag, fn, *args):
        counts[tag] = counts.get(tag, 0) + 1
        return fn(*args)

    search._dispatch_hook = hook
    out = search.run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    # Frontiers here never exceed one chunk: exactly one chunk step per
    # level — the pre-fix driver dispatched two whenever
    # max_n % chunk == 0 (the widening added a full invalid chunk).
    assert counts["sharded.step"] == out.depth
    mesh8 = make_mesh(8)
    # The legacy promote-boundary exchange needs the ceil-split slack
    # on a wide mesh; the fused row exchange (ISSUE 12 default) has no
    # rebalance at all, so no slack either.
    assert ShardedTensorSearch(
        proto, mesh8, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, superstep=False)._rebalance_slack() == 7
    assert ShardedTensorSearch(
        proto, mesh8, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, row_exchange=True)._rebalance_slack() == 0


# ------------------------------------------------------- level records

def test_level_records_on_outcome():
    """Satellite: structured per-level throughput records ride the
    outcome (depth/chunks/wall/explored/unique/next_frontier) — the
    bench emits them as its throughput series."""
    proto = _pruned_pingpong()
    mesh = make_mesh(8)
    out = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10).run()
    assert out.levels, "SearchOutcome.levels must carry per-level records"
    for i, rec in enumerate(out.levels):
        assert rec["depth"] == i + 1
        for key in ("chunks", "wall", "explored", "unique",
                    "next_frontier"):
            assert key in rec, rec
        assert rec["chunks"] >= 1
    # Cumulative counters are monotone; the final record's totals match
    # the outcome's.
    uniq = [r["unique"] for r in out.levels]
    assert uniq == sorted(uniq)
    assert out.levels[-1]["explored"] == out.states_explored
    assert out.levels[-1]["unique"] == out.unique_states


# ------------------------------------------------- mid-level time budget

class _DispatchClock:
    """Deterministic wall clock for time-budget tests: time() returns
    ``base + n_dispatches * step`` where the dispatch hook advances the
    counter — the budget then expires at an exact, chosen dispatch
    instead of a wall-clock race."""

    def __init__(self, step: float):
        self.base = 1_000_000.0
        self.step = step
        self.dispatches = 0

    def time(self) -> float:
        return self.base + self.dispatches * self.step

    def sleep(self, secs: float) -> None:  # pragma: no cover
        pass


def _violating_clientserver():
    p = make_clientserver_protocol(n_clients=1, w=1)
    done = p.goals["CLIENTS_DONE"]
    return dataclasses.replace(
        p, goals={}, invariants={"NEVER_DONE": lambda s, f=done: ~f(s)})


def _clocked_run(proto, superstep, max_secs, clock, **kw):
    mesh = make_mesh(8)
    kw.setdefault("chunk_per_device", 32)
    kw.setdefault("frontier_cap", 1 << 9)
    kw.setdefault("visited_cap", 1 << 12)
    search = ShardedTensorSearch(proto, mesh, max_secs=max_secs,
                                 superstep=superstep, **kw)

    def hook(tag, fn, *args):
        clock.dispatches += 1
        return fn(*args)

    search._dispatch_hook = hook
    return search.run()


@pytest.mark.parametrize("superstep", [True, False],
                         ids=["superstep", "legacy"])
def test_time_budget_returns_time_exhausted_mid_run(superstep,
                                                    monkeypatch):
    """Satellite: a tiny max_secs returns TIME_EXHAUSTED (with the
    partial counts, never a crash) under BOTH drivers.  The fake clock
    charges one 'second' per dispatch, so the budget expires after the
    first level's work — deterministically."""
    proto = _pruned_pingpong()
    full = _clocked_run(proto, superstep, None, _DispatchClock(0.0))
    assert full.end_condition == "SPACE_EXHAUSTED"

    clock = _DispatchClock(1.0)
    monkeypatch.setattr(sharded_mod, "time", clock)
    out = _clocked_run(proto, superstep, 3.5, clock)
    assert out.end_condition == "TIME_EXHAUSTED"
    assert 0 < out.states_explored < full.states_explored
    assert out.unique_states >= 1


@pytest.mark.parametrize("superstep", [True, False],
                         ids=["superstep", "legacy"])
def test_time_budget_never_masks_violation_in_completed_chunks(
        superstep, monkeypatch):
    """Satellite: a violation found in chunks already completed must be
    reported even when the wall budget is ALREADY exhausted at the
    sync — the checks run before any TIME_EXHAUSTED return.  The fake
    clock makes the budget expire during the violation's own level."""
    proto = _violating_clientserver()
    base = _clocked_run(proto, superstep, None, _DispatchClock(0.0))
    assert base.end_condition == "INVARIANT_VIOLATED"

    # The run takes `total` dispatches, the last being the one whose
    # sync finds the violation.  A budget of total - 0.5 dispatch-
    # "seconds" passes every check BEFORE that dispatch (elapsed <=
    # total - 1) but is exhausted at its sync (elapsed == total) — the
    # violation must still win.
    counting = _DispatchClock(0.0)
    total = _count_dispatches(proto, superstep, counting)
    clock = _DispatchClock(1.0)
    monkeypatch.setattr(sharded_mod, "time", clock)
    out = _clocked_run(proto, superstep, total - 0.5, clock)
    assert out.end_condition == "INVARIANT_VIOLATED", (
        "TIME_EXHAUSTED masked a violation found in completed chunks")
    assert out.predicate_name == base.predicate_name
    assert out.depth == base.depth


def _count_dispatches(proto, superstep, clock):
    mesh = make_mesh(8)
    search = ShardedTensorSearch(proto, mesh, chunk_per_device=32,
                                 frontier_cap=1 << 9,
                                 visited_cap=1 << 12,
                                 superstep=superstep)

    def hook(tag, fn, *args):
        clock.dispatches += 1
        return fn(*args)

    search._dispatch_hook = hook
    search.run()
    return clock.dispatches


# ------------------------------------------- compile cache + AOT warm-up

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_CHILD = """
import json, os, sys
sys.path.insert(0, %r)
from dslabs_tpu.tpu import compile_cache
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
from tests.test_superstep import _pruned_pingpong

out = {"setup": compile_cache.setup()}
if "--build" in sys.argv:
    proto, mesh = _pruned_pingpong(), make_mesh(8)
    kw = dict(chunk_per_device=16, frontier_cap=1 << 8,
              visited_cap=1 << 10, aot_warmup=True)
    cold = ShardedTensorSearch(proto, mesh, **kw)
    out["populated"] = bool(os.listdir(out["setup"]))
    res = cold.run()
    warm = ShardedTensorSearch(proto, mesh, **kw)
    res2 = warm.run()
    out.update(cold=cold.compile_secs, warm=warm.compile_secs,
               end=res.end_condition, outcome_secs=res.compile_secs,
               same=res.unique_states == res2.unique_states,
               after=compile_cache.cache_dir())
print(json.dumps(out))
""" % _REPO


def _cache_child(cwd, cache_env, *argv):
    import json
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(cache_env)
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD, *argv], cwd=str(cwd),
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_env_var_places_it_and_second_aot_is_fast(tmp_path):
    """Contract, variable SET: the cache is where
    JAX_COMPILATION_CACHE_DIR says and the code sets no other
    directory; it is populated, and a second identical construction's
    recorded compile time drops (the AOT .lower().compile() hits the
    on-disk cache instead of XLA)."""
    cache = str(tmp_path / "xla-cache")
    out = _cache_child(tmp_path, {"JAX_COMPILATION_CACHE_DIR": cache},
                       "--build")
    assert out["setup"] == cache and out["after"] == cache
    assert out["populated"], "persistent cache dir not populated"
    assert out["end"] == "SPACE_EXHAUSTED" and out["same"]
    assert out["cold"] > 0
    assert out["outcome_secs"] == round(out["cold"], 3)
    # The XLA-compile half is served from disk; what remains is
    # tracing.  On CPU the margin is small, so assert a robust drop
    # rather than "near-zero".
    assert out["warm"] < out["cold"]


def test_compile_cache_default_is_checkout_from_any_cwd(tmp_path):
    """Contract, variable UNSET: ``<checkout>/.jax_cache``, computed
    from the package's location — the same path from two processes
    started in different working directories."""
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir()
    b.mkdir()
    want = os.path.join(_REPO, ".jax_cache")
    assert _cache_child(a, {})["setup"] == want
    assert _cache_child(b, {})["setup"] == want


def test_compile_cache_has_one_place():
    """No directory argument, no DSLABS_* name, no per-checkpoint
    default: with the variable set (conftest) setup() leaves JAX's
    directory alone whatever else the environment says."""
    import inspect

    from dslabs_tpu.tpu import checkpoint as ckpt_mod
    from dslabs_tpu.tpu import compile_cache

    assert not inspect.signature(compile_cache.setup).parameters
    assert not hasattr(compile_cache, "setup_for_checkpoint")
    assert "compile_cache" not in ckpt_mod.run_dir_layout("/x/s.npz")
    assert "DSLABS_" not in inspect.getsource(compile_cache)
    prev = compile_cache.cache_dir()
    assert prev == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert compile_cache.setup() == prev == compile_cache.cache_dir()
