"""Host-RAM spill tier + capacity ladder (ISSUE 6, tpu/spill.py,
docs/capacity.md): strict searches survive HBM exhaustion with EXACT
counts, never a dropped state:

* strict DEPTH_EXHAUSTED with the device visited table capped at ~1/8
  of the reachable state count: exact unique/explored/verdict parity
  against the uncapped run and ``dropped_states == 0`` — single-device
  AND sharded engines (the acceptance criterion);
* a run SIGKILLed mid-spill resumes from the unified checkpoint to the
  identical verdict and counts (the dump's visited_keys is the exact
  device ∪ host-tier union, CRC-checked and .prev-rotated like every
  other dump);
* the supervisor's capacity ladder: ``CapacityOverflow`` becomes a
  classified, recoverable failure — the rung retries with spill
  enabled, resuming from checkpoint;
* the new spill dispatches (drain/evict/reinject) ride the standard
  ``_dispatch`` seam: FaultPlan site rules target them, transient
  faults retry in place, a hang is abandoned by the watchdog and the
  ladder fails over — verdict parity throughout;
* a spill checkpoint from a FOREIGN config is refused loudly
  (CheckpointMismatch), never resumed silently;
* the early-warning instrumentation (DSLABS_VISITED_WARN) and loud
  beam-drop accounting (DSLABS_DROPPED_WARN, dropped_states) fire
  before/at the degradations they describe.

Marked ``capacity`` (``make capacity-smoke``); paxos d5 additionally
``slow``.
"""

import dataclasses
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import checkpoint as ckpt_mod  # noqa: E402
from dslabs_tpu.tpu import spill as spill_mod  # noqa: E402
from dslabs_tpu.tpu.engine import (CapacityOverflow,  # noqa: E402
                                   TensorSearch)
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import (ShardedTensorSearch,  # noqa: E402
                                    make_mesh)
from dslabs_tpu.tpu.supervisor import (FaultPlan,  # noqa: E402
                                       RetryPolicy, SearchSupervisor,
                                       TransientDeviceError)

pytestmark = pytest.mark.capacity


def _pruned_pingpong():
    pp = make_pingpong_protocol(2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def _pruned_clientserver(nc=3, w=4):
    cs = make_clientserver_protocol(n_clients=nc, w=w)
    return dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]})


# Shared uncapped lab1 reference (module-scoped: the baseline is used
# by several parity tests and costs a full strict BFS).
LAB1_DEPTH = 11


@pytest.fixture(scope="module")
def lab1_base():
    out = TensorSearch(_pruned_clientserver(), chunk=1024,
                       max_depth=LAB1_DEPTH).run()
    assert out.end_condition == "DEPTH_EXHAUSTED"
    return out


def _eighth_cap(unique: int) -> int:
    return 1 << max(3, int(np.floor(np.log2(max(unique // 8, 8)))))


def _assert_exact(a, b):
    assert a.end_condition == b.end_condition
    assert a.unique_states == b.unique_states
    assert a.states_explored == b.states_explored
    assert a.depth == b.depth


# ------------------------------------------------------------ unit layer

def test_host_tier_absorb_contains_dedup():
    """The tier is an EXACT set: absorb dedups within the batch and
    against the store, contains answers per row, host_cap is a loud
    wall (the ladder escalates it, never a silent drop)."""
    tier = spill_mod.HostVisitedTier(host_cap=8)
    keys = np.arange(24, dtype=np.uint32).reshape(6, 4)
    dup = np.concatenate([keys, keys[:3]])
    assert tier.absorb(dup) == 6
    assert len(tier) == 6
    assert tier.contains(keys).all()
    assert not tier.contains(keys + np.uint32(100)).any()
    assert tier.absorb(keys) == 0          # idempotent
    with pytest.raises(CapacityOverflow):
        tier.absorb(np.arange(100, 100 + 12 * 4,
                              dtype=np.uint32).reshape(12, 4))


def test_spill_manager_unique_formula():
    """unique = len(tier) + vis_n_epoch - dup_epoch, with refilter
    charging duplicates and evict starting a fresh epoch."""
    sp = spill_mod.SpillManager(spill_mod.SpillConfig(high_water=0.5))
    keys = np.arange(40, dtype=np.uint32).reshape(10, 4)
    sp.evict(keys)                         # epoch 1 -> tier
    assert sp.unique(0) == 10
    rows = np.arange(12, dtype=np.int32).reshape(3, 4)
    kept = sp.refilter(rows, keys[:3])     # all three are re-discoveries
    assert len(kept) == 0 and sp.dup_epoch == 3
    assert sp.unique(3) == 10              # 3 device inserts, all dups
    sp.evict(keys[:3])                     # dups absorb to nothing new
    assert len(sp.tier) == 10 and sp.dup_epoch == 0


# ------------------------------------------------- engine parity layer

def test_device_spill_parity_pingpong():
    """Tiny space, table capped to a single bucket: evictions and
    refilters happen, counts stay exact (single-device engine)."""
    pp = _pruned_pingpong()
    base = TensorSearch(pp, chunk=64, max_depth=12).run()
    sp = TensorSearch(pp, chunk=64, max_depth=12, visited_cap=8,
                      spill=True).run()
    _assert_exact(base, sp)
    assert sp.spilled_keys > 0
    assert sp.dropped_states == 0


def test_device_spill_parity_lab1_eighth_capacity(lab1_base):
    """ACCEPTANCE: strict lab1 with the device visited table capped at
    ~1/8 of the reachable count completes DEPTH_EXHAUSTED with exact
    unique/explored parity and zero dropped states — 'table full'
    degrades to 'slower, still exact'."""
    cap = _eighth_cap(lab1_base.unique_states)
    assert cap * 8 <= lab1_base.unique_states * 2
    out = TensorSearch(_pruned_clientserver(), chunk=16,
                       max_depth=LAB1_DEPTH, visited_cap=cap,
                       frontier_cap=1 << 11, spill=True).run()
    _assert_exact(lab1_base, out)
    assert out.dropped_states == 0
    assert out.spilled_keys > 0            # the tier really engaged
    assert out.host_tier_hits > 0          # refilter really corrected
    assert out.respilled_frontier > 0      # frontier really spooled


def test_sharded_spill_parity_lab1_eighth_capacity(lab1_base):
    """The same acceptance bar on the sharded engine (2-device mesh):
    global abort/revert, sharded drain/evict/reinject, exact counts."""
    cap_total = _eighth_cap(lab1_base.unique_states)
    mesh = make_mesh(2)
    out = ShardedTensorSearch(
        _pruned_clientserver(), mesh, chunk_per_device=16,
        frontier_cap=256, visited_cap=cap_total, max_depth=LAB1_DEPTH,
        strict=True, spill=True).run()
    _assert_exact(lab1_base, out)
    assert out.dropped_states == 0
    assert out.spilled_keys > 0
    # Per-level load factor rides SearchOutcome.levels (satellite).
    assert out.levels and all("load_factor" in r for r in out.levels)


def test_spill_checkpoint_resume_parity(lab1_base, tmp_path):
    """A spill run checkpointed per level resumes from its dump to the
    identical verdict and counts (in-process half of the kill-resume
    acceptance; the dump's visited_keys is the device ∪ tier union)."""
    cap = _eighth_cap(lab1_base.unique_states)
    pth = str(tmp_path / "spill.ckpt")
    kw = dict(chunk=16, visited_cap=cap, frontier_cap=1 << 11,
              spill=True, checkpoint_path=pth, checkpoint_every=1)
    partial = TensorSearch(_pruned_clientserver(), max_depth=6,
                           **kw).run()
    assert partial.depth == 6
    assert os.path.exists(pth)
    out = TensorSearch(_pruned_clientserver(), max_depth=LAB1_DEPTH,
                       **kw).run(resume=True)
    _assert_exact(lab1_base, out)
    # Cross-engine: a NON-spill engine with a big enough table resumes
    # the same spill dump (the format is tier-agnostic).
    out2 = TensorSearch(_pruned_clientserver(), chunk=1024,
                        max_depth=LAB1_DEPTH, visited_cap=1 << 20,
                        checkpoint_path=pth).run(resume=True)
    _assert_exact(lab1_base, out2)


@pytest.mark.fault
def test_sigkill_mid_spill_resume_parity(lab1_base, tmp_path):
    """ACCEPTANCE: the capped lab1 run SIGKILLed MID-SPILL (tier
    already populated, checkpoints on disk) resumes from the dump to
    the identical DEPTH_EXHAUSTED verdict and exact counts."""
    cap = _eighth_cap(lab1_base.unique_states)
    pth = str(tmp_path / "kill.ckpt")
    child_src = (
        "import dataclasses\n"
        "from dslabs_tpu.tpu.engine import TensorSearch\n"
        "from dslabs_tpu.tpu.protocols.clientserver import"
        " make_clientserver_protocol\n"
        "cs = make_clientserver_protocol(n_clients=3, w=4)\n"
        "cs = dataclasses.replace(cs, goals={},"
        " prunes={'CLIENTS_DONE': cs.goals['CLIENTS_DONE']})\n"
        f"TensorSearch(cs, chunk=16, max_depth={LAB1_DEPTH},"
        f" visited_cap={cap}, frontier_cap=2048, spill=True,"
        f" checkpoint_path={pth!r}, checkpoint_every=1).run()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR="/tmp/jaxcache-cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", child_src], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        # Kill once the dump proves the spill tier is live (the run
        # evicts by ~depth 5-6 at 1/8 capacity).
        deadline = time.time() + 120
        while time.time() < deadline:
            d = ckpt_mod.peek_depth(pth)
            if d is not None and d >= 6:
                break
            if proc.poll() is not None:
                break
            time.sleep(0.05)
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert ckpt_mod.peek_depth(pth) is not None
    out = TensorSearch(_pruned_clientserver(), chunk=16,
                       max_depth=LAB1_DEPTH, visited_cap=cap,
                       frontier_cap=2048, spill=True,
                       checkpoint_path=pth,
                       checkpoint_every=1).run(resume=True)
    _assert_exact(lab1_base, out)
    assert out.dropped_states == 0


def test_bfs_refuses_foreign_spill_checkpoint(tmp_path):
    """A spill dump written by a DIFFERENT protocol config is refused
    with a loud CheckpointMismatch naming both fingerprints — never
    resumed (or skipped) silently."""
    pth = str(tmp_path / "foreign.ckpt")
    pp_engine = TensorSearch(_pruned_pingpong(), chunk=64,
                             max_depth=12, visited_cap=8, spill=True,
                             checkpoint_path=pth, checkpoint_every=1)
    pp_engine.run()
    assert os.path.exists(pth)
    lab1 = TensorSearch(_pruned_clientserver(), chunk=64,
                        max_depth=4, visited_cap=1 << 12, spill=True,
                        checkpoint_path=pth)
    assert not lab1.has_resumable_checkpoint()
    with pytest.raises(ckpt_mod.CheckpointMismatch):
        lab1.run(resume=True)


# ------------------------------------------------- supervisor ladder

def test_supervisor_capacity_ladder(lab1_base, tmp_path):
    """spill='ladder': CapacityOverflow is a CLASSIFIED failure (kind
    'capacity' on the chain) and the rung retries WITH the spill tier,
    resuming from its checkpoint — identical verdict and counts."""
    cap = _eighth_cap(lab1_base.unique_states)
    sup = SearchSupervisor(
        _pruned_clientserver(), ladder=("device",), mesh=make_mesh(1),
        chunk=32, visited_cap=max(cap * 2, 256),
        frontier_cap=1 << 11, max_depth=LAB1_DEPTH,
        checkpoint_path=str(tmp_path / "ladder.ckpt"),
        checkpoint_every=2, policy=RetryPolicy(max_retries=1),
        spill="ladder")
    out = sup.run()
    _assert_exact(lab1_base, out)
    assert any(f.kind == "capacity" for f in sup.failures)
    assert out.failovers >= 1
    assert out.spilled_keys > 0


def test_supervisor_default_still_passes_capacity_through():
    """The historical contract is untouched by default: without the
    opt-in, CapacityOverflow passes through unwrapped (also pinned by
    test_supervisor.py)."""
    from dslabs_tpu.tpu.visited import BKT

    with pytest.raises(CapacityOverflow):
        SearchSupervisor(
            _pruned_clientserver(nc=1, w=2), ladder=("device",),
            mesh=make_mesh(1), chunk=64, visited_cap=BKT,
            policy=RetryPolicy(max_retries=1)).run()


# ----------------------------------------- spill-dispatch fault matrix

@pytest.mark.fault
def test_faultplan_spill_dispatch_transient_retry(lab1_base):
    """Transient raise-variants targeted at EVERY new spill site
    (drain/refilter, evict, reinject) via FaultPlan site rules: each
    retries in place through the standard boundary, counts exact."""
    cap = _eighth_cap(lab1_base.unique_states)
    plan = FaultPlan()
    for site in ("spill_drain", "spill_evict", "spill_reinject"):
        plan.raise_at(1, engine="device", site=site,
                      error=TransientDeviceError)
    sup = SearchSupervisor(
        _pruned_clientserver(), ladder=("device",), mesh=make_mesh(1),
        chunk=16, visited_cap=cap, frontier_cap=1 << 11,
        max_depth=LAB1_DEPTH, policy=RetryPolicy(max_retries=3),
        spill=True, fault_plan=plan)
    out = sup.run()
    _assert_exact(lab1_base, out)
    assert plan.fired == 3
    assert out.retries == 3


@pytest.mark.fault
def test_faultplan_spill_dispatch_hang_fails_over(lab1_base):
    """A HANG on a spill dispatch is abandoned by the wall-clock
    watchdog (never retried in place) and the ladder fails over to the
    host rung — verdict parity, degradation visible."""
    cap = _eighth_cap(lab1_base.unique_states)
    plan = FaultPlan().hang_at(2, engine="device", site="spill_drain")
    sup = SearchSupervisor(
        _pruned_clientserver(), ladder=("device", "host"),
        mesh=make_mesh(1), chunk=16, visited_cap=cap,
        frontier_cap=1 << 11, max_depth=LAB1_DEPTH,
        policy=RetryPolicy(max_retries=1, deadline_secs=1.5,
                           deadline_first_secs=90.0),
        spill=True, fault_plan=plan)
    out = sup.run()
    assert out.engine == "host"
    assert out.failovers == 1
    assert sup.failures[0].kind == "wedged"
    _assert_exact(lab1_base, out)


# ------------------------------------------------ loud-accounting layer

def test_visited_warn_fires_before_overflow():
    """DSLABS_VISITED_WARN (default 0.85): operators see table
    pressure BEFORE the overflow contract degrades anything."""
    proto = _pruned_clientserver(nc=3, w=2)
    with pytest.warns(RuntimeWarning, match="capacity pressure"):
        out = ShardedTensorSearch(
            proto, make_mesh(1), chunk_per_device=64,
            frontier_cap=1 << 10, visited_cap=64, strict=False,
            max_depth=5).run()
    assert out.end_condition == "DEPTH_EXHAUSTED"


def test_dropped_states_surfaced_and_warned(monkeypatch):
    """Beam drops are a COUNT everywhere (SearchOutcome.dropped_states)
    and loud past DSLABS_DROPPED_WARN — millions of drops can no
    longer hide behind a flag."""
    monkeypatch.setenv("DSLABS_DROPPED_WARN", "1")
    proto = _pruned_clientserver(nc=3, w=3)
    with pytest.warns(RuntimeWarning, match="dropped"):
        out = ShardedTensorSearch(
            proto, make_mesh(1), chunk_per_device=64,
            frontier_cap=64, visited_cap=1 << 12, strict=False,
            max_depth=8).run()
    assert out.dropped_states > 0
    assert out.dropped_states == out.dropped


def test_spill_record_trace_rejected():
    with pytest.raises(ValueError, match="record_trace"):
        TensorSearch(_pruned_pingpong(), spill=True, record_trace=True)


# ------------------------------------------------- async drain (ISSUE 15c)

@pytest.mark.capacity2
def test_async_drain_default_on_and_sync_parity(lab1_base):
    """The async gear is the default (DSLABS_SPILL_ASYNC), its counts
    are exact, and the legacy sync gear produces the identical
    verdict — async is a scheduling change, never a semantic one."""
    cap = _eighth_cap(lab1_base.unique_states)
    kw = dict(chunk=16, max_depth=LAB1_DEPTH, visited_cap=cap,
              frontier_cap=1 << 11)
    a = TensorSearch(_pruned_clientserver(),
                     spill=spill_mod.SpillConfig(async_drain=True),
                     **kw).run()
    s = TensorSearch(_pruned_clientserver(),
                     spill=spill_mod.SpillConfig(async_drain=False),
                     **kw).run()
    _assert_exact(lab1_base, a)
    _assert_exact(lab1_base, s)
    assert a.dropped_states == s.dropped_states == 0
    # The async run measured its wall split; overlap = drain work the
    # driver never blocked on (host drain no longer additive with the
    # device chunk wall).
    assert a.spill_drain_ms > 0
    assert a.spill_drain_ms >= a.spill_wait_ms
    assert s.spill_wait_ms == 0 and s.spill_drain_ms == 0


@pytest.mark.capacity2
def test_async_drain_level_records_carry_wall_split(lab1_base):
    """The per-level records carry the drain/wait/overlap split
    (telemetry satellite: the spill detour's cost is attributable per
    level, not just in aggregate)."""
    from dslabs_tpu.tpu import telemetry as tel_mod

    cap = _eighth_cap(lab1_base.unique_states)
    tel = tel_mod.Telemetry()
    out = TensorSearch(_pruned_clientserver(), chunk=16,
                       max_depth=LAB1_DEPTH, visited_cap=cap,
                       frontier_cap=1 << 11, spill=True,
                       telemetry=tel).run()
    _assert_exact(lab1_base, out)
    recs = [r for r in tel.levels if r.get("spill")]
    assert recs, "spill level records missing the wall split"
    for r in recs:
        for k in ("drain_wall", "drain_wait", "drain_overlap"):
            assert k in r["spill"]
    total_drain = sum(r["spill"]["drain_wall"] for r in recs)
    assert abs(total_drain - out.spill_drain_ms / 1000.0) < 0.25


@pytest.mark.capacity2
def test_async_drain_worker_error_surfaces_loudly():
    """A drain job that raises (host tier full) surfaces at the next
    barrier as the same loud CapacityOverflow the sync gear raises —
    never swallowed on the worker thread."""
    with pytest.raises(CapacityOverflow, match="host spill tier"):
        TensorSearch(_pruned_clientserver(), chunk=16,
                     max_depth=LAB1_DEPTH, visited_cap=64,
                     frontier_cap=1 << 11,
                     spill=spill_mod.SpillConfig(
                         async_drain=True, host_cap=32)).run()


@pytest.mark.capacity2
@pytest.mark.fault
def test_async_drain_abort_revert_chaos(lab1_base):
    """ACCEPTANCE (abort/revert chaos): transient faults injected at
    every spill dispatch site under the ASYNC gear retry through the
    standard boundary with exact counts — the abort-wholesale-revert
    contract holds while drains are in flight."""
    cap = _eighth_cap(lab1_base.unique_states)
    plan = FaultPlan()
    for site in ("spill_drain", "spill_evict", "spill_reinject"):
        plan.raise_at(1, engine="device", site=site,
                      error=TransientDeviceError)
    sup = SearchSupervisor(
        _pruned_clientserver(), ladder=("device",), mesh=make_mesh(1),
        chunk=16, visited_cap=cap, frontier_cap=1 << 11,
        max_depth=LAB1_DEPTH, policy=RetryPolicy(max_retries=3),
        spill=spill_mod.SpillConfig(async_drain=True),
        fault_plan=plan)
    out = sup.run()
    _assert_exact(lab1_base, out)
    assert plan.fired == 3
    assert out.dropped_states == 0


# ------------------------------------------------------------ slow tier

@pytest.mark.slow
def test_spill_parity_paxos_d5():
    """Third protocol family at depth 5 (the perf-smoke paxos rung)
    through the capacity ladder: exact parity at ~1/8 table capacity."""
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    proto = make_paxos_protocol(n=3, n_clients=1, w=1, max_slots=2,
                                net_cap=16, timer_cap=4)
    base = TensorSearch(proto, chunk=1024, max_depth=5,
                        visited_cap=1 << 15).run()
    assert base.end_condition == "DEPTH_EXHAUSTED"
    cap = _eighth_cap(base.unique_states)
    out = TensorSearch(proto, chunk=16, max_depth=5, visited_cap=cap,
                       frontier_cap=1 << 12, spill=True).run()
    _assert_exact(base, out)
    assert out.dropped_states == 0
    assert out.spilled_keys > 0
