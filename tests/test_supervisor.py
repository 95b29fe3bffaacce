"""Fault-tolerant search supervisor (ISSUE 2): every recovery path
proven end-to-end on CPU via the deterministic fault-injection harness
(tpu/supervisor.py FaultPlan) installed at the dispatch boundary:

* transient-error retry succeeds within budget (identical outcome);
* exhausted retries / fatal errors fail over sharded -> single-device
  -> host on a lab1 strict BFS with a verdict identical to the
  unfaulted run;
* a run killed mid-search resumes from its checkpoint in both engines
  (and across engines — the dump format is engine-agnostic);
* a hung dispatch is detected by the wall-clock watchdog, abandoned,
  and recovered on the next rung;
* no recovery path ever returns a silent partial verdict — total
  failure is a loud SupervisorExhausted, semantic errors
  (CapacityOverflow, CheckpointMismatch) pass straight through.

Marked ``fault`` (``make fault-smoke`` runs exactly this suite under
JAX_PLATFORMS=cpu).
"""

import dataclasses
import os

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import checkpoint as ckpt_mod  # noqa: E402
from dslabs_tpu.tpu.engine import CapacityOverflow, TensorSearch  # noqa: E402
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import make_mesh  # noqa: E402
from dslabs_tpu.tpu.supervisor import (DispatchTimeout, EngineFailure,  # noqa: E402
                                       FaultPlan, RetryPolicy,
                                       SearchSupervisor,
                                       SupervisorExhausted,
                                       TransientDeviceError,
                                       classify_failure, install_retry)

pytestmark = pytest.mark.fault


class FatalError(RuntimeError):
    """An injected NON-transient failure (classified fatal)."""


def _pruned_pingpong():
    pp = make_pingpong_protocol(2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def _pruned_clientserver():
    cs = make_clientserver_protocol(n_clients=1, w=2)
    return dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]})


def _sup(proto, **kw):
    kw.setdefault("mesh", make_mesh(8))
    kw.setdefault("chunk", 16)
    kw.setdefault("frontier_cap", 1 << 8)
    kw.setdefault("visited_cap", 1 << 10)
    return SearchSupervisor(proto, **kw)


def _same_verdict(a, b):
    assert a.end_condition == b.end_condition
    assert a.unique_states == b.unique_states
    assert a.states_explored == b.states_explored


# ------------------------------------------------------- classification

def test_failure_classification():
    assert classify_failure(TransientDeviceError("x")) == "transient"
    assert classify_failure(DispatchTimeout("x")) == "wedged"
    assert classify_failure(FatalError("x")) == "fatal"

    class XlaRuntimeError(RuntimeError):
        pass

    assert classify_failure(
        XlaRuntimeError("RESOURCE_EXHAUSTED: hbm oom")) == "transient"
    assert classify_failure(XlaRuntimeError("INVALID_ARGUMENT")) == "fatal"


# ------------------------------------------------------ retry-in-place

def test_transient_retry_within_budget_identical_outcome():
    """Two injected transient failures, budget three: the run recovers
    IN PLACE on the sharded rung; verdict and counts match the
    unfaulted run and the retries are visible on the outcome."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    assert base.end_condition == "SPACE_EXHAUSTED"
    out = _sup(proto,
               fault_plan=FaultPlan().raise_at(3, count=2),
               policy=RetryPolicy(max_retries=3,
                                  backoff_base=0.001)).run()
    _same_verdict(out, base)
    assert out.engine == "sharded"
    assert out.retries == 2
    assert out.failovers == 0


def test_retry_budget_is_per_rung():
    """Retries spent on a failed rung do not starve the next rung: each
    engine gets the full budget (the counters are per-engine)."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    plan = (FaultPlan()
            .raise_always(engine="sharded")            # exhausts rung 1
            .raise_at(2, count=1, engine="device"))    # one transient
    out = _sup(proto, fault_plan=plan,
               policy=RetryPolicy(max_retries=2,
                                  backoff_base=0.001)).run()
    _same_verdict(out, base)
    assert out.engine == "device"
    assert out.failovers == 1


# ---------------------------------------------------------- failover

def test_failover_ladder_lab1_strict_verdict_parity():
    """The acceptance path: exhausted retries on the sharded rung, a
    fatal error on the single-device rung — the host loop (the parity
    oracle) lands the IDENTICAL verdict on a lab1 strict BFS."""
    proto = _pruned_clientserver()
    base = _sup(proto, chunk=64, frontier_cap=1 << 9,
                visited_cap=1 << 12).run()
    assert base.end_condition == "SPACE_EXHAUSTED"
    plan = (FaultPlan()
            .raise_always(engine="sharded")
            .raise_always(error=FatalError, engine="device"))
    out = _sup(proto, chunk=64, frontier_cap=1 << 9,
               visited_cap=1 << 12, fault_plan=plan,
               policy=RetryPolicy(max_retries=1,
                                  backoff_base=0.001)).run()
    _same_verdict(out, base)
    assert out.engine == "host"
    assert out.failovers == 2
    assert out.retries >= 1          # the sharded rung did retry first


def test_goal_verdict_survives_failover():
    """Failover preserves TERMINAL verdicts too, not just exhaustion:
    the pingpong goal is found at the same BFS depth on the next rung."""
    proto = make_pingpong_protocol(2)
    base = _sup(proto).run()
    assert base.end_condition == "GOAL_FOUND"
    out = _sup(proto,
               fault_plan=FaultPlan().raise_always(error=FatalError,
                                                   engine="sharded"),
               policy=RetryPolicy(max_retries=0)).run()
    assert out.end_condition == "GOAL_FOUND"
    assert out.predicate_name == base.predicate_name
    assert out.depth == base.depth
    assert out.engine == "device" and out.failovers == 1


def test_all_rungs_fail_is_loud_and_attributable():
    """No silent partial verdict: when every rung fails, the supervisor
    raises SupervisorExhausted carrying the per-rung failure chain."""
    proto = _pruned_pingpong()
    with pytest.raises(SupervisorExhausted) as ei:
        _sup(proto,
             fault_plan=FaultPlan().raise_always(error=FatalError),
             policy=RetryPolicy(max_retries=0)).run()
    assert len(ei.value.failures) == 3
    assert all(isinstance(f, EngineFailure) for f in ei.value.failures)
    assert [f.engine for f in ei.value.failures] == [
        "sharded", "device", "host"]


def test_capacity_overflow_passes_through_unwrapped():
    """Semantic errors must NEVER be absorbed by retry or failover —
    a too-small strict visited table raises CapacityOverflow through
    the boundary unchanged (the capacity ladder owns that failure)."""
    from dslabs_tpu.tpu.visited import BKT

    proto = _pruned_clientserver()
    with pytest.raises(CapacityOverflow):
        _sup(proto, ladder=("device", "host"), chunk=64,
             visited_cap=BKT,
             policy=RetryPolicy(max_retries=3)).run()


# ---------------------------------------------------------- watchdog

def test_hung_dispatch_detected_and_recovered():
    """A dispatch that hangs (injected wedge) is abandoned by the
    wall-clock watchdog at its deadline and the search restarts on the
    next rung — same verdict, failover visible."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    # Hang dispatch 4 of the sharded rung (a warm site — the first
    # dispatch per site gets the compile-inclusive grace deadline).
    out = _sup(proto,
               fault_plan=FaultPlan().hang_at(4, engine="sharded",
                                              secs=60.0),
               policy=RetryPolicy(max_retries=1, backoff_base=0.001,
                                  deadline_secs=1.0,
                                  deadline_first_secs=300.0)).run()
    _same_verdict(out, base)
    assert out.engine == "device"
    assert out.failovers == 1


# ------------------------------------------------- checkpoint + resume

def test_kill_resume_single_device_engine(tmp_path):
    """Kill-and-resume on the single-device device-resident loop: a
    checkpointed run cut at depth 2 resumes to the identical verdict,
    unique count, and explored count as an uninterrupted run."""
    proto = _pruned_pingpong()
    full = TensorSearch(proto, chunk=64).run()
    ckpt = str(tmp_path / "dev.npz")
    cut = TensorSearch(proto, chunk=64, max_depth=2,
                       checkpoint_path=ckpt, checkpoint_every=1)
    assert cut.run().end_condition == "DEPTH_EXHAUSTED"
    assert os.path.exists(ckpt)
    resumed = TensorSearch(proto, chunk=64, checkpoint_path=ckpt)
    r = resumed.run(resume=True)
    _same_verdict(r, full)
    assert resumed._resumed_from_depth == 2


def test_kill_resume_crosses_engines(tmp_path):
    """The unified dump is ENGINE-AGNOSTIC: a checkpoint written by the
    single-device loop resumes on the host loop and vice versa — the
    property supervisor failover depends on."""
    proto = _pruned_pingpong()
    full = TensorSearch(proto, chunk=64).run()
    ckpt = str(tmp_path / "cross.npz")
    TensorSearch(proto, chunk=64, max_depth=2, checkpoint_path=ckpt,
                 checkpoint_every=1).run()
    host = TensorSearch(proto, chunk=64, checkpoint_path=ckpt,
                        use_host_visited=True).run(resume=True)
    _same_verdict(host, full)

    ckpt2 = str(tmp_path / "cross2.npz")
    TensorSearch(proto, chunk=64, max_depth=2, use_host_visited=True,
                 checkpoint_path=ckpt2, checkpoint_every=1).run()
    dev = TensorSearch(proto, chunk=64,
                       checkpoint_path=ckpt2).run(resume=True)
    _same_verdict(dev, full)


def test_failover_resumes_from_checkpoint(tmp_path):
    """A rung killed mid-search (fatal fault after the depth-2 dump):
    the next rung RESUMES from the checkpoint instead of the root and
    reports the resumed depth on the outcome."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    ckpt = str(tmp_path / "fo.npz")
    plan = FaultPlan().raise_at(8, error=FatalError, engine="sharded")
    out = _sup(proto, fault_plan=plan, checkpoint_path=ckpt,
               checkpoint_every=1,
               policy=RetryPolicy(max_retries=0)).run()
    _same_verdict(out, base)
    assert out.engine == "device"
    assert out.failovers == 1
    assert out.resumed_from_depth > 0


def test_checkpoint_mismatch_rejected_loudly(tmp_path):
    """Satellite: a dump from a different protocol/capacity config is
    refused with BOTH fingerprints in the error — never silently
    resumed, never silently ignored."""
    proto = _pruned_pingpong()
    ckpt = str(tmp_path / "mm.npz")
    TensorSearch(proto, chunk=64, max_depth=2, checkpoint_path=ckpt,
                 checkpoint_every=1).run()
    bigger = dataclasses.replace(proto, net_cap=proto.net_cap * 2)
    other = TensorSearch(bigger, chunk=64, checkpoint_path=ckpt)
    assert not other.has_resumable_checkpoint()
    with pytest.raises(ckpt_mod.CheckpointMismatch) as ei:
        other.run(resume=True)
    msg = str(ei.value)
    assert other._ckpt_fingerprint() in msg            # live config
    assert TensorSearch(proto, chunk=64)._ckpt_fingerprint() in msg
    # Differing STRICTNESS is a semantic mismatch too (beam counts may
    # over-report) — also refused.
    beam = TensorSearch(proto, chunk=64, strict=False,
                        checkpoint_path=ckpt)
    with pytest.raises(ckpt_mod.CheckpointMismatch):
        beam.run(resume=True)


def test_supervisor_zero_fault_plan_is_transparent():
    """A supervisor with the default policy and no faults changes
    nothing: same verdict/counts as the bare engine, zero counters
    (the perf-smoke gate rides this same path)."""
    proto = _pruned_pingpong()
    bare = TensorSearch(proto, chunk=64).run()
    out = _sup(proto, ladder=("device",), chunk=64).run()
    _same_verdict(out, bare)
    assert (out.retries, out.failovers, out.resumed_from_depth) == (0, 0, 0)
    assert (out.abandoned_threads, out.child_restarts,
            out.killed_dispatches) == (0, 0, 0)
    assert out.engine == "device"


def test_superstep_transient_fault_retries_in_place():
    """ISSUE 3 satellite: a FaultPlan fault injected INSIDE a superstep
    dispatch retries in place.  The sharded rung's dispatch sequence is
    init, (superstep, promote)*: index 3 IS a superstep dispatch."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    sup = _sup(proto, fault_plan=FaultPlan().raise_at(3, count=2),
               policy=RetryPolicy(max_retries=3, backoff_base=0.001))
    out = sup.run()
    _same_verdict(out, base)
    assert out.engine == "sharded"
    assert out.retries == 2
    assert out.failovers == 0


def test_superstep_fatal_fails_over_and_resumes_checkpoint(tmp_path):
    """ISSUE 3 satellite: a fatal fault inside a superstep dispatch
    fails over down the ladder and the next rung resumes from the
    unified checkpoint at the correct depth — the dispatch-boundary /
    checkpoint contracts survive the superstep refactor unchanged."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    ckpt = str(tmp_path / "ss.npz")
    # Dispatch 7 = the level-4 superstep (init + 2/level); checkpoints
    # land after levels 1..3 (async skip-if-busy may skip some, never
    # all — level gaps outlast the tiny dump).
    plan = FaultPlan().raise_at(7, error=FatalError, engine="sharded")
    sup = _sup(proto, fault_plan=plan, checkpoint_path=ckpt,
               checkpoint_every=1, policy=RetryPolicy(max_retries=0))
    out = sup.run()
    _same_verdict(out, base)
    assert out.engine == "device"
    assert out.failovers == 1
    assert 0 < out.resumed_from_depth <= 3


def test_superstep_watchdog_deadline_scales_with_trip_count():
    """The watchdog's steady-state deadline stretches by the published
    superstep trip-count scale (a fused level step legitimately runs a
    whole level's chunk work), while other sites keep the single-
    dispatch deadline."""
    from dslabs_tpu.tpu.supervisor import DispatchBoundary

    class Search:
        _dispatch_deadline_scales = {"superstep": 8.0}

    b = DispatchBoundary(RetryPolicy(deadline_secs=2.0))
    b.install(Search())
    assert b._deadline_scale("sharded.superstep") == 8.0
    assert b._deadline_scale("sharded.promote") == 1.0
    bare = DispatchBoundary(RetryPolicy(deadline_secs=2.0))
    assert bare._deadline_scale("sharded.superstep") == 1.0


def test_abandoned_thread_accounting_and_warning():
    """ISSUE 4 satellite: the in-process watchdog can only ABANDON a
    wedged dispatch, leaking a blocked daemon thread.  The boundary
    counts the still-blocked threads (surfaced as
    SearchOutcome.abandoned_threads / bench JSON) and warns past the
    threshold so in-process-mode degradation is visible."""
    import time as _time

    from dslabs_tpu.tpu.supervisor import DispatchBoundary

    b = DispatchBoundary(RetryPolicy(max_retries=0, deadline_secs=0.2,
                                     deadline_first_secs=0.2))

    def _block():
        # A genuinely blocked call (ignores the fault plan's release
        # event) — the wedged-XLA shape the watchdog cannot interrupt.
        _time.sleep(6.0)

    with pytest.raises(EngineFailure):
        b.dispatch("device.step", _block)
    assert b.abandoned_alive() == 1
    assert b.timeouts == 1
    with pytest.warns(RuntimeWarning, match="abandoned"):
        with pytest.raises(EngineFailure):
            b.dispatch("device.step", _block)
    assert b.abandoned_alive() == 2


def test_install_retry_single_engine():
    """install_retry (the backend's light-touch wrapper): transient
    faults retry in place on a bare engine; exhaustion is a loud
    EngineFailure, not a silent fallback."""
    proto = _pruned_pingpong()
    base = TensorSearch(proto, chunk=64).run()
    faulted = TensorSearch(proto, chunk=64)
    boundary = install_retry(
        faulted, RetryPolicy(max_retries=2, backoff_base=0.001),
        FaultPlan().raise_at(2, count=1))
    out = faulted.run()
    _same_verdict(out, base)
    assert boundary.retries == 1

    dead = TensorSearch(proto, chunk=64)
    install_retry(dead, RetryPolicy(max_retries=1, backoff_base=0.001),
                  FaultPlan().raise_always())
    with pytest.raises(EngineFailure):
        dead.run()
