"""Sharded multi-chip BFS: verdict + unique-state parity vs the
single-device engine on the 8-device virtual CPU mesh (conftest.py).

Both configurations run to exhaustion (pruned space / depth limit), so
unique-state counts are exploration-order independent and must match the
single-device engine exactly — any routing/dedup-return regression in the
fingerprint-exchange path (sharded.py) shows up as a count mismatch.
"""

import dataclasses

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu.engine import TensorSearch
from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh


def _pruned_pingpong():
    pp = make_pingpong_protocol(workload_size=2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def test_make_mesh_raises_on_too_few_devices(monkeypatch):
    """``make_mesh(n)`` takes the DEFAULT backend's devices or raises:
    a CPU mesh never stands in for accelerators that are not there."""
    n = len(jax.devices())
    assert make_mesh(n).devices.size == n
    with pytest.raises(RuntimeError, match=f"need {n + 1} devices"):
        make_mesh(n + 1)
    # A one-accelerator default backend asked for four: raise, although
    # jax.devices("cpu") could have supplied them.
    real = jax.devices

    def fake(backend=None):
        return real(backend) if backend else real()[:1]

    monkeypatch.setattr(jax, "devices", fake)
    with pytest.raises(RuntimeError, match="need 4 devices"):
        make_mesh(4)


def test_outcomes_name_their_platform():
    """Every verdict says which device computed it — engine, sharded
    engine, and through the warden's pipe format."""
    from dslabs_tpu.tpu.warden import outcome_from_dict, outcome_to_dict

    proto = _pruned_pingpong()
    dev = jax.devices()[0]
    single = TensorSearch(proto, chunk=64).run()
    sharded = ShardedTensorSearch(
        proto, make_mesh(2), chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10).run()
    for out in (single, sharded, outcome_from_dict(
            outcome_to_dict(sharded))):
        assert out.platform == dev.platform == "cpu"
        assert out.device_kind == dev.device_kind


@pytest.mark.parametrize("strict", [True, False])
def test_sharded_exhaustive_parity(strict):
    """SPACE_EXHAUSTED verdict and exact unique counts, both with the
    in-chunk dedup prefilter (strict) and with owner-side-only dedup
    (bench mode, strict=False)."""
    proto = _pruned_pingpong()
    mesh = make_mesh(8)
    single = TensorSearch(proto, chunk=64).run()
    sharded = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, strict=strict).run()
    assert sharded.end_condition == single.end_condition == "SPACE_EXHAUSTED"
    assert sharded.unique_states == single.unique_states
    assert sharded.states_explored == single.states_explored
    assert sharded.dropped == 0


def test_sharded_staged_search_from_goal_state():
    """run(initial=...) — the staged-search pattern on the sharded
    engine (PaxosTest.java:886-1096): extract a goal state from phase 1,
    search onward from it, and match the single-device engine's staged
    verdict and counts."""
    from dslabs_tpu.tpu.protocols.clientserver import \
        make_clientserver_protocol

    proto = make_clientserver_protocol(n_clients=1, w=2)
    mesh = make_mesh(8)
    phase1 = ShardedTensorSearch(
        proto, mesh, chunk_per_device=32, frontier_cap=1 << 9,
        visited_cap=1 << 12, strict=True).run()
    assert phase1.end_condition == "GOAL_FOUND"

    # Phase 2: from the goal state, the whole pruned space is exhausted.
    proto2 = dataclasses.replace(
        proto, goals={}, prunes={"DONE": proto.goals["CLIENTS_DONE"]})
    single2 = TensorSearch(proto2, chunk=64).run(
        initial=phase1.goal_state)
    sharded2 = ShardedTensorSearch(
        proto2, mesh, chunk_per_device=32, frontier_cap=1 << 9,
        visited_cap=1 << 12, strict=True).run(initial=phase1.goal_state)
    assert (sharded2.end_condition == single2.end_condition
            == "SPACE_EXHAUSTED")
    assert sharded2.unique_states == single2.unique_states
    assert sharded2.states_explored == single2.states_explored


def test_sharded_violation_trace_replays_on_object_twin():
    """A sharded INVARIANT_VIOLATED yields a trace that replays on the
    object twin to a state violating the same predicate — the capability
    the round-2 verdict flagged as missing (production engine explaining
    its own counterexamples)."""
    from dslabs_tpu.testing.predicates import CLIENTS_DONE
    from dslabs_tpu.tpu.protocols.clientserver import \
        make_clientserver_protocol
    from dslabs_tpu.tpu.trace import reconstruct_object_trace
    from tests.test_tpu_trace import _object_initial

    p = make_clientserver_protocol(n_clients=1, w=1)
    done = p.goals["CLIENTS_DONE"]
    p = dataclasses.replace(
        p, goals={}, invariants={"NEVER_DONE": lambda s, f=done: ~f(s)})
    mesh = make_mesh(8)
    sharded = ShardedTensorSearch(
        p, mesh, chunk_per_device=32, frontier_cap=1 << 9,
        visited_cap=1 << 12, strict=True, record_trace=True)
    outcome = sharded.run()
    assert outcome.end_condition == "INVARIANT_VIOLATED"
    assert outcome.trace, "sharded record_trace must produce an event list"

    single = TensorSearch(p, chunk=64, record_trace=True)
    s_out = single.run()
    assert s_out.end_condition == "INVARIANT_VIOLATED"
    # Same violation DEPTH as the single-device engine (BFS shortest).
    assert len(outcome.trace) == len(s_out.trace)

    never_done = CLIENTS_DONE.negate()
    end = reconstruct_object_trace(sharded, outcome, _object_initial(1, 1),
                                   predicate=never_done)
    r = never_done.check(end)
    assert not r.value, "replayed end state must violate NEVER_DONE"
    assert end.depth <= len(outcome.trace)


def test_checkpoint_resume_identical_outcome(tmp_path):
    """Kill-and-resume semantics (SURVEY §5 frontier checkpointing): a
    search checkpointed every level, interrupted, then resumed from the
    dump must reach the identical verdict, unique count, and explored
    count as an uninterrupted run."""
    proto = _pruned_pingpong()
    mesh = make_mesh(8)
    full = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10).run()
    assert full.end_condition == "SPACE_EXHAUSTED"

    ckpt = str(tmp_path / "search.npz")
    # "Crash" after 2 levels: only the checkpoint file survives.
    interrupted = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, max_depth=2,
        checkpoint_path=ckpt, checkpoint_every=1)
    out = interrupted.run()
    assert out.end_condition == "DEPTH_EXHAUSTED"
    import os
    assert os.path.exists(ckpt)

    resumed = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, checkpoint_path=ckpt)
    r = resumed.run(resume=True)
    assert r.end_condition == full.end_condition
    assert r.unique_states == full.unique_states
    assert r.states_explored == full.states_explored

    # The unified dump format (tpu/checkpoint.py) is engine-knob
    # agnostic: a DIFFERENT chunk size resumes the same file to the
    # same verdict and counts (the dump stores semantic search state,
    # not a carry layout).
    other = ShardedTensorSearch(
        proto, mesh, chunk_per_device=32, frontier_cap=1 << 8,
        visited_cap=1 << 10, checkpoint_path=ckpt)
    assert other.has_resumable_checkpoint()
    o = other.run(resume=True)
    assert o.end_condition == full.end_condition
    assert o.unique_states == full.unique_states
    assert o.states_explored == full.states_explored

    # A dump from a different PROTOCOL/CAPACITY config is rejected
    # loudly — CheckpointMismatch naming both fingerprints, never a
    # silent skip (see also tests/test_supervisor.py).
    import dataclasses as _dc

    import pytest as _pytest

    from dslabs_tpu.tpu.checkpoint import CheckpointMismatch

    bigger = _dc.replace(proto, net_cap=proto.net_cap * 2)
    mismatched = ShardedTensorSearch(
        bigger, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, checkpoint_path=ckpt)
    assert not mismatched.has_resumable_checkpoint()
    with _pytest.raises(CheckpointMismatch) as ei:
        mismatched.run(resume=True)
    assert proto.name in str(ei.value)
    assert mismatched._ckpt_fingerprint() in str(ei.value)

    # Resuming a checkpoint saved AFTER the final level (empty frontier)
    # returns the finished verdict instead of crashing.
    done_ckpt = str(tmp_path / "done.npz")
    finished = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, checkpoint_path=done_ckpt,
        checkpoint_every=1)
    f1 = finished.run()
    assert f1.end_condition == "SPACE_EXHAUSTED"
    f2 = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, checkpoint_path=done_ckpt).run(resume=True)
    assert f2.end_condition == "SPACE_EXHAUSTED"
    assert f2.unique_states == f1.unique_states


def test_event_window_spill_exact_counts():
    """A tiny ev_budget with window spill must reproduce the full-grid
    unique/explored counts exactly: events past a window re-step the
    chunk at the next window (sharded.py round-4 spill), so the budget
    is a throughput knob, never a coverage cut."""
    proto = _pruned_pingpong()
    mesh = make_mesh(8)
    full = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, strict=True).run()
    # Budget far below the protocol's event grid: forces multi-pass
    # spills on nearly every loaded chunk.
    tiny = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, strict=True, ev_budget=(2, 1),
        ev_spill=True).run()
    assert tiny.end_condition == full.end_condition == "SPACE_EXHAUSTED"
    assert tiny.unique_states == full.unique_states
    assert tiny.states_explored == full.states_explored
    assert tiny.dropped == 0


def test_count_only_final_level_matches_depth_limit():
    """max_depth runs count/check the final level's fresh states without
    building its frontier (noapp); unique/explored totals must equal a
    run whose frontier cap could hold that level."""
    proto = _pruned_pingpong()
    mesh = make_mesh(8)
    wide = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, strict=True, max_depth=4).run()
    assert wide.end_condition == "DEPTH_EXHAUSTED"
    single = TensorSearch(proto, chunk=64, max_depth=4).run()
    assert single.end_condition == "DEPTH_EXHAUSTED"
    assert wide.unique_states == single.unique_states
    assert wide.states_explored == single.states_explored
