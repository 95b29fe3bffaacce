"""Elastic mesh resilience (ISSUE 9): the degraded-mesh failover
ladder, the adaptive OOM knob-shrink, and the seeded chaos harness —
every path on the 8-virtual-device CPU dryrun mesh:

* ``expand_ladder`` turns ``"sharded"`` into
  ``sharded(D) -> sharded(D/2) -> ... -> sharded(2)`` and a fatal mesh
  rung degrades by HALVES (engine stays ``"sharded"``, ``mesh_width``
  and ``mesh_shrunk`` events say which half);
* cross-mesh-width resume parity matrix: one strict search
  checkpointed on an 8-wide mesh resumes on 4-, 2-, then 1-wide
  meshes to the IDENTICAL verdict/unique/explored with zero drops
  (pingpong + lab1), including the warden SIGKILL-mid-level variant
  (8-wide child killed, 4-wide child killed, 2-wide child finishes);
* an OOM-classified dispatch failure costs a knob-shrink RE-LEVEL
  (halved chunk + superstep budget, resume in place), not a rung —
  bounded by DSLABS_KNOB_SHRINKS, then the rung burns normally;
* the seeded chaos soak (tpu/chaos.py): >= 20 deterministic faults
  across >= 3 dispatch sites — transient storms, OOMs, a fatal, a
  hang — and the strict verdict still matches the fault-free run
  exactly.

Marked ``chaos`` (``make chaos-smoke``); the long soak variants are
additionally ``slow``.
"""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import chaos as chaos_mod  # noqa: E402
from dslabs_tpu.tpu.chaos import (ChaosOOM, ChaosSpec,  # noqa: E402
                                  build_plan, soak)
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh  # noqa: E402
from dslabs_tpu.tpu.supervisor import (FaultPlan,  # noqa: E402
                                       RetryPolicy, SearchSupervisor,
                                       classify_oom, expand_ladder)
from dslabs_tpu.tpu.telemetry import Telemetry  # noqa: E402
from dslabs_tpu.tpu.warden import Warden  # noqa: E402

pytestmark = pytest.mark.chaos

CHILD_ENV = {"JAX_COMPILATION_CACHE_DIR": "/tmp/jaxcache-cpu"}


class FatalError(RuntimeError):
    """Injected non-transient, non-OOM failure."""


def _pruned_pingpong():
    pp = make_pingpong_protocol(2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def _pruned_clientserver():
    cs = make_clientserver_protocol(n_clients=1, w=2)
    return dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]})


# Module-level for warden children ("tests.test_chaos:prune_*").
def prune_clientserver(cs):
    return dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]})


LAB1_REFS = {
    "factory": "dslabs_tpu.tpu.protocols.clientserver:"
               "make_clientserver_protocol",
    "factory_kwargs": {"n_clients": 1, "w": 2},
    "transform": "tests.test_chaos:prune_clientserver",
}

# One shared config per protocol family so every test (and the warden
# children, via the persistent compile cache) reuses the same XLA
# programs per mesh width.
PP_KW = dict(chunk=16, frontier_cap=1 << 8, visited_cap=1 << 10)
LAB1_KW = dict(chunk=64, frontier_cap=1 << 9, visited_cap=1 << 12)


def _sup(proto, **kw):
    kw.setdefault("mesh", make_mesh(8))
    for k, v in PP_KW.items():
        kw.setdefault(k, v)
    return SearchSupervisor(proto, **kw)


def _same_verdict(a, b):
    assert a.end_condition == b.end_condition
    assert a.unique_states == b.unique_states
    assert a.states_explored == b.states_explored


# ------------------------------------------------------ ladder mechanics

def test_expand_ladder_widths():
    """The width ladder is pinned: sharded(D) -> halves down to 2,
    then the historical device/host tail; non-elastic and narrow
    meshes expand to the identity."""
    assert expand_ladder(("sharded", "device", "host"), 8, True) == [
        ("sharded", None), ("sharded", 4), ("sharded", 2),
        ("device", None), ("host", None)]
    assert expand_ladder(("sharded", "device", "host"), 6, True) == [
        ("sharded", None), ("sharded", 3), ("sharded", 2),
        ("device", None), ("host", None)]
    assert expand_ladder(("sharded", "device"), 2, True) == [
        ("sharded", None), ("device", None)]
    assert expand_ladder(("sharded", "device", "host"), 8, False) == [
        ("sharded", None), ("device", None), ("host", None)]
    assert expand_ladder(("device", "host"), 8, True) == [
        ("device", None), ("host", None)]


def test_classify_oom_markers():
    assert classify_oom(MemoryError("boom"))
    assert classify_oom(ChaosOOM("chaos injected allocation failure"))
    assert classify_oom(RuntimeError("RESOURCE_EXHAUSTED: hbm"))
    assert classify_oom(RuntimeError("ran out of memory on device"))
    assert not classify_oom(RuntimeError("INVALID_ARGUMENT"))
    assert not classify_oom(None)


def test_elastic_fatal_degrades_by_half_not_cliff(tmp_path):
    """TENTPOLE: a fatal error on the 8-wide rung costs HALF the mesh
    — the supervisor rebuilds a 4-wide mesh, resumes the unified
    checkpoint re-sharded to the new owner map, and lands the
    identical strict verdict with the shrink on the outcome and the
    flight log."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    assert base.end_condition == "SPACE_EXHAUSTED"
    tel = Telemetry()
    out = _sup(proto, elastic=True,
               fault_plan=FaultPlan().raise_at(8, error=FatalError,
                                               engine="sharded"),
               checkpoint_path=str(tmp_path / "el.npz"),
               checkpoint_every=1, telemetry=tel,
               policy=RetryPolicy(max_retries=0)).run()
    _same_verdict(out, base)
    assert out.engine == "sharded"          # still a MESH verdict
    assert out.mesh_width == 4              # ... on half the chips
    assert out.mesh_shrinks == 1
    assert out.failovers == 1
    assert out.resumed_from_depth > 0
    assert out.dropped_states == 0
    kinds = [e.get("kind") for e in tel.events]
    assert "mesh_shrunk" in kinds
    shrunk = next(e for e in tel.events
                  if e.get("kind") == "mesh_shrunk")
    assert (shrunk["from_width"], shrunk["to_width"]) == (8, 4)


def test_knob_shrink_absorbs_oom_in_place(tmp_path):
    """TENTPOLE: an OOM-classified dispatch failure retries IN PLACE
    with halved knobs — a re-level, not a failover; the outcome and
    the knobs_shrunk event carry the story."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    tel = Telemetry()
    sup = _sup(proto, elastic=True,
               fault_plan=FaultPlan().raise_at(6, error=MemoryError,
                                               engine="sharded"),
               checkpoint_path=str(tmp_path / "oom.npz"),
               checkpoint_every=1, telemetry=tel,
               policy=RetryPolicy(max_retries=0))
    out = sup.run()
    _same_verdict(out, base)
    assert out.engine == "sharded"
    assert out.mesh_width == 8              # the mesh never shrank
    assert out.mesh_shrinks == 0
    assert out.knob_retries == 1
    assert out.failovers == 0
    kinds = [e.get("kind") for e in tel.events]
    assert "knobs_shrunk" in kinds and "mesh_shrunk" not in kinds
    # The re-level rebuilt the rung with the chunk halved.
    shrunk = sup._engines[("sharded", None, None, 1)]
    assert shrunk.cpd == PP_KW["chunk"] // 2


def test_knob_shrink_ladder_is_bounded_then_rung_burns():
    """A persistent OOM exhausts the bounded shrink ladder (default 2
    re-levels) and the rung burns normally — the next rung still lands
    the exact verdict."""
    proto = _pruned_pingpong()
    base = _sup(proto).run()
    out = _sup(proto, ladder=("sharded", "device"),
               fault_plan=FaultPlan().raise_always(
                   error=MemoryError, engine="sharded"),
               policy=RetryPolicy(max_retries=0)).run()
    _same_verdict(out, base)
    assert out.engine == "device"
    assert out.knob_retries == 2            # DSLABS_KNOB_SHRINKS
    assert out.failovers == 1


# ------------------------------------------- cross-width resume parity

def _resume_matrix(proto, tmp_path, base_kw, stage_depths):
    """Run the full-width baseline, then the SAME search staged across
    8 -> 4 -> 2 -> 1 wide meshes via checkpoint resume; the final
    verdict/counts must be exact."""
    kw = dict(chunk_per_device=base_kw["chunk"],
              frontier_cap=base_kw["frontier_cap"],
              visited_cap=base_kw["visited_cap"])
    base = ShardedTensorSearch(proto, make_mesh(8), **kw).run()
    assert base.end_condition == "SPACE_EXHAUSTED"
    ck = str(tmp_path / "matrix.npz")
    widths = (8, 4, 2, 1)
    out = None
    for w, d in zip(widths, stage_depths):
        search = ShardedTensorSearch(
            proto, make_mesh(w), max_depth=d, checkpoint_path=ck,
            checkpoint_every=1, **kw)
        out = search.run(resume=(w != widths[0]))
        if d is not None:
            assert out.end_condition in ("DEPTH_EXHAUSTED",
                                         "SPACE_EXHAUSTED")
    _same_verdict(out, base)
    assert out.dropped_states == 0
    return base, out


def test_cross_width_resume_matrix_pingpong(tmp_path):
    """SATELLITE: strict pingpong checkpointed at depth 2 on the
    8-wide mesh resumes on 4-, 2-, and 1-wide meshes (the unified
    dump re-shards frontier + visited keys per owner) with exact
    unique/explored/verdict parity and zero drops."""
    _resume_matrix(_pruned_pingpong(), tmp_path, PP_KW,
                   (2, 3, 4, None))


def test_cross_width_resume_matrix_lab1(tmp_path):
    """SATELLITE: the same 8 -> 4 -> 2 -> 1 parity matrix on the lab1
    strict clientserver BFS (deeper space, more checkpoints cross the
    width changes)."""
    _resume_matrix(_pruned_clientserver(), tmp_path, LAB1_KW,
                   (2, 4, 6, None))


def test_warden_sigkill_mid_level_resumes_on_narrower_meshes(tmp_path):
    """ACCEPTANCE: strict lab1 on the 8-device CPU dryrun mesh,
    SIGKILLed mid-level (after a durable checkpoint), resumes on a
    4-wide child; THAT child is SIGKILLed too and the 2-wide child
    finishes — exact verdict/unique/explored parity,
    ``dropped_states == 0``, both shrinks attributable."""
    proto = _pruned_clientserver()
    base = ShardedTensorSearch(
        proto, make_mesh(8), chunk_per_device=LAB1_KW["chunk"],
        frontier_cap=LAB1_KW["frontier_cap"],
        visited_cap=LAB1_KW["visited_cap"]).run()
    w = Warden(**LAB1_REFS, ladder=("sharded", "device", "host"),
               elastic=True, checkpoint_path=str(tmp_path / "wk.npz"),
               checkpoint_every=1, env=CHILD_ENV,
               chunk=LAB1_KW["chunk"],
               frontier_cap=LAB1_KW["frontier_cap"],
               visited_cap=LAB1_KW["visited_cap"],
               # at=2 + after_ckpt: each targeted child dies on its
               # first dispatch after a DURABLE checkpoint exists —
               # deterministic mid-level kills on both the 8-wide and
               # the (shorter-lived, resumed) 4-wide child.
               fault={"kind": "die", "at": 2, "spawns": [0, 1],
                      "after_ckpt": True})
    out = w.run()
    _same_verdict(out, base)
    assert out.engine == "sharded"
    assert out.mesh_width == 2
    assert out.mesh_shrinks == 2
    assert out.child_restarts == 2
    assert out.resumed_from_depth > 0
    assert out.dropped_states == 0
    assert [d.kind for d in w.deaths] == ["oom", "oom"]


def test_swarm_checkpoint_survives_mesh_width_change(tmp_path):
    """SATELLITE bugfix: swarm dumps no longer pin D/K in their
    fingerprint — a fleet checkpointed on 8 devices resumes on 4
    (walker rows / histories / PRNG keys / key groups redistributed),
    while a genuinely different config (another seed) still refuses
    loudly."""
    from dslabs_tpu.tpu import checkpoint as ckpt_mod
    from dslabs_tpu.tpu.swarm import SwarmSearch

    proto = _pruned_pingpong()
    ck = str(tmp_path / "swarm.npz")
    kw = dict(walkers_per_device=8, max_steps=12, steps_per_round=4,
              seed=7, visited_cap=1 << 10, checkpoint_path=ck,
              checkpoint_every=1)
    first = SwarmSearch(proto, mesh=make_mesh(8), max_rounds=2, **kw)
    out1 = first.run(check_initial=False)
    assert os.path.exists(ck)
    explored1 = out1.states_explored

    with pytest.warns(RuntimeWarning, match="redistributes"):
        resumed = SwarmSearch(proto, mesh=make_mesh(4), max_rounds=4,
                              **kw)
        out2 = resumed.run(check_initial=False, resume=True)
    assert out2.resumed_from_depth >= 1     # continued, not restarted
    assert out2.states_explored >= explored1

    other = SwarmSearch(proto, mesh=make_mesh(4), max_rounds=1,
                        **{**kw, "seed": 8})
    with pytest.raises(ckpt_mod.CheckpointMismatch):
        other.run(check_initial=False, resume=True)


# --------------------------------------------------- the chaos harness

def test_chaos_plan_is_seed_deterministic():
    """Same seed -> bit-identical schedule; different seed -> a
    different one.  The kind budget is exact: every requested fault is
    scheduled."""
    counts = {("sharded", "init"): 1, ("sharded", "superstep"): 10,
              ("sharded", "promote"): 9}
    spec = ChaosSpec(seed=3, faults=24)
    p1, p2 = build_plan(spec, counts), build_plan(spec, counts)
    assert p1.schedule == p2.schedule
    assert len(p1.schedule) == 24
    kinds = [k for (_e, _s, _i, k) in p1.schedule]
    assert kinds.count("oom") == 2
    assert kinds.count("fatal") == 1
    assert kinds.count("hang") == 1
    assert kinds.count("transient") == 20
    sites = {(e, s) for (e, s, _i, _k) in p1.schedule}
    assert len(sites) == 3
    p3 = build_plan(ChaosSpec(seed=4, faults=24), counts)
    assert p3.schedule != p1.schedule
    # Hangs pin to the promote site (lowest watchdog deadline scale).
    assert all(s == "promote" for (_e, s, _i, k) in p1.schedule
               if k == "hang")


def test_chaos_soak_lab1_acceptance(tmp_path):
    """ACCEPTANCE: a seeded chaos soak on strict lab1 over the
    8-device dryrun mesh injects >= 20 faults across >= 3 dispatch
    sites — transient storms, OOM re-levels, a fatal rung burn, a
    hang — and returns the fault-free verdict with IDENTICAL
    unique/explored counts and zero dropped states."""
    report = soak(
        _pruned_clientserver(),
        spec=ChaosSpec(seed=1, faults=24),
        supervisor_kwargs=dict(mesh=make_mesh(8), **LAB1_KW),
        checkpoint_path=str(tmp_path / "soak.npz"),
        min_fired=20, min_sites=3)
    assert report["parity"] is True
    assert report["fired"] >= 20
    assert len(report["sites_fired"]) >= 3
    assert report["chaos"]["dropped_states"] == 0
    # The soak exercised BOTH degradation axes, attributably.
    assert report["chaos"]["mesh_shrinks"] >= 1
    assert report["chaos"]["knob_retries"] >= 1
    assert report["chaos"]["retries"] >= 10
    assert "hang" in report["kinds_fired"]


@pytest.mark.slow
def test_chaos_soak_long_multi_seed(tmp_path):
    """The long soak (``make chaos-smoke``): three seeds, more faults
    each — sustained injection across every site never breaks strict
    parity."""
    for seed in (11, 12, 13):
        report = soak(
            _pruned_clientserver(),
            spec=ChaosSpec(seed=seed, faults=32, oom_faults=3),
            supervisor_kwargs=dict(mesh=make_mesh(8), **LAB1_KW),
            checkpoint_path=str(tmp_path / f"soak{seed}.npz"),
            min_fired=24, min_sites=3)
        assert report["parity"] is True


@pytest.mark.slow
def test_chaos_cli_smoke(tmp_path, capsys):
    """The by-hand entry point: ``python -m dslabs_tpu.tpu.chaos``
    prints the soak report as one JSON line and exits 0 on parity."""
    import json

    # lab1 reuses the XLA programs the suite already compiled (the
    # CLI's kwargs match LAB1_KW by design).
    assert chaos_mod.main(["--protocol", "lab1", "--seed", "2",
                           "--faults", "20", "--mesh", "8"]) == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["parity"] is True
