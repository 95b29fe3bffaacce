"""Batched job lanes (ISSUE 14, tpu/lanes.py): N tenant searches as
ONE compiled program.

The load-bearing contract is EXACT PARITY: a job run in a lane batch
lands the bit-identical unique/explored/verdict its solo run lands, at
every batch width, with lane-mates at different depths, through
continuous-batching swap-ins, across a SIGKILL-mid-batch resume, and
with a poisoned neighbor evicted mid-flight.  On top of that the
amortisation pin (a 4-lane batch spends <= 0.5x the dispatches of four
solo runs — the economics the feature exists for), the solo-path
overhead guard (lanes off = solo engines untouched), the service
integration (lane packer quotas, COSTS sums, eviction-to-solo), and
the observability schema (STATUS lanes block).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from dslabs_tpu.tpu import visited as visited_mod
from dslabs_tpu.tpu.engine import TensorSearch
from dslabs_tpu.tpu.lanes import (LaneBatchWarden, LaneJob, LaneSearch,
                                  job_signature)
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol
from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol

pytestmark = pytest.mark.lanes

# Children share the suite's persistent compile cache
# (tests/conftest.py) or every spawn pays a cold XLA build.
CHILD_ENV = {"JAX_COMPILATION_CACHE_DIR": "/tmp/jaxcache-cpu"}

KW = dict(frontier_cap=1 << 10, chunk=64, visited_cap=1 << 12)


# Module-level so lane-batch children can import them by reference —
# closures cannot cross the spawn boundary.

def prune_pingpong(pp):
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def prune_clientserver(cs):
    return dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]})


def _pingpong():
    return prune_pingpong(make_pingpong_protocol(workload_size=2))


def _lab1_wide():
    # A bigger space (582 explored / 80 unique / depth 11) so
    # multi-chunk waves and mixed-depth lanes are genuinely exercised.
    return prune_clientserver(
        make_clientserver_protocol(n_clients=2, w=2))


def _same(a, b):
    assert a.end_condition == b.end_condition
    assert a.states_explored == b.states_explored
    assert a.unique_states == b.unique_states
    assert a.depth == b.depth


class _Spy:
    """Dispatch counter at the _dispatch seam (the overhead-guard
    idiom from tests/test_telemetry.py)."""

    def __init__(self):
        self.n = 0
        self.tags = []

    def __call__(self, tag, fn, *args):
        self.n += 1
        self.tags.append(tag)
        return fn(*args)


# ------------------------------------------------------ parity matrix

@pytest.mark.parametrize("L", [1, 2, 4])
def test_lane_parity_matrix_pingpong_strict(L):
    """ACCEPTANCE: every lane's unique/explored/verdict is
    bit-identical to its solo run at L in {1, 2, 4}."""
    proto = _pingpong()
    solo = TensorSearch(proto, strict=True, **KW).run()
    ls = LaneSearch(proto, n_lanes=L, strict=True, **KW)
    res = ls.run_lanes([LaneJob(f"j{i}") for i in range(L)])
    assert not res.errors
    assert len(res.outcomes) == L
    for out in res.outcomes.values():
        _same(out, solo)
        assert out.engine == "lanes"
        assert out.lane_width == L


@pytest.mark.parametrize("strict", [True, False])
def test_lane_parity_lab1(strict):
    proto = _lab1_wide()
    solo = TensorSearch(proto, strict=strict, **KW).run()
    ls = LaneSearch(proto, n_lanes=2, strict=strict, **KW)
    res = ls.run_lanes([LaneJob("a"), LaneJob("b")])
    assert not res.errors
    for out in res.outcomes.values():
        _same(out, solo)


def test_lane_parity_over_a_spilling_window():
    """A window that one kind overflows: solo, the re-step skips the
    kind whose table is empty (a device branch, engine._kinds_live);
    under the lane ``vmap`` the branch's predicate is batched and
    lowers to a select over both results — the counts stay solo's, and
    the full grid's."""
    proto = _lab1_wide()
    full = TensorSearch(proto, strict=True, **KW).run()
    solo = TensorSearch(proto, strict=True, ev_budget=(16, 1), **KW).run()
    _same(solo, full)
    ls = LaneSearch(proto, n_lanes=2, strict=True, ev_budget=(16, 1),
                    **KW)
    res = ls.run_lanes([LaneJob("a"), LaneJob("b")])
    assert not res.errors
    for out in res.outcomes.values():
        _same(out, solo)


def test_lane_parity_mixed_depth_limits():
    """Lane-mates at DIFFERENT per-lane depth limits finish at
    different levels; each still matches its own solo run exactly —
    a finished lane is a provable no-op for its neighbors."""
    proto = _lab1_wide()
    solo = {d: TensorSearch(proto, strict=True, max_depth=d,
                            **KW).run()
            for d in (None, 4, 7)}
    ls = LaneSearch(proto, n_lanes=4, strict=True, **KW)
    res = ls.run_lanes([LaneJob("full"), LaneJob("d4", max_depth=4),
                        LaneJob("d7", max_depth=7),
                        LaneJob("full2")])
    assert not res.errors
    _same(res.outcomes["full"], solo[None])
    _same(res.outcomes["full2"], solo[None])
    _same(res.outcomes["d4"], solo[4])
    _same(res.outcomes["d7"], solo[7])


def test_lane_goal_verdict_parity():
    """Terminal-flag verdicts (checkState order) survive the lane
    extraction: same predicate, same first-hit state, same counters."""
    proto = make_pingpong_protocol(workload_size=2)   # has a goal
    solo = TensorSearch(proto, strict=True, **KW).run()
    ls = LaneSearch(proto, n_lanes=2, strict=True, **KW)
    res = ls.run_lanes([LaneJob("g0"), LaneJob("g1")])
    assert not res.errors
    for out in res.outcomes.values():
        _same(out, solo)
        assert out.predicate_name == solo.predicate_name
        assert out.goal_state is not None
        for k in solo.goal_state:
            assert np.array_equal(np.asarray(out.goal_state[k]),
                                  np.asarray(solo.goal_state[k])), k


# --------------------------------------------- continuous batching

def test_continuous_batching_swap_in_parity():
    """More jobs than lanes: drained lanes refill at level boundaries
    (zero recompiles — same jitted programs) and every swapped-in
    job's verdict is bit-identical to solo."""
    proto = _lab1_wide()
    solo = TensorSearch(proto, strict=True, **KW).run()
    solo_d4 = TensorSearch(proto, strict=True, max_depth=4,
                           **KW).run()
    ls = LaneSearch(proto, n_lanes=2, strict=True, **KW)
    jobs = [LaneJob("a", max_depth=4), LaneJob("b"),
            LaneJob("c", max_depth=4), LaneJob("d"), LaneJob("e")]
    res = ls.run_lanes(jobs, swap=True)
    assert not res.errors
    assert res.swaps >= 2            # lanes were genuinely refilled
    for jid in ("b", "d", "e"):
        _same(res.outcomes[jid], solo)
    for jid in ("a", "c"):
        _same(res.outcomes[jid], solo_d4)


def test_dispatch_amortization_4_lanes():
    """ACCEPTANCE: a 4-lane batch's dispatches-per-job is <= 0.5x the
    4-solo baseline (measured at the _dispatch seam — the same seam
    telemetry spans and the COSTS ledger count)."""
    proto = _lab1_wide()
    spy = _Spy()
    solo = TensorSearch(proto, strict=True, **KW)
    solo._dispatch_hook = spy
    solo.run()
    solo_n = spy.n
    spy4 = _Spy()
    ls = LaneSearch(proto, n_lanes=4, strict=True, **KW)
    ls._dispatch_hook = spy4
    res = ls.run_lanes([LaneJob(f"x{i}") for i in range(4)])
    assert not res.errors
    assert spy4.n / 4 <= 0.5 * solo_n, (spy4.n, solo_n)
    # one superstep + one promote + one sync per LEVEL for the WHOLE
    # batch — the shape the amortisation comes from.
    assert spy4.tags.count("lanes.superstep") == res.levels


def test_solo_paths_untouched_when_lanes_off():
    """Overhead guard: building and running a LaneSearch in the same
    process leaves the solo engine's dispatch + device_get counts and
    the visited-insert lowering override untouched."""
    from dslabs_tpu.tpu import engine as engine_mod

    proto = _pingpong()

    def measure():
        spy = _Spy()
        gets = {"n": 0}
        orig = engine_mod.device_get
        s = TensorSearch(proto, strict=True, **KW)
        s._dispatch_hook = spy

        def counting_get(x):
            gets["n"] += 1
            return orig(x)

        engine_mod.device_get = counting_get
        try:
            out = s.run()
        finally:
            engine_mod.device_get = orig
        return out, spy.n, gets["n"]

    out_before, n_before, g_before = measure()
    ls = LaneSearch(proto, n_lanes=2, strict=True, **KW)
    ls.run_lanes([LaneJob("a"), LaneJob("b")])
    assert visited_mod._FORCE_JNP == 0    # override is trace-scoped
    out_after, n_after, g_after = measure()
    _same(out_before, out_after)
    assert n_before == n_after
    assert g_before == g_after


# --------------------------------------------- checkpoints + resume

def test_lane_checkpoint_is_solo_resumable(tmp_path):
    """A lane's per-lane dump is the ENGINE-AGNOSTIC unified format:
    a solo TensorSearch resumes it to the exact full-run verdict —
    the mechanism a poisoned lane's solo retry rides."""
    proto = _lab1_wide()
    solo = TensorSearch(proto, strict=True, **KW).run()
    ckpt = str(tmp_path / "lane0" / "ckpt.npz")
    os.makedirs(os.path.dirname(ckpt))
    ls = LaneSearch(proto, n_lanes=2, strict=True, **KW)
    res = ls.run_lanes([
        LaneJob("stub", max_depth=6, checkpoint_path=ckpt,
                checkpoint_every=1),
        LaneJob("mate", max_depth=3)])
    assert not res.errors
    resumed = TensorSearch(proto, strict=True,
                           checkpoint_path=ckpt, **KW)
    assert resumed.has_resumable_checkpoint()
    out = resumed.run(resume=True)
    _same(out, solo)


def test_sigkill_mid_batch_resumes_every_lane(tmp_path):
    """ACCEPTANCE: a SIGKILLed lane-batch child respawns and EVERY
    lane resumes from its own checkpoint to the bit-identical solo
    verdict (per-lane fault domains inside one process)."""
    proto = _lab1_wide()
    solo = TensorSearch(proto, strict=True, **KW).run()
    jobs = []
    for i in range(4):
        ck = str(tmp_path / f"j{i}" / "ckpt.npz")
        os.makedirs(os.path.dirname(ck))
        jobs.append({"job_id": f"j{i}", "checkpoint_path": ck,
                     "checkpoint_every": 1})
    w = LaneBatchWarden(
        factory="dslabs_tpu.tpu.protocols.clientserver:"
                "make_clientserver_protocol",
        factory_kwargs={"n_clients": 2, "w": 2},
        transform="tests.test_lanes:prune_clientserver",
        jobs=jobs, n_lanes=4, strict=True,
        run_dir=str(tmp_path / "batch"),
        env=CHILD_ENV, extra_sys_path=[os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))],
        fault={"kind": "die", "at": 12}, **KW)
    res = w.run()
    assert res.errors == {}, res.errors
    assert res.child_restarts >= 1
    assert w.deaths and w.deaths[0]["kind"] == "oom"
    for jid in ("j0", "j1", "j2", "j3"):
        _same(res.outcomes[jid], solo)
    # shares of the batch still sum to ~1.0 across the restart
    total = sum(o.lane_share for o in res.outcomes.values())
    assert 0.99 <= total <= 1.01, total
    # the batch run dir is watchable (flight + STATUS with the
    # schema-pinned per-lane block)
    st = json.load(open(tmp_path / "batch" / "STATUS.json"))
    assert st["lanes"], st
    for lrec in st["lanes"]:
        for key in ("lane", "job_id", "depth", "explored", "unique",
                    "frontier"):
            assert key in lrec, (key, lrec)


def test_poisoned_lane_evicts_neighbors_bit_exact():
    """ACCEPTANCE: a lane that hits the strict visited-pressure
    contract is POISONED (eviction error, solo-retry material) while
    its lane-mate's verdict stays bit-identical to solo."""
    proto = _lab1_wide()
    kw = dict(frontier_cap=1 << 10, chunk=64, visited_cap=64)
    # Solo contract at this cap: the full-space job raises.
    from dslabs_tpu.tpu.engine import CapacityOverflow

    with pytest.raises(CapacityOverflow):
        TensorSearch(proto, strict=True, **kw).run()
    solo_d3 = TensorSearch(proto, strict=True, max_depth=3,
                           **kw).run()
    ls = LaneSearch(proto, n_lanes=2, strict=True, **kw)
    res = ls.run_lanes([LaneJob("big"), LaneJob("small", max_depth=3)])
    assert "big" in res.errors
    assert "CapacityOverflow" in res.errors["big"]
    _same(res.outcomes["small"], solo_d3)


# ------------------------------------------------- scheduler packing

def _job(tenant, seq, **over):
    from dslabs_tpu.service.queue import Job

    kw = dict(job_id=f"{tenant}-{seq:03d}", tenant=tenant,
              factory="f:mk", factory_kwargs={"w": 2}, strict=True,
              chunk=64, frontier_cap=256, visited_cap=1024,
              ladder=("device", "host"))
    kw.update(over)
    return Job(**kw)


def test_job_signature_eligibility():
    base = _job("a", 1)
    assert job_signature(base) == job_signature(_job("b", 2))
    # different knobs / factory = different program shapes
    assert job_signature(base) != job_signature(_job("a", 3, chunk=32))
    assert job_signature(base) != job_signature(
        _job("a", 4, factory="g:mk"))
    # not lane-eligible: chaos faults, evicted-solo, non-device ladder
    assert job_signature(_job("a", 5, fault={"kind": "die"})) is None
    assert job_signature(_job("a", 6, solo=True)) is None
    assert job_signature(
        _job("a", 7, ladder=("sharded", "host"))) is None


def test_pick_batch_quota_and_signature():
    """The lane packer preserves DRR semantics: a tenant's lane count
    obeys its quota, non-matching heads are restored in order, and
    matching jobs across tenants fill the batch."""
    from dslabs_tpu.service.scheduler import DeficitRoundRobin

    drr = DeficitRoundRobin(quota=1)
    for t in ("a", "b", "c"):
        drr.push(_job(t, 1))
        drr.push(_job(t, 2))
    batch = drr.pick_batch({}, job_signature, max_jobs=4)
    # quota 1: ONE job per tenant despite 2 queued each
    assert len(batch) == 3
    assert sorted(j.tenant for j in batch) == ["a", "b", "c"]
    assert drr.pending() == 3          # the rest stayed queued
    # quota 2 lets both of a tenant's jobs share a batch
    drr2 = DeficitRoundRobin(quota=2)
    for t in ("a", "b"):
        drr2.push(_job(t, 1))
        drr2.push(_job(t, 2))
    batch2 = drr2.pick_batch({}, job_signature, max_jobs=4)
    assert len(batch2) == 4
    # an incompatible head never joins and is not lost
    drr3 = DeficitRoundRobin(quota=1)
    drr3.push(_job("a", 1))
    drr3.push(_job("b", 1, chunk=32))     # different signature
    batch3 = drr3.pick_batch({}, job_signature, max_jobs=4)
    assert [j.tenant for j in batch3] == ["a"]
    assert drr3.pending() == 1
    nxt = drr3.pick({})
    assert nxt is not None and nxt.tenant == "b"


# --------------------------------------------------- service stack

def _mk_server(root, lanes, **over):
    from dslabs_tpu.service.server import CheckServer

    kw = dict(workers=1, queue_cap=16, elastic=False, admission=False,
              env=CHILD_ENV, lanes=lanes)
    kw.update(over)
    return CheckServer(str(root), **kw)


def _submit_jobs(srv, tenants=("alice", "bob"), per=2):
    for t in tenants:
        for _ in range(per):
            r = srv.submit(
                factory="dslabs_tpu.tpu.protocols.pingpong:"
                        "make_exhaustive_pingpong",
                factory_kwargs={"workload_size": 2}, tenant=t,
                chunk=64, frontier_cap=1 << 8, visited_cap=1 << 12,
                max_secs=60.0)
            assert r.get("accepted"), r


def test_service_lane_drain_costs_match_solo(tmp_path):
    """ACCEPTANCE: per-tenant COSTS sums across a batched drain equal
    the solo drain's exactly (explored/unique are copied from
    bit-identical verdicts), dispatches-per-job drops to <= 0.5x, the
    cost shares of each batch sum to its device seconds (no double
    billing), and the lanes observability block lands in
    SERVER_STATUS + the drain summary + the journal."""
    from dslabs_tpu.tpu import tracing

    def drain(lanes, root):
        srv = _mk_server(root, lanes, quota=2)
        _submit_jobs(srv)
        summary = srv.drain(max_secs=300)
        srv.close()
        return summary

    solo = drain(0, tmp_path / "solo")
    lane = drain(4, tmp_path / "lane")
    assert solo["failed"] == 0 and lane["failed"] == 0
    key = ("tenant", "end", "unique", "explored", "depth")
    sv = sorted(tuple(r.get(k) for k in key) for r in solo["results"])
    lv = sorted(tuple(r.get(k) for k in key) for r in lane["results"])
    assert sv == lv
    agg = {}
    for mode, root in (("solo", tmp_path / "solo"),
                       ("lane", tmp_path / "lane")):
        recs, torn = tracing.read_flight_lax(
            str(root / tracing.COSTS_NAME))
        assert torn == 0
        agg[mode] = tracing.aggregate_costs(recs)
    for t in ("alice", "bob"):
        assert agg["solo"][t]["explored"] == agg["lane"][t]["explored"]
        assert agg["solo"][t]["unique"] == agg["lane"][t]["unique"]
    assert (lane["dispatches_per_job"]
            <= 0.5 * solo["dispatches_per_job"])
    lb = lane["lanes"]
    assert lb["batches"] >= 1 and lb["jobs_in_lanes"] == 4
    assert lb["evicted"] == 0
    assert lb["by_signature"]
    st = json.load(open(tmp_path / "lane" / "SERVER_STATUS.json"))
    assert st["lanes"]["batches"] == lb["batches"]
    journal, _ = tracing.read_flight_lax(
        str(tmp_path / "lane" / "journal.jsonl"))
    evs = [r for r in journal if r.get("t") == "lane_batch"]
    assert evs and all(r.get("run_dir") for r in evs)
    # trace attribution: a lane job's causal tree carries the SHARED
    # batch spans, marked shared
    tr = tracing.assemble(str(tmp_path / "lane"),
                          job=evs[0]["jobs"][0])
    (j,) = tr["jobs"]
    kinds = {n["kind"] for n in j["nodes"]}
    assert "lane_batch" in kinds, kinds
    shared = [n for n in j["nodes"] if n.get("shared")]
    assert shared


@pytest.mark.slow
def test_service_evicted_lane_retries_solo(tmp_path):
    """ACCEPTANCE (eviction end to end): a job whose lane poisons
    (strict table pressure) is re-queued ``solo=True`` and still
    lands a verdict through the solo warden ladder (host rung's
    unbounded visited set), while its lane-mates' batched verdicts
    stand."""
    srv = _mk_server(tmp_path, 2, quota=2)
    # One tenant, two jobs: same signature, so they batch; the tiny
    # visited cap poisons BOTH strict lanes -> both evict -> both
    # retry solo -> host-rung verdicts.
    for _ in range(2):
        r = srv.submit(
            factory="dslabs_tpu.tpu.protocols.pingpong:"
                    "make_exhaustive_pingpong",
            factory_kwargs={"workload_size": 2}, tenant="carol",
            chunk=64, frontier_cap=1 << 8, visited_cap=8,
            max_secs=120.0)
        assert r.get("accepted"), r
    summary = srv.drain(max_secs=300)
    srv.close()
    assert summary["completed"] == 2, summary
    assert summary["lanes"]["evicted"] == 2
    ends = {r["end"] for r in summary["results"]}
    assert ends == {"SPACE_EXHAUSTED"}, ends
    engines = {r["engine"] for r in summary["results"]}
    assert "lanes" not in engines       # the verdicts came from solo


# ------------------------------------------------- observability

def test_lane_dispatch_sites_registered_and_clean():
    """The lane programs are canonical dispatch sites: every tag in
    LaneSearch.dispatch_site_programs() is registered in
    telemetry.DISPATCH_SITES (no J0), and the jaxpr audit of the lane
    engine reports ZERO findings — `analysis all` covers the new hot
    path."""
    from dslabs_tpu.analysis.jaxpr_audit import audit_search
    from dslabs_tpu.tpu.telemetry import DISPATCH_SITES

    for tag in ("lanes.init", "lanes.superstep", "lanes.promote",
                "lanes.inject", "lanes.restore", "lanes.sync",
                "lanes.flags"):
        assert tag in DISPATCH_SITES, tag
    assert DISPATCH_SITES["lanes.superstep"]["hot"]
    assert DISPATCH_SITES["lanes.superstep"]["donated"]
    ls = LaneSearch(_pingpong(), n_lanes=2, frontier_cap=1 << 8,
                    visited_cap=1 << 10)
    assert set(ls.dispatch_site_programs()) <= set(DISPATCH_SITES)
    findings = audit_search(ls)
    assert findings == [], [f.as_dict() for f in findings]


def test_status_lanes_schema_and_watch(tmp_path):
    """STATUS.json from a lane batch is schema-pinned with the
    per-lane block and `telemetry watch` renders a batched child."""
    from dslabs_tpu.tpu import telemetry as tel_mod

    tel = tel_mod.Telemetry.for_checkpoint(
        str(tmp_path / "ckpt.npz"), engine_hint="lane-batch")
    ls = LaneSearch(_pingpong(), n_lanes=2, telemetry=tel, **KW)
    res = ls.run_lanes([LaneJob("a"), LaneJob("b", max_depth=3)])
    tel.close()
    assert not res.errors
    st = json.load(open(tmp_path / "STATUS.json"))
    assert isinstance(st["lanes"], list) and st["lanes"]
    for lrec in st["lanes"]:
        assert set(lrec) >= {"lane", "job_id", "depth", "explored",
                             "unique", "frontier"}
    frame = tel_mod.render_watch(str(tmp_path))
    assert "job lane" in frame
    # level records carry one per-device lane per RESIDENT job lane
    lane_levels = [r for r in tel.levels if r.get("lanes")]
    assert lane_levels
    first = lane_levels[0]
    assert len(first["per_device"]["explored"]) == len(first["lanes"])
