"""Checking-as-a-service suite (ISSUE 11, ``make service-smoke``).

Covers the service layer bottom-up:

* the UNIFIED child-death taxonomy (supervisor.classify_child_death —
  one vocabulary for the warden's failover, the elastic ladder's
  classify_oom, and the scheduler's retry policy), table-driven;
* the bounded persistent queue: structured queue-full retry-after
  rejection (never raises, never blocks), torn-tail journal replay,
  tmp+replace compaction;
* DRR fairness + per-tenant concurrency quotas + the degrade policy
  (oom -> knob-shrink, wedge -> rung-step, failed -> no retry);
* the CPU-pinned conformance admission gate: an unsound spec is
  rejected with structured SpecError-derived findings BEFORE any twin
  compiles;
* ACCEPTANCE — the tenant-isolation chaos soak: three tenants, a
  seeded oom/hang/crash fault schedule killing one tenant's jobs;
  every unaffected tenant's verdict is bit-exact vs its solo baseline,
  the affected tenant lands degraded-but-sound verdicts or a
  structured failure (never a silent partial one), a full-queue
  submission gets the structured retry-after rejection, and no
  cross-tenant telemetry bleed (each job's run dir is self-contained).
"""

import json
import os
import signal
import textwrap
import time

import pytest

from dslabs_tpu.service import (AttemptPlan, CheckServer,
                                DeficitRoundRobin, Job, RetrySpec,
                                ServiceQueue, degrade, fairness_index,
                                replay_journal)
from dslabs_tpu.tpu.supervisor import (CHILD_RC_FAILED, _OOM_MARKERS,
                                       classify_child_death,
                                       classify_oom)
from dslabs_tpu.tpu.warden import classify_death

pytestmark = pytest.mark.service

# Children are fresh processes: share the suite's persistent compile
# cache (tests/conftest.py) or every spawn pays a cold XLA build.
CHILD_ENV = {"JAX_COMPILATION_CACHE_DIR": "/tmp/jaxcache-cpu"}
FACTORY = ("dslabs_tpu.tpu.protocols.pingpong:"
           "make_exhaustive_pingpong")
SMALL = dict(factory_kwargs={"workload_size": 2}, chunk=64,
             frontier_cap=1 << 8, visited_cap=1 << 12)
# The grace ladder the warden hang test uses: an injected hang is cut
# at steady_grace + slack ~ 4 s instead of the compile-sized default.
GRACES = {"boot_grace": 120.0, "first_grace": 120.0,
          "steady_grace": 3.0, "idle_grace": 60.0, "grace_slack": 1.0}


def _server(root, **kw):
    kw.setdefault("admission", False)
    kw.setdefault("elastic", False)
    kw.setdefault("env", CHILD_ENV)
    kw.setdefault("warden_kwargs", dict(GRACES))
    return CheckServer(str(root), **kw)


def _same_verdict(a: dict, b: dict):
    for key in ("end", "unique", "explored", "depth"):
        assert a[key] == b[key], (key, a, b)


# ------------------------------------------- unified death taxonomy

# (exitcode, killed_by_warden, stderr tail, expected kind) — the one
# table the warden's failover, the service scheduler's retry policy,
# and the elastic ladder's OOM re-level all agree on.
TAXONOMY = [
    (-signal.SIGKILL, True, (), "wedge"),               # warden kill
    (-signal.SIGKILL, False, (), "oom"),                # kernel OOM
    (-signal.SIGSEGV, False, (), "crash"),
    (-signal.SIGTERM, False, (), "crash"),
    (CHILD_RC_FAILED, False, (), "failed"),             # clean report
    (1, False, (), "crash"),
    (86, False, (), "crash"),
    (None, False, (), "crash"),
    # stderr markers refine ONLY the abrupt kinds: a MemoryError
    # traceback / RESOURCE_EXHAUSTED tail turns a crash into an oom …
    (1, False, ("Traceback …", "MemoryError",), "oom"),
    (-signal.SIGSEGV, False, ("RESOURCE_EXHAUSTED: out of memory",),
     "oom"),
    (86, False, ("XlaRuntimeError: Allocation failure on device",),
     "oom"),
    # … but a warden kill stays a wedge and a clean report stays
    # failed even when stderr chattered about memory earlier.
    (-signal.SIGKILL, True, ("MemoryError",), "wedge"),
    (CHILD_RC_FAILED, False, ("MemoryError",), "failed"),
]


def test_unified_death_taxonomy_table():
    for exitcode, killed, stderr, want in TAXONOMY:
        got = classify_child_death(exitcode, killed, stderr)
        assert got == want, (exitcode, killed, stderr, got, want)
        # warden.classify_death IS the same function (one vocabulary).
        assert classify_death(exitcode, killed, stderr) == want


def test_taxonomy_agrees_with_classify_oom():
    """Every marker the elastic ladder's knob-shrink trigger
    (classify_oom) recognises also flips an abrupt child death to
    ``oom`` — the scheduler's retry policy and the in-process re-level
    can never disagree about what an OOM is."""
    for marker in _OOM_MARKERS:
        assert classify_oom(RuntimeError(f"XlaRuntimeError: {marker}"))
        assert classify_child_death(1, False, (marker,)) == "oom"
        assert classify_child_death(-signal.SIGSEGV, False,
                                    (marker,)) == "oom"


# ------------------------------------------------ queue + journal

def test_queue_full_returns_structured_rejection(tmp_path):
    q = ServiceQueue(str(tmp_path), cap=2)
    a = q.submit(Job(job_id=q.next_id("a"), tenant="a", factory="m:f"))
    b = q.submit(Job(job_id=q.next_id("a"), tenant="a", factory="m:f"))
    assert a["accepted"] and b["accepted"]
    t0 = time.time()
    r = q.submit(Job(job_id=q.next_id("b"), tenant="b", factory="m:f"))
    # Never blocks (sub-second), never raises, fully structured.
    assert time.time() - t0 < 1.0
    assert r == {"accepted": False, "rejected": True,
                 "reason": "queue_full",
                 "retry_after_secs": r["retry_after_secs"],
                 "queue_depth": 2, "queue_cap": 2}
    assert r["retry_after_secs"] > 0
    assert q.summary()["backpressure"] is True
    q.close()


def test_journal_replay_tolerates_torn_tail(tmp_path):
    q = ServiceQueue(str(tmp_path), cap=8)
    for i in range(3):
        q.submit(Job(job_id=q.next_id("t"), tenant="t", factory="m:f"))
    q.mark_started("t-000001", attempt=1)
    q.mark_done("t-000001", {"end": "SPACE_EXHAUSTED", "unique": 8})
    q.mark_started("t-000002", attempt=1)   # crash-interrupted
    q.close()
    # A SIGKILL mid-append leaves one torn tail line — the replayer
    # must shrug it off exactly like the flight-recorder reader.
    with open(q.journal_path, "a") as f:
        f.write('{"t": "done", "job_id": "t-0000')
    pending, records, seq = replay_journal(q.journal_path)
    assert seq == 3
    assert records["t-000001"]["status"] == "done"
    # started-but-unfinished jobs re-queue (the crash-recovery path).
    assert sorted(j.job_id for j in pending) == ["t-000002", "t-000003"]
    # A fresh queue over the same journal resumes that state.
    q2 = ServiceQueue(str(tmp_path), cap=8)
    assert q2.depth() == 2
    q2.close()


def test_journal_compaction_is_atomic(tmp_path):
    q = ServiceQueue(str(tmp_path), cap=8)
    for i in range(2):
        q.submit(Job(job_id=q.next_id("t"), tenant="t", factory="m:f"))
    q.mark_done("t-000001", {"end": "SPACE_EXHAUSTED"})
    q.compact()
    # tmp+replace: no stray .tmp, and the compacted journal replays to
    # the identical state.
    assert not os.path.exists(q.journal_path + ".tmp")
    pending, records, seq = replay_journal(q.journal_path)
    assert records["t-000001"]["status"] == "done"
    assert [j.job_id for j in pending] == ["t-000002"]
    # The queue keeps appending durably after compaction.
    q.submit(Job(job_id=q.next_id("t"), tenant="t", factory="m:f"))
    assert seq == 2 and q.depth() == 2
    q.close()


# --------------------------------------------- scheduler + fairness

def test_drr_interleaves_tenants_and_honors_quota():
    s = DeficitRoundRobin(quota=1)
    for i in range(4):
        s.push(Job(job_id=f"a-{i}", tenant="a", factory="m:f"))
    for i in range(2):
        s.push(Job(job_id=f"b-{i}", tenant="b", factory="m:f"))
    order, running = [], {}
    while True:
        j = s.pick(running)
        if j is None:
            break
        order.append(j.job_id)
    # A 4-deep backlog cannot starve the 2-job tenant: strict
    # alternation while both are backlogged.
    assert order == ["a-0", "b-0", "a-1", "b-1", "a-2", "a-3"]
    # Quota: a tenant at its concurrency limit is ineligible …
    s2 = DeficitRoundRobin(quota=1)
    s2.push(Job(job_id="a-0", tenant="a", factory="m:f"))
    assert s2.pick({"a": 1}) is None
    # … and a freed slot makes it runnable again.
    assert s2.pick({"a": 0}).job_id == "a-0"


def test_drr_budget_weighting():
    """A tenant submitting one 4-unit job and a tenant submitting four
    1-unit jobs get the same budget share: the big job must wait for
    its deficit, letting the small jobs through first."""
    s = DeficitRoundRobin(quota=4)
    s.push(Job(job_id="big-0", tenant="big", factory="m:f",
               budget_units=4.0))
    for i in range(4):
        s.push(Job(job_id=f"small-{i}", tenant="small", factory="m:f"))
    order = []
    while True:
        j = s.pick({})
        if j is None:
            break
        order.append(j.job_id)
    assert order.index("big-0") >= 2
    assert sorted(order) == ["big-0", "small-0", "small-1", "small-2",
                             "small-3"]


def test_degrade_policy_table():
    retry = RetrySpec(max_attempts=3)
    p = AttemptPlan(attempt=1, chunk=64, ladder=("device", "host"))
    # oom -> knob-shrink re-level: the next attempt is strictly lighter.
    nxt = degrade(p, "oom", retry)
    assert (nxt.chunk, nxt.knob_shrinks, nxt.ladder) == (32, 1,
                                                         ("device",
                                                          "host"))
    # wedge -> kill + rung-step.
    nxt = degrade(p, "wedge", retry)
    assert (nxt.ladder, nxt.rung_steps) == (("host",), 1)
    assert degrade(AttemptPlan(1, 64, ("host",)), "wedge",
                   retry).ladder == ("host",)
    # crash -> plain bounded retry.
    assert degrade(p, "crash", retry).chunk == 64
    # failed -> structured failure, never a retry.
    assert degrade(p, "failed", retry) is None
    # the retry budget is a hard bound for every kind.
    assert degrade(AttemptPlan(3, 64, ("device",)), "oom", retry) is None


def test_fairness_index_pinned():
    assert fairness_index({}) == 1.0
    assert fairness_index({"a": {"verdicts": 4, "budget_spent": 4.0},
                           "b": {"verdicts": 2,
                                 "budget_spent": 2.0}}) == 1.0
    # a converts budget 4x better than b: max/mean = 2 / 1.25 = 1.6
    assert fairness_index({"a": {"verdicts": 4, "budget_spent": 2.0},
                           "b": {"verdicts": 1,
                                 "budget_spent": 2.0}}) == 1.6


# ------------------------------------------------- admission gate

UNSOUND_MODULE = textwrap.dedent("""
    import random


    class EvilNode:
        def __init__(self, address):
            self.peers = []

        def handle_Req(self, message, sender):
            message["seq"] = random.randint(0, 3)   # C1 + C2
            self.send(message, sender)


    def make_evil_protocol():
        return EvilNode("n1")
""")


def test_admission_rejects_unsound_spec_before_any_twin(tmp_path):
    (tmp_path / "evil_user_proto.py").write_text(UNSOUND_MODULE)
    srv = _server(tmp_path / "svc", admission=True,
                  extra_sys_path=[str(tmp_path)])
    res = srv.submit("evil_user_proto:make_evil_protocol",
                     tenant="mallory")
    assert res["accepted"] is False and res["reason"] == "unsound_spec"
    codes = {f["code"] for f in res["findings"]}
    assert codes & {"C1", "C2"}, res["findings"]
    for f in res["findings"]:        # SpecError-derived finding shape
        assert {"code", "path", "obj", "line", "message"} <= set(f)
    # Rejected BEFORE any twin compiled: no job dir, nothing queued,
    # and the rejection is on the tenant's ledger.
    assert not os.path.exists(os.path.join(str(tmp_path / "svc"),
                                           "jobs"))
    assert srv.queue.depth() == 0
    assert srv.server_status()["tenants"]["mallory"]["rejected"] == 1
    # A sound shipped factory passes the same gate (cached per spec).
    ok = srv.submit(FACTORY, tenant="alice", **SMALL)
    assert ok["accepted"], ok
    srv.close()


# ------------------------------------- scheduler-level degradation

def test_oom_death_costs_a_knob_shrink_relevel(tmp_path):
    """A job whose ONLY rung dies OOM-shaped is retried by the
    scheduler with halved chunk knobs, resumed from its own durable
    checkpoint — the PR 9 knob-shrink answer applied at job
    granularity — and still lands the exact verdict."""
    solo = _server(tmp_path / "solo", workers=1)
    solo.submit(FACTORY, tenant="base", **SMALL)
    base = solo.drain()["results"][0]
    solo.close()
    assert base["status"] == "done"

    srv = _server(tmp_path / "svc", workers=1)
    srv.submit(FACTORY, tenant="alice", ladder=("device",),
               fault={"kind": "die", "at": 8, "after_ckpt": True},
               **SMALL)
    out = srv.drain()["results"][0]
    srv.close()
    assert out["status"] == "done"
    _same_verdict(out, base)
    assert out["attempts"] == 2
    assert out["knob_shrinks"] == 1
    assert [d["kind"] for d in out["deaths"]] == ["oom"]
    assert out["degraded"] is True
    assert out["resumed_from_depth"] > 0


# --------------------------------- ACCEPTANCE: tenant isolation soak

def test_tenant_isolation_chaos_soak(tmp_path):
    """ISSUE 11 acceptance: >= 3 tenants, a seeded fault schedule
    killing one tenant's jobs (oom, hang, crash variants) plus a
    deterministic in-child failure; neighbors' verdicts bit-exact vs
    their solo baselines, the victim degraded-but-sound or
    structured-failed, a full-queue submission rejected with the
    structured retry-after shape, zero cross-tenant telemetry bleed."""

    def run_solo(tenant):
        srv = _server(tmp_path / f"solo-{tenant}", workers=1)
        assert srv.submit(FACTORY, tenant=tenant, **SMALL)["accepted"]
        summary = srv.drain()
        srv.close()
        assert summary["completed"] == 1
        return summary["results"][0]

    base_b = run_solo("bob")
    base_c = run_solo("carol")
    _same_verdict(base_b, base_c)            # same protocol, same space

    srv = _server(tmp_path / "svc", workers=2, queue_cap=6)
    # The seeded schedule on tenant alice: one job per fault variant.
    faults = {
        "oom": {"kind": "die", "at": 8, "after_ckpt": True},
        "hang": {"kind": "hang", "at": 8},
        "crash": {"kind": "exit", "at": 5},
    }
    alice_jobs = {}
    for kind, fault in faults.items():
        res = srv.submit(FACTORY, tenant="alice", fault=fault, **SMALL)
        assert res["accepted"], res
        alice_jobs[res["job_id"]] = kind
    # A deterministic in-child failure on a single-rung ladder: must
    # land a STRUCTURED failure (never a silent partial verdict).
    res = srv.submit(FACTORY, tenant="alice", ladder=("device",),
                     fault={"kind": "raise", "at": 3}, **SMALL)
    assert res["accepted"]
    raise_job = res["job_id"]
    assert srv.submit(FACTORY, tenant="bob", **SMALL)["accepted"]
    assert srv.submit(FACTORY, tenant="carol", **SMALL)["accepted"]
    # Queue is now at cap: the next submission gets the structured
    # retry-after rejection, not an exception and not a stall.
    over = srv.submit(FACTORY, tenant="dave", **SMALL)
    assert over["accepted"] is False
    assert over["reason"] == "queue_full"
    assert over["retry_after_secs"] > 0
    assert over["queue_depth"] == 6 and over["queue_cap"] == 6

    summary = srv.drain()
    srv.close()
    results = {r["job_id"]: r for r in summary["results"]}
    assert len(results) == 6

    # Unaffected tenants: bit-exact vs their SOLO baselines, zero
    # degradation absorbed.
    for tenant, base in (("bob", base_b), ("carol", base_c)):
        (job,) = [r for r in results.values() if r["tenant"] == tenant]
        assert job["status"] == "done"
        _same_verdict(job, base)
        assert job["degraded"] is False and not job["deaths"]

    # The victim: every fault variant lands a degraded-but-SOUND
    # verdict (exact counts, recovered via failover-from-checkpoint),
    # with the death classified under the unified taxonomy …
    want_kind = {"oom": "oom", "hang": "wedge", "crash": "crash"}
    for job_id, kind in alice_jobs.items():
        r = results[job_id]
        assert r["status"] == "done", r
        _same_verdict(r, base_b)
        assert r["degraded"] is True
        assert [d["kind"] for d in r["deaths"]] == [want_kind[kind]], r
    # … and the deterministic failure is a structured verdict, not a
    # silent partial one and not an endless retry.
    r = results[raise_job]
    assert r["status"] == "failed" and r["kind"] == "failed"
    assert r["attempts"] == 1 and r["deaths"]

    # Zero cross-tenant telemetry bleed: every job's run dir is
    # self-contained (own STATUS.json + flight log + checkpoint), and
    # no other tenant's job id appears in it.
    run_dirs = {r["run_dir"] for r in results.values()}
    assert len(run_dirs) == 6
    for r in results.values():
        listing = os.listdir(r["run_dir"])
        assert "flight.jsonl" in listing and "STATUS.json" in listing
        blob = ""
        for name in ("flight.jsonl", "STATUS.json"):
            with open(os.path.join(r["run_dir"], name)) as f:
                blob += f.read()
        for other in results.values():
            if other["job_id"] != r["job_id"]:
                assert other["job_id"] not in blob

    # The aggregate monitor: SERVER_STATUS.json carries the per-tenant
    # ledger and the fairness index.
    with open(os.path.join(str(tmp_path / "svc"),
                           "SERVER_STATUS.json")) as f:
        status = json.load(f)
    assert status["queue_depth"] == 0 and status["backpressure"] is False
    t = status["tenants"]
    assert t["alice"]["completed"] == 3 and t["alice"]["failed"] == 1
    assert t["bob"]["completed"] == 1 and t["carol"]["completed"] == 1
    assert t["dave"]["rejected"] == 1
    assert summary["fairness_index"] >= 1.0


# ----------------------------------------------------------- CLI

@pytest.mark.slow
def test_service_cli_submit_status_drain(tmp_path, capsys):
    from dslabs_tpu.service.__main__ import main

    root = str(tmp_path / "svc")
    rc = main(["submit", "--root", root, "--tenant", "alice",
               "--factory", FACTORY,
               "--kwargs", json.dumps({"workload_size": 2}),
               "--chunk", "64", "--no-admission"])
    sub = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and sub["accepted"]

    rc = main(["status", "--root", root])
    st = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and st["queue"]["queue_depth"] == 1

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jaxcache-cpu")
    rc = main(["drain", "--root", root, "--no-admission",
               "--workers", "1"])
    dr = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and dr["completed"] == 1 and dr["failed"] == 0
    assert dr["results"][0]["tenant"] == "alice"

    rc = main(["status", "--root", root])
    st = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert st["server"]["tenants"]["alice"]["completed"] == 1
