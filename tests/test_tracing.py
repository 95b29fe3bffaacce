"""End-to-end causal tracing + per-tenant cost accounting (ISSUE 13,
tpu/tracing.py, ``make trace-smoke``).

The contract under test:

* **Trace-ID discipline** — ``submit`` mints a trace id, the journal
  persists it, the warden threads it to children via
  ``DSLABS_TRACE_ID``/``DSLABS_PARENT_SPAN``, and every flight-recorder
  span / STATUS.json carries it, at ZERO added dispatches or transfers
  (the overhead guard in tests/test_telemetry.py is extended for this).
* **ACCEPTANCE** — a job whose child is SIGKILLed mid-level yields a
  ``telemetry trace`` timeline reconstructed FROM DISK ALONE with an
  unbroken parent chain submit -> queue -> admission -> attempt ->
  child run -> in-flight dispatch, the kill point named.
* **Cost ledger** — per-tenant COSTS.jsonl sums agree with the jobs'
  SearchOutcome counters EXACTLY; device-seconds by site and dispatch
  counts come from the spans already on disk.
* **Torn reads** — the assembler and the ``service status`` CLI
  tolerate a mid-write SERVER_STATUS snapshot and a torn COSTS tail.
* **Retention** — the scheduler-idle sweep prunes only finished run
  dirs, never running/queued jobs, journaling each prune.
"""

import json
import os

import pytest

from dslabs_tpu.tpu import tracing
from dslabs_tpu.tpu import telemetry as tel_mod

pytestmark = pytest.mark.trace

FACTORY = ("dslabs_tpu.tpu.protocols.pingpong:"
           "make_exhaustive_pingpong")
SMALL = dict(factory_kwargs={"workload_size": 2}, chunk=64,
             frontier_cap=1 << 8, visited_cap=1 << 12)
CHILD_ENV = {"JAX_COMPILATION_CACHE_DIR": "/tmp/jaxcache-cpu"}
GRACES = {"boot_grace": 120.0, "first_grace": 120.0,
          "steady_grace": 30.0, "idle_grace": 60.0, "grace_slack": 1.0}


def _server(root, **kw):
    from dslabs_tpu.service import CheckServer

    kw.setdefault("admission", False)
    kw.setdefault("elastic", False)
    kw.setdefault("env", CHILD_ENV)
    kw.setdefault("warden_kwargs", dict(GRACES))
    return CheckServer(str(root), **kw)


# ------------------------------------------------------------- id basics

def test_trace_ids_and_env_roundtrip(monkeypatch):
    a, b = tracing.mint_trace_id(), tracing.mint_trace_id()
    assert a != b and len(a) == 16 and len(tracing.new_span_id()) == 8
    assert tracing.attempt_span_id("t-000001", 2) == "t-000001:a2"
    env = tracing.child_trace_env(a, "t-000001:a2")
    assert env == {tracing.TRACE_ENV: a,
                   tracing.PARENT_ENV: "t-000001:a2"}
    monkeypatch.setenv(tracing.TRACE_ENV, a)
    monkeypatch.setenv(tracing.PARENT_ENV, "t-000001:a2")
    assert tracing.current_trace() == (a, "t-000001:a2")
    monkeypatch.delenv(tracing.TRACE_ENV)
    monkeypatch.delenv(tracing.PARENT_ENV)
    assert tracing.current_trace() == (None, None)


def test_read_flight_lax_and_segmentation(tmp_path):
    """A per-job flight log is appended to by EVERY child: a SIGKILLed
    first child can leave a torn line MID-file with a second child's
    records after it — the lax reader skips it (counted) and the
    segmenter scopes in-flight detection per child, because dispatch
    indices restart in every child."""
    p = tmp_path / "flight.jsonl"
    lines = [
        {"t": "meta", "started": 100.0, "span_id": "s1",
         "parent_span": "j:a1", "trace_id": "abc"},
        {"t": "dispatch", "ts": 0.1, "tag": "device.step", "i": 0},
        {"t": "span", "ts": 0.2, "tag": "device.step", "i": 0,
         "wall": 0.1},
        {"t": "dispatch", "ts": 0.3, "tag": "device.step", "i": 1},
    ]
    body = "\n".join(json.dumps(r) for r in lines)
    body += "\n" + '{"t": "span", "ts": 0.35, "tag":'       # torn
    lines2 = [
        {"t": "meta", "started": 110.0, "span_id": "s2",
         "parent_span": "j:a1", "trace_id": "abc"},
        {"t": "dispatch", "ts": 0.1, "tag": "host.expand", "i": 0},
        {"t": "span", "ts": 0.2, "tag": "host.expand", "i": 0,
         "wall": 0.1},
    ]
    body += "\n" + "\n".join(json.dumps(r) for r in lines2) + "\n"
    p.write_text(body)
    recs, torn = tracing.read_flight_lax(str(p))
    assert torn == 1 and len(recs) == 7
    segs = tracing.segment_flight(recs)
    assert len(segs) == 2
    # Segment 1 died inside device.step i=1; segment 2 is clean even
    # though its dispatch indices restarted at 0.
    assert segs[0]["in_flight"]["i"] == 1
    assert segs[0]["in_flight"]["tag"] == "device.step"
    assert segs[1]["in_flight"] is None


def test_load_json_tolerant_mid_write(tmp_path):
    p = tmp_path / "SERVER_STATUS.json"
    p.write_text('{"t": "server_status", "queue_de')   # mid-write
    assert tracing.load_json_tolerant(str(p)) is None
    p.write_text(json.dumps({"t": "server_status", "queue_depth": 0}))
    assert tracing.load_json_tolerant(str(p))["queue_depth"] == 0
    assert tracing.load_json_tolerant(str(tmp_path / "nope.json")) is None


# ------------------------------------------------ recorder integration

def test_spans_and_status_carry_trace_and_run_dir_trace_cli(
        tmp_path, monkeypatch, capsys):
    """A recorder inside a traced process stamps trace/span ids into
    the meta record, every span, and STATUS.json — and ``telemetry
    trace <run-dir>`` assembles the single-run causal tree from the
    flight log alone."""
    import dataclasses

    pytest.importorskip("jax")
    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol

    trace = tracing.mint_trace_id()
    monkeypatch.setenv(tracing.TRACE_ENV, trace)
    monkeypatch.setenv(tracing.PARENT_ENV, "job-1:a1")
    pp = make_pingpong_protocol(workload_size=2)
    pp = dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})
    tel = tel_mod.Telemetry.for_checkpoint(
        str(tmp_path / "search.ckpt"), engine_hint="trace-test")
    assert tel.trace_id == trace and tel.parent_span == "job-1:a1"
    search = TensorSearch(pp, max_depth=8, frontier_cap=1 << 10,
                          visited_cap=1 << 12, telemetry=tel)
    out = search.run()
    tel.close()
    # The verdict is stamped at span emission (engine-side).
    assert out.trace_id == trace

    recs = tel_mod.read_flight(str(tmp_path / "flight.jsonl"))
    meta = recs[0]
    assert meta["t"] == "meta" and meta["trace_id"] == trace
    assert meta["parent_span"] == "job-1:a1"
    spans = [r for r in recs if r["t"] == "span"]
    assert spans and all(s.get("trace") == trace for s in spans)
    oc = [r for r in recs if r["t"] == "outcome"][-1]
    assert oc["trace"] == trace

    st = json.loads((tmp_path / "STATUS.json").read_text())
    assert st["trace_id"] == trace
    assert st["parent_span"] == "job-1:a1"
    assert st["span_id"] == tel.span_id
    # Satellite: BOTH rates, schema-pinned.
    assert st["rate_per_min"] is not None
    assert st["rate_per_min_window"] is not None
    # watch --json: the scripting hook, staleness verdict included.
    frame = tel_mod.watch_frame(str(tmp_path))
    assert frame["trace_id"] == trace
    assert frame["finished"] is True
    assert frame["in_flight"] is None
    assert isinstance(frame["stale"], bool)

    # The run-dir trace CLI: one causal tree from the flight log alone.
    assert tel_mod.main(["trace", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "== dslabs causal trace" in text
    assert trace in text
    tr = tracing.assemble(str(tmp_path))
    j = tr["jobs"][0]
    assert j["trace_id"] == trace
    ids = {n["span_id"] for n in j["nodes"]}
    assert all(n["parent"] is None or n["parent"] in ids
               for n in j["nodes"])
    assert j["phases"]["search_secs"] > 0


# ----------------------------------- ACCEPTANCE: SIGKILL + cost ledger

def test_sigkill_mid_level_trace_chain_and_cost_ledger(tmp_path, capsys):
    """ISSUE 13 acceptance: a job whose warden child is SIGKILLed
    mid-level yields a ``telemetry trace`` timeline reconstructed from
    disk alone with an UNBROKEN parent chain submit -> queue ->
    admission -> attempt -> child run -> in-flight dispatch (the kill
    point named); the per-tenant COSTS.jsonl sums agree with the jobs'
    SearchOutcome counters exactly; torn snapshots of SERVER_STATUS
    and COSTS never break the readers; the retention sweep prunes only
    finished run dirs."""
    root = tmp_path / "svc"
    srv = _server(root, workers=1)
    # alice: child SIGKILLs itself mid-run (after a durable checkpoint,
    # so the resume chain is deterministic) — warden fails over to the
    # host rung and still lands the exact verdict.
    res_a = srv.submit(FACTORY, tenant="alice",
                       ladder=("device", "host"),
                       fault={"kind": "die", "at": 8,
                              "after_ckpt": True}, **SMALL)
    assert res_a["accepted"] and res_a["trace_id"]
    # bob: clean single-rung baseline.
    res_b = srv.submit(FACTORY, tenant="bob", ladder=("device",),
                       **SMALL)
    assert res_b["accepted"]
    summary = srv.drain()
    srv.close()
    results = {r["tenant"]: r for r in summary["results"]}
    assert results["alice"]["status"] == "done"
    assert results["bob"]["status"] == "done"
    assert [d["kind"] for d in results["alice"]["deaths"]] == ["oom"]
    # The verdict carries the submit's trace id end to end.
    assert results["alice"]["trace_id"] == res_a["trace_id"]

    # ---- the causal tree, from disk alone
    tr = tracing.assemble(str(root), job=res_a["job_id"])
    (j,) = tr["jobs"]
    assert j["trace_id"] == res_a["trace_id"]
    assert j["status"] == "done"
    nodes = {n["span_id"]: n for n in j["nodes"]}
    # Unbroken parent chain: every node's parent exists.
    for n in j["nodes"]:
        assert n["parent"] is None or n["parent"] in nodes, n
    kinds = {n["kind"] for n in j["nodes"]}
    assert {"submit", "queue", "admission", "attempt", "run",
            "in_flight", "outcome"} <= kinds
    # The in-flight dispatch of the SIGKILLed child is named, and its
    # chain walks back to the submit root: dispatch -> run (child) ->
    # attempt -> submit.
    inflight = [n for n in j["nodes"] if n["kind"] == "in_flight"]
    assert len(inflight) == 1
    assert inflight[0]["tag"].startswith("device.")
    run_node = nodes[inflight[0]["parent"]]
    assert run_node["kind"] == "run"
    attempt = nodes[run_node["parent"]]
    assert attempt["kind"] == "attempt"
    assert nodes[attempt["parent"]]["kind"] == "submit"
    # The child run is linked via the DERIVED attempt span id (the
    # warden passed it through DSLABS_PARENT_SPAN).
    assert attempt["span_id"] == tracing.attempt_span_id(
        res_a["job_id"], 1)
    # Phase latency breakdown present.
    ph = j["phases"]
    assert ph["queue_wait_secs"] is not None
    assert ph["compile_secs"] >= 0 and ph["search_secs"] > 0
    assert ph["total_secs"] > 0
    # Rendered timeline names the kill point; CLI exits 0.
    text = tracing.render_trace(tr)
    assert "!! in-flight" in text and "device." in text
    assert tel_mod.main(["trace", str(root), "--job",
                         res_a["job_id"]]) == 0
    capsys.readouterr()

    # ---- perfetto export
    pf = tracing.to_perfetto(tr)
    names = {e.get("name") for e in pf["traceEvents"]}
    assert any(n and n.startswith("in-flight") for n in names)
    assert any(e.get("ph") == "X" for e in pf["traceEvents"])

    # ---- the cost ledger: sums agree with the verdicts EXACTLY
    costs_path = os.path.join(str(root), tracing.COSTS_NAME)
    recs, torn = tracing.read_flight_lax(costs_path)
    assert torn == 0
    per = tracing.aggregate_costs(recs)
    for tenant in ("alice", "bob"):
        v = results[tenant]
        assert per[tenant]["explored"] == v["explored"], tenant
        assert per[tenant]["unique"] == v["unique"], tenant
        assert per[tenant]["jobs"] == 1
        assert per[tenant]["device_secs"] > 0
        assert per[tenant]["dispatches"] > 0
        assert per[tenant]["cost_per_unique"] > 0
    assert per["alice"]["failovers"] == 1      # the burned device rung
    # The drain summary and SERVER_STATUS surface the same ledger.
    assert summary["costs"]["alice"]["unique"] == \
        results["alice"]["unique"]
    assert summary["cost_per_unique"] > 0
    st = tracing.load_json_tolerant(
        os.path.join(str(root), "SERVER_STATUS.json"))
    assert st["tenants"]["alice"]["costs"]["device_secs"] > 0

    # ---- torn/partial snapshots never break the readers (satellite)
    with open(costs_path, "a") as f:
        f.write('{"t": "cost", "tenant": "ali')      # torn tail
    recs2, torn2 = tracing.read_flight_lax(costs_path)
    assert torn2 == 1 and len(recs2) == len(recs)
    with open(os.path.join(str(root), "SERVER_STATUS.json"), "w") as f:
        f.write('{"t": "server_status", "tena')      # mid-write race
    tr2 = tracing.assemble(str(root))                # must not raise
    assert tr2["server"] is None
    assert tr2["costs"]["alice"]["unique"] == results["alice"]["unique"]
    from dslabs_tpu.service.__main__ import main as svc_main

    assert svc_main(["status", "--root", str(root)]) == 0
    status_line = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert status_line["server"] is None             # torn -> degraded
    assert status_line["costs"]["bob"]["jobs"] == 1

    # ---- retention sweep (satellite): prune oldest finished run dirs
    srv2 = _server(root, keep=1)
    job_dirs = {t: results[t]["run_dir"] for t in ("alice", "bob")}
    assert all(os.path.isdir(d) for d in job_dirs.values())
    pruned = srv2.retention_sweep()
    srv2.close()
    assert pruned == [res_a["job_id"]]               # oldest finished
    assert not os.path.exists(job_dirs["alice"])
    assert os.path.isdir(job_dirs["bob"])
    journal, _ = tracing.read_flight_lax(
        os.path.join(str(root), "journal.jsonl"))
    prunes = [r for r in journal if r.get("t") == "prune"]
    assert [r["job_id"] for r in prunes] == [res_a["job_id"]]
    # The causal chain survives the prune (journal + ledger remain).
    tr3 = tracing.assemble(str(root), job=res_a["job_id"])
    kinds3 = {n["kind"] for n in tr3["jobs"][0]["nodes"]}
    assert {"submit", "queue", "admission", "attempt"} <= kinds3


# ------------------------------------------------- retention unit rules

def test_retention_never_touches_unfinished_jobs(tmp_path):
    srv = _server(tmp_path / "svc", keep=0)
    for jid, status in (("t-000001", "done"), ("t-000002", "failed"),
                        ("t-000003", "pending"),
                        ("t-000004", "running")):
        srv.queue.records[jid] = {"status": status, "tenant": "t",
                                  "job": {"job_id": jid}}
        os.makedirs(srv.job_dir(jid))
    pruned = srv.retention_sweep()
    srv.close()
    assert pruned == ["t-000001", "t-000002"]
    assert not os.path.exists(srv.job_dir("t-000001"))
    assert os.path.isdir(srv.job_dir("t-000003"))
    assert os.path.isdir(srv.job_dir("t-000004"))


# --------------------------------------------------- cost meter units

def test_cost_meter_replays_ledger_and_flight_costs(tmp_path):
    flight = tmp_path / "flight.jsonl"
    recs = [
        {"t": "meta", "started": 100.0},
        {"t": "span", "ts": 0.1, "tag": "device.init", "i": 0,
         "wall": 1.0, "retries": 0},
        {"t": "span", "ts": 0.3, "tag": "device.step", "i": 1,
         "wall": 0.5, "retries": 1},
        {"t": "span", "ts": 0.6, "tag": "device.step", "i": 2,
         "wall": 0.25, "retries": 0},
        {"t": "level", "ts": 0.7, "depth": 1, "wall": 0.6},
        {"t": "outcome", "ts": 0.8, "compile_secs": 2.0},
    ]
    flight.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    fc = tracing.CostMeter.flight_costs(str(flight))
    assert fc["device_secs"] == 1.75
    assert fc["device_secs_by_site"] == {"device.init": 1.0,
                                         "device.step": 0.75}
    assert fc["dispatches"] == 3 and fc["retries"] == 1
    # compile = AOT (outcome) + first dispatch per site (1.0 + 0.5).
    assert fc["compile_secs"] == 3.5
    assert fc["search_secs"] == 0.25
    assert fc["levels"] == 1

    path = str(tmp_path / "COSTS.jsonl")
    m = tracing.CostMeter(path)
    m.charge({"job_id": "a-1", "tenant": "a", "status": "done",
              "unique": 10, "explored": 20, "budget_units": 2.0},
             str(flight))
    m.charge({"job_id": "a-2", "tenant": "a", "status": "failed",
              "unique": 0, "explored": 0})
    m.close()
    # A restarted meter replays the ledger (totals survive).
    m2 = tracing.CostMeter(path)
    per = m2.tenant_summary()
    assert per["a"]["jobs"] == 2 and per["a"]["completed"] == 1
    assert per["a"]["unique"] == 10 and per["a"]["explored"] == 20
    assert per["a"]["cost_per_unique"] == round(1.75 / 10, 9)
    tot = m2.totals()
    assert tot["device_secs"] == 1.75 and tot["unique"] == 10
    m2.close()
