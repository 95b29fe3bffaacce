"""Unified telemetry layer (ISSUE 7, tpu/telemetry.py).

The contract under test, in the paper's discipline that every signal
must come from scalar readbacks already paid for:

* **span count == dispatch count** on pingpong, BOTH engines — the
  recorder rides the existing ``_dispatch`` seam, one span per
  dispatch, never more, never fewer;
* **zero added overhead** — attaching telemetry changes neither the
  dispatch counts nor the number of device->host readbacks (the
  ``engine.device_get`` spy), the hard acceptance constraint;
* **crash-safe flight recorder** — a SIGKILL'd run leaves a parseable
  JSONL tail whose last record names the IN-FLIGHT dispatch;
* **report CLI** — renders per-level throughput and per-site latency
  percentiles from the flight log alone (golden sections pinned);
* **supervisor integration** — retries/failovers become events.

``make obs-smoke`` runs this file.
"""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import engine  # noqa: E402
from dslabs_tpu.tpu import telemetry as tel_mod  # noqa: E402
from dslabs_tpu.tpu.engine import TensorSearch  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh  # noqa: E402
from dslabs_tpu.tpu.telemetry import (Telemetry, build_report,  # noqa: E402
                                      read_flight, render_report)

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pruned_pingpong():
    pp = make_pingpong_protocol(workload_size=2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def _counting_hook(counts):
    def hook(tag, fn, *args):
        counts[tag] = counts.get(tag, 0) + 1
        return fn(*args)
    return hook


def _spans(tel):
    return [r for r in tel.ring if r["t"] == "span"]


# ------------------------------------------------- span/dispatch parity

def test_span_count_equals_dispatch_count_device_engine():
    counts = {}
    tel = Telemetry()
    search = TensorSearch(_pruned_pingpong(), max_depth=8,
                          frontier_cap=1 << 10, visited_cap=1 << 12)
    search._dispatch_hook = _counting_hook(counts)
    tel.attach(search)
    out = search.run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert sum(counts.values()) == len(_spans(tel))
    # Span tags are the dispatch tags, verbatim.
    by_tag = {}
    for s in _spans(tel):
        by_tag[s["tag"]] = by_tag.get(s["tag"], 0) + 1
    assert by_tag == counts


def test_span_count_equals_dispatch_count_sharded_engine():
    counts = {}
    tel = Telemetry()
    search = ShardedTensorSearch(
        _pruned_pingpong(), make_mesh(8), chunk_per_device=16,
        frontier_cap=1 << 8, visited_cap=1 << 10, max_depth=8,
        telemetry=tel)
    search._dispatch_hook = _counting_hook(counts)
    out = search.run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert sum(counts.values()) == len(_spans(tel))
    assert any(s["tag"] == "sharded.superstep" for s in _spans(tel))


# ----------------------------------------------------- overhead guard

def test_overhead_guard_no_added_dispatches_or_transfers(
        monkeypatch, tmp_path):
    """ACCEPTANCE (extended by ISSUE 8, then ISSUE 10): telemetry adds
    ZERO device dispatches and ZERO device->host readbacks — dispatch
    counts and device_get call counts are bit-identical with and
    without the recorder, both engines, WITH the per-device stats
    lanes and the STATUS.json live-monitor writer enabled (full
    flight-recorder config, not a RAM-only stub).  ISSUE 10 extension:
    the soundness sanitizer OFF (DSLABS_SANITIZE unset or =0) adds
    zero dispatches, zero transfers, and zero telemetry events too."""
    monkeypatch.delenv("DSLABS_SANITIZE", raising=False)
    proto = _pruned_pingpong()
    gets = []
    real = engine.device_get

    def spy(x):
        gets.append(1)
        return real(x)

    monkeypatch.setattr(engine, "device_get", spy)

    def full_tel(name):
        # Flight log + derived STATUS.json: the whole mesh-scope
        # recorder, every writer engaged.
        tel = Telemetry(flight_log=str(tmp_path / name / "flight.jsonl"))
        assert tel.status_path is not None
        return tel

    def run_device(telemetry):
        counts = {}
        s = TensorSearch(proto, max_depth=8, frontier_cap=1 << 10,
                         visited_cap=1 << 12, telemetry=telemetry)
        s._dispatch_hook = _counting_hook(counts)
        del gets[:]
        out = s.run()
        return counts, len(gets), out

    c0, g0, o0 = run_device(None)
    c1, g1, o1 = run_device(full_tel("dev"))
    assert c0 == c1, "telemetry changed the dispatch schedule"
    assert g0 == g1, "telemetry added device->host transfers"
    assert (o0.unique_states, o0.end_condition) == \
        (o1.unique_states, o1.end_condition)
    assert (tmp_path / "dev" / "STATUS.json").exists()

    # ISSUE 13 extension: causal tracing ENABLED (trace context in the
    # env, trace fields on every span) is bit-identical too — the
    # trace discipline is record fields only, never device work.
    monkeypatch.setenv("DSLABS_TRACE_ID", "cafe0123cafe0123")
    monkeypatch.setenv("DSLABS_PARENT_SPAN", "job-x:a1")
    tel_tr = full_tel("dev-traced")
    assert tel_tr.trace_id == "cafe0123cafe0123"
    ct, gt, ot = run_device(tel_tr)
    assert ct == c0, "tracing changed the dispatch schedule"
    assert gt == g0, "tracing added device->host transfers"
    assert (ot.unique_states, ot.end_condition) == \
        (o0.unique_states, o0.end_condition)
    assert ot.trace_id == "cafe0123cafe0123"
    spans_tr = [r for r in tel_tr.ring if r["t"] == "span"]
    assert spans_tr and all(s.get("trace") == "cafe0123cafe0123"
                            for s in spans_tr)
    monkeypatch.delenv("DSLABS_TRACE_ID")
    monkeypatch.delenv("DSLABS_PARENT_SPAN")

    # ISSUE 10: DSLABS_SANITIZE=0 is bit-identical to unset — same
    # dispatch schedule, same transfer count, and no sanitizer events
    # in the recorder.
    monkeypatch.setenv("DSLABS_SANITIZE", "0")
    tel_off = full_tel("dev-sanitize-off")
    c2, g2, _o2 = run_device(tel_off)
    assert c2 == c0, "DSLABS_SANITIZE=0 changed the dispatch schedule"
    assert g2 == g0, "DSLABS_SANITIZE=0 added device->host transfers"
    assert not [e for e in tel_off.events
                if e.get("kind") == "sanitizer_finding"]
    monkeypatch.delenv("DSLABS_SANITIZE", raising=False)

    def run_sharded(telemetry):
        counts = {}
        s = ShardedTensorSearch(
            proto, make_mesh(8), chunk_per_device=16,
            frontier_cap=1 << 8, visited_cap=1 << 10, max_depth=8,
            telemetry=telemetry)
        s._dispatch_hook = _counting_hook(counts)
        del gets[:]
        s.run()
        return counts, len(gets)

    cs0, gs0 = run_sharded(None)
    cs1, gs1 = run_sharded(full_tel("sharded"))
    assert cs0 == cs1, "telemetry changed the sharded dispatch schedule"
    assert gs0 == gs1, "telemetry added sharded device->host transfers"
    assert (tmp_path / "sharded" / "STATUS.json").exists()

    # ISSUE 13: tracing enabled, sharded engine — still bit-identical.
    monkeypatch.setenv("DSLABS_TRACE_ID", "cafe0123cafe0123")
    cst, gst = run_sharded(full_tel("sharded-traced"))
    assert cst == cs0, "tracing changed the sharded dispatch schedule"
    assert gst == gs0, "tracing added sharded device->host transfers"
    monkeypatch.delenv("DSLABS_TRACE_ID")


# ------------------------------------------------------- flight log IO

def test_flight_log_records_and_levels(tmp_path):
    flight = str(tmp_path / "flight.jsonl")
    tel = Telemetry(flight_log=flight)
    search = TensorSearch(_pruned_pingpong(), max_depth=8,
                          frontier_cap=1 << 10, visited_cap=1 << 12)
    tel.attach(search)
    out = search.run()
    tel.close()
    recs = read_flight(flight)
    kinds = {r["t"] for r in recs}
    assert {"meta", "dispatch", "span", "level", "outcome"} <= kinds
    spans = [r for r in recs if r["t"] == "span"]
    starts = [r for r in recs if r["t"] == "dispatch"]
    assert len(spans) == len(starts)        # every start closed
    levels = [r for r in recs if r["t"] == "level"]
    assert len(levels) == out.depth
    oc = [r for r in recs if r["t"] == "outcome"][-1]
    assert oc["end_condition"] == out.end_condition
    assert oc["unique_states"] == out.unique_states


def test_read_flight_tolerates_torn_tail_only(tmp_path):
    p = tmp_path / "t.jsonl"
    good = json.dumps({"t": "span", "tag": "device.step", "i": 0})
    p.write_text(good + "\n" + good + "\n" + '{"t": "disp')  # torn tail
    assert len(read_flight(str(p))) == 2
    # A torn line mid-file is corruption, not truncation.
    p.write_text('{"t": "sp\n' + good + "\n")
    with pytest.raises(ValueError):
        read_flight(str(p))


def test_run_dir_layout_names_flight_log(tmp_path):
    from dslabs_tpu.tpu import checkpoint as ckpt_mod

    ck = str(tmp_path / "search.ckpt")
    lay = ckpt_mod.run_dir_layout(ck)
    assert lay["flight_log"] == str(tmp_path / "flight.jsonl")
    assert "compile_cache" not in lay     # one cache, not one per run dir
    tel = Telemetry.for_checkpoint(ck)
    assert tel.flight_log == lay["flight_log"]
    tel.close()


# ------------------------------------------------------ SIGKILL survival

_KILL_CHILD = r"""
import dataclasses, sys, time
from dslabs_tpu.tpu.engine import TensorSearch
from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol
from dslabs_tpu.tpu.telemetry import Telemetry

pp = make_pingpong_protocol(workload_size=2)
pp = dataclasses.replace(pp, goals={},
                         prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})
search = TensorSearch(pp, max_depth=10, frontier_cap=1 << 10,
                      visited_cap=1 << 12)
n = [0]
def hook(tag, fn, *args):
    n[0] += 1
    if n[0] == 6:
        print("WEDGED", flush=True)
        time.sleep(600.0)           # the wedge: parent SIGKILLs us here
    return fn(*args)
search._dispatch_hook = hook
Telemetry(flight_log=sys.argv[1]).attach(search)
search.run()
"""


def test_flight_log_survives_sigkill_names_inflight_dispatch(tmp_path):
    """ACCEPTANCE: a SIGKILL'd run leaves a parseable JSONL tail whose
    last record is the begin marker of the dispatch that was in
    flight — the wedge is attributable from the file alone."""
    flight = str(tmp_path / "flight.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL_CHILD, flight],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=ROOT)
    try:
        line = proc.stdout.readline()       # blocks until mid-dispatch
        assert "WEDGED" in line
        time.sleep(0.3)                     # let the marker line flush
        proc.send_signal(signal.SIGKILL)
    finally:
        proc.wait(timeout=30)
    recs = read_flight(flight)              # parses despite the kill
    assert recs, "flight log must survive SIGKILL"
    spans = {(r["tag"], r["i"]) for r in recs if r["t"] == "span"}
    starts = [r for r in recs if r["t"] == "dispatch"]
    open_starts = [r for r in starts if (r["tag"], r["i"]) not in spans]
    assert len(open_starts) == 1, recs[-3:]
    # The report names the same in-flight dispatch.
    rep = build_report(recs)
    assert rep["in_flight"] is not None
    assert rep["in_flight"]["tag"] == open_starts[0]["tag"]
    assert "in-flight at EOF" in render_report(rep)


# --------------------------------------------- per-device lanes / skew

def test_per_device_lanes_and_skew_on_8_device_mesh():
    """ACCEPTANCE (ISSUE 8): on the n_devices=8 CPU dryrun mesh
    every level record carries per-device
    lanes with 8 entries and finite skew metrics — read off the SAME
    fused stats vector the level sync already pays for."""
    import math

    tel = Telemetry()
    search = ShardedTensorSearch(
        _pruned_pingpong(), make_mesh(8), chunk_per_device=16,
        frontier_cap=1 << 8, visited_cap=1 << 10, max_depth=8,
        telemetry=tel)
    out = search.run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert out.levels, "sharded outcome must carry level records"
    for rec in out.levels:
        pd = rec["per_device"]
        for lane in ("explored", "frontier", "load_factor", "drops"):
            assert len(pd[lane]) == 8, (lane, pd)
        sk = rec["skew"]
        for lane in ("explored", "frontier"):
            assert math.isfinite(sk[lane]["imbalance"])
            assert math.isfinite(sk[lane]["cv"])
            assert sk[lane]["imbalance"] >= 1.0 or \
                sk[lane]["mean"] == 0.0
        # The level's per-device explored deltas sum to the level's
        # global explored delta (the lanes ARE the pre-psum values).
    total = sum(sum(r["per_device"]["explored"]) for r in out.levels)
    assert total == out.states_explored
    # on_level fed the registry gauges.
    assert "skew.sharded" in tel.registry.gauges
    assert tel.registry.gauges["skew.sharded"].value >= 1.0


def test_per_device_lanes_swarm_rounds():
    """Swarm rounds keep their pre-psum per-device walker stats in the
    same round readback: 8 lanes per round record on the 8-device
    mesh."""
    from dslabs_tpu.tpu.swarm import SwarmSearch

    tel = Telemetry()
    sw = SwarmSearch(_pruned_pingpong(), mesh=make_mesh(8),
                     walkers_per_device=4, max_steps=8,
                     steps_per_round=4, seed=0, visited_cap=1 << 10,
                     max_rounds=2)
    tel.attach(sw)
    sw.run()
    rounds = [r for r in tel.levels if r.get("engine") == "swarm"]
    assert rounds, "swarm rounds must land level records"
    for rec in rounds:
        assert len(rec["per_device"]["explored"]) == 8
        assert len(rec["per_device"]["unique"]) == 8
        assert rec["skew"]["explored"]["imbalance"] >= 1.0


# ------------------------------------------------- STATUS.json / watch

def test_status_json_schema_and_watch_finished_run(tmp_path, capsys):
    """Tentpole leg 2: the engines' feeds atomically rewrite
    STATUS.json in the run dir (schema pinned here), and
    ``telemetry watch`` renders depth/rate/skew from the run dir
    ALONE."""
    from dslabs_tpu.tpu import checkpoint as ckpt_mod

    ck = str(tmp_path / "search.ckpt")
    assert ckpt_mod.run_dir_layout(ck)["status"] == \
        str(tmp_path / "STATUS.json")
    tel = Telemetry.for_checkpoint(ck)
    assert tel.status_path == str(tmp_path / "STATUS.json")
    search = ShardedTensorSearch(
        _pruned_pingpong(), make_mesh(8), chunk_per_device=16,
        frontier_cap=1 << 8, visited_cap=1 << 10, max_depth=8,
        telemetry=tel)
    out = search.run()
    tel.close()

    st = json.loads((tmp_path / "STATUS.json").read_text())
    for key in ("t", "pid", "updated", "uptime", "spans", "levels",
                "last_span", "in_flight", "flight_log", "engine",
                "depth", "explored", "unique", "rate_per_min",
                "rate_per_min_window", "skew", "per_device",
                "end_condition", "mesh_width", "trace_id",
                "parent_span", "span_id"):
        assert key in st, f"STATUS.json missing {key!r}"
    # ISSUE 13 satellite: BOTH rates are real numbers — cumulative
    # over the whole run, sliding-window over the last N levels.
    assert st["rate_per_min"] is not None
    assert st["rate_per_min_window"] is not None
    assert st["t"] == "status"
    assert st["pid"] == os.getpid()
    assert st["engine"] == "sharded"
    assert st["depth"] == out.depth
    assert st["end_condition"] == out.end_condition
    assert st["in_flight"] is None          # run finished cleanly
    assert len(st["per_device"]["explored"]) == 8
    # Live mesh width (ISSUE 9): derived from the per-device lanes so
    # `telemetry watch` shows a degraded mesh the moment it shrinks.
    assert st["mesh_width"] == 8

    assert tel_mod.main(["watch", str(tmp_path), "--once"]) == 0
    text = capsys.readouterr().out
    assert f"depth {out.depth}" in text
    assert "rate" in text
    assert "skew:" in text
    assert f"end: {out.end_condition}" in text


_WATCH_KILL_CHILD = r"""
import dataclasses, sys, time
from dslabs_tpu.tpu.engine import TensorSearch
from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol
from dslabs_tpu.tpu.telemetry import Telemetry

pp = make_pingpong_protocol(workload_size=2)
pp = dataclasses.replace(pp, goals={},
                         prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})
search = TensorSearch(pp, max_depth=10, frontier_cap=1 << 10,
                      visited_cap=1 << 12)
n = [0]
def hook(tag, fn, *args):
    n[0] += 1
    if n[0] == 6:
        print("WEDGED", flush=True)
        time.sleep(600.0)           # the wedge: parent SIGKILLs us here
    return fn(*args)
search._dispatch_hook = hook
Telemetry.for_checkpoint(sys.argv[1] + "/search.ckpt").attach(search)
search.run()
"""


def test_watch_survives_sigkill_mid_level(tmp_path):
    """ACCEPTANCE: ``telemetry watch`` renders a run in ANOTHER
    process from the run dir alone and survives that run being
    SIGKILLed mid-level — the atomic STATUS.json is never torn, the
    flight log's torn tail is tolerated, and the last in-flight
    dispatch is named."""
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", _WATCH_KILL_CHILD, run_dir],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=ROOT)
    try:
        line = proc.stdout.readline()       # blocks until mid-dispatch
        assert "WEDGED" in line
        time.sleep(0.3)                     # let the marker line flush
        # The run is alive but wedged: the watcher (another process's
        # view, same code path) already renders from the dir alone.
        live = tel_mod.render_watch(run_dir)
        assert "in-flight" in live
        proc.send_signal(signal.SIGKILL)
    finally:
        proc.wait(timeout=30)
    frame = tel_mod.render_watch(run_dir)
    assert "engine device" in frame         # depth/rate line rendered
    assert "depth" in frame and "rate" in frame
    assert "in-flight" in frame, frame      # the dispatch it died in
    assert tel_mod.main(["watch", run_dir, "--once"]) == 0


# ------------------------------------------------- report --json schema

def test_report_json_schema_pin(tmp_path, capsys):
    """ISSUE 8 satellite: ``report --json`` emits the same sections as
    the rendered report, machine-readable — ONE schema for grading
    scripts (top-level keys pinned)."""
    flight = str(tmp_path / "flight.jsonl")
    tel = Telemetry(flight_log=flight)
    search = TensorSearch(_pruned_pingpong(), max_depth=8,
                          frontier_cap=1 << 10, visited_cap=1 << 12)
    tel.attach(search)
    out = search.run()
    tel.close()
    assert tel_mod.main(["report", flight, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    for key in ("meta", "n_spans", "sites", "series", "timeline",
                "outcomes", "counts", "total_wall", "compile_wall",
                "in_flight", "source"):
        assert key in rep, f"report --json missing {key!r}"
    assert rep["source"] == flight
    assert rep["in_flight"] is None
    assert len(rep["series"]["device"]) == out.depth
    # The per-device lanes ride the series records (the heatmap's and
    # the graders' one source).
    assert rep["series"]["device"][0]["per_device"]["explored"]
    assert rep["outcomes"][-1]["end_condition"] == out.end_condition


# ------------------------------------------------------------ report CLI

def test_report_cli_golden_sections(tmp_path, capsys):
    """The report CLI renders per-level throughput and per-site latency
    percentiles FROM THE LOG ALONE (acceptance) — section headers and
    key fields pinned."""
    flight = str(tmp_path / "flight.jsonl")
    tel = Telemetry(flight_log=flight)
    search = ShardedTensorSearch(
        _pruned_pingpong(), make_mesh(8), chunk_per_device=16,
        frontier_cap=1 << 8, visited_cap=1 << 10, max_depth=8,
        telemetry=tel)
    out = search.run()
    tel.close()
    # A run dir (the checkpoint's directory) resolves to flight.jsonl.
    assert tel_mod.main(["report", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    for header in ("== dslabs run report", "-- dispatch latency by site --",
                   "-- per-level throughput --",
                   "-- per-device skew (explored share per level) --",
                   "-- recovery timeline --",
                   "-- spill / overflow / recovery counts --"):
        assert header in text, f"missing section {header!r}"
    # Heatmap rows: one per level, 8 cells wide, with skew columns.
    heat = [ln for ln in text.splitlines() if ln.startswith("d ")
            or (ln.startswith("d") and "|" in ln and "imb=" in ln)]
    assert len(heat) == out.depth
    assert all(ln.count("|") == 2 and "cv=" in ln for ln in heat)
    assert "sharded.superstep" in text
    assert "[engine sharded]" in text
    assert f"outcome: {out.end_condition}" in text
    assert "p50ms" in text and "p99ms" in text and "states/s" in text
    # One throughput row per completed level.
    lines = text.splitlines()
    i = lines.index("[engine sharded]")
    rows = [ln for ln in lines[i + 2:] if ln and ln[0] != "["
            and not ln.startswith("--")]
    assert len([r for r in rows if r.strip()
                and r.strip()[0].isdigit()]) == out.depth


# ------------------------------------------- supervisor / event plumbing

def test_supervisor_retries_become_events_and_span_retries():
    from dslabs_tpu.tpu.supervisor import (FaultPlan, RetryPolicy,
                                           SearchSupervisor)

    tel = Telemetry()
    plan = FaultPlan().raise_at(2, engine="host")
    sup = SearchSupervisor(
        _pruned_pingpong(), ladder=("host",),
        policy=RetryPolicy(max_retries=2, backoff_base=0.001),
        fault_plan=plan, max_depth=8, chunk=1 << 8,
        frontier_cap=1 << 10, visited_cap=1 << 12, telemetry=tel)
    out = sup.run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert out.retries >= 1
    ev = {r["kind"] for r in tel.events if r.get("t") == "event"}
    assert "rung" in ev and "retry" in ev
    assert tel.registry.counters["events.retry"].value >= 1
    # The retry is charged to the span of the dispatch that absorbed it.
    assert sum(s["retries"] for s in _spans(tel)) == out.retries
