"""Observability smoke driver (`make obs-smoke`, ISSUE 8 satellite):
the end-to-end CLI paths the pytest tier exercises through the API —

1. run a tiny search with a run-dir recorder (flight.jsonl +
   STATUS.json) and render it with ``telemetry watch --once`` and
   ``telemetry report`` (the watch-on-a-finished-run step);
2. (ISSUE 13) run the same search INSIDE a trace context and assemble
   it with ``telemetry trace`` — the causal tree, the trace id on
   every span, ``watch --json``, and the Perfetto export
   (the trace-assembler step);
3. (ISSUE 14) a lane-batch run dir's STATUS.json renders its per-lane
   block through ``telemetry watch`` (the lanes leg);
4. (ISSUE 15) drive the PACKED path end to end: a domain-declared
   generated spec runs with the bit-packed frontier encoding ON,
   its STATUS.json carries the schema-pinned ``capacity`` block
   (bytes_per_state / pack_ratio) and ``telemetry watch`` renders it
   (the capacity2 leg);
5. (ISSUE 16) the same job drained twice through a real CheckServer:
   the second drain lands as a journaled ``memo_hit`` (the memo leg).

Exits nonzero on any mismatch; prints one OK line per step."""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"     # before jax loads

from dslabs_tpu.tpu import compile_cache
from dslabs_tpu.tpu import telemetry as tel_mod

compile_cache.setup()


def run_search(run_dir: str):
    import dataclasses

    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol

    pp = make_pingpong_protocol(workload_size=2)
    pp = dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})
    tel = tel_mod.Telemetry.for_checkpoint(
        os.path.join(run_dir, "search.ckpt"), engine_hint="obs-smoke")
    search = TensorSearch(pp, max_depth=8, frontier_cap=1 << 10,
                          visited_cap=1 << 12, telemetry=tel)
    out = search.run()
    tel.close()
    return out


def run_lane_batch(run_dir: str):
    """A tiny 2-lane batch with a run-dir recorder — the lanes watch
    fixture (ISSUE 14)."""
    import dataclasses

    from dslabs_tpu.tpu.lanes import LaneJob, LaneSearch
    from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol

    pp = make_pingpong_protocol(workload_size=2)
    pp = dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})
    tel = tel_mod.Telemetry.for_checkpoint(
        os.path.join(run_dir, "search.ckpt"), engine_hint="lane-batch")
    search = LaneSearch(pp, n_lanes=2, frontier_cap=1 << 10,
                        visited_cap=1 << 12, telemetry=tel)
    res = search.run_lanes([LaneJob("smoke-a"), LaneJob("smoke-b")])
    tel.close()
    assert not res.errors, res.errors
    return res


def main() -> int:
    run_dir = tempfile.mkdtemp(prefix="dslabs_obs_smoke_")
    out = run_search(run_dir)
    assert out.end_condition == "SPACE_EXHAUSTED", out.end_condition

    # -- watch on a finished run, from the run dir alone
    frame = tel_mod.render_watch(run_dir)
    for needle in ("depth", "rate", "engine device",
                   f"end: {out.end_condition}"):
        assert needle in frame, (needle, frame)
    rc = tel_mod.main(["watch", run_dir, "--once"])
    assert rc == 0, rc
    rc = tel_mod.main(["report", run_dir])
    assert rc == 0, rc
    print("obs-smoke: watch + report on a finished run OK")

    # -- trace assembler (ISSUE 13): the same run inside a trace
    # context assembles into a causal tree from the run dir alone.
    from dslabs_tpu.tpu import tracing

    trace_dir = tempfile.mkdtemp(prefix="dslabs_obs_smoke_trace_")
    trace_id = tracing.mint_trace_id()
    os.environ[tracing.TRACE_ENV] = trace_id
    try:
        run_search(trace_dir)
    finally:
        os.environ.pop(tracing.TRACE_ENV, None)
    rc = tel_mod.main(["trace", trace_dir])
    assert rc == 0, rc
    tr = tracing.assemble(trace_dir)
    (j,) = tr["jobs"]
    assert j["trace_id"] == trace_id, j
    ids = {n["span_id"] for n in j["nodes"]}
    assert all(n["parent"] is None or n["parent"] in ids
               for n in j["nodes"]), "broken parent chain"
    assert j["phases"]["search_secs"] > 0, j["phases"]
    frame = tel_mod.watch_frame(trace_dir)
    assert frame["trace_id"] == trace_id and frame["finished"], frame
    pf = tracing.to_perfetto(tr)
    assert pf["traceEvents"], "perfetto export empty"
    print("obs-smoke: trace assembler (causal tree + perfetto) OK")

    # -- lanes leg (ISSUE 14): a lane-batch STATUS.json (the child's
    # monitor file) renders the per-lane block through the watch CLI.
    lane_dir = tempfile.mkdtemp(prefix="dslabs_obs_smoke_lanes_")
    run_lane_batch(lane_dir)
    frame = tel_mod.render_watch(lane_dir)
    assert "job lane" in frame, frame
    rc = tel_mod.main(["watch", lane_dir, "--once"])
    assert rc == 0, rc
    print("obs-smoke: batched watch OK")

    # -- capacity2 leg (ISSUE 15): the packed path end to end.
    import dataclasses

    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.specs import clientserver_spec

    cap_dir = tempfile.mkdtemp(prefix="dslabs_obs_smoke_cap2_")
    cs = clientserver_spec(2, 2).compile()
    cs = dataclasses.replace(
        cs, goals={}, prunes={"DONE": cs.goals["CLIENTS_DONE"]})
    tel = tel_mod.Telemetry.for_checkpoint(
        os.path.join(cap_dir, "search.ckpt"), engine_hint="capacity2")
    search = TensorSearch(cs, chunk=128, frontier_cap=1 << 10,
                          visited_cap=1 << 12, telemetry=tel)
    assert search._pk is not None, "generated spec must derive packing"
    out = search.run()
    tel.close()
    assert out.pack_ratio and out.pack_ratio >= 2.0, out.pack_ratio
    assert out.bytes_per_state < out.bytes_per_state_unpacked, out
    st = tel_mod.load_status(
        os.path.join(cap_dir, "STATUS.json"))
    assert st["capacity"]["bytes_per_state"] == out.bytes_per_state, st
    assert st["capacity"]["pack_ratio"] == out.pack_ratio, st
    frame = tel_mod.render_watch(cap_dir)
    assert "capacity:" in frame and "bytes_per_state" in frame, frame
    print("obs-smoke: packed path + capacity block OK")

    # -- memo leg (ISSUE 16, service/memo.py): the same job drained
    # TWICE through a real CheckServer — the second drain lands as a
    # journaled memo_hit with zero dispatches.
    from dslabs_tpu.service import CheckServer

    memo_root = tempfile.mkdtemp(prefix="dslabs_obs_smoke_memo_")
    srv = CheckServer(
        memo_root, workers=1, admission=False, elastic=False)
    job = dict(factory="dslabs_tpu.tpu.protocols.pingpong:"
                       "make_exhaustive_pingpong",
               factory_kwargs={"workload_size": 2}, chunk=64,
               frontier_cap=1 << 8, visited_cap=1 << 12)
    srv.submit(tenant="first", **job)
    first = srv.drain()
    assert first["completed"] == 1, first
    srv.submit(tenant="second", **job)
    second = srv.drain()
    srv.close()
    assert second["memo"]["hits"] == 1, second["memo"]
    with open(os.path.join(memo_root, "journal.jsonl")) as f:
        kinds = [json.loads(ln).get("t") for ln in f if ln.strip()]
    assert "memo_hit" in kinds, kinds
    print("obs-smoke: memo drain-twice hit OK")
    print(json.dumps({"obs_smoke": "ok", "run_dir": run_dir,
                      "trace_dir": trace_dir, "trace_id": trace_id}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
