"""Benchmark: lab3 multi-Paxos BFS unique-states/minute on the TPU tensor
backend (BASELINE.md north star: >= 1e8 unique lab3-paxos states/min on a
v5e-8; this runs on whatever chips the driver provides).

The measured engine is the device-resident sharded BFS
(dslabs_tpu/tpu/sharded.py) over a mesh of all available devices — on one
chip the all_to_all degenerates to an identity and the loop still keeps
the frontier + visited set in HBM with one scalar sync per level.  All
device arithmetic is int32/uint32 (round 1 crashed the TPU worker inside
x64-emulated fingerprints; x64 is banned from device code).

Structure — BOUNDED and DIAGNOSABLE; the parent stays off JAX and runs
one phase child at a time (a chip belongs to one process):

* A **hard global deadline** (DSLABS_BENCH_DEADLINE_SECS, default 480 s):
  every phase gets min(its own cap, time remaining); when the deadline
  expires the parent prints the best-so-far JSON line.
* **Last-line JSON, honest exit code**: SIGTERM/SIGINT handlers plus a
  top-level try/except print the best-so-far result (tagged with the
  signal / traceback).  The exit code is 0 only when a measured phase
  produced a number; a failed pre-flight or a run with no number
  exits non-zero with the error named in the JSON.
* **Warden probes**: every phase child heartbeats on stderr and is
  watched by the shared silence monitor (tpu/warden.py LineWatch) — a
  WEDGED runtime stops heartbeating and is SIGKILLed at the silence
  budget (preflight: ~60 s), not at the full phase budget.
* A **pre-flight** subprocess (tiny matmul) distinguishes a wedged
  accelerator runtime from a slow compile: if 256x256 @ 256x256 cannot
  finish in its window, the bench reports "TPU runtime wedged" and
  exits non-zero — no CPU number stands in for the device.
* **Heartbeats on stderr**: phase start/end lines here plus per-level
  lines from the search children (their recorder's ``on_level``) — stderr passes
  straight through, stdout carries exactly one JSON line.
* **compile_secs** is measured (the warm-up run) and reported per phase.
* **Calibration is cached** (/tmp/dslabs_bench_cal.json, keyed by the
  protocol signature) so re-runs spend their window on the measurement.
* The **strict drop-free rate is the headline** (Search.java:405-505
  semantics: BFS never silently narrows; dropped=0 enforced fatally),
  one attempt, child-side time bound (a slow run returns a partial rate,
  TIME_EXHAUSTED, instead of a parent kill).  Beam runs only with time
  left and is reported under "beam" (dropped_states is a first-class
  field, warned past DSLABS_DROPPED_WARN); the **swarm explorer's**
  deep-probe rates (walkers/sec, unique-states/min, deepest depth —
  tpu/swarm.py) ride under "swarm", and the **capacity ladder's**
  1/8-visited-capacity spill rate vs uncapped (exact-parity flag,
  spill counters — tpu/spill.py) under "spill".

Budget table (vs the 480 s deadline): docs/resilience.md.
"""

import json
import os
import signal
import subprocess
import sys
import time
import traceback

BASELINE_STATES_PER_MIN = 1e8

DEADLINE_SECS = float(os.environ.get("DSLABS_BENCH_DEADLINE_SECS", 480.0))
# Preflight: import + client init + one tiny (cached) compile.  Budget
# + slack is capped at 120 s TOTAL so a wedged preflight is reported
# well inside the deadline.
PREFLIGHT_CAP_SECS = 90.0
PREFLIGHT_KILL_SLACK_SECS = 30.0
# Heartbeat-silence kill budgets (tpu/warden.py LineWatch): the
# preflight child heartbeats between its boot stages, so a wedged
# runtime dies at ~60 s, not at the phase budget; measured phases
# heartbeat per level/phase and get a LONG leash — their one
# legitimate silence is a cold-cache XLA compile, and the preflight has
# already proven the runtime alive before any measured phase runs.
PREFLIGHT_SILENCE_SECS = float(os.environ.get(
    "DSLABS_BENCH_PREFLIGHT_SILENCE_SECS", 60.0))
PHASE_SILENCE_SECS = float(os.environ.get(
    "DSLABS_BENCH_SILENCE_SECS", 330.0))
CALIBRATE_CAP_SECS = 240.0
STRICT_CAP_SECS = 420.0      # child budget cap; parent adds kill slack
BEAM_CAP_SECS = 300.0
SWARM_CAP_SECS = 150.0       # swarm-explorer phase (ISSUE 5)
SPILL_CAP_SECS = 120.0       # capacity-ladder phase (ISSUE 6)
CAPACITY2_CAP_SECS = 120.0   # packed/symmetry/async-drain phase (ISSUE 15)
SERVICE_CAP_SECS = 120.0     # multi-tenant service phase (ISSUE 11)
MESH_CAP_SECS = 150.0        # 8-device mesh headline phase (ISSUE 12)
LANES_CAP_SECS = 150.0       # batched-job-lanes phase (ISSUE 14)
MEMO_CAP_SECS = 150.0        # cross-job memoization phase (ISSUE 16)
SCENARIOS_CAP_SECS = 120.0   # fault-scenario phase (ISSUE 19)
LABS_CAP_SECS = 120.0        # generated-labs packing phase (ISSUE 20)
# Parent backstop beyond the child's budget.  Generous on purpose: the
# child's time checks are level-granular (a slow level can overrun
# max_secs by ~30 s, sharded.py round-3 note), the strict child floors
# its search at 45 s even when compile ate the budget — a kill here
# loses the phase's number entirely, so the slack must cover the worst
# honest overrun.
KILL_SLACK_SECS = 150.0
# Fallback budgets if calibration is unavailable (round-3 measured).
FALLBACK_EV_BUDGET = (40, 8)
CAL_CACHE = "/tmp/dslabs_bench_cal.json"
# Beam ladder (chunk/device, frontier, visited): lead rung = the round-3
# measured config; the smaller rungs are OOM fallbacks so a worker crash
# on the big config still lands a beam number.
BEAM_LADDER = [
    (8192, 1 << 19, 1 << 24),
    (1024, 1 << 18, 1 << 23),
    (64, 1 << 12, 1 << 18),
]

_T0 = time.time()

# Run directory for per-phase telemetry flight logs (tpu/telemetry.py):
# the parent hands each phase child its own flight-recorder path via
# DSLABS_BENCH_FLIGHT, so a SIGKILLed/wedged child still leaves its
# last dispatches on disk and the error JSON can name the in-flight
# dispatch instead of one scraped stderr line.
_RUNDIR_REQUESTED = os.environ.get("DSLABS_BENCH_RUNDIR",
                                   "/tmp/dslabs_bench")
_RUNDIR_STATE = {"path": None, "substituted": False}

# Structured wedge diagnostics collected by _sub on phase failure;
# attached to the last-line JSON as "wedge_diagnostics" by _emit.
_DIAGNOSTICS = []


def _rundir() -> str:
    """The run directory, PROVEN writable.  When the requested dir
    cannot be created or written (read-only FS, permission error) the
    bench falls back to a fresh tempdir instead of silently losing
    every phase's flight log — the substitution is noted in the
    last-line JSON, and wedge diagnostics on a dead phase keep
    working (they read the flight tail from the actual dir)."""
    if _RUNDIR_STATE["path"]:
        return _RUNDIR_STATE["path"]
    path = _RUNDIR_REQUESTED
    try:
        os.makedirs(path, exist_ok=True)
        probe = os.path.join(path, f".probe.{os.getpid()}")
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
    except OSError:
        import tempfile

        path = tempfile.mkdtemp(prefix="dslabs_bench_")
        _RUNDIR_STATE["substituted"] = True
        _hb(f"run dir {_RUNDIR_REQUESTED!r} unwritable — flight logs "
            f"fall back to {path}")
    _RUNDIR_STATE["path"] = path
    return path


def _phase_telemetry(label: str):
    """The phase child's flight recorder.  The parent's path (env)
    wins; standalone phase invocations land in the run dir."""
    from dslabs_tpu.tpu.telemetry import Telemetry

    class Heartbeat(Telemetry):
        """One stderr line a level: the parent's silence watch reads
        them as heartbeats."""

        def on_level(self, engine, record):
            super().on_level(engine, record)
            _hb(f"[level {record.get('depth')}] {engine} "
                f"dt={record.get('wall', 0.0):.2f}s "
                f"explored={record.get('explored')} "
                f"unique={record.get('unique')} "
                f"next={record.get('next_frontier')}")

    path = os.environ.get("DSLABS_BENCH_FLIGHT")
    if not path:
        path = os.path.join(_rundir(), f"{label}.flight.jsonl")
    try:
        os.remove(path)     # stale spans must not pollute this run
    except OSError:
        pass
    # Telemetry itself degrades to RAM-only recording if even this
    # path is unwritable (summary() then carries flight_error).
    return Heartbeat(flight_log=path, engine_hint=label)


def _note_wedge(label: str, message: str, watch, flight) -> None:
    """ISSUE-7 satellite: a dead phase's error
    JSON carries the child's last heartbeat AND its last
    flight-recorder spans — the in-flight dispatch included — never
    just the final scraped stderr line."""
    from dslabs_tpu.tpu import telemetry as tel_mod

    tail = list(watch.tail) if watch is not None else []
    _DIAGNOSTICS.append({
        "phase": label,
        "message": message,
        "last_heartbeat": tail[-1] if tail else None,
        "stderr_tail": tail[-3:],
        "last_spans": tel_mod.tail_records(flight, 6),
    })


def _note_phase_telemetry(result: dict, label: str, phase) -> None:
    """Collect a phase's telemetry summary under the top-level
    ``telemetry`` block (pinned by the bench-JSON schema test)."""
    t = (phase or {}).get("telemetry") if isinstance(phase, dict) \
        else None
    if not t:
        return
    result.setdefault(
        "telemetry", {"run_dir": _rundir(), "phases": {}})[
        "phases"][label] = t


def _remaining() -> float:
    return DEADLINE_SECS - (time.time() - _T0)


def _hb(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _bench_protocol():
    import dataclasses

    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    # Two clients widen the space enough to sustain large frontiers.
    # Goals are stripped: the bench measures sustained exploration
    # throughput, and a lucky beam hitting CLIENTS_DONE mid-run would end
    # it early with a run-dependent rate.
    protocol = make_paxos_protocol(n=3, n_clients=2, w=1, max_slots=3,
                                   net_cap=64, timer_cap=6)
    return dataclasses.replace(protocol, goals={})


_PROTO_SIG = "paxos-n3-c2-w1-s3-net64-t6-v5"


def _persistent_cache():
    """Every phase child — the PREFLIGHT included — and every warden /
    service grandchild resolves the same persistent cache
    (tpu/compile_cache.py: JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache), so a warm run's preflight matmul and the
    search programs skip XLA entirely."""
    from dslabs_tpu.tpu import compile_cache

    compile_cache.setup()


# --------------------------------------------------------------- children

def _preflight() -> dict:
    """Accelerator liveness probe — a WARDEN PROBE twice over: the
    child heartbeats between its boot stages (so the parent's silence
    monitor kills a wedged runtime in ~60 s, not at the phase budget),
    and the tiny matmul runs through the same dispatch boundary the
    search hot loops use (tpu/supervisor.py ``probe_device``), so a
    wedge that lets heartbeats through still surfaces as a classified,
    attributable ``DispatchTimeout`` inside this bounded subprocess
    instead of a bare hang in a 400 s search phase."""
    tel = _phase_telemetry("preflight")
    wedge = os.environ.get("DSLABS_BENCH_FAKE_WEDGE")
    if wedge == "hang":
        # Test knob, hang shape: the child goes SILENT (a true
        # wedge) — only the parent's silence kill ends it.
        # The hang happens INSIDE a telemetry span, so the flight log's
        # torn tail names the in-flight dispatch (the satellite fix).
        _hb("preflight: simulated wedge (hanging)")
        with tel.span("preflight.hang"):
            time.sleep(100000.0)
    if wedge:
        # Test knob, fast shape: the wedge raises immediately so the
        # failed-pre-flight exit is exercisable cheaply in CI.
        raise RuntimeError("fake TPU wedge (DSLABS_BENCH_FAKE_WEDGE)")
    _hb("preflight: boot (import + compile cache)")
    with tel.span("preflight.boot"):
        _persistent_cache()
    from dslabs_tpu.tpu.supervisor import probe_device

    _hb("preflight: probe matmul")
    with tel.span("preflight.matmul"):
        res = probe_device(deadline_secs=float(os.environ.get(
            "DSLABS_PREFLIGHT_DEADLINE_SECS", "60.0")))
    res["telemetry"] = tel.summary()
    return res


def _calibrate(max_depth: int = 7) -> dict:
    """Measure per-state valid-event occupancy on a shallow full-grid
    strict prefix; budgets = measured max + headroom (growth continues
    past the calibration depth — the spill covers the tail for strict,
    and beam counts the drops as before)."""
    import jax
    import jax.numpy as jnp

    _persistent_cache()

    from dslabs_tpu.tpu.engine import SENTINEL, timer_deliverable_mask
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    protocol = _bench_protocol()
    mesh = make_mesh(len(jax.devices()))
    search = ShardedTensorSearch(
        protocol, mesh, chunk_per_device=1024, frontier_cap=1 << 17,
        visited_cap=1 << 22, max_depth=1, strict=True)

    def stats(carry):
        cur, cur_n = carry["cur"], carry["cur_n"][0]
        states = search.unflatten_rows(cur)
        valid = jnp.arange(cur.shape[0]) < cur_n
        msgs = jnp.sum(states["net"][:, :, 0] != SENTINEL, axis=1)
        tmrs = jnp.sum(jax.vmap(jax.vmap(timer_deliverable_mask))(
            states["timers"]), axis=(1, 2))
        return (jnp.max(jnp.where(valid, msgs, 0)),
                jnp.max(jnp.where(valid, tmrs, 0)))

    jstats = jax.jit(stats)
    bm = bt = 1
    with mesh:
        carry = search._init_carry(search.initial_state())
        max_n, depth, t0 = 1, 0, time.time()
        while max_n > 0 and depth < max_depth:
            depth += 1
            carry, lvl = search._superstep_call(carry, 1 << 30)
            max_n = search._sync_checks(carry, depth, t0, lvl)[4]
            carry = search._finish_level(carry)
            m, t = (int(x) for x in jax.tree.map(jnp.asarray,
                                                 jstats(carry)))
            bm, bt = max(bm, m), max(bt, t)
    p = search.p
    # Headroom: message occupancy keeps growing past the calibration
    # depth (~1/level); timers are structurally bounded by the retry
    # re-arm pattern.  Budgets clamp to the full grid.
    return {"bm": min(bm + bm // 2 + 4, p.net_cap),
            "bt": min(bt + 2, p.n_nodes * p.timer_cap),
            "measured": [bm, bt], "depth": depth}


def _run_rung(chunk_per_device: int, frontier_cap: int, visited_cap: int,
              max_secs: float, ev_budget) -> dict:
    import jax

    _persistent_cache()

    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    tel = _phase_telemetry("rung")
    mesh = make_mesh(len(jax.devices()))
    # Warm-up depth 2, not 1: the final depth-limited level skips the
    # frontier promotion (count-only), so a depth-1 run would leave
    # _finish_level uncompiled and charge its compile to the window.
    # aot_warmup compiles the superstep/promote/init programs at
    # construction (.lower().compile(), persistent-cache backed) —
    # compile cost is measured on its own, never inside the window.
    t_c = time.time()
    search = ShardedTensorSearch(
        _bench_protocol(), mesh, chunk_per_device=chunk_per_device,
        frontier_cap=frontier_cap, visited_cap=visited_cap, max_depth=2,
        strict=False, ev_budget=ev_budget, aot_warmup=True,
        telemetry=tel)
    search.run()  # warm-up: residual compiles + runtime plumbing
    compile_secs = time.time() - t_c
    search.max_depth = 64
    search.max_secs = max_secs
    outcome = search.run()
    elapsed = max(outcome.elapsed_secs, 1e-9)
    return {
        "value": outcome.unique_states / elapsed * 60.0,
        "unique": outcome.unique_states,
        "explored": outcome.states_explored,
        "depth": outcome.depth,
        "end": outcome.end_condition,
        "dropped": outcome.dropped,
        # Beam drops under their roadmap name (ISSUE 6 satellite: the
        # BENCH_r03 5.8M-drop shape is a first-class JSON field, and
        # the engine warns loudly past DSLABS_DROPPED_WARN).
        "dropped_states": outcome.dropped_states,
        "elapsed": elapsed,
        "compile_secs": round(compile_secs, 1),
        "aot_compile_secs": outcome.compile_secs,
        "levels": outcome.levels,
        "retries": outcome.retries,
        "failovers": outcome.failovers,
        "resumed_from_depth": outcome.resumed_from_depth,
        "mesh_shrinks": outcome.mesh_shrinks,
        "knob_retries": outcome.knob_retries,
        "telemetry": tel.summary(),
    }


def _run_strict(ev_budget, budget_secs: float) -> dict:
    """The drop-free HEADLINE number: a strict (exact, nothing
    truncated) BFS of the bench protocol to depth 10 — every valid event
    of every reachable state expanded, dropped=0 enforced fatally.

    ``budget_secs`` bounds the whole phase CHILD-SIDE: whatever the
    warm-up compile leaves is handed to search.max_secs, so a slow run
    lands a partial rate (TIME_EXHAUSTED) instead of dying to the
    parent's kill with nothing on stdout.

    Config notes: chunk 8192 (on one device the routing bucket holds the
    whole batch, so strict skips the in-chunk prefilter too); the
    calibrated ev_budget WINDOW-SPILLS (a state with more valid events
    re-steps its chunk at the next window — never a coverage cut); the
    final level counts fresh states without building the ~4x-over-cap
    depth-10 frontier.  DSLABS_BENCH_CKPT=1 additionally runs async
    incremental checkpoints every 2 levels (overhead-demonstration)."""
    import jax

    _persistent_cache()

    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    from dslabs_tpu.tpu.supervisor import RetryPolicy, SearchSupervisor

    t_phase = time.time()
    tel = _phase_telemetry("strict")
    mesh = make_mesh(len(jax.devices()))
    ckpt = {}
    if os.environ.get("DSLABS_BENCH_CKPT"):
        ckpt = {"checkpoint_path": "/tmp/bench_strict.ckpt",
                "checkpoint_every": 2}
    # The measured run goes through the search SUPERVISOR
    # (tpu/supervisor.py): transient dispatch errors retry with backoff
    # instead of killing the phase, and the outcome's retries /
    # failovers / resumed_from_depth counters land in the BENCH json so
    # the perf trajectory shows robustness overhead.  Ladder = sharded
    # only — a failover to the single-device engine would change what
    # the headline number measures.
    sup = SearchSupervisor(
        _bench_protocol(), ladder=("sharded",), mesh=mesh, chunk=8192,
        frontier_cap=(1 << 20) + (1 << 18), visited_cap=1 << 24,
        max_depth=2, strict=True, ev_budget=ev_budget,
        policy=RetryPolicy(max_retries=3), aot_warmup=True,
        telemetry=tel, **ckpt)
    t_c = time.time()
    sup.run()  # warm-up: AOT at engine build + residual compiles
    compile_secs = time.time() - t_c
    sup.max_depth = 10
    sup.max_secs = max(45.0, budget_secs - (time.time() - t_phase))
    t0 = time.time()
    outcome = sup.run()
    return {
        "value": outcome.unique_states / max(outcome.elapsed_secs, 1e-9)
        * 60.0,
        "unique": outcome.unique_states,
        "explored": outcome.states_explored,
        "depth": outcome.depth,
        "end": outcome.end_condition,
        "dropped": outcome.dropped,
        "dropped_states": outcome.dropped_states,
        "elapsed": time.time() - t0,
        "compile_secs": round(compile_secs, 1),
        "aot_compile_secs": outcome.compile_secs,
        "levels": outcome.levels,
        "retries": outcome.retries,
        "failovers": outcome.failovers,
        "resumed_from_depth": outcome.resumed_from_depth,
        "abandoned_threads": outcome.abandoned_threads,
        # Elastic-mesh resilience counters (ISSUE 9): how much mesh /
        # knob degradation this number absorbed — `telemetry compare`
        # flags a run that suddenly needs them (resilience regression).
        "mesh_shrinks": outcome.mesh_shrinks,
        "knob_retries": outcome.knob_retries,
        "mesh_width": outcome.mesh_width,
        "telemetry": tel.summary(),
    }


def _run_mesh(budget_secs: float) -> dict:
    """The 8-device mesh headline phase (ISSUE 12): a strict BFS whose
    frontier, visited table, and expansion run owner-sharded over a
    width-``DSLABS_MESH_WIDTH`` (default 8) mesh with the fused
    in-superstep row exchange — the configuration ROADMAP #1 promotes
    to the headline.  On a box with >= width real accelerators the
    full paxos bench protocol runs on them; otherwise the phase runs
    on the CPU VIRTUAL mesh (the child CPU-pinned by its parent, the
    result tagged ``virtual_cpu_mesh`` and never a headline beside a
    real accelerator) with the lab1 client-server workload.

    The JSON carries what the acceptance criteria read: ``mesh_width``,
    aggregate ``skew`` (finite — derived from the per-level per-device
    lanes, which ride on ``levels``), and the recovery counters
    (``mesh_shrinks``/``knob_retries`` must be 0 for the number to be
    trusted as a full-width rate)."""
    import dataclasses

    width = int(os.environ.get("DSLABS_MESH_WIDTH", "8") or "8")
    _persistent_cache()
    import jax

    from dslabs_tpu.tpu.sharded import make_mesh
    from dslabs_tpu.tpu.supervisor import RetryPolicy, SearchSupervisor

    t_phase = time.time()
    tel = _phase_telemetry("mesh")
    mesh = make_mesh(width)
    platform = mesh.devices.flat[0].platform
    virtual = platform == "cpu"
    if virtual:
        # The GENERATED lab1 spec (identical state space to the hand
        # twin — 150 unique / 831 explored at depth 6) so the packed
        # wire engages: the hand protocol derives the identity codec
        # and would bench raw lanes (ISSUE 18a).
        from dslabs_tpu.tpu.specs import clientserver_spec

        proto = dataclasses.replace(
            clientserver_spec(3, 4).compile(), goals={})
        config = f"lab1-clientserver c3-w4 strict mesh x{width}"
        kw = dict(chunk=256, frontier_cap=1 << 13,
                  visited_cap=1 << 17)
        depth = int(os.environ.get("DSLABS_MESH_DEPTH", "12"))
    else:
        proto = _bench_protocol()
        config = f"lab3-paxos strict mesh x{width}"
        kw = dict(chunk=4096, frontier_cap=1 << 18,
                  visited_cap=1 << 22, ev_budget=FALLBACK_EV_BUDGET)
        depth = int(os.environ.get("DSLABS_MESH_DEPTH", "10"))
    sup = SearchSupervisor(
        proto, ladder=("sharded",), mesh=mesh, max_depth=2,
        strict=True, policy=RetryPolicy(max_retries=3),
        aot_warmup=True, telemetry=tel, **kw)
    t_c = time.time()
    sup.run()   # warm-up: AOT + residual compiles, outside the window
    compile_secs = time.time() - t_c
    sup.max_depth = depth
    # 90 s of measured search is plenty for a stable rate; the floor
    # keeps a compile-heavy cold run landing a partial number.
    sup.max_secs = max(20.0, min(
        budget_secs - (time.time() - t_phase), 90.0))
    t0 = time.time()
    outcome = sup.run()
    elapsed = max(time.time() - t0, 1e-9)
    levels = outcome.levels or []
    imb = [lv["skew"]["explored"]["imbalance"] for lv in levels
           if lv.get("skew")]
    cv = [lv["skew"]["explored"]["cv"] for lv in levels
          if lv.get("skew")]
    skew = {
        "imbalance_max": round(max(imb), 4) if imb else 1.0,
        "imbalance_mean": round(sum(imb) / len(imb), 4) if imb else 1.0,
        "cv_max": round(max(cv), 4) if cv else 0.0,
        "levels_measured": len(imb),
    }
    # Estimated ICI wire bytes per exchanged state (ISSUE 18a): the
    # packed row width the all_to_all actually ships (the engine stamps
    # it on the outcome) vs the raw-lane width, plus the 16-byte
    # fingerprint key that rides beside every row either way.  The
    # ledger guards wire_bytes_per_state (telemetry compare, rc 1 on a
    # rise: the codec fell back to identity).
    wire = {
        "wire_bytes_per_state": int(outcome.bytes_per_state or 0),
        "wire_bytes_per_state_raw": int(
            outcome.bytes_per_state_unpacked or 0),
        "key_bytes_per_state": 16,
        "pack_ratio": float(outcome.pack_ratio or 1.0),
    }
    return {
        "value": outcome.unique_states / elapsed * 60.0,
        "unique": outcome.unique_states,
        "explored": outcome.states_explored,
        "depth": outcome.depth,
        "end": outcome.end_condition,
        "dropped": outcome.dropped,
        "dropped_states": outcome.dropped_states,
        "elapsed": round(elapsed, 2),
        "compile_secs": round(compile_secs, 1),
        "aot_compile_secs": outcome.compile_secs,
        "config": config,
        "platform": platform,
        "mesh_width": width,
        "virtual_cpu_mesh": virtual,
        "skew": skew,
        # Top-level copies the ledger guards read (telemetry
        # compare_ledger: mesh:wire_bytes_per_state rises or
        # mesh:imbalance_max rises past threshold -> rc 1).
        "imbalance_max": skew["imbalance_max"],
        "wire": wire,
        "levels": levels,
        "retries": outcome.retries,
        "failovers": outcome.failovers,
        "resumed_from_depth": outcome.resumed_from_depth,
        "mesh_shrinks": outcome.mesh_shrinks,
        "knob_retries": outcome.knob_retries,
        "telemetry": tel.summary(),
    }


def _run_swarm(budget_secs: float) -> dict:
    """Swarm-explorer throughput phase (ISSUE 5, tpu/swarm.py): a
    diversified random-walk fleet over the full mesh on the bench
    protocol, reporting walkers/sec, unique-states/min, and the
    deepest depth reached — the deep-probe half of the portfolio the
    strict/beam BFS phases cannot measure.  Same always-reports
    guarantees as every phase: child-side time bound, heartbeats on
    stderr, one JSON line on stdout."""
    import jax

    _persistent_cache()

    from dslabs_tpu.tpu.sharded import make_mesh
    from dslabs_tpu.tpu.swarm import SwarmSearch

    t_phase = time.time()
    tel = _phase_telemetry("swarm")
    mesh = make_mesh(len(jax.devices()))
    sw = SwarmSearch(
        _bench_protocol(), mesh=mesh,
        walkers_per_device=int(os.environ.get("DSLABS_SWARM_WALKERS",
                                              "256")),
        max_steps=int(os.environ.get("DSLABS_SWARM_STEPS", "128")),
        steps_per_round=64, seed=0, visited_cap=1 << 22)
    _hb("swarm: fleet built, compiling round program")
    tel.attach(sw)
    sw.max_secs = max(20.0, budget_secs - (time.time() - t_phase) - 10)
    outcome = sw.run()
    sd = outcome.swarm or {}
    return {
        "value": sd.get("unique_per_min", 0.0),
        "walkers_per_sec": sd.get("walkers_per_sec", 0.0),
        "unique_per_min": sd.get("unique_per_min", 0.0),
        "deepest": sd.get("deepest", outcome.depth),
        "unique": outcome.unique_states,
        "explored": outcome.states_explored,
        "end": outcome.end_condition,
        "rounds": sd.get("rounds", 0),
        "restarts": outcome.walker_restarts,
        "overflow_restarts": outcome.swarm_overflow,
        "vis_over": outcome.visited_overflow,
        "elapsed": round(outcome.elapsed_secs, 2),
        "compile_secs": outcome.compile_secs,
        "telemetry": tel.summary(),
    }


def _run_spill(budget_secs: float) -> dict:
    """Capacity-ladder phase (ISSUE 6, tpu/spill.py): a strict lab1
    BFS measured twice on the identical protocol/depth — uncapped,
    then with the device visited table capped at ~1/8 of the measured
    unique-state count and the host-RAM spill tier enabled — so the
    round records what graceful degradation under HBM exhaustion
    costs: states/min both ways, exact unique/explored parity flag,
    spill counters, and ``dropped_states`` (must be 0 — the whole
    point).  Same always-reports guarantees as every phase: child-side
    time bound, heartbeats on stderr, one JSON line on stdout."""
    import dataclasses
    import math

    _persistent_cache()

    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.protocols.clientserver import \
        make_clientserver_protocol

    t_phase = time.time()
    tel = _phase_telemetry("spill")
    proto = dataclasses.replace(
        make_clientserver_protocol(n_clients=3, w=4), goals={})
    depth = int(os.environ.get("DSLABS_SPILL_DEPTH", "11"))

    def run_one(visited_cap, spill, chunk):
        search = TensorSearch(proto, chunk=chunk, frontier_cap=1 << 15,
                              max_depth=2, visited_cap=visited_cap,
                              spill=spill, telemetry=tel)
        t_c = time.time()
        search.run()          # warm-up: compile outside the window
        compile_secs = time.time() - t_c
        search.max_depth = depth
        search.max_secs = max(
            20.0, (budget_secs - (time.time() - t_phase)) / 2)
        t0 = time.time()
        out = search.run()
        return out, max(time.time() - t0, 1e-9), compile_secs

    _hb("spill: uncapped reference run")
    un, dt_u, cs_u = run_one(1 << 20, False, 2048)
    cap = 1 << max(3, int(math.floor(
        math.log2(max(un.unique_states // 8, 8)))))
    _hb(f"spill: capped run (visited_cap {cap} ~ "
        f"{cap / max(un.unique_states, 1):.2f} of "
        f"{un.unique_states} states)")
    sp, dt_s, cs_s = run_one(cap, True, 16)
    parity = (un.end_condition == sp.end_condition
              and un.unique_states == sp.unique_states
              and un.states_explored == sp.states_explored)
    return {
        "value": sp.unique_states / dt_s * 60.0,
        "uncapped_per_min": round(un.unique_states / dt_u * 60.0, 1),
        "visited_cap": cap,
        "capped_fraction": round(cap / max(un.unique_states, 1), 4),
        "end": sp.end_condition, "depth": sp.depth,
        "unique": sp.unique_states, "explored": sp.states_explored,
        "exact_parity": parity,
        "spilled_keys": sp.spilled_keys,
        "host_tier_hits": sp.host_tier_hits,
        "respilled_frontier": sp.respilled_frontier,
        "dropped_states": sp.dropped_states,
        "compile_secs": round(cs_u + cs_s, 1),
        "total_secs": round(time.time() - t_phase, 1),
        "telemetry": tel.summary(),
    }


def _run_capacity2(budget_secs: float) -> dict:
    """Capacity round 2 phase (ISSUE 15, tpu/packing.py /
    tpu/symmetry.py / tpu/spill.py async gear): on the GENERATED lab1
    spec (domain-declared, so the packed frontier encoding engages) —
    bytes_per_state packed vs unpacked, exact-parity flag, and
    packed-path states/min; a packed 1/8-table spill run for the async
    drain's overlap ratio (host drain wall hidden behind device
    compute); and the symmetry quotient on the generated paxos spec
    (canonical vs raw unique counts, verdict parity).  The ledger's
    ``capacity:bytes_per_state`` guard compares this phase across
    rounds (a rise past threshold = rc 1).  Same always-reports
    guarantees as every phase."""
    import dataclasses
    import math

    _persistent_cache()

    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.specs import clientserver_spec, paxos_spec

    t_phase = time.time()
    tel = _phase_telemetry("capacity2")
    cs = clientserver_spec(3, 4).compile()
    proto = dataclasses.replace(
        cs, goals={}, prunes={"DONE": cs.goals["CLIENTS_DONE"]})
    depth = int(os.environ.get("DSLABS_CAPACITY2_DEPTH", "9"))

    def run_one(packed, spill=False, visited_cap=1 << 20, chunk=2048):
        # NOTE: engine reuse (warm-up then measure) is safe in spill
        # mode since SpillManager.reset_run — the tier no longer leaks
        # across runs.
        search = TensorSearch(proto, chunk=chunk, frontier_cap=1 << 15,
                              max_depth=2, visited_cap=visited_cap,
                              packed=packed, spill=spill,
                              telemetry=tel)
        t_c = time.time()
        search.run()          # warm-up: compile outside the window
        compile_secs = time.time() - t_c
        search.max_depth = depth
        search.max_secs = max(
            15.0, (budget_secs - (time.time() - t_phase)) / 3)
        t0 = time.time()
        out = search.run()
        return out, max(time.time() - t0, 1e-9), compile_secs

    _hb("capacity2: unpacked reference run")
    un, dt_u, cs_u = run_one(False)
    _hb("capacity2: packed run")
    pkd, dt_p, cs_p = run_one(True)
    parity = (un.end_condition == pkd.end_condition
              and un.unique_states == pkd.unique_states
              and un.states_explored == pkd.states_explored)
    # Floor 256: one chunk's unique successors must fit an EMPTY
    # table (the spill contract's hard minimum) — tiny smoke depths
    # would otherwise derive a cap below chunk * mean-events.
    cap = 1 << max(8, int(math.floor(
        math.log2(max(pkd.unique_states // 8, 8)))))
    _hb(f"capacity2: packed async-spill run (visited_cap {cap})")
    sp, _dt_s, cs_s = run_one(True, spill=True, visited_cap=cap,
                              chunk=16)
    drain_ms = sp.spill_drain_ms
    overlap_ratio = (round(max(0, drain_ms - sp.spill_wait_ms)
                           / drain_ms, 4) if drain_ms > 0 else 0.0)
    # Symmetry quotient: canonical vs raw unique counts on the
    # generated single-decree paxos spec (reduction is opt-in — this
    # is the measured win, not a default behavior change).
    px = paxos_spec(3).compile()
    pxp = dataclasses.replace(px, goals={},
                              prunes={"D": px.goals["DECIDED"]})
    _hb("capacity2: symmetry quotient (paxos raw vs canonical)")
    raw = TensorSearch(pxp, chunk=256, visited_cap=1 << 14,
                       telemetry=tel).run()
    sym = TensorSearch(pxp, chunk=256, visited_cap=1 << 14,
                       symmetry=True, telemetry=tel).run()
    return {
        "value": round(pkd.unique_states / dt_p * 60.0, 1),
        "unpacked_per_min": round(un.unique_states / dt_u * 60.0, 1),
        "bytes_per_state": pkd.bytes_per_state,
        "bytes_per_state_unpacked": un.bytes_per_state,
        "pack_ratio": pkd.pack_ratio,
        "exact_parity": parity,
        "end": pkd.end_condition, "depth": pkd.depth,
        "unique": pkd.unique_states, "explored": pkd.states_explored,
        "spill_visited_cap": cap,
        "spill_exact_parity": (sp.unique_states == pkd.unique_states
                               and sp.states_explored
                               == pkd.states_explored),
        "spill_drain_ms": drain_ms,
        "spill_wait_ms": sp.spill_wait_ms,
        "spill_overlap_ratio": overlap_ratio,
        "dropped_states": sp.dropped_states,
        "symmetry": {
            "raw_unique": raw.unique_states,
            "canonical_unique": sym.unique_states,
            "quotient": round(raw.unique_states
                              / max(sym.unique_states, 1), 3),
            "verdict_parity": raw.end_condition == sym.end_condition,
            "perms": sym.symmetry_perms},
        "compile_secs": round(cs_u + cs_p + cs_s, 1),
        "total_secs": round(time.time() - t_phase, 1),
        "telemetry": tel.summary(),
    }


def _run_service(budget_secs: float) -> dict:
    """Checking-as-a-service phase (ISSUE 11, dslabs_tpu/service/): a
    multi-tenant drain — three tenants submit small exhaustive
    pingpong jobs through the admission gate into the bounded journal
    queue, the DRR scheduler runs each as its own warden fault domain
    — reporting PER-TENANT throughput and the fairness index
    (max/mean verdicts-per-tenant-budget; `telemetry compare` flags a
    rise past the threshold as a regression).  Same always-reports
    guarantees as every phase: child-side time bound, heartbeats on
    stderr, one JSON line on stdout."""
    import tempfile

    _persistent_cache()

    from dslabs_tpu.service import CheckServer

    t_phase = time.time()
    root = tempfile.mkdtemp(prefix="service-", dir=_rundir())
    tenants = ("alice", "bob", "carol")
    jobs_per = max(1, int(os.environ.get("DSLABS_SERVICE_BENCH_JOBS",
                                         "2") or "2"))
    srv = CheckServer(
        root, workers=2, queue_cap=max(8, 3 * jobs_per + 1),
        elastic=False)
    rejected = 0
    for j in range(jobs_per):
        for t in tenants:
            res = srv.submit(
                factory="dslabs_tpu.tpu.protocols.pingpong:"
                        "make_exhaustive_pingpong",
                factory_kwargs={"workload_size": 2}, tenant=t,
                chunk=64, frontier_cap=1 << 8, visited_cap=1 << 12,
                max_secs=30.0)
            if not res.get("accepted"):
                rejected += 1
    _hb(f"service: {3 * jobs_per} jobs submitted "
        f"({rejected} rejected), draining")
    summary = srv.drain(
        max_secs=max(20.0, budget_secs - (time.time() - t_phase) - 10))
    srv.close()
    return {
        "value": summary["verdicts_per_min"],
        "jobs": summary["jobs"],
        "completed": summary["completed"],
        "failed": summary["failed"],
        "rejected": rejected,
        "fairness_index": summary["fairness_index"],
        # The per-tenant cost ledger (ISSUE 13, tpu/tracing.py):
        # device-seconds / dispatches / compile split per tenant, plus
        # the aggregate cost-per-unique-state the ledger compare
        # tracks for regressions (telemetry.compare_ledger).
        "cost_per_unique": summary.get("cost_per_unique"),
        "device_secs": summary.get("device_secs"),
        "costs": summary.get("costs"),
        "per_tenant": {
            t: {"verdicts": s["verdicts"],
                "verdicts_per_min": s["verdicts_per_min"],
                "budget_spent": s["budget_spent"]}
            for t, s in summary["per_tenant"].items()},
        "queue": summary["queue"],
        "total_secs": round(time.time() - t_phase, 1),
    }


def _run_lanes(budget_secs: float) -> dict:
    """Batched job lanes phase (ISSUE 14, tpu/lanes.py): FOUR tenants
    each submit one identical small exhaustive job, drained twice —
    solo (lanes off, the 4-solo baseline) and as one 4-lane batch —
    and the phase reports aggregate states/min plus
    **dispatches-per-job** for both, the amortisation headline the
    ledger's ``service:dispatches_per_job`` / ``lanes:occupancy``
    compare guards track (regression => rc 1).  Verdicts are asserted
    bit-identical between the two drains (lane parity is a bench
    invariant, not just a test).  Same always-reports guarantees as
    every phase."""
    import tempfile

    _persistent_cache()

    from dslabs_tpu.service import CheckServer

    t_phase = time.time()
    tenants = ("alice", "bob", "carol", "dave")

    def _drain(lanes: int) -> dict:
        root = tempfile.mkdtemp(prefix=f"lanes{lanes}-",
                                dir=_rundir())
        srv = CheckServer(
            root, workers=1, queue_cap=len(tenants) + 4,
            elastic=False, admission=False, lanes=lanes)
        for t in tenants:
            srv.submit(
                factory="dslabs_tpu.tpu.protocols.pingpong:"
                        "make_exhaustive_pingpong",
                factory_kwargs={"workload_size": 2}, tenant=t,
                chunk=64, frontier_cap=1 << 8, visited_cap=1 << 12,
                max_secs=30.0)
        left = budget_secs - (time.time() - t_phase) - 10
        summary = srv.drain(max_secs=max(20.0, left / 2))
        srv.close()
        return summary

    _hb("lanes: 4-solo baseline drain")
    solo = _drain(0)
    _hb(f"lanes: solo dpj={solo.get('dispatches_per_job')}; "
        "4-lane batched drain")
    lane = _drain(4)
    wall = max(lane.get("wall_secs", 0.0), 1e-9)
    explored = sum(int(r.get("explored", 0) or 0)
                   for r in lane.get("results", ()))
    key = ("tenant", "end", "unique", "explored", "depth")
    sv = sorted(tuple(r.get(k) for k in key)
                for r in solo.get("results", ()))
    lv = sorted(tuple(r.get(k) for k in key)
                for r in lane.get("results", ()))
    dpj = lane.get("dispatches_per_job")
    solo_dpj = solo.get("dispatches_per_job")
    return {
        # aggregate throughput of the batched drain — the phase value
        # the ledger tracks alongside the amortisation guards.
        "value": round(explored / wall * 60.0, 1),
        "jobs": lane.get("jobs"),
        "completed": lane.get("completed"),
        "failed": lane.get("failed"),
        "lanes": 4,
        "dispatches_per_job": dpj,
        "solo_dispatches_per_job": solo_dpj,
        "dpj_ratio": (round(dpj / solo_dpj, 3)
                      if dpj and solo_dpj else None),
        "occupancy": (lane.get("lanes") or {}).get("mean_occupancy"),
        "swaps": (lane.get("lanes") or {}).get("swaps"),
        "evicted": (lane.get("lanes") or {}).get("evicted"),
        "verdict_parity": sv == lv,
        "fairness_index": lane.get("fairness_index"),
        "cost_per_unique": lane.get("cost_per_unique"),
        "total_secs": round(time.time() - t_phase, 1),
    }


_MEMO_CHAIN_SRC = """\
from dslabs_tpu.tpu.compiler import (Field, MessageType, NodeKind,
                                     ProtocolSpec, TimerType)


def make_chain():
    spec = ProtocolSpec(
        "memo-bench-chain",
        nodes=[NodeKind("proc", 1, (Field("x", init=0, hi=4),))],
        messages=[MessageType("S1", ()), MessageType("S2", ()),
                  MessageType("S3", ())],
        timers=[TimerType("TICK", (), 10, 10)],
        net_cap=4, timer_cap=1)

    @spec.on("proc", "S1")
    def h1(ctx, m):
        ctx.put("x", 1)
        ctx.send("S2", 0)

    @spec.on("proc", "S2")
    def h2(ctx, m):
        ctx.put("x", 2)
        ctx.send("S3", 0)

    @spec.on("proc", "S3")
    def h3(ctx, m):
        ctx.put("x", %(final)d)

    spec.initial_messages.append(("S1", 0, 0, {}))

    def no_four(v):
        return v.get("proc", 0, "x") != 4

    spec.invariants["NO_FOUR"] = no_four
    return spec.compile()
"""


def _run_memo(budget_secs: float) -> dict:
    """Cross-job memoization phase (ISSUE 16, service/memo.py): one
    pingpong job is checked COLD, resubmitted identically (verdict-
    cache hit), resubmitted after only the depth budget grew (warm
    start from the archived tier), and a one-handler spec edit is
    re-checked incrementally — reporting device-seconds per reuse
    state, the hit_rate the ledger's ``memo:hit_rate`` guard tracks
    (drop past the threshold => rc 1), levels_skipped, and
    device_secs_saved.  Same always-reports guarantees as every
    phase."""
    import tempfile

    _persistent_cache()

    from dslabs_tpu.service import CheckServer

    t_phase = time.time()
    specs_dir = tempfile.mkdtemp(prefix="memo-specs-", dir=_rundir())

    def _cost(root, tenant):
        path = os.path.join(root, "COSTS.jsonl")
        secs = 0.0
        try:
            with open(path) as f:
                for line in f:
                    rec = json.loads(line)
                    if rec.get("tenant") == tenant:
                        secs += float(rec.get("device_secs", 0.0)
                                      or 0.0)
        except OSError:
            pass
        return round(secs, 4)

    pp = dict(factory="dslabs_tpu.tpu.protocols.pingpong:"
                      "make_exhaustive_pingpong",
              factory_kwargs={"workload_size": 2}, chunk=64,
              frontier_cap=1 << 8, visited_cap=1 << 12)
    root = tempfile.mkdtemp(prefix="memo-", dir=_rundir())
    srv = CheckServer(root, workers=1, elastic=False,
                      extra_sys_path=[specs_dir])
    # Stage 1+2: cold, then the exact-key hit.
    srv.submit(tenant="cold", **pp)
    srv.drain(max_secs=max(20.0, budget_secs / 4))
    _hb("memo: cold verdict landed, resubmitting identical job")
    srv.submit(tenant="hit", **pp)
    # Stage 3: only the budget changed — warm start from the tier.
    with open(os.path.join(specs_dir, "memo_bench_chain.py"),
              "w") as f:
        f.write(_MEMO_CHAIN_SRC % {"final": 3})
    chain = dict(factory="memo_bench_chain:make_chain", chunk=64,
                 frontier_cap=1 << 8, visited_cap=1 << 12)
    srv.submit(tenant="chain_cold", max_depth=2, **chain)
    srv.drain(max_secs=max(20.0, budget_secs / 4))
    _hb("memo: chain depth-2 archived, growing budget (warm start)")
    srv.submit(tenant="warm", **chain)
    srv.drain(max_secs=max(20.0, budget_secs / 4))
    # Stage 4: the one-handler edit — incremental re-check.
    with open(os.path.join(specs_dir, "memo_bench_chain.py"),
              "w") as f:
        f.write(_MEMO_CHAIN_SRC % {"final": 4})
    _hb("memo: one-handler edit, incremental re-check")
    srv.submit(tenant="incr", **chain)
    summary = srv.drain(
        max_secs=max(20.0, budget_secs - (time.time() - t_phase) - 5))
    srv.close()
    memo = summary.get("memo", {})
    done = [r for r in srv.results if r.get("status") == "done"]
    wall = max(time.time() - t_phase, 1e-9)
    return {
        # verdicts/min across all reuse states — the phase value the
        # ledger tracks beside the hit_rate guard.
        "value": round(len(done) / wall * 60.0, 1),
        "jobs": summary.get("jobs"),
        "completed": summary.get("completed"),
        "failed": summary.get("failed"),
        "hit_rate": memo.get("hit_rate"),
        "hits": memo.get("hits"),
        "warm_starts": memo.get("warm_starts"),
        "incremental": memo.get("incremental"),
        "levels_skipped": memo.get("levels_skipped"),
        "device_secs_saved": memo.get("device_secs_saved"),
        "device_secs": {
            "cold": _cost(root, "cold"),
            "hit": _cost(root, "hit"),
            "warm": _cost(root, "warm"),
            "incremental": _cost(root, "incr")},
        "total_secs": round(time.time() - t_phase, 1),
    }


def _run_scenarios(budget_secs: float) -> dict:
    """Fault-scenario phase (ISSUE 19, tpu/faults.py): on the generated
    single-decree paxos spec — states/min with the partition fault
    lanes ON (paxos_partition_spec: cut/heal as model events) vs the
    plain fault-free spec OFF, the fault-event share of the explored
    space, and the ``verdict_parity`` flag the ledger's
    ``scenarios:verdict_parity`` guard pins: a ZERO-BUDGET FaultModel
    (constant controller lanes, no valid fault events) must land the
    exact fault-free verdict/explored/unique — the overhead-guard
    invariant every scenario rides on.  Same always-reports guarantees
    as every phase."""
    import dataclasses

    _persistent_cache()

    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.faults import FaultModel, Partition
    from dslabs_tpu.tpu.specs import paxos_partition_spec, paxos_spec

    t_phase = time.time()
    tel = _phase_telemetry("scenarios")

    def _pruned(p):
        return dataclasses.replace(
            p, goals={}, prunes=dict(p.goals),
            invariants=dict(p.invariants))

    def run_one(proto):
        search = TensorSearch(proto, chunk=256, frontier_cap=1 << 14,
                              visited_cap=1 << 17, telemetry=tel)
        search.run()          # warm-up: compile outside the window
        t0 = time.time()
        out = search.run()
        return out, max(time.time() - t0, 1e-9)

    _hb("scenarios: fault-free baseline (plain paxos)")
    base, dt_b = run_one(_pruned(paxos_spec(3).compile()))
    _hb("scenarios: zero-budget FaultModel (overhead guard)")
    fm0 = FaultModel(partition=Partition(
        blocks=(("proposer",), ("acceptor",)), max_eras=0))
    zb, _dt_z = run_one(_pruned(paxos_spec(3, fault=fm0).compile()))
    parity = (zb.end_condition == base.end_condition
              and zb.states_explored == base.states_explored
              and zb.unique_states == base.unique_states)
    _hb("scenarios: partition cut/heal scenario (fault lanes on)")
    sc, dt_s = run_one(_pruned(paxos_partition_spec(3).compile()))
    share = (round(sc.fault_events / sc.states_explored, 4)
             if sc.states_explored else 0.0)
    return {
        "value": round(sc.states_explored / dt_s * 60.0, 1),
        "rate_off": round(base.states_explored / dt_b * 60.0, 1),
        "verdict_parity": int(parity),
        "fault_event_share": share,
        "end": sc.end_condition, "depth": sc.depth,
        "unique": sc.unique_states, "explored": sc.states_explored,
        "fault_events": sc.fault_events,
        "partition_events": sc.partition_events,
        "base": {"end": base.end_condition,
                 "unique": base.unique_states,
                 "explored": base.states_explored},
        "total_secs": round(time.time() - t_phase, 1),
        "telemetry": tel.summary(),
    }


def _run_labs(budget_secs: float) -> dict:
    """Generated-labs packing phase (ISSUE 20, tpu/specs_lab3.py +
    tpu/specs_lab4.py): the shipped lab3/lab4 protocols are COMPILED
    from ProtocolSpec now, so their Field/Slots domain declarations
    reach the bit-packer (tpu/packing.py) — the hand twins declared
    nothing and derived identity.  Reports packed bytes-per-state for
    each generated lab spec plus the summed ``bytes_per_state`` the
    ledger's ``labs:bytes_per_state`` guard pins (a rise = domains
    stopped reaching the packer), the minimum pack ratio across the
    set (acceptance floor: >= 2x), and states/min on a short search of
    the generated paxos spec as the phase value."""
    import dataclasses

    _persistent_cache()

    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.packing import derive_packing
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol
    from dslabs_tpu.tpu.specs_lab4 import (make_join_protocol,
                                           make_shardstore_multi_protocol,
                                           make_shardstore_protocol,
                                           make_shardstore_tx_protocol)

    t_phase = time.time()
    tel = _phase_telemetry("labs")
    specs = [
        ("lab3_paxos", make_paxos_protocol()),
        ("lab4_join", make_join_protocol(1)),
        ("lab4_shardstore", make_shardstore_protocol([1, 1])),
        ("lab4_tx", make_shardstore_tx_protocol(1)),
        ("lab4_multi", make_shardstore_multi_protocol()),
    ]
    per_lab, total_packed, total_raw, min_ratio = {}, 0, 0, None
    for label, proto in specs:
        _hb(f"labs: derive packing for {label} ({proto.name})")
        eng = TensorSearch(dataclasses.replace(proto, goals={}),
                           chunk=64)
        pk = eng._pk or derive_packing(eng.p, eng.lanes)
        per_lab[label] = {
            "bytes_per_state": pk.bytes_per_state,
            "bytes_per_state_unpacked": pk.bytes_per_state_unpacked,
            "pack_ratio": round(pk.pack_ratio, 2),
        }
        total_packed += pk.bytes_per_state
        total_raw += pk.bytes_per_state_unpacked
        r = pk.pack_ratio
        min_ratio = r if min_ratio is None else min(min_ratio, r)
    _hb("labs: states/min on the generated paxos spec")
    # Depth 6 keeps compile + two runs (warm-up, timed) inside the
    # phase cap on the CPU backend; the rate, not the space, is the
    # phase value.
    proto = dataclasses.replace(make_paxos_protocol(), goals={})
    search = TensorSearch(proto, chunk=256, frontier_cap=1 << 12,
                          visited_cap=1 << 16, max_depth=6,
                          telemetry=tel)
    search.run()              # warm-up: compile outside the window
    t0 = time.time()
    out = search.run()
    dt = max(time.time() - t0, 1e-9)
    return {
        "value": round(out.states_explored / dt * 60.0, 1),
        "bytes_per_state": total_packed,
        "bytes_per_state_unpacked": total_raw,
        "min_pack_ratio": round(min_ratio, 2),
        "labs": per_lab,
        "end": out.end_condition, "depth": out.depth,
        "unique": out.unique_states, "explored": out.states_explored,
        "total_secs": round(time.time() - t_phase, 1),
        "telemetry": tel.summary(),
    }


# ----------------------------------------------------------------- parent

_CURRENT_CHILD = None     # live phase Popen, killed by the signal handler


def _sub(args, child_budget: float, label: str,
         kill_slack: float = KILL_SLACK_SECS,
         silence=None):
    """Run a bench phase subprocess as a WARDEN PROBE (tpu/warden.py
    LineWatch): the child's stderr is TEE'd line by line to this
    process's stderr (live heartbeats in the driver tail) while the
    last lines are buffered so a failure's JSON error stays
    attributable, and a child whose heartbeats stop for ``silence``
    seconds — a wedged runtime — is SIGKILLed immediately instead of
    at the full budget.  stdout's last line is the phase JSON.
    Returns (parsed dict, None) or (None, error string)."""
    global _CURRENT_CHILD
    from dslabs_tpu.tpu.warden import LineWatch

    # The kill slack must never push past the GLOBAL deadline — a
    # driver that enforces DSLABS_BENCH_DEADLINE_SECS externally would
    # otherwise kill US first and lose the JSON line (the rc=124
    # shape).  With too little deadline left to even start+kill a
    # child, SKIP the phase outright (best-so-far JSON beats a race).
    if _remaining() < 20:
        err = f"{label} skipped: global deadline exhausted"
        _hb(f"phase {label}: SKIPPED (deadline)")
        return None, err
    timeout = min(child_budget + kill_slack, _remaining() - 5)
    _hb(f"phase {label}: start (budget {child_budget:.0f}s, "
        f"kill at {timeout:.0f}s"
        + (f", silence kill at {silence:.0f}s" if silence else "")
        + f", deadline in {_remaining():.0f}s)")
    t0 = time.time()

    def _tee(line):
        sys.stderr.write(line)
        sys.stderr.flush()

    try:
        flight = os.path.join(_rundir(), f"{label}.flight.jsonl")
        # Live-monitor hint (ISSUE 8 satellite): any terminal can tail
        # this phase — depth/rate/skew plus the in-flight dispatch —
        # while it runs, or post-mortem after a kill.
        _hb(f"phase {label}: watch with `python -m "
            f"dslabs_tpu.tpu.telemetry watch {_rundir()}`")
        env = dict(os.environ, DSLABS_BENCH_FLIGHT=flight)
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)] + args,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
        _CURRENT_CHILD = proc
        watch = LineWatch(proc, proc.stderr, on_line=_tee)
        status, rc = watch.wait(timeout, silence=silence)
        if status == "silence":
            err = (f"{label} wedged: no heartbeat for {silence:.0f}s "
                   f"(killed at +{time.time() - t0:.0f}s; last stderr: "
                   f"{' | '.join(watch.tail[-2:])})")
            _hb(f"phase {label}: WEDGED ({err})")
            _note_wedge(label, err, watch, flight)
            return None, err
        if status == "total":
            err = (f"{label} killed at {timeout:.0f}s "
                   "(accelerator hang or compile overrun; last stderr: "
                   f"{' | '.join(watch.tail[-2:])})")
            _hb(f"phase {label}: TIMEOUT ({err})")
            _note_wedge(label, err, watch, flight)
            return None, err
        # The child's stdout is one small JSON line printed at exit, so
        # reading it after wait() cannot deadlock on a full pipe.
        stdout = proc.stdout.read()
        if rc == 0 and stdout.strip():
            out = json.loads(stdout.strip().splitlines()[-1])
            _hb(f"phase {label}: ok in {time.time() - t0:.0f}s")
            return out, None
        err = f"{label} exited rc={rc}"
        if watch.tail:
            err += f" last-stderr={watch.tail[-1]}"
        _hb(f"phase {label}: FAILED ({err})")
        _note_wedge(label, err, watch, flight)
        return None, err
    except Exception:
        err = traceback.format_exc(limit=2).strip().splitlines()[-1][:300]
        _hb(f"phase {label}: ERROR ({err})")
        _note_wedge(label, err, None, None)
        return None, err
    finally:
        _CURRENT_CHILD = None


def _load_cal_cache():
    try:
        with open(CAL_CACHE) as f:
            data = json.load(f)
        if data.get("sig") == _PROTO_SIG:
            return data["cal"]
    except Exception:
        pass
    return None


def _store_cal_cache(cal) -> None:
    try:
        with open(CAL_CACHE, "w") as f:
            json.dump({"sig": _PROTO_SIG, "cal": cal}, f)
    except Exception:
        pass


_EMITTED = False
_EXIT_CODE = 1


def _ledger_path() -> str:
    return (os.environ.get("DSLABS_BENCH_LEDGER")
            or os.path.join(_rundir(), "BENCH_HISTORY.jsonl"))


def _append_ledger(result: dict) -> None:
    """Cross-run bench ledger (ISSUE 8): every run's last-line JSON —
    telemetry summaries included — appends to BENCH_HISTORY.jsonl, so
    the BENCH_r0N trajectory is a queryable artifact
    (`python -m dslabs_tpu.tpu.telemetry compare <ledger>` diffs the
    latest run against the best prior run per phase).  Never fatal —
    the ledger is an artifact, not a dependency."""
    try:
        from dslabs_tpu.tpu import telemetry as tel_mod

        path = _ledger_path()
        if tel_mod.append_ledger(
                path, dict(result, t="bench",
                           ts=round(time.time(), 1))) is not None:
            result["ledger"] = path
    except Exception:  # noqa: BLE001 — the JSON line must still print
        pass


def _emit(result: dict) -> None:
    """Print THE one JSON line (idempotent: the signal handler and the
    normal path can both reach here; only the first wins)."""
    global _EMITTED, _EXIT_CODE
    if _EMITTED:
        return
    _EMITTED = True
    # 0 only when a measured phase produced a number: a failed
    # pre-flight or an all-phases-failed run is a failed run.
    _EXIT_CODE = 0 if result.get("value", 0) > 0 else 1
    if _DIAGNOSTICS and "wedge_diagnostics" not in result:
        # Every dead phase's last heartbeat + flight-recorder spans
        # ride the error JSON (ISSUE-7 satellite; schema-pinned).
        result["wedge_diagnostics"] = _DIAGNOSTICS
    if _RUNDIR_STATE["substituted"]:
        # The run-dir fallback substitution is never silent: graders
        # reading the JSON learn where the flight logs actually are.
        result["run_dir_substituted"] = {
            "requested": _RUNDIR_REQUESTED,
            "actual": _RUNDIR_STATE["path"]}
    _append_ledger(result)
    print(json.dumps(result))
    sys.stdout.flush()


def _install_signal_emitters(result: dict) -> None:
    """Guarantee the last-line JSON even under an external kill: an
    external ``timeout``'s SIGTERM or a ^C prints the best-so-far
    result — tagged with the signal — kills the live phase child, and
    exits (0 only if a measured phase had already produced a number)."""

    def _on_signal(signum, frame):
        name = signal.Signals(signum).name
        result.setdefault(
            "error", f"killed by {name} (external timeout?) at "
                     f"+{time.time() - _T0:.0f}s")
        result["total_secs"] = round(time.time() - _T0, 1)
        child = _CURRENT_CHILD
        if child is not None:
            try:
                child.kill()
            except OSError:
                pass
        _emit(result)
        # os._exit: the handler may be interrupting arbitrary frames
        # (a child wait, a JSON dump) — unwind nothing, the line is
        # already out.
        os._exit(_EXIT_CODE)

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)


def _set_headline(result: dict, phase: dict, kind: str, platform: str,
                  n_dev, workload: str = "lab3-paxos") -> None:
    """Install a phase's rate as the bench's single headline number."""
    result["metric"] = (f"{workload} {kind} unique states/min "
                        f"(sharded tensor backend, {platform} x{n_dev})")
    result["value"] = round(phase["value"], 1)
    result["vs_baseline"] = round(
        phase["value"] / BASELINE_STATES_PER_MIN, 6)
    # Compile time rides SEPARATELY from the steady-state rate: with
    # the persistent compile cache warm, aot_compile_secs collapses to
    # near-zero and the headline is pure search throughput.
    for k in ("compile_secs", "aot_compile_secs"):
        if phase.get(k) is not None:
            result[k] = phase[k]
    # Robustness counters ride the headline (ISSUE 2/4): the perf
    # trajectory shows what recovery, if any, the number absorbed —
    # abandoned_threads included, so in-process watchdog degradation
    # (leaked wedged-dispatch threads) is visible in the JSON.
    for k in ("retries", "failovers", "resumed_from_depth",
              "abandoned_threads", "mesh_shrinks", "knob_retries"):
        result[k] = phase.get(k, 0)
    # Mesh-scope headline context (ISSUE 12): the width the number was
    # measured at (telemetry compare flags a silent narrow-mesh
    # fallback as a regression even at equal states/min), the
    # aggregate shard skew, and the virtual-mesh tag when the phase
    # ran on forced host-platform devices.
    for k in ("mesh_width", "skew", "virtual_cpu_mesh"):
        if phase.get(k) is not None:
            result[k] = phase[k]


def _mesh_phase(result: dict, force_cpu: bool,
                headline_ok=lambda phase: True) -> bool:
    """Run the 8-device mesh phase child (ISSUE 12) and install it;
    promotes the phase to the HEADLINE when its recovery timeline is
    clean (``mesh_shrinks == 0 && knob_retries == 0`` — a degraded run
    is recorded but never trusted as the full-width rate) and
    ``headline_ok`` agrees.  Returns True iff the headline was set."""
    if _remaining() < 60:
        result["mesh_error"] = "skipped: deadline nearly exhausted"
        return False
    budget = min(MESH_CAP_SECS, max(_remaining() - 40, 45))
    args = ["--mesh"] + (["cpu"] if force_cpu else []) + [str(budget)]
    mesh_res, mesh_err = _sub(args, budget, "mesh", kill_slack=30.0,
                              silence=PHASE_SILENCE_SECS)
    if mesh_res is None:
        result["mesh_error"] = mesh_err
        return False
    result["mesh"] = mesh_res
    _note_phase_telemetry(result, "mesh", mesh_res)
    clean = (mesh_res.get("mesh_shrinks", 0) == 0
             and mesh_res.get("knob_retries", 0) == 0
             and mesh_res.get("value", 0) > 0)
    if not (clean and headline_ok(mesh_res)):
        return False
    workload = ("lab1-clientserver c3-w4"
                if mesh_res.get("virtual_cpu_mesh") else "lab3-paxos")
    _set_headline(result, mesh_res,
                  f"strict BFS (mesh x{mesh_res['mesh_width']})",
                  mesh_res["platform"], mesh_res["mesh_width"],
                  workload=workload)
    return True


def main() -> None:
    result = {
        "metric": ("lab3-paxos strict BFS unique states/min "
                   "(sharded tensor backend)"),
        "value": 0.0, "unit": "states/min", "vs_baseline": 0.0,
        "deadline_secs": DEADLINE_SECS,
    }
    _install_signal_emitters(result)

    # ---- phase 0: pre-flight (wedge detection + platform probe).
    # Kill budget <= 120 s TOTAL (cap 90 + slack 30) and a ~60 s
    # heartbeat-silence kill: a wedged runtime dies in about a minute.
    pf, pf_err = _sub(["--preflight"],
                      min(PREFLIGHT_CAP_SECS, max(_remaining() - 30, 30)),
                      "preflight",
                      kill_slack=PREFLIGHT_KILL_SLACK_SECS,
                      silence=PREFLIGHT_SILENCE_SECS)
    if pf is None:
        # No stand-in: a failed pre-flight is reported as what it is —
        # the named error on the last line and a non-zero exit.  A CPU
        # number under the bench's name would hide the device.
        result["error"] = (
            "TPU runtime wedged or unreachable: pre-flight 256x256 "
            f"matmul failed ({pf_err})")
        result["total_secs"] = round(time.time() - _T0, 1)
        _emit(result)
        return
    platform, n_dev = pf["platform"], pf["n_devices"]
    on_cpu = platform == "cpu"
    result["metric"] = (f"lab3-paxos strict BFS unique states/min "
                        f"(sharded tensor backend, {platform} x{n_dev})")
    result["preflight_secs"] = pf["secs"]
    _note_phase_telemetry(result, "preflight", pf)

    if on_cpu:
        # CI / smoke shape: the 8-device virtual-mesh phase is the
        # headline (ISSUE 12), one small beam rung rides along.
        mesh_headline = _mesh_phase(result, force_cpu=True)
        beam, beam_err = _sub(
            ["--rung", "64", str(1 << 12), str(1 << 18), "30.0",
             str(FALLBACK_EV_BUDGET[0]), str(FALLBACK_EV_BUDGET[1])],
            min(BEAM_CAP_SECS, max(_remaining() - 15, 45)), "beam-cpu",
            silence=PHASE_SILENCE_SECS)
        if beam:
            if not mesh_headline:
                _set_headline(result, beam, "BFS (beam)", platform,
                              n_dev)
            result["beam"] = beam
            _note_phase_telemetry(result, "beam", beam)
        elif not mesh_headline:
            result["error"] = beam_err
        if _remaining() > 75:
            swarm, swarm_err = _sub(
                ["--swarm", str(min(60.0, _remaining() - 15))],
                min(60.0, _remaining() - 10), "swarm-cpu",
                silence=PHASE_SILENCE_SECS)
            if swarm is not None:
                result["swarm"] = swarm
                _note_phase_telemetry(result, "swarm", swarm)
        if _remaining() > 75:
            spill_res, _spill_err = _sub(
                ["--spill", str(min(90.0, _remaining() - 15))],
                min(90.0, _remaining() - 10), "spill-cpu",
                silence=PHASE_SILENCE_SECS)
            if spill_res is not None:
                result["spill"] = spill_res
                _note_phase_telemetry(result, "spill", spill_res)
        if _remaining() > 75:
            cap2, _cap2_err = _sub(
                ["--capacity2", str(min(90.0, _remaining() - 15))],
                min(90.0, _remaining() - 10), "capacity2-cpu",
                silence=PHASE_SILENCE_SECS)
            if cap2 is not None:
                result["capacity2"] = cap2
                _note_phase_telemetry(result, "capacity2", cap2)
        if _remaining() > 75:
            svc, _svc_err = _sub(
                ["--service", str(min(90.0, _remaining() - 15))],
                min(90.0, _remaining() - 10), "service-cpu",
                silence=PHASE_SILENCE_SECS)
            if svc is not None:
                result["service"] = svc
        if _remaining() > 75:
            lanes_res, _lanes_err = _sub(
                ["--lanes", str(min(120.0, _remaining() - 15))],
                min(120.0, _remaining() - 10), "lanes-cpu",
                silence=PHASE_SILENCE_SECS)
            if lanes_res is not None:
                result["lanes"] = lanes_res
        if _remaining() > 75:
            memo_res, _memo_err = _sub(
                ["--memo", str(min(120.0, _remaining() - 15))],
                min(120.0, _remaining() - 10), "memo-cpu",
                silence=PHASE_SILENCE_SECS)
            if memo_res is not None:
                result["memo"] = memo_res
        if _remaining() > 75:
            scen_res, _scen_err = _sub(
                ["--scenarios", str(min(90.0, _remaining() - 15))],
                min(90.0, _remaining() - 10), "scenarios-cpu",
                silence=PHASE_SILENCE_SECS)
            if scen_res is not None:
                result["scenarios"] = scen_res
        if _remaining() > 75:
            labs_res, _labs_err = _sub(
                ["--labs", str(min(90.0, _remaining() - 15))],
                min(90.0, _remaining() - 10), "labs-cpu",
                silence=PHASE_SILENCE_SECS)
            if labs_res is not None:
                result["labs"] = labs_res
        _emit(result)
        return

    # ---- phase 1: measured budgets (cached across runs)
    cal = _load_cal_cache()
    if cal is not None:
        _hb(f"calibration: cache hit {cal}")
        result["calibration"] = dict(cal, cached=True)
    elif _remaining() > (STRICT_CAP_SECS + CALIBRATE_CAP_SECS
                         + 2 * KILL_SLACK_SECS):
        # Cold calibration only when it cannot starve the strict phase
        # (raise DSLABS_BENCH_DEADLINE_SECS for the fully-calibrated
        # run); otherwise the round-3 measured fallback budgets hold.
        cal, cal_err = _sub(["--calibrate"], CALIBRATE_CAP_SECS,
                            "calibrate", silence=PHASE_SILENCE_SECS)
        if cal is not None:
            _store_cal_cache(cal)
            result["calibration"] = cal
        else:
            result["calibration_error"] = cal_err
    else:
        _hb("calibration: skipped (deadline reserves the window for "
            "strict; fallback ev budgets apply)")
    ev = (cal["bm"], cal["bt"]) if cal else FALLBACK_EV_BUDGET
    result["ev_budget"] = list(ev)

    # ---- phase 2: the strict drop-free headline (ONE attempt,
    # child-side budget so a slow run still lands a partial rate).  The
    # kill slack is reserved OUT of the remaining deadline so a floored
    # child (compile ate the budget, 45 s search minimum) still emits
    # its JSON before both the parent kill and the global deadline.
    strict, strict_err = None, None
    budget = min(STRICT_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 60:
        strict, strict_err = _sub(
            ["--strict", str(ev[0]), str(ev[1]), str(budget)],
            budget, "strict", silence=PHASE_SILENCE_SECS)
        if strict is not None:
            result["strict"] = strict
            _note_phase_telemetry(result, "strict", strict)
            _set_headline(result, strict, "strict BFS", platform, n_dev)
        else:
            result["strict_error"] = strict_err
    else:
        result["strict_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 3: the beam throughput rate (only with time remaining;
    # smaller fallback rungs catch an OOM on the lead config)
    beam = beam_err = None
    for chunk, f_cap, v_cap in BEAM_LADDER:
        budget = min(BEAM_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
        if budget <= 60:
            _hb("beam: skipped (deadline)")
            break
        run_secs = max(30.0, min(120.0, budget - 150.0))
        beam, beam_err = _sub(
            ["--rung", str(chunk), str(f_cap), str(v_cap),
             str(run_secs), str(ev[0]), str(ev[1])], budget,
            f"beam-{chunk}", silence=PHASE_SILENCE_SECS)
        if beam is not None:
            break
    if beam is not None:
        result["beam"] = beam
        _note_phase_telemetry(result, "beam", beam)
        if strict is None:
            _set_headline(result, beam, "BFS (beam)", platform, n_dev)
    elif strict is None:
        result["error"] = "; ".join(
            str(e) for e in (strict_err, beam_err) if e)

    # ---- phase 3.5: the 8-device mesh phase (ISSUE 12).  With >= 8
    # real accelerators it IS the headline (the paper's target
    # configuration); on a narrower box the parent CPU-pins the child
    # and it runs the CPU virtual mesh — recorded with per-device lanes
    # + skew, tagged virtual_cpu_mesh, and compared by the ledger's
    # mesh_width guard, but never allowed to displace a real
    # accelerator headline with a virtual-mesh rate.
    _mesh_phase(result,
                force_cpu=n_dev < int(os.environ.get(
                    "DSLABS_MESH_WIDTH", "8") or "8"),
                headline_ok=lambda p: not p.get("virtual_cpu_mesh"))

    # ---- phase 4: the swarm explorer's deep-probe rates (walkers/sec,
    # unique-states/min, deepest depth) — the portfolio's other half.
    # Never the headline; skipped rather than raced when the deadline
    # is nearly spent.
    budget = min(SWARM_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 45:
        swarm, swarm_err = _sub(["--swarm", str(budget)], budget,
                                "swarm", silence=PHASE_SILENCE_SECS)
        if swarm is not None:
            result["swarm"] = swarm
            _note_phase_telemetry(result, "swarm", swarm)
        else:
            result["swarm_error"] = swarm_err
    else:
        result["swarm_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 5: the capacity ladder (states/min at 1/8 visited
    # capacity with the host-RAM spill tier vs uncapped, exact-parity
    # flag, dropped_states == 0).  Never the headline; skipped rather
    # than raced when the deadline is nearly spent.
    budget = min(SPILL_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 45:
        spill_res, spill_err = _sub(["--spill", str(budget)], budget,
                                    "spill", silence=PHASE_SILENCE_SECS)
        if spill_res is not None:
            result["spill"] = spill_res
            _note_phase_telemetry(result, "spill", spill_res)
        else:
            result["spill_error"] = spill_err
    else:
        result["spill_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 5.2: capacity round 2 (ISSUE 15) — packed vs unpacked
    # bytes_per_state + packed states/min, async spill overlap ratio,
    # symmetry quotient.  The ledger's capacity:bytes_per_state guard
    # compares it across rounds.  Never the headline; skipped rather
    # than raced when the deadline is nearly spent.
    budget = min(CAPACITY2_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 45:
        cap2, cap2_err = _sub(["--capacity2", str(budget)], budget,
                              "capacity2", silence=PHASE_SILENCE_SECS)
        if cap2 is not None:
            result["capacity2"] = cap2
            _note_phase_telemetry(result, "capacity2", cap2)
        else:
            result["capacity2_error"] = cap2_err
    else:
        result["capacity2_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 5.5: the multi-tenant service drain (ISSUE 11) —
    # per-tenant throughput + the fairness index the ledger compare
    # tracks.  Never the headline; skipped rather than raced when the
    # deadline is nearly spent.
    budget = min(SERVICE_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 45:
        svc, svc_err = _sub(["--service", str(budget)], budget,
                            "service", silence=PHASE_SILENCE_SECS)
        if svc is not None:
            result["service"] = svc
        else:
            result["service_error"] = svc_err
    else:
        result["service_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 5.6: batched job lanes (ISSUE 14) — aggregate
    # states/min and dispatches-per-job for a 4-lane batch vs the
    # 4-solo baseline; the ledger compare guards amortisation
    # (service:dispatches_per_job rise / lanes:occupancy drop = rc 1).
    # Never the headline; skipped rather than raced near the deadline.
    budget = min(LANES_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 45:
        lanes_res, lanes_err = _sub(["--lanes", str(budget)], budget,
                                    "lanes", silence=PHASE_SILENCE_SECS)
        if lanes_res is not None:
            result["lanes"] = lanes_res
        else:
            result["lanes_error"] = lanes_err
    else:
        result["lanes_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 5.7: cross-job memoization (ISSUE 16) — cold / hit /
    # warm-start / incremental device-seconds plus the hit_rate the
    # ledger's ``memo:hit_rate`` guard tracks (drop => rc 1) and
    # ``service:device_secs_saved`` rendering.  Never the headline;
    # skipped rather than raced near the deadline.
    budget = min(MEMO_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 45:
        memo_res, memo_err = _sub(["--memo", str(budget)], budget,
                                  "memo", silence=PHASE_SILENCE_SECS)
        if memo_res is not None:
            result["memo"] = memo_res
        else:
            result["memo_error"] = memo_err
    else:
        result["memo_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 5.8: fault scenarios (ISSUE 19) — states/min with the
    # partition fault lanes on vs off, the fault-event share, and the
    # zero-budget verdict_parity flag the ledger's
    # ``scenarios:verdict_parity`` guard pins (0 = rc 1 regardless of
    # threshold).  Never the headline; skipped rather than raced near
    # the deadline.
    budget = min(SCENARIOS_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 45:
        scen_res, scen_err = _sub(["--scenarios", str(budget)], budget,
                                  "scenarios",
                                  silence=PHASE_SILENCE_SECS)
        if scen_res is not None:
            result["scenarios"] = scen_res
        else:
            result["scenarios_error"] = scen_err
    else:
        result["scenarios_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 5.9: generated-labs packing (ISSUE 20) — packed
    # bytes-per-state across the ProtocolSpec-compiled lab3/lab4
    # protocols (the ``labs:bytes_per_state`` ledger guard) plus the
    # >= 2x minimum pack-ratio floor.  Never the headline; skipped
    # rather than raced near the deadline.
    budget = min(LABS_CAP_SECS, _remaining() - KILL_SLACK_SECS - 10)
    if budget > 45:
        labs_res, labs_err = _sub(["--labs", str(budget)], budget,
                                  "labs", silence=PHASE_SILENCE_SECS)
        if labs_res is not None:
            result["labs"] = labs_res
        else:
            result["labs_error"] = labs_err
    else:
        result["labs_error"] = "skipped: deadline nearly exhausted"

    # ---- phase 6: the soundness sanitizer (ISSUE 10) — findings per
    # leg + waived count off `python -m dslabs_tpu.analysis all` in a
    # CPU-pinned child (static: lowers, never compiles or dispatches).
    # `telemetry compare` flags a findings increase over the best
    # prior ledger entry as a regression, same rc-1 severity as a rate
    # drop.  Never the headline, never fatal, skipped when the
    # deadline is nearly spent.
    if _remaining() - KILL_SLACK_SECS > 30:
        try:
            from dslabs_tpu import analysis

            result["sanitizer"] = analysis.sanitizer_summary(
                timeout=max(30, min(180, int(_remaining()
                                             - KILL_SLACK_SECS))))
        except Exception as e:  # noqa: BLE001 — JSON must still land
            result["sanitizer"] = {"error": f"{type(e).__name__}: {e}"}
    else:
        result["sanitizer"] = {"error":
                               "skipped: deadline nearly exhausted"}

    result["total_secs"] = round(time.time() - _T0, 1)
    _emit(result)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--rung":
        chunk, f_cap, v_cap = map(int, sys.argv[2:5])
        ev = ((int(sys.argv[6]), int(sys.argv[7]))
              if len(sys.argv) > 7 else FALLBACK_EV_BUDGET)
        print(json.dumps(_run_rung(chunk, f_cap, v_cap,
                                   float(sys.argv[5]), ev)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--strict":
        ev = (int(sys.argv[2]), int(sys.argv[3]))
        budget = (float(sys.argv[4]) if len(sys.argv) > 4
                  else STRICT_CAP_SECS)
        print(json.dumps(_run_strict(ev, budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--swarm":
        budget = (float(sys.argv[2]) if len(sys.argv) > 2
                  else SWARM_CAP_SECS)
        print(json.dumps(_run_swarm(budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--spill":
        budget = (float(sys.argv[2]) if len(sys.argv) > 2
                  else SPILL_CAP_SECS)
        print(json.dumps(_run_spill(budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--capacity2":
        budget = (float(sys.argv[2]) if len(sys.argv) > 2
                  else CAPACITY2_CAP_SECS)
        print(json.dumps(_run_capacity2(budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--service":
        budget = (float(sys.argv[2]) if len(sys.argv) > 2
                  else SERVICE_CAP_SECS)
        print(json.dumps(_run_service(budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--lanes":
        budget = (float(sys.argv[2]) if len(sys.argv) > 2
                  else LANES_CAP_SECS)
        print(json.dumps(_run_lanes(budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--memo":
        budget = (float(sys.argv[2]) if len(sys.argv) > 2
                  else MEMO_CAP_SECS)
        print(json.dumps(_run_memo(budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--scenarios":
        budget = (float(sys.argv[2]) if len(sys.argv) > 2
                  else SCENARIOS_CAP_SECS)
        print(json.dumps(_run_scenarios(budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--labs":
        budget = (float(sys.argv[2]) if len(sys.argv) > 2
                  else LABS_CAP_SECS)
        print(json.dumps(_run_labs(budget)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--mesh":
        # A leading "cpu" arg pins the whole child to the CPU backend
        # with a virtual device per mesh slot (set before jax loads);
        # without it make_mesh(width) takes the default backend's
        # devices or raises.
        _args = sys.argv[2:]
        if _args and _args[0] == "cpu":
            os.environ["JAX_PLATFORMS"] = "cpu"
            _args = _args[1:]
            _xf = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in _xf:
                os.environ["XLA_FLAGS"] = (
                    _xf + " --xla_force_host_platform_device_count="
                    + os.environ.get("DSLABS_MESH_WIDTH", "8")).strip()
        print(json.dumps(_run_mesh(
            float(_args[0]) if _args else MESH_CAP_SECS)))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--calibrate":
        print(json.dumps(_calibrate()))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--preflight":
        print(json.dumps(_preflight()))
        sys.exit(0)
    try:
        main()
    except BaseException:
        # ANY escape from main (SystemExit from a signal handler
        # already emitted; everything else lands here) still prints a
        # tagged, parsable JSON line — and exits non-zero: there is no
        # number.
        tb = traceback.format_exc(limit=3)
        _emit({
            "metric": "lab3-paxos strict BFS unique states/min "
                      "(tensor backend)",
            "value": 0.0, "unit": "states/min", "vs_baseline": 0.0,
            "error": tb.strip().splitlines()[-1][:300],
            "total_secs": round(time.time() - _T0, 1),
        })
    sys.exit(_EXIT_CODE)
