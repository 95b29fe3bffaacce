"""Driver ``timeboxed_swarm``: ONE swarm fleet of random depth-first
probes from a lab search test's root (``tpu/swarm.py`` ``SwarmSearch``),
run until the window's clock ends it — upstream's RANDOM search, the
second half of its "BFS + random DFS" checker.

The fleet is the lab entry's own: ``backend.probe_fleet``, the one
function ``backend.tensor_dfs``'s probe is built by, handed the object
``SearchState`` and ``SearchSettings`` the test builds and the
configuration's ``fleet`` (walkers a chip, steps a dispatch, table,
strict).  A program without that function (every commit before PR 43)
cannot run this cell and fails here, at the import, before JAX is
touched.

Set-up builds the fleet, loads or compiles its round program and warms
every program a run dispatches with a short run of the same object,
one step a dispatch, each walker's whole row digested after every step
by the harness's own plain hash (``swarm_reference.row_digest_fn``).  The
window is one ``run()`` under ``max_secs``: it ends at the first dispatch
boundary past the box.  ``states_per_s`` is the fleet's fresh unique
states over its elapsed seconds, both as it reports them at that
boundary.  A traced run's slice is the first whole round dispatched
after ``trace_after_secs`` of the window.

``correct`` (``harness/swarm_reference.py``; the object checker runs
after the window): ``replayed`` walkers drawn from the seed, the deepest
of ``drawn``, replay event by event on the object state — every event
applies, the object invariants hold on every state, the twin's own step
ends on the walker's row, the object state decodes to it, and the
fleet's fingerprint gives distinct keys to the distinct states of the
way; the fleet's cumulative fresh count by walk depth EQUALS the object BFS's at depths
``<= equal_depth`` (live on the seeded state) and exceeds it at no pinned
depth; the warm-up's ``fresh``, at the timed size, EQUALS the number of
distinct rows its walkers stood on, counted from the rows' digests on
the host and not from the fleet's keys (a walker that restarts hides
the one state it ended on, so the count may exceed the rows by the
restarts at most: none in the cell's warm-up); truncated steps, refused steps,
unresolved table keys, events beyond the event window and terminals are
0 and ``explored = fresh + revisit``; the seeded state binds the twin
the configuration names, from its own root.  The warm-up's counters as
an earlier run of this policy read them (``reference.tripwire``) are
printed beside this run's and decide nothing."""

from __future__ import annotations

import gc
import time

from benchmark.harness import spans as spans_mod
from benchmark.harness import swarm_reference as ref
from benchmark.harness.runner import Check, equal
from dslabs_tpu.tpu.backend import probe_fleet

ROUND = "swarm.round"        # the fleet's dispatch tag


def build_fleet(ctx):
    """``(state, settings, binding, search)``: the seeded object state
    and the test's settings, the binding the lab adapter gives for
    them, and ``probe_fleet``'s fleet at the configuration's ``fleet``
    (the test's root is the twin's own: nothing is replayed)."""
    from dslabs_tpu.tpu import backend

    cfg = ctx.cell.config
    state = ref.build_state(cfg["deployment"]["object_state"], ctx.seed)
    settings = ref.build_settings(cfg["search"])
    binding = backend.resolve_binding(state).probe_binding()
    fleet = cfg["fleet"]
    search, root, history, binding = probe_fleet(
        binding, settings, state, walkers=fleet["walkers"],
        steps_per_round=fleet["steps_per_round"],
        visited_cap=fleet["visited_cap"], strict=fleet["strict"])
    if root is not None or history:
        raise RuntimeError("the test's root is the twin's own")
    return state, settings, binding, search


def row_recorder(digest):
    """The warm-up's recorder: after every round it digests the rows
    the walkers stand on (``digest``: the harness's own hash, on the
    device; 16 bytes a walker come back)."""
    import numpy as np

    from dslabs_tpu.tpu.telemetry import Telemetry

    class RowRecorder(Telemetry):
        def __init__(self):
            super().__init__(ring=64)
            self.digests = []           # [K, 4] uint32 a round

        def record_dispatch(self, search, tag, hook, fn, *args):
            out = super().record_dispatch(search, tag, hook, fn, *args)
            if tag == ROUND:
                self.digests.append(np.asarray(digest(out[0]["rows"])))
            return out

    return RowRecorder()


def warm_up(search, steps: int) -> dict:
    """``steps`` single-step rounds of the fleet from the root: the
    run's counters, and ``distinct_rows``, the different rows its
    walkers stood on but the root, by the harness's digests (the round
    of no step that opens a run shows every walker on the root)."""
    recorder = row_recorder(ref.row_digest_fn(search.lanes))
    kept = search._telemetry, search.steps_per_round
    recorder.attach(search)
    search.steps_per_round, search.max_rounds = 1, steps
    search.max_secs = None
    try:
        warm = search.run(check_initial=False)
    finally:
        search._telemetry, search.steps_per_round = kept
    if warm.end_condition != "TIME_EXHAUSTED":
        raise RuntimeError(f"warm-up ended {warm.end_condition}")
    if ref.distinct_rows(recorder.digests[:1]) != 1:
        raise RuntimeError("the walkers do not start on one root")
    return dict(warm.swarm, compile_s=float(warm.compile_secs),
                distinct_rows=ref.distinct_rows(recorder.digests) - 1)


def prepare(ctx) -> None:
    cfg = ctx.cell.config
    with spans_mod.span("construct"):
        state, settings, binding, search = build_fleet(ctx)
    ctx.note(f"fleet of {search.walkers} walkers a chip, bounds "
             f"{search.min_steps}-{search.max_steps}, "
             f"{search.steps_per_round} steps a dispatch, table "
             f"{search.visited_cap}, caps {search.p.net_cap} / "
             f"{search.p.timer_cap}; warm-up run of the same fleet")
    with spans_mod.span("warmup"):
        warm = warm_up(search, int(cfg["reference"]["warmup_steps"]))
    # What tracing the twin left on the heap is collected here, in
    # set-up, and never in the window.
    gc.collect()
    ctx.state.update(state=state, settings=settings, binding=binding,
                     search=search, compile_s=warm.pop("compile_s"),
                     warmup=warm)


def round_recorder(tracer, after_secs: float):
    """A traced run's recorder: it keeps every round's stats vector and
    end, and runs ``tracer`` over the first WHOLE round dispatched once
    ``after_secs`` of the window have passed."""
    from dslabs_tpu.tpu.telemetry import Telemetry

    class RoundRecorder(Telemetry):
        def __init__(self):
            super().__init__(ring=64)
            self.t0 = time.time()
            self.rounds = []            # [(stats, end wall)] by round
            self.traced_round = None    # index into ``rounds``

        def record_dispatch(self, search, tag, hook, fn, *args):
            if tag != ROUND:
                return super().record_dispatch(search, tag, hook, fn,
                                               *args)
            mine = (tracer.started is None and self.rounds
                    and time.time() - self.t0 >= after_secs)
            if mine:
                tracer.start()
            try:
                with spans_mod.span("round"):
                    out = super().record_dispatch(search, tag, hook, fn,
                                                  *args)
            finally:
                if mine:
                    tracer.stop()
            if mine:
                self.traced_round = len(self.rounds)
            self.rounds.append(([int(x) for x in out[1]], time.time()))
            return out

    return RoundRecorder()


def measure(ctx, seconds: float) -> dict:
    params = ctx.cell.params
    search = ctx.state["search"]
    search.max_rounds, search.max_secs = None, seconds
    recorder = None
    if ctx.trace:
        recorder = round_recorder(ctx.tracer,
                                  float(params["trace_after_secs"]))
        recorder.attach(search)
    try:
        with spans_mod.span("search"):
            out = search.run(check_initial=False)
    finally:
        search._telemetry = None
    sd = dict(out.swarm)
    # dispatch boundaries: a traced run has each round's end (the first
    # entry is the warm-up round of no step), any run their mean
    walls = ([b[1] - a[1] for a, b in zip(recorder.rounds,
                                          recorder.rounds[1:])]
             if recorder is not None
             else [out.elapsed_secs / max(sd["rounds"], 1)])
    measured = {
        "outcome": {
            "end_condition": out.end_condition,
            "unique_states": int(out.unique_states),
            "states_explored": int(out.states_explored),
            "elapsed_secs": float(out.elapsed_secs),
            "platform": out.platform, "device_kind": out.device_kind,
            "mesh_width": int(search.n_devices),
            "row_bytes": 4 * int(search.lanes)},
        "swarm": sd,
        "steps_per_round": int(search.steps_per_round),
        "round_walls": walls,
        "compile_s": ctx.state["compile_s"],
        "warmup": ctx.state["warmup"],
        # rounds dispatched, and rounds that broke a guarantee: the
        # counters are the run's, so any non-zero one fails them all
        "attempted": sd["rounds"],
        "failed": (sd["rounds"] if (sd["overflow_restarts"]
                                    or sd["refused"] or sd["vis_over"]
                                    or sd["ev_rem"])
                   else 0),
    }
    i = recorder.traced_round if recorder is not None else None
    if i is not None and i >= 1:
        now, before = recorder.rounds[i][0], recorder.rounds[i - 1][0]
        measured["traced_round"] = {
            "round": i, "steps": now[7],
            "explored": now[0] - before[0], "fresh": now[1] - before[1],
            "restarts": now[3] - before[3]}
    return measured


def replayed_walkers(ctx, measured: dict) -> list:
    """The ``replayed`` deepest of ``drawn`` walkers drawn from the
    seed, each replayed on a fresh seeded object state."""
    cfg = ctx.cell.config
    search, binding = ctx.state["search"], ctx.state["binding"]
    check = cfg["reference"]
    drawn = ref.walker_sample(ctx.seed, measured["swarm"]["walkers"],
                              int(check["drawn"]))
    snap = search.walker_snapshot(drawn)
    deepest = sorted(range(len(drawn)),
                     key=lambda i: -len(snap[i][1]))[:int(check["replayed"])]
    spec = cfg["deployment"]["object_state"]
    out = []
    for i in deepest:
        row, events = snap[i]
        got = ref.replay_walker(
            binding, search, ref.build_state(spec, ctx.seed), row, events,
            ctx.state["settings"].invariants)
        out.append(dict(got, walker=drawn[i]))
    return out


def root_is_the_twins(ctx) -> Check:
    """The seeded state binds, through the lab adapter, the twin the
    configuration names at the probe's caps, and starts from the twin's
    own root: what ``tensor_dfs`` would bind for this very state."""
    from dslabs_tpu.tpu import backend
    from dslabs_tpu.tpu.adapters.paxos import PaxosBinding

    cfg = ctx.cell.config
    proto = cfg["protocol"]
    want = [proto["name"], proto["net_cap"], proto["timer_cap"]]
    try:
        state = ref.build_state(cfg["deployment"]["object_state"],
                                ctx.seed)
        binding = backend.resolve_binding(state).probe_binding()
        if type(binding) is not PaxosBinding:
            raise backend.NoTensorTwin(
                f"bound {type(binding).__name__}, not the Paxos binding")
        binding.check_settings(ref.build_settings(cfg["search"]))
        caps = binding.probe_caps()
        got = [binding.build_protocol(*caps).name, *caps]
        if binding.derive_root(None, state) != (None, []):
            got.append("root replayed")
    except backend.NoTensorTwin as e:
        got = f"NoTensorTwin: {e}"
    return equal("reference.root_is_the_twins", got, want)


def verify(ctx, measured: dict) -> list:
    cfg = ctx.cell.config
    check = cfg["reference"]
    out, sd = measured["outcome"], measured["swarm"]
    spec = cfg["deployment"]["object_state"]
    pinned = {int(d): int(n) for d, n in check["counts"].items()}
    equal_depth = int(check["equal_depth"])
    t = time.time()
    live = ref.bfs_counts(spec, ctx.seed, equal_depth)
    ctx.note(f"object checker to depth {equal_depth}: {live} in "
             f"{time.time() - t:.1f}s")
    got = ref.cumulative(sd["fresh_by_depth"])
    ctx.note(f"the fleet ended {out['end_condition']} after "
             f"{out['elapsed_secs']:.3f}s: {sd}; cumulative by depth "
             f"{got}; dispatch walls min / median / max "
             f"{_spread(measured['round_walls'])}")
    checks = [equal("platform", out["platform"], ctx.dev["platform"]),
              equal("mesh_width", out["mesh_width"], ctx.cell.chips),
              root_is_the_twins(ctx),
              equal("end_condition", out["end_condition"],
                    "TIME_EXHAUSTED")]
    for d in sorted(pinned):
        if d <= equal_depth:
            checks.append(equal(f"reference.live_vs_pinned.depth{d}",
                                live[d], pinned[d]))
            checks.append(equal(f"fresh.cumulative.depth{d}", got[d],
                                live[d]))
        else:
            checks.append(Check(f"fresh.cumulative.depth{d}", got[d],
                                f"<={pinned[d]}", got[d] <= pinned[d]))
    # The warm-up's steps from the root, at the timed size: every state
    # the fleet counted fresh is a row some walker stood on after some
    # step — but the state a probe ended on, one a restart at the most
    # — and the harness counted those rows itself, by digests of its
    # own.  A key that merges two states, or a table that drops one,
    # counts fewer than the rows.
    warm = measured["warmup"]
    ctx.note(f"the warm-up's {warm['rounds']} steps: {warm}; an earlier "
             f"run of this policy read {check['tripwire']} (a tripwire: "
             f"it decides nothing)")
    rows, hidden = warm["distinct_rows"], warm["restarts"]
    checks.append(Check(
        "warmup.fresh_is_the_distinct_rows", warm["unique"],
        f"{rows}..{rows + hidden}",
        rows <= warm["unique"] <= rows + hidden))
    checks += [equal("swarm_overflow", sd["overflow_restarts"], 0),
               equal("swarm_refused", sd["refused"], 0),
               equal("visited_overflow", sd["vis_over"], 0),
               equal("ev_remaining", sd["ev_rem"], 0),
               equal("explored_is_fresh_plus_revisit", sd["explored"],
                     sd["unique"] + sd["revisits"])]
    t = time.time()
    walkers = replayed_walkers(ctx, measured)
    ctx.note(f"replayed {len(walkers)} walkers, {sum(w['events'] for w in walkers)}"
             f" events, in {time.time() - t:.1f}s: depths "
             f"{[w['events'] for w in walkers]}")
    checks.append(equal("replay.walkers", len(walkers),
                        int(check["replayed"])))
    bad = [w for w in walkers
           if w["applied"] != w["events"] or w["violated"]
           or not w["row_equal"] or w["decoded"]]
    checks.append(equal("replay.diverged", [
        {k: w[k] for k in ("walker", "events", "applied", "violated",
                           "row_equal", "decoded")} for w in bad], []))
    # the fleet's dedup key tells apart every two states of the replays
    rows = set().union(*(w["rows"] for w in walkers))
    keys = set().union(*(w["keys"] for w in walkers))
    checks.append(equal("replay.distinct_keys", len(keys), len(rows)))
    return checks


def _spread(walls):
    if not walls:
        return None
    walls = sorted(walls)
    return [round(w, 4) for w in (walls[0], walls[len(walls) // 2],
                                  walls[-1])]


def end_to_end(ctx, measured: dict) -> dict:
    out = measured["outcome"]
    return {"states_per_s": out["unique_states"] / out["elapsed_secs"]}
