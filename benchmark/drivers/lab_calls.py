"""Driver ``lab_calls``: a closed loop of ONE caller cycling calls of the
lab entry point ``backend.tensor_bfs`` — what ``run_tests.py
--search-backend tensor`` dispatches a search test to — each on a fresh
``SearchState`` built from the seed, until the window ends.

The configuration's ``calls`` name the kinds of call (their invariants,
goals and prunes); the cell's ``cycle`` orders them, and the seed rotates
which comes first.  The window runs WHOLE cycles (it ends at the first
cycle boundary past ``--seconds``), so that every seed does the same
calls in another order and ``verdict_s`` — the wall seconds of all the
calls over their number — never depends on where in a cycle the clock
fell.

After the window the object checker (``dslabs_tpu/search/search.py``)
makes each kind of call once on the same seeded state; ``correct`` holds
every timed call to it: equal end condition, equal discovered count
where the space was exhausted (a goal or violation search stops inside a
level, where the two checkers' counts are not comparable), a terminal
state of equal (minimal) depth, on which the original predicate gives
the terminal answer — ``tensor_bfs`` has replayed the witness on the
object twin — and all guarantee counters zero."""

from __future__ import annotations

import time

from benchmark.harness import spans as spans_mod
from benchmark.harness import states
from benchmark.harness.runner import GUARANTEE_COUNTERS, Check, equal



def _terminal(results):
    """``(state, the original predicate gives the terminal answer on
    it)`` of a goal or violation verdict, else ``(None, True)``."""
    end = results.end_condition.name
    if end == "GOAL_FOUND":
        st = results.goal_matching_state
        return st, any(g.check(st).value for g in results.goals)
    if end == "INVARIANT_VIOLATED":
        st = results.invariant_violating_state
        return st, any(not i.check(st).value for i in results.invariants)
    return None, True


def _record(kind: str, results, wall_s: float) -> dict:
    st, holds = _terminal(results)
    rec = {"kind": kind, "wall_s": wall_s,
           "end_condition": results.end_condition.name,
           "discovered_count": int(results.discovered_count),
           "terminal_depth": None if st is None else int(st.depth),
           "terminal_holds": bool(holds)}
    out = getattr(results, "tensor_outcome", None)
    if out is not None:
        rec.update(search_s=float(out.elapsed_secs),
                   compile_s=float(out.compile_secs or 0.0),
                   platform=out.platform,
                   counters={k: int(getattr(out, k))
                             for k in GUARANTEE_COUNTERS})
    return rec


def one_call(ctx, kind: str) -> dict:
    """One call of the entry point on a fresh seeded state; the span is
    from handing it the state and settings to holding the results."""
    from dslabs_tpu.tpu import backend

    cfg = ctx.cell.config
    state = states.build(cfg["deployment"]["object_state"], ctx.seed)
    settings = states.settings(cfg["calls"][kind])
    t = time.time()
    with spans_mod.span("call." + kind):
        results = backend.tensor_bfs(state, settings)
    return _record(kind, results, time.time() - t)


def cycle(ctx) -> list:
    order = list(ctx.cell.params["cycle"])
    rot = ctx.seed % len(order)
    return order[rot:] + order[:rot]


def prepare(ctx) -> None:
    t = time.time()
    with spans_mod.span("warmup"):
        warm = [one_call(ctx, kind) for kind in cycle(ctx)]
    ctx.state.update(warmup_s=time.time() - t, warm=warm)


def measure(ctx, seconds: float) -> dict:
    order = cycle(ctx)
    traced = int(ctx.cell.params.get("traced_calls", len(order)))
    calls = []
    t0 = time.time()
    while time.time() - t0 < seconds:
        for kind in order:
            if ctx.tracer is not None and len(calls) == 0:
                ctx.tracer.start()
            calls.append(one_call(ctx, kind))
            if ctx.tracer is not None and len(calls) == traced:
                ctx.tracer.stop()
    return {"calls": calls, "warmup_s": ctx.state["warmup_s"],
            "attempted": len(calls), "failed": 0}


def reference(ctx) -> dict:
    """Each kind of call, once, on the object checker."""
    from dslabs_tpu.search.search import BFS

    cfg = ctx.cell.config
    ref = {}
    for kind in ctx.cell.params["cycle"]:
        state = states.build(cfg["deployment"]["object_state"], ctx.seed)
        t = time.time()
        results = BFS(states.settings(cfg["calls"][kind])).run(state)
        ref[kind] = _record(kind, results, time.time() - t)
    return ref


def verify(ctx, measured: dict) -> list:
    ref = reference(ctx)
    pinned = ctx.cell.config["reference"]
    ctx.note("object checker: " + ", ".join(
        f"{k} {r['end_condition']}/{r['discovered_count']}/depth "
        f"{r['terminal_depth']} in {r['wall_s']:.2f}s"
        for k, r in ref.items()))
    checks = []
    for kind, r in ref.items():
        # the live reference against the configuration's pinned answers
        want = pinned[kind]
        checks.append(equal(f"reference.{kind}.end_condition",
                            r["end_condition"], want["end_condition"]))
        if "discovered_count" in want:
            checks.append(equal(f"reference.{kind}.discovered_count",
                                r["discovered_count"],
                                want["discovered_count"]))
        if "terminal_depth" in want:
            checks.append(equal(f"reference.{kind}.terminal_depth",
                                r["terminal_depth"],
                                want["terminal_depth"]))
    bad = 0
    for i, c in enumerate(measured["calls"]):
        r = ref[c["kind"]]
        mine = [equal("end_condition", c["end_condition"],
                      r["end_condition"]),
                equal("terminal_depth", c["terminal_depth"],
                      r["terminal_depth"]),
                equal("terminal_holds", c["terminal_holds"], True),
                equal("platform", c.get("platform"),
                      ctx.dev["platform"]),
                equal("counters", sum(c["counters"].values()), 0)]
        if r["end_condition"] == "SPACE_EXHAUSTED":
            mine.append(equal("discovered_count", c["discovered_count"],
                              r["discovered_count"]))
        bad += not all(m.ok for m in mine)
        checks += [Check(f"call{i}.{c['kind']}.{m.name}", m.value,
                         m.limit, m.ok) for m in mine]
    measured["failed"] = bad
    return checks


def end_to_end(ctx, measured: dict) -> dict:
    calls = measured["calls"]
    return {"verdict_s": sum(c["wall_s"] for c in calls) / len(calls)}
