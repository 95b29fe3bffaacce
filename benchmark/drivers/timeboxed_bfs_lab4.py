"""Driver ``timeboxed_bfs_lab4``: ``timeboxed_bfs``'s one strict BFS, run
until the window's clock ends it, on a lab 4 twin whose root is a STAGED
state — ShardStorePart1Test's search tests start from the Join phase's
goal state plus the store clients' workers, not from a fresh system.

The timed side is ``timeboxed_bfs``'s, unchanged: the same supervisor
(``build_supervisor``), warm-up (``prepare``), window (``measure``) and
``states_per_s`` (``end_to_end``); the twin's initial state bakes the
joins in, so the search starts at the joined root by construction.  What
this driver replaces is the REFERENCE's side:

* **the state**: ``lab4_phases.build_state`` (shard master, one-server
  groups, the config controller's ``Join`` a group) searched by the
  object checker under the configuration's ``join`` settings to its
  goal state, then every store client's worker added to that state as
  the test adds it (``add_clients``: one APPEND each, the keys
  upstream's, the values drawn from the seed);
* **the settings**: the configuration's ``search`` entry (test12's:
  ``RESULTS_OK``, the controller's node off, the controller's and the
  master's timers off), with ``max_depth`` RELATIVE to the joined
  state's depth — level ``d`` of the twin's search is depth
  ``joined.depth + d`` of the object checker's;
* **one more check**: the joined state IS the twin's root, by the lab
  adapter's own validation (what ``ShardStoreBinding.derive_root`` runs
  on a staged state) under those settings, and the twin it would bind
  is the configuration's — so the counts compared are of one space.

``correct`` is otherwise ``timeboxed_bfs``'s: platform and width, the
cumulative unique count at every completed depth equal to the live
object checker's up to ``reference_live_depth`` and to the pinned
``reference_counts`` above it, the run past ``must_pass_depth``, the
guarantee counters 0."""

from __future__ import annotations

import random
import time

from benchmark.drivers import lab4_phases
from benchmark.drivers.timeboxed_bfs import (build_supervisor, end_to_end,
                                             measure, prepare)
from benchmark.harness import states
from benchmark.harness.runner import (GUARANTEE_COUNTERS, Check, at_least,
                                      equal)

__all__ = ["add_clients", "build_supervisor", "end_to_end", "joined_state",
           "measure", "prepare", "reference_counts", "root_is_the_twins",
           "verify"]


def add_clients(state, spec: dict, seed: int):
    """Every store client's worker, added to ``state`` (the Join phase's
    goal state) in place: one APPEND to the client's key of a value
    drawn from the seed, expecting that value back."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    rng = random.Random(seed)
    for client in spec["clients"]:
        value = states._word(rng, 4)
        state.add_client_worker(
            LocalAddress(client["address"]),
            kv_workload([f"APPEND:{client['key']}:{value}"], [value]))
    return state


def joined_state(ctx):
    """The state the test searches from: the Join phase's goal state by
    the object checker, plus the clients."""
    from dslabs_tpu.search.search import BFS

    cfg = ctx.cell.config
    spec = cfg["deployment"]["object_state"]
    root = lab4_phases.build_state(spec, ctx.seed)
    joined = BFS(lab4_phases.build_settings(cfg["join"], root)).run(
        root).goal_matching_state
    if joined is None:
        raise RuntimeError("the Join phase found no goal state")
    return add_clients(joined, spec, ctx.seed)


def reference_counts(ctx, joined, upto: int) -> dict:
    """Cumulative unique counts at depths 1..upto BELOW ``joined`` by
    the object checker (a depth-limited BFS each: the checker reports
    one count a run)."""
    from dslabs_tpu.search.search import BFS

    search = ctx.cell.config["search"]
    return {d: int(BFS(lab4_phases.build_settings(
        dict(search, max_depth=d), joined)).run(joined).discovered_count)
        for d in range(1, upto + 1)}


def root_is_the_twins(ctx, joined) -> Check:
    """``joined`` passes the lab adapter's validation as the canonical
    root of the twin the configuration names: what ``tensor_bfs`` would
    bind for this very state and settings, and start from without a
    replay."""
    from dslabs_tpu.tpu import backend

    cfg = ctx.cell.config
    proto = cfg["protocol"]
    caps = proto["kwargs"]
    want = [proto["name"], caps["groups_of"]]
    try:
        binding = backend.resolve_binding(joined)
        binding.check_settings(
            lab4_phases.build_settings(cfg["search"], joined))
        got = [binding.build_protocol(caps["net_cap"],
                                      caps["timer_cap"]).name,
               getattr(binding, "groups_of", None)]
        if binding.derive_root(None, joined) != (None, []):
            got.append("root replayed, not validated")
    except backend.NoTensorTwin as e:
        got = f"NoTensorTwin: {e}"
    return equal("reference.root_is_the_twins", got, want)


def verify(ctx, measured: dict) -> list:
    cfg = ctx.cell.config
    pinned = {int(d): int(n) for d, n in cfg["reference_counts"].items()}
    live_depth = int(cfg["reference_live_depth"])
    t = time.time()
    joined = joined_state(ctx)
    live = reference_counts(ctx, joined, live_depth)
    ctx.note(f"object checker from the joined state (depth "
             f"{joined.depth}) to depth + {live_depth}: {live} in "
             f"{time.time() - t:.1f}s")
    out = measured["outcome"]
    got = {int(lv["depth"]): int(lv["unique"])
           for lv in measured["levels"]}
    checks = [equal("platform", out["platform"], ctx.dev["platform"]),
              equal("mesh_width", out["mesh_width"], ctx.cell.chips),
              root_is_the_twins(ctx, joined)]
    for d in sorted(pinned):
        if d <= live_depth:
            checks.append(equal(f"reference.live_vs_pinned.depth{d}",
                                live[d], pinned[d]))
        if d in got:
            limit = live[d] if d <= live_depth else pinned[d]
            checks.append(equal(f"unique.depth{d}", got[d], limit))
    checks.append(at_least("completed_depth", max(got, default=0),
                           int(cfg["must_pass_depth"])))
    checks += [equal(k, out[k], 0) for k in GUARANTEE_COUNTERS]
    return checks
