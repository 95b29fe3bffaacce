"""Driver ``timeboxed_bfs_lab4_multi``: ``timeboxed_bfs_lab4``'s strict
BFS from a STAGED lab 4 root, for replica groups of SEVERAL servers —
``ShardStoreBaseTest.setupStates(G, n, 1, shards)`` with n > 1, each
group a Paxos-replicated log.

Everything timed is ``timeboxed_bfs``'s and everything staged is
``timeboxed_bfs_lab4``'s (``lab4_phases.build_state`` already builds
``servers_per_group`` servers a group; the Join phase, the relative
``max_depth`` and the live and pinned counts are that driver's).  What
this driver replaces is what read the one-server twin's shape:

* **the workload**: the multi-server twin's alphabet is own-key PUTs
  (``tpu/specs_lab4.py make_shardstore_multi_spec``: client command
  ``k`` is ``PUT key-k``), so a client's worker gets the configuration's
  ``command`` on its ``key`` with a value drawn from the seed, expecting
  ``PutOk`` — ShardStorePart1Test test11's first command;
* **the twin's identity**: the one-server binding is told from the
  configuration by ``groups_of``; the multi-server one
  (``adapters/shardstore.py ShardStoreMultiBinding``) by its ``shape``
  — groups, servers a group, shards, commands — held to the factory's
  kwargs.

The binding is imported where this file is: a program without it
(every commit before PR 40) cannot decide this cell's ``correct``, and
fails here at once, before JAX is touched, instead of searching for a
window and then reading ``NoTensorTwin``.
"""

from __future__ import annotations

import random
import time

from benchmark.drivers import lab4_phases
from benchmark.drivers.timeboxed_bfs import (build_supervisor, end_to_end,
                                             measure, prepare)
from benchmark.drivers.timeboxed_bfs_lab4 import reference_counts
from benchmark.harness import states
from benchmark.harness.runner import (GUARANTEE_COUNTERS, Check, at_least,
                                      equal)
from dslabs_tpu.tpu.adapters.shardstore import ShardStoreMultiBinding

__all__ = ["add_clients", "build_supervisor", "end_to_end", "joined_state",
           "measure", "prepare", "reference_counts", "root_is_the_twins",
           "verify"]

def add_clients(state, spec: dict, seed: int):
    """Every store client's worker, added to ``state`` (the Join phase's
    goal state) in place: the client's one command on its key with a
    value drawn from the seed (``PUT``: expecting ``PutOk``)."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload

    rng = random.Random(seed)
    for client in spec["clients"]:
        if client["command"] != "PUT":
            raise ValueError("the multi-server twin's alphabet is own-key "
                             f"PUTs, not {client['command']!r}")
        value = states._word(rng, 4)
        state.add_client_worker(
            LocalAddress(client["address"]),
            kv_workload([f"PUT:{client['key']}:{value}"], ["PutOk"]))
    return state


def joined_state(ctx):
    """The state the search starts from: the Join phase's goal state by
    the object checker, plus the client."""
    from dslabs_tpu.search.search import BFS

    cfg = ctx.cell.config
    spec = cfg["deployment"]["object_state"]
    root = lab4_phases.build_state(spec, ctx.seed)
    joined = BFS(lab4_phases.build_settings(cfg["join"], root)).run(
        root).goal_matching_state
    if joined is None:
        raise RuntimeError("the Join phase found no goal state")
    return add_clients(joined, spec, ctx.seed)


def root_is_the_twins(ctx, joined) -> Check:
    """``joined`` passes the lab adapter's validation as the canonical
    root of the twin the configuration names: what ``tensor_bfs`` would
    bind for this very state and settings (the multi-server binding),
    and start from without a replay."""
    from dslabs_tpu.tpu import backend

    cfg = ctx.cell.config
    proto = cfg["protocol"]
    caps = proto["kwargs"]
    want = [proto["name"], [caps["n_groups"], caps["n"],
                            caps["num_shards"], caps["w"]]]
    try:
        binding = backend.resolve_binding(joined)
        if type(binding) is not ShardStoreMultiBinding:
            raise backend.NoTensorTwin(
                f"bound {type(binding).__name__}, not the multi-server "
                "binding")
        binding.check_settings(
            lab4_phases.build_settings(cfg["search"], joined))
        got = [binding.build_protocol(caps["net_cap"],
                                      caps["timer_cap"]).name,
               list(binding.shape)]
        if binding.derive_root(None, joined) != (None, []):
            got.append("root replayed, not validated")
    except backend.NoTensorTwin as e:
        got = f"NoTensorTwin: {e}"
    return equal("reference.root_is_the_twins", got, want)


def verify(ctx, measured: dict) -> list:
    """``timeboxed_bfs_lab4``'s comparison, from this driver's joined
    state and through this driver's ``root_is_the_twins``."""
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
    # what the configuration's ``sizing.window_end`` records
    ctx.note(f"the search ended {out['end_condition']} in depth "
             f"{out['depth']}: {out['unique_states']} unique, "
             f"{out['states_explored']} explored after "
             f"{out['elapsed_secs']:.3f}s")
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
