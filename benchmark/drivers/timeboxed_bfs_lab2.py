"""Driver ``timeboxed_bfs_lab2``: ``timeboxed_bfs``'s one strict BFS, run
until the window's clock ends it, on lab 2's COMPILED twin
(``dslabs_tpu/tpu/specs.py`` ``pb_spec``): PrimaryBackupTest test18's
system — a view server, two ``PBServer``s, two clients with one APPEND
each to ONE key — searched from its root with every timer live.

The timed side is ``timeboxed_bfs``'s, unchanged: the same supervisor
(``build_supervisor``), window (``measure``) and ``states_per_s``
(``end_to_end``), and its warm-up with one thing added (``prepare``):
the visited table's PLACE in the device's memory is the same in every
process.  What this driver brings besides is the REFERENCE's side:

* **the state** (``build_state``): the ``SearchState`` the test builds
  (``tests/test_lab2_pb.py`` ``make_search_state`` and test18's nodes),
  with the one key and the two APPENDed values drawn from the seed;
* **the settings**: the configuration's ``search`` entry — the
  invariant ``APPENDS_LINEARIZABLE`` on, no goal, no prune, nothing
  gated off — with ``max_depth`` set for each depth counted;
* **one more check** (``root_is_the_twins``): the lab adapter
  (``PrimaryBackupBinding``), handed that very state, binds the twin
  the configuration names — the shared-key twin, at its caps — and takes
  the state for the twin's own root, with nothing to replay: so the
  counts compared are of one space.

``correct`` is otherwise ``timeboxed_bfs``'s: platform and width, the
cumulative unique count at every completed depth equal to the live
object checker's up to ``reference_live_depth`` and to the pinned
``reference_counts`` above it, the run past ``must_pass_depth``, the
guarantee counters 0.  (A delta lane past its window is a semantic
overflow: the search raises, and the run has no last line at all.)"""

from __future__ import annotations

import random
import time

from benchmark.drivers.timeboxed_bfs import (build_supervisor, end_to_end,
                                             measure)
from benchmark.harness import spans as spans_mod
from benchmark.harness import states
from benchmark.harness.runner import (GUARANTEE_COUNTERS, Check, at_least,
                                      equal)

__all__ = ["build_state", "build_settings", "build_supervisor",
           "end_to_end", "hold_table_place", "measure", "prepare",
           "reference_counts", "root_is_the_twins", "verify"]


# Bytes of the hole kept for the carry's small buffers, in front of the
# table's: the first place a small buffer fits, and a better fit for it
# than the table's hole, so none is carved out of that one.
SMALL_HOLE_BYTES = 64 << 20


def hold_table_place(cell) -> list:
    """The process's FIRST buffers on the device, before anything is
    built: a hole for small buffers, one of the visited table's exact
    shape, and a one-word fence behind each so that neither hole grows
    when a neighbour is freed.  ``[small, fence, table, fence]``."""
    import jax.numpy as jnp

    from dslabs_tpu.tpu import visited

    held = [jnp.zeros((SMALL_HOLE_BYTES // 4,), jnp.uint32),
            jnp.zeros((1,), jnp.uint32),
            visited.empty_table(cell.config["engine"]["visited_cap"]),
            jnp.zeros((1,), jnp.uint32)]
    for buf in held:
        buf.block_until_ready()
    return held


def prepare(ctx) -> None:
    """``timeboxed_bfs.prepare`` — one supervisor built, its programs
    compiled or loaded, warmed by a shallow run of the same object —
    with the table's place held from the process's start and let go as
    the first carry is built (the ``sharded.init`` dispatch, seen by
    the supervisor's ``dispatch_observer``).

    Why: 38 % of a state's cost here is the table's gathers and
    scatters, and what a bucket's 32 words cost depends on WHERE in the
    device's memory the table lies (PERF.md section 6, PR 47: 72.8 to
    82.8 ms a level between processes, every other operation equal to
    0.1 %).  The first carry goes where the small buffers that set-up
    left behind allow, which differs from process to process, and every
    later carry of the process goes back there: a process is fast or
    slow by 1-3 % as a whole.  A hole of the table's exact size, made
    while the device's memory is still empty, is the one place a
    buffer of that size fits best in every process.  Nothing is held
    once a carry exists, so the run's peak is the carry's."""
    params = ctx.cell.params
    held = hold_table_place(ctx.cell)

    def let_go(phase, tag, index, depth):
        if tag != "sharded.init":
            return
        if phase == "start" and held[2] is not None:
            held[0].delete()
            held[2].delete()
            held[0] = held[2] = None
        elif phase == "done":
            ctx.note(f"device memory under the first carry: {_memory()}")

    with spans_mod.span("construct"):
        sup = build_supervisor(ctx.cell, params["warmup_depth"])
    sup.dispatch_observer = let_go
    ctx.note("warm-up run of the same supervisor")
    with spans_mod.span("warmup"):
        warm = sup.run()
    sup.dispatch_observer = None
    if warm.end_condition != "DEPTH_EXHAUSTED":
        raise RuntimeError(f"warm-up ended {warm.end_condition}")
    if held[2] is not None:
        raise RuntimeError("no sharded.init dispatch let the table's "
                           "place go")
    ctx.note(f"device memory after the warm-up: {_memory()}")
    ctx.state.update(sup=sup, compile_s=float(warm.compile_secs),
                     fences=(held[1], held[3]))


def _memory() -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "largest_free_block_bytes")}


def build_state(spec: dict, seed: int):
    """A fresh ``SearchState`` of the configuration's ``object_state``:
    equal ``seed`` gives equal commands, every time it is called."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.clientserver.kv_workload import kv_workload
    from dslabs_tpu.labs.clientserver.kvstore import KVStore
    from dslabs_tpu.labs.primarybackup.pb import PBClient, PBServer
    from dslabs_tpu.labs.primarybackup.viewserver import ViewServer
    from dslabs_tpu.search.search_state import SearchState
    from dslabs_tpu.testing.generator import NodeGenerator

    rng = random.Random(seed)
    vsa = LocalAddress(spec["view_server"])
    key = states._word(rng)

    def server(a):
        return ViewServer(a) if a == vsa else PBServer(a, vsa, KVStore())

    state = SearchState(NodeGenerator(
        server_supplier=server,
        client_supplier=lambda a: PBClient(a, vsa),
        workload_supplier=lambda a: None))
    state.add_server(vsa)
    for i in range(1, spec["servers"] + 1):
        state.add_server(LocalAddress(f"server{i}"))
    for i in range(1, spec["clients"] + 1):
        # no expected result: what an APPEND to a shared key returns
        # depends on the order, and APPENDS_LINEARIZABLE judges it
        state.add_client_worker(
            LocalAddress(f"client{i}"),
            kv_workload([f"APPEND:{key}:{states._word(rng, 4)}"]))
    return state


def build_settings(spec: dict, max_depth=None):
    """``SearchSettings`` of the configuration's ``search`` entry."""
    from dslabs_tpu.labs.clientserver import kv_workload

    settings = states.settings(dict(spec, invariants=[], max_depth=max_depth))
    for name in spec["invariants"]:
        settings.add_invariant(getattr(kv_workload, name))
    return settings


def reference_counts(ctx, upto: int) -> dict:
    """Cumulative unique counts at depths 1..upto by the object checker
    on the seeded state (a depth-limited BFS each: the checker reports
    one count a run)."""
    from dslabs_tpu.search.search import BFS

    cfg = ctx.cell.config
    counts = {}
    for d in range(1, upto + 1):
        res = BFS(build_settings(cfg["search"], d)).run(
            build_state(cfg["deployment"]["object_state"], ctx.seed))
        counts[d] = int(res.discovered_count)
    return counts


def root_is_the_twins(ctx) -> Check:
    """The lab adapter on the seeded state binds the configuration's
    twin — its name, node counts, workload length, whether the key is
    shared, at the configuration's caps — and derives the twin's
    canonical root: nothing replayed."""
    from dslabs_tpu.tpu import backend

    cfg = ctx.cell.config
    proto = cfg["protocol"]
    kw = proto["kwargs"]
    want = [proto["name"], kw["ns"], kw["n_clients"], kw["w"],
            kw["shared_key"], kw["net_cap"], kw["timer_cap"]]
    try:
        state = build_state(cfg["deployment"]["object_state"], ctx.seed)
        binding = backend.resolve_binding(state)
        binding.check_settings(build_settings(cfg["search"]))
        twin = binding.build_protocol(kw["net_cap"], kw["timer_cap"])
        got = [twin.name, binding.ns, binding.nc, binding.w,
               binding.shared_key, twin.net_cap, twin.timer_cap]
        if binding.derive_root(None, state) != (None, []):
            got.append("root replayed, not the twin's own")
    except backend.NoTensorTwin as e:
        got = f"NoTensorTwin: {e}"
    return equal("reference.root_is_the_twins", got, want)


def verify(ctx, measured: dict) -> list:
    cfg = ctx.cell.config
    pinned = {int(d): int(n) for d, n in cfg["reference_counts"].items()}
    live_depth = int(cfg["reference_live_depth"])
    t = time.time()
    live = reference_counts(ctx, live_depth)
    ctx.note(f"object checker to depth {live_depth}: {live} in "
             f"{time.time() - t:.1f}s")
    out = measured["outcome"]
    got = {int(lv["depth"]): int(lv["unique"])
           for lv in measured["levels"]}
    checks = [equal("platform", out["platform"], ctx.dev["platform"]),
              equal("mesh_width", out["mesh_width"], ctx.cell.chips),
              equal("bytes_per_state", out["bytes_per_state"],
                    cfg["protocol"]["packed_bytes_per_state"]),
              root_is_the_twins(ctx)]
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
