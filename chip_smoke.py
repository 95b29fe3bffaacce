#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the strict search still runs
on the chip.

One process, no child that needs the chip.  Run it with no arguments on
a machine with one TPU; it exits non-zero (and prints no result line)
on any other backend — it never sets ``JAX_PLATFORMS`` and never falls
back to the CPU.  Phases, each printing one JSON line:

1. ``lab``       the lab 1 client-server search through the normal entry
                 point (``backend.tensor_bfs`` / ``tensor_dfs``, ladder
                 rung 1, exactly what ``run_tests.py --lab 1 --no-run
                 --search-backend tensor`` dispatches to), judged
                 against the object checker on the same ``SearchState``:
                 equal verdict and unique count, a violating predicate
                 whose witness replays on the object twin, and one
                 ``dfs`` call (rollout probe, then BFS).
2. ``flagship``  the flagship protocol (lab 3 multi-Paxos: 3 replicas, 2
                 clients, 842 lanes, net_cap 64, timer_cap 6) under the
                 strict sharded engine at the flagship caps — one
                 construction, two runs: depth 6 to DEPTH_EXHAUSTED with
                 a pinned unique count, then depth 9 / 60 s (printed,
                 not judged).
3. ``warm``      a second construction of the flagship search whose
                 compile seconds show that its programs were LOADED —
                 from the executable store (tpu/compile_cache.py) or
                 the persistent cache — and whose superstep has the
                 text, hash for hash, of the one compiled in place.

``--four-chips`` runs ONE phase and nothing else: the flagship protocol
on ``make_mesh(4)`` of four real devices against the one-device engine
in the same process.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Every phase is a function of its caps, so ``tests/test_chip_smoke.py``
rehearses each at tiny caps on the CPU mesh; ``main()`` checks the
device and then calls them at the real caps.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# ---------------------------------------------------------------- constants
#
# The flagship caps are the `engine` block of
# benchmark/configs/lab3-paxos-n3c2.json (chunk, frontier, visited
# table, event window), the configuration of the cells paxos3-deep and
# paxos3-deep-mesh4.  Why the chunk is 1024: until PR 32 the TPU
# compiler refused the superstep at chunk 8192 — 42.16 GB of HBM against
# the chip's 15.75 GB, almost all of it [chunk*48, 1] uint32 columns
# padded 128x by the (8, 128) tile (CHANGES.md, PR 22, has the timings
# and memory per chunk).  It compiles there now
# (tests/test_chip_compile.py, -m slow), but no chip run has timed a
# larger chunk.  A chunk is a batch size, not a width.
FLAGSHIP = dict(chunk=1024, frontier_cap=(1 << 20) + (1 << 18),
                visited_cap=1 << 24)
# That file's `ev_budget` — the (message, timer) event window the
# strict path compiles; strict runs re-step over-budget chunks, so it
# is a throughput knob, never a bound on correctness.
EV_BUDGET = (40, 8)
# Unique states of the flagship protocol after BFS depth d, strict
# (exact, so independent of device, chunk and mesh width).  Both are
# the OBJECT checker's counts (search.BFS on the lab 3 PaxosServer x3 +
# two one-PUT clients, max_depth d: 8 / 38 / 162 / 713 / 3,258 /
# 15,102 / 69,673 at depths 1-7; depth 6 took 45 s, depth 7 250 s,
# PR 22), and the CPU run of the same tensor engine gives the same
# series.
FLAGSHIP_UNIQUE = {6: 15102, 7: 69673}
FLAGSHIP_DEPTH = 6
FOUR_CHIP_DEPTH = 7
# Depth 9 is the deepest level these caps hold whole (the cell
# paxos3-deep completes it and is stopped by its clock inside depth 10,
# whose frontier passes frontier_cap): a bound by depth holds at any
# speed, where "depth 10 under 60 s" overflowed once the engine reached
# depth 10 inside the minute (PR 46's chip run: CapacityOverflow).
DEEP_DEPTH, DEEP_SECS = 9, 60.0


_T0 = time.time()


def _hb(msg: str) -> None:
    """Progress on stderr: nothing is shown while a chip call runs, so
    the tail of stderr is what says where a cut run had got to."""
    print(f"[chip_smoke +{time.time() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _emit(rec: dict) -> dict:
    print(json.dumps(rec), flush=True)
    return rec


def _device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(devices=None):
    """Peak device memory (``memory_stats()``) over ``devices``; None
    where the backend reports none (the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in (devices if devices is not None else jax.devices())]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def _cache_events():
    """Count what a construction LOADED instead of compiling while the
    block runs — JAX's own persistent-compile-cache events (hits,
    misses) and the executable store's hits (tpu/compile_cache.py: a
    program loaded there is never looked up in JAX's cache), which
    count among ``hits`` — the direct evidence of whether a
    construction compiled or read the disk."""
    import jax.monitoring

    from dslabs_tpu.tpu import compile_cache

    seen = {"hits": 0, "misses": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["misses"] += 1

    stored = compile_cache.totals()["exe_store_hit_n"]
    jax.monitoring.register_event_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_listener(listener)
        seen["hits"] += compile_cache.totals()["exe_store_hit_n"] - stored


def _text_hash(search) -> str:
    """SHA-256 of the optimised text of an engine's superstep
    executable, as its warm-up compiled or loaded it."""
    import hashlib

    return hashlib.sha256(
        search._aot_exes["superstep"].as_text().encode()).hexdigest()


def _outcome_fields(out) -> dict:
    return {
        "platform": out.platform, "device_kind": out.device_kind,
        "verdict": out.end_condition, "unique": out.unique_states,
        "explored": out.states_explored, "depth": out.depth,
        "levels": len(out.levels or []),
        "run_secs": round(out.elapsed_secs, 3),
        "dropped": out.dropped, "retries": out.retries,
        "failovers": out.failovers, "knob_retries": out.knob_retries,
        "visited_overflow": out.visited_overflow,
    }


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _clean(out, what: str) -> None:
    for k in ("dropped", "failovers", "retries", "knob_retries",
              "visited_overflow"):
        _check(getattr(out, k) == 0, f"{what}: {k}={getattr(out, k)}")


# ---------------------------------------------------------------- lab phase

def _lab1_state(num_clients: int, rounds: int):
    """tests/test_lab1.py ``_search_state`` + test09's workload: the
    lab 1 part 3 search-test shape (KV server, ``num_clients`` clients
    appending to their own keys)."""
    from dslabs_tpu.core.address import LocalAddress
    from dslabs_tpu.labs.clientserver.clientserver import (SimpleClient,
                                                           SimpleServer)
    from dslabs_tpu.labs.clientserver.kv_workload import \
        append_different_key_workload
    from dslabs_tpu.labs.clientserver.kvstore import KVStore
    from dslabs_tpu.search.search_state import SearchState
    from dslabs_tpu.testing.generator import NodeGenerator

    server = LocalAddress("server")
    gen = NodeGenerator(
        server_supplier=lambda a: SimpleServer(a, KVStore()),
        client_supplier=lambda a: SimpleClient(a, server),
        workload_supplier=lambda a: append_different_key_workload(rounds))
    state = SearchState(gen)
    state.add_server(server)
    for i in range(1, num_clients + 1):
        state.add_client_worker(LocalAddress(f"client{i}"))
    return state


def lab_phase(num_clients: int = 2, rounds: int = 2) -> dict:
    """Lab 1 through ``backend.tensor_bfs`` / ``tensor_dfs`` (the ladder
    and the rollout probe are the entry point's own) against the object
    checker."""
    from dslabs_tpu.search.results import EndCondition
    from dslabs_tpu.search.search import BFS
    from dslabs_tpu.search.settings import SearchSettings
    from dslabs_tpu.testing.predicates import (CLIENTS_DONE, NONE_DECIDED,
                                               RESULTS_OK)
    from dslabs_tpu.tpu import backend

    def mk():
        return _lab1_state(num_clients, rounds)

    def exhaust():
        # test09's second phase: prune CLIENTS_DONE, exhaust the space.
        return (SearchSettings().add_invariant(RESULTS_OK)
                .add_prune(CLIENTS_DONE).max_time(600))

    _hb("lab: object checker, then tensor_bfs x2, violation, dfs")
    t = time.time()
    obj = BFS(exhaust()).run(mk())
    obj_secs = time.time() - t
    _check(obj.end_condition == EndCondition.SPACE_EXHAUSTED,
           f"object checker: {obj.end_condition}")

    # BFS: cold call (compiles), then the same call again (warm).
    t = time.time()
    res = backend.tensor_bfs(mk(), exhaust())
    cold = time.time() - t
    t = time.time()
    res2 = backend.tensor_bfs(mk(), exhaust())
    warm = time.time() - t
    out = res.tensor_outcome
    _check(res.end_condition == obj.end_condition,
           f"bfs verdict {res.end_condition} != object "
           f"{obj.end_condition}")
    _check(res.discovered_count == obj.discovered_count,
           f"bfs unique {res.discovered_count} != object "
           f"{obj.discovered_count}")
    _check(res2.discovered_count == obj.discovered_count,
           "second bfs call disagrees")
    _clean(out, "lab bfs")

    # A violating predicate whose witness replays on the object twin:
    # tensor_bfs replays the tensor trace on the object SearchState and
    # the ORIGINAL object predicate must fail on the replayed state.
    vio = backend.tensor_bfs(
        mk(), SearchSettings().add_invariant(NONE_DECIDED).max_time(600))
    _check(vio.end_condition == EndCondition.INVARIANT_VIOLATED,
           f"violation verdict {vio.end_condition}")
    witness = vio.invariant_violating_state
    _check(witness is not None and witness.depth > 0
           and not NONE_DECIDED.check(witness).value,
           "violation witness does not replay on the object twin")

    # One dfs call of the same suite: the rollout probe (its visited
    # table is <= 2^18 slots — the insert the chip's compiler used to
    # refuse), then the strict BFS.
    t = time.time()
    dres = backend.tensor_dfs(mk(), exhaust())
    dfs_secs = time.time() - t
    _check(dres.probe_secs is not None, "dfs ran no rollout probe")
    _check(dres.end_condition == obj.end_condition
           and dres.discovered_count == obj.discovered_count,
           f"dfs {dres.end_condition}/{dres.discovered_count} != object "
           f"{obj.end_condition}/{obj.discovered_count}")
    _clean(dres.tensor_outcome, "lab dfs")

    return _emit({
        "phase": "lab", "config": f"lab1 c{num_clients} r{rounds}",
        **_outcome_fields(out),
        "object_unique": obj.discovered_count,
        "object_secs": round(obj_secs, 3),
        "first_call_secs": round(cold, 3),
        "compile_secs": round(max(cold - warm, 0.0), 3),
        "second_call_secs": round(warm, 3),
        "violation": {"verdict": vio.end_condition.name,
                      "witness_depth": witness.depth,
                      "platform": vio.tensor_outcome.platform},
        "dfs": {"verdict": dres.end_condition.name,
                "unique": dres.discovered_count,
                "probe_secs": round(dres.probe_secs, 3),
                "secs": round(dfs_secs, 3),
                "platform": dres.tensor_outcome.platform},
        "peak_bytes": _peak_bytes(),
    })


# ----------------------------------------------------------- flagship phases

def flagship_protocol():
    """The `protocol` block of benchmark/configs/lab3-paxos-n3c2.json
    (``tests/test_chip_smoke.py`` holds the two together)."""
    import dataclasses

    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    # Two clients widen the space enough to sustain large frontiers.
    # Goals are stripped: the phases judge exploration to a depth
    # against pinned counts, and a run that hit CLIENTS_DONE would end
    # early with a verdict the counts say nothing about.
    protocol = make_paxos_protocol(n=3, n_clients=2, w=1, max_slots=3,
                                   net_cap=64, timer_cap=6)
    return dataclasses.replace(protocol, goals={})


def _flagship_supervisor(mesh, chunk, frontier_cap, visited_cap,
                         max_depth):
    """The flagship protocol under the search supervisor, as the cell
    paxos3-deep builds it: ladder = sharded only (a failover would
    change what is being proven), AOT warm-up on."""
    from dslabs_tpu.tpu.supervisor import RetryPolicy, SearchSupervisor

    return SearchSupervisor(
        flagship_protocol(), ladder=("sharded",), mesh=mesh, chunk=chunk,
        frontier_cap=frontier_cap, visited_cap=visited_cap,
        max_depth=max_depth, strict=True, ev_budget=EV_BUDGET,
        policy=RetryPolicy(max_retries=3), aot_warmup=True)


def flagship_phase(chunk: int, frontier_cap: int, visited_cap: int,
                   depth: int = FLAGSHIP_DEPTH, expect_unique=None,
                   deep_depth: int = DEEP_DEPTH,
                   deep_secs: float = DEEP_SECS) -> dict:
    """One construction, two runs (``max_depth`` is not compiled in):
    ``depth`` to DEPTH_EXHAUSTED with the pinned unique count, then
    ``deep_depth`` under ``deep_secs`` — printed, not judged, beyond
    getting past ``depth`` cleanly."""
    from dslabs_tpu.tpu import compile_cache
    from dslabs_tpu.tpu.sharded import make_mesh

    mesh = make_mesh(1)
    _hb(f"flagship: construct + AOT compile at chunk {chunk}, then "
        f"depth {depth}")
    t = time.time()
    sup = _flagship_supervisor(mesh, chunk, frontier_cap, visited_cap,
                               depth)
    with _cache_events() as cache:
        out = sup.run()
    wall = time.time() - t
    _check(out.end_condition == "DEPTH_EXHAUSTED" and out.depth == depth,
           f"flagship: {out.end_condition} at depth {out.depth}")
    if expect_unique is not None:
        _check(out.unique_states == expect_unique,
               f"flagship depth {depth}: unique {out.unique_states} != "
               f"pinned {expect_unique}")
    _clean(out, "flagship")
    rec = _emit({
        "phase": "flagship", "chunk": chunk,
        "frontier_cap": frontier_cap, "visited_cap": visited_cap,
        "ev_budget": list(EV_BUDGET), "max_depth": depth,
        **_outcome_fields(out), "pinned_unique": expect_unique,
        "bytes_per_state": out.bytes_per_state,
        "bytes_per_state_unpacked": out.bytes_per_state_unpacked,
        "compile_secs": out.compile_secs,
        # hits == 0: this construction compiled everything (a cold
        # cache); hits > 0: the machine came with a warm one.
        "cache_hits": cache["hits"], "cache_misses": cache["misses"],
        "wall_secs": round(wall, 3),
        "cache_dir": compile_cache.cache_dir(),
        "peak_bytes": _peak_bytes(mesh.devices.flat),
        "superstep_text": _text_hash(sup._engines["sharded"]),
    })

    sup.max_depth, sup.max_secs = deep_depth, deep_secs
    _hb(f"flagship: depth {deep_depth} under {deep_secs:.0f}s")
    t = time.time()
    deep = sup.run()
    _check(deep.depth > depth, f"deep run stopped at depth {deep.depth}")
    _clean(deep, "flagship deep")
    _emit({
        "phase": "flagship-deep", "max_depth": deep_depth,
        "max_secs": deep_secs, **_outcome_fields(deep),
        "wall_secs": round(time.time() - t, 3),
        "peak_bytes": _peak_bytes(mesh.devices.flat),
    })
    return rec


def warm_phase(chunk: int, frontier_cap: int, visited_cap: int,
               first: dict) -> dict:
    """A SECOND construction of the flagship engine in this process:
    new jit objects, so the in-memory caches miss and the AOT warm-up
    goes to the disk — the executable store, else ``.lower().compile()``
    and the persistent cache.  It must HIT (the store's counter, JAX's
    own cache-hit events), and where the ``first`` construction
    (``flagship_phase``'s record) was cold its compile seconds must
    drop to under half."""
    from dslabs_tpu.tpu import compile_cache
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    gc.collect()
    _hb("warm: second construction")
    with _cache_events() as events:
        search = ShardedTensorSearch(
            flagship_protocol(), make_mesh(1), chunk_per_device=chunk,
            frontier_cap=frontier_cap, visited_cap=visited_cap,
            max_depth=2, strict=True, ev_budget=EV_BUDGET,
            aot_warmup=True)
    cache = compile_cache.cache_dir()
    first_was_cold = first["cache_hits"] == 0
    _check(events["hits"] > 0 and (
        not first_was_cold
        or search.compile_secs < 0.5 * first["compile_secs"]),
        f"warm construction: {events['hits']} cache hits, compiled for "
        f"{search.compile_secs:.1f}s against {first['compile_secs']:.1f}s"
        f" — neither the store nor the persistent cache at {cache} was "
        f"hit")
    # Whatever the warm-up was handed — an executable loaded from the
    # store, or from the persistent cache — is the program the first
    # construction compiled, hash for hash.
    text = _text_hash(search)
    _check(text == first.get("superstep_text", text),
           f"warm construction: superstep text {text[:12]} is not the "
           f"first construction's {first.get('superstep_text', '')[:12]}")
    out = search.run()
    _check(out.end_condition == "DEPTH_EXHAUSTED", out.end_condition)
    return _emit({
        "phase": "warm", "platform": out.platform,
        "device_kind": out.device_kind,
        "first_compile_secs": first["compile_secs"],
        "first_was_cold": first_was_cold,
        "warm_compile_secs": round(search.compile_secs, 3),
        "cache_hits": events["hits"], "cache_misses": events["misses"],
        "store_hits": compile_cache.totals()["exe_store_hit_n"],
        "superstep_text": text, "cache_dir": cache,
        "cache_entries": len(os.listdir(cache)),
        "verdict": out.end_condition, "unique": out.unique_states,
    })


def four_chip_phase(chunk: int, frontier_cap: int, visited_cap: int,
                    depth: int = FOUR_CHIP_DEPTH, expect_unique=None,
                    width: int = 4) -> dict:
    """The flagship protocol on ``make_mesh(width)`` against the
    one-device engine in the same process: equal verdict and unique
    count, the visited table and both frontier buffers laid out over
    ``width`` DISTINCT devices, every device's explored lane non-zero."""
    from dslabs_tpu.tpu.sharded import make_mesh

    runs = {}
    for w in (1, width):
        mesh = make_mesh(w)
        _hb(f"four-chips: mesh x{w}: construct + AOT compile, then "
            f"depth {depth}")
        t = time.time()
        sup = _flagship_supervisor(mesh, chunk, frontier_cap,
                                   visited_cap, depth)
        out = sup.run()
        _hb(f"four-chips: mesh x{w}: {out.end_condition} unique "
            f"{out.unique_states} (compile {out.compile_secs:.1f}s, "
            f"search {out.elapsed_secs:.1f}s)")
        _check(out.end_condition == "DEPTH_EXHAUSTED", out.end_condition)
        _clean(out, f"mesh x{w}")
        eng = sup._engines["sharded"]
        runs[w] = (out, eng, mesh, time.time() - t)
    one, wide = runs[1][0], runs[width][0]
    _check(one.unique_states == wide.unique_states
           and one.end_condition == wide.end_condition,
           f"x{width} {wide.end_condition}/{wide.unique_states} != x1 "
           f"{one.end_condition}/{one.unique_states}")
    if expect_unique is not None:
        _check(wide.unique_states == expect_unique,
               f"depth {depth}: unique {wide.unique_states} != pinned "
               f"{expect_unique}")
    # Layout: the carry the run starts from (the same init program the
    # run dispatched) must sit on `width` distinct devices — code that
    # has never seen a second chip may have put everything on the first.
    eng, mesh = runs[width][1], runs[width][2]
    _hb("four-chips: layout of the initial carry")
    carry = eng._init_carry(eng.initial_state())
    layout = {}
    for leaf in ("visited", "cur", "nxt"):
        devs = carry[leaf].sharding.device_set
        layout[leaf] = sorted(d.id for d in devs)
        _check(len(devs) == width,
               f"{leaf} is laid out over {len(devs)} devices, not {width}")
        shard_devs = {s.device for s in carry[leaf].addressable_shards}
        _check(len(shard_devs) == width, f"{leaf} shards share devices")
    del carry
    explored = [0] * width
    for lv in wide.levels or []:
        for i, e in enumerate(lv["per_device"]["explored"]):
            explored[i] += e
    _check(all(e > 0 for e in explored),
           f"per-device explored lanes {explored}: an idle device")
    return _emit({
        "phase": "four-chips", "mesh_width": width, "chunk": chunk,
        "frontier_cap": frontier_cap, "visited_cap": visited_cap,
        "max_depth": depth, **_outcome_fields(wide),
        "pinned_unique": expect_unique,
        "one_device": {**_outcome_fields(one),
                       "compile_secs": one.compile_secs,
                       "wall_secs": round(runs[1][3], 3)},
        "compile_secs": wide.compile_secs,
        "wall_secs": round(runs[width][3], 3),
        "layout_device_ids": layout,
        "explored_per_device": explored,
        "peak_bytes": _peak_bytes(mesh.devices.flat),
    })


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the four-device flagship phase "
                         "against the one-device engine")
    args = ap.parse_args(argv)

    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: the default backend is {dev['platform']!r} "
              f"({dev['kind']}), not a TPU — nothing was run",
              file=sys.stderr)
        return 2
    if args.four_chips:
        if dev["count"] < 4:
            print(f"chip_smoke --four-chips: {dev['count']} device(s)",
                  file=sys.stderr)
            return 2
        four_chip_phase(**FLAGSHIP,
                        expect_unique=FLAGSHIP_UNIQUE[FOUR_CHIP_DEPTH])
    else:
        lab_phase()
        flag = flagship_phase(
            **FLAGSHIP, expect_unique=FLAGSHIP_UNIQUE[FLAGSHIP_DEPTH])
        warm_phase(**FLAGSHIP, first=flag)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
