"""Bisect the sharded chunk step via the engine's `_stop_after` dev hook:
run a REAL search to load the frontier + visited table, snapshot the
carry, then time progressively truncated variants of the genuine
`_build_chunk_step` program (no drifting copy).  Self-feeding loops only
(each step consumes the previous carry) — independent-arg microbenchmarks
measure the argument transfer, not the step.

A thin client of the telemetry API (tpu/telemetry.py): every timed
iteration is a span (`bisect.<stage>`; the compile-paying first dispatch
is its own `.compile` site), the table is the shared per-site latency
renderer, and ``--flight <path>`` leaves a flight log the report CLI can
render.  Dev tool, not part of the test suite."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from dslabs_tpu.tpu import compile_cache

compile_cache.setup()

from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
from dslabs_tpu.tpu.telemetry import Telemetry, render_sites

ARGS = [a for a in sys.argv[1:] if not a.startswith("--")]
CHUNK = int(ARGS[0]) if len(ARGS) > 0 else 1024
EVB = int(ARGS[1]) if len(ARGS) > 1 else 48  # 48 -> (40, 8)
WARM_DEPTH = 10
ITERS = 20
STAGES = ["events", "handlers", "tail", "fp", "expand", "route",
          "a2a", "probe", "back", None]


def make_search(stop_after):
    import dataclasses
    protocol = make_paxos_protocol(n=3, n_clients=2, w=1, max_slots=3,
                                   net_cap=64, timer_cap=6)
    protocol = dataclasses.replace(protocol, goals={})
    mesh = make_mesh(len(jax.devices()))
    s = ShardedTensorSearch(protocol, mesh, chunk_per_device=CHUNK,
                            frontier_cap=1 << 17, visited_cap=1 << 23,
                            max_depth=WARM_DEPTH, strict=False,
                            ev_budget=((40, 8) if EVB == 48 else (EVB or None)))
    s._stop_after = stop_after
    # Rebuild the jitted step AFTER setting the hook (the ctor built it
    # with stop_after=None).
    s._chunk_step = jax.jit(s._build_chunk_step(), donate_argnums=0)
    return s


def warm_carry(s):
    """Run the REAL search (full program) to WARM_DEPTH, returning the
    loaded device-resident carry — no host roundtrip of the 1.5 GB
    carry."""
    import time

    state = s.initial_state()
    carry = s._init_carry(state)
    max_n = 1
    depth = 0
    t0 = time.time()
    while depth < WARM_DEPTH:
        depth += 1
        n_chunks = -(-(max_n + s.n_devices - 1) // s.cpd)
        for _ in range(n_chunks):
            carry = s._chunk_step(carry)
        _, _, _, _, max_n, _ = s._sync_checks(carry, depth, t0)
        carry = s._finish_level(carry)
    return carry, max_n


def main():
    flight = None
    if "--flight" in sys.argv:
        flight = sys.argv[sys.argv.index("--flight") + 1]
    tel = Telemetry(flight_log=flight, engine_hint="profile_sharded2")

    for stop in STAGES:
        sv = make_search(None)          # warm with the FULL program
        name = stop or "full"
        with sv.mesh:
            carry, max_n = warm_carry(sv)
            if stop is not None:        # then swap in the variant
                sv._stop_after = stop
                sv._chunk_step = jax.jit(sv._build_chunk_step(),
                                         donate_argnums=0)
            c = carry
            with tel.span(f"bisect.{name}.compile", frontier=max_n):
                c = sv._chunk_step(c)
                jax.block_until_ready(c["explored"])
            # Each iteration blocks inside its span (same discipline as
            # tools/profile_sharded.py): the chunk step self-feeds, so
            # the device work is serialized either way and the span
            # wall is the honest per-step cost.
            for _ in range(ITERS):
                with tel.span(f"bisect.{name}"):
                    c = sv._chunk_step(c)
                    jax.block_until_ready(c["explored"])
            st = tel.summary()["sites"][f"bisect.{name}"]
            dt = max(st["total"] / max(st["count"], 1), 1e-9)
            print(f"{name:8s} (frontier/dev {max_n}) "
                  f"steady {dt*1e3:8.2f} ms  "
                  f"({CHUNK*sv._num_events()/dt/1e6:.2f}M pairs/s)",
                  flush=True)

    print()
    print(render_sites(tel.summary()))
    if flight:
        print(f"\nflight log: {flight} "
              f"(python -m dslabs_tpu.tpu.telemetry report {flight})")
    tel.close()


if __name__ == "__main__":
    main()
