"""Time the sharded chunk step end-to-end and in pieces on the current
accelerator — a thin client of the telemetry API (tpu/telemetry.py):
every timed block is a span, the table is the shared per-site latency
renderer, and ``--flight <path>`` leaves a flight log the report CLI
can render.  Dev tool, not part of the test suite."""

import sys

import jax

from dslabs_tpu.tpu import compile_cache

compile_cache.setup()
import numpy as np

from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
from dslabs_tpu.tpu.telemetry import Telemetry, render_sites


def main():
    flight = None
    if "--flight" in sys.argv:
        flight = sys.argv[sys.argv.index("--flight") + 1]
    tel = Telemetry(flight_log=flight, engine_hint="profile_sharded")

    protocol = make_paxos_protocol(n=3, n_clients=2, w=1, max_slots=3,
                                   net_cap=64, timer_cap=6)
    mesh = make_mesh(len(jax.devices()))
    search = ShardedTensorSearch(
        protocol, mesh, chunk_per_device=256,
        frontier_cap=1 << 16, visited_cap=1 << 21, max_depth=1,
        strict=False)
    state = search.initial_state()
    with mesh:
        carry = search._init_carry(state)
        with tel.span("profile.chunk_step_compile"):
            carry = search._chunk_step(carry)
            jax.block_until_ready(carry["nxt_n"])

        # steady state: run 20 chunk steps back to back (the carry-resident
        # chunk index self-increments; work is shape-identical regardless
        # of occupancy) — one span each, so the table shows p50/p90.
        for _ in range(20):
            with tel.span("profile.chunk_step"):
                carry = search._chunk_step(carry)
                jax.block_until_ready(carry["nxt_n"])

        with tel.span("profile.finish_level_compile"):
            carry = search._finish_level(carry)
            jax.block_until_ready(carry["nxt_n"])
        for _ in range(5):
            with tel.span("profile.finish_level"):
                carry = search._finish_level(carry)
                jax.block_until_ready(carry["nxt_n"])

        # host-sync cost per level
        for _ in range(5):
            with tel.span("profile.host_sync"):
                _ = int(np.asarray(carry["overflow"]).sum())
                _ = int(np.asarray(carry["drops"]).sum())
                _ = np.asarray(carry["vis_n"])
                _ = int(np.asarray(carry["explored"]).sum())
                _ = np.asarray(carry["flag_cnt"])
                _ = int(np.asarray(carry["nxt_n"]).max())

    print(render_sites(tel.summary()))
    if flight:
        print(f"\nflight log: {flight} "
              f"(python -m dslabs_tpu.tpu.telemetry report {flight})")
    tel.close()


if __name__ == "__main__":
    main()
