"""Self-feeding (dependency-chained) microbenchmark: wide row scatter /
gather cost vs lane alignment.

A thin client of the telemetry API (tpu/telemetry.py): each iteration is
a span (`align.l<lanes>.<op>`), the table is the shared per-site latency
renderer, ``--flight <path>`` leaves a flight log the report CLI can
render.  Dev tool."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from dslabs_tpu.tpu import compile_cache

compile_cache.setup()
import jax.numpy as jnp

from dslabs_tpu.tpu.telemetry import Telemetry, render_sites

B, F = 24064, 65537
ITERS = 10


def run(tel, lanes):
    key = jax.random.PRNGKey(0)
    rows = jax.random.randint(key, (B, lanes), 0, 1000, jnp.int32)
    nxt = jnp.zeros((F, lanes), jnp.int32)
    sdst = jax.random.permutation(key, F)[:B]
    gidx = jax.random.randint(key, (B,), 0, F, jnp.int32)

    @jax.jit
    def scatter_step(nxt, rows):
        nxt = nxt.at[sdst].set(rows)
        # feed back: rows depend on nxt so iterations serialize
        rows = rows + nxt[0, 0]
        return nxt, rows

    @jax.jit
    def gather_step(nxt, rows):
        g = nxt[gidx]                      # [B, lanes] wide gather
        rows = rows + g
        nxt = nxt + rows[0, 0]
        return nxt, rows

    gb = B * lanes * 4 / 1e9
    for name, fn in (("scatter", scatter_step), ("gather", gather_step)):
        site = f"align.l{lanes}.{name}"
        with tel.span(f"{site}.compile"):
            n2, r2 = fn(nxt, rows)
            jax.block_until_ready(r2)
        n2, r2 = nxt, rows
        for _ in range(ITERS):
            with tel.span(site, gb=gb):
                n2, r2 = fn(n2, r2)
                jax.block_until_ready(r2)
        st = tel.summary()["sites"][site]
        dt = max(st["p50"], 1e-9)
        print(f"lanes={lanes:5d} {name:8s} {dt*1e3:9.2f} ms "
              f"({gb/dt:7.1f} GB/s eff)")


def main():
    flight = None
    if "--flight" in sys.argv:
        flight = sys.argv[sys.argv.index("--flight") + 1]
    tel = Telemetry(flight_log=flight, engine_hint="profile_align")
    lane_args = [int(x) for x in sys.argv[1:] if x.isdigit()]
    for lanes in (lane_args or [1354, 1408, 1280]):
        run(tel, lanes)
    print()
    print(render_sites(tel.summary()))
    if flight:
        print(f"\nflight log: {flight} "
              f"(python -m dslabs_tpu.tpu.telemetry report {flight})")
    tel.close()


if __name__ == "__main__":
    main()
