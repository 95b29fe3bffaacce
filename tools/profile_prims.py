"""Microbenchmark TPU primitive costs for [B, lanes] row movement.

A thin client of the telemetry API (tpu/telemetry.py): each iteration is
a span (`prims.<name>`), the table is the shared per-site latency
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

B, LANES, F = 24064, 1354, 65537
ITERS = 10


def bench(tel, name, fn, *args):
    site = "prims." + name.replace(" ", "_")
    fn = jax.jit(fn, donate_argnums=0) if name.startswith("donate") \
        else jax.jit(fn)
    with tel.span(f"{site}.compile"):
        out = fn(*args)
        jax.block_until_ready(out)
    for _ in range(ITERS):
        with tel.span(site):
            out = fn(*args)
            jax.block_until_ready(out)
    st = tel.summary()["sites"][site]
    dt = max(st["p50"], 1e-9)
    gb = B * LANES * 4 / 1e9
    print(f"{name:36s} {dt*1e3:9.2f} ms  ({gb/dt:6.1f} GB/s eff)")


def main():
    flight = None
    if "--flight" in sys.argv:
        flight = sys.argv[sys.argv.index("--flight") + 1]
    tel = Telemetry(flight_log=flight, engine_hint="profile_prims")

    key = jax.random.PRNGKey(0)
    rows = jax.random.randint(key, (B, LANES), 0, 1000, jnp.int32)
    nxt = jnp.zeros((F, LANES), jnp.int32)
    gidx = jax.random.randint(key, (2 * B,), 0, B, jnp.int32)
    sdst = jax.random.permutation(key, F)[:B]
    sel = jax.random.bernoulli(key, 0.3, (B,))

    bench(tel, "copy rows * 2", lambda r: r * 2, rows)
    bench(tel, "gather 2B rows [gidx]", lambda r, g: r[g], rows, gidx)
    bench(tel, "gather B rows [sdst range]", lambda r, s: r[s % B],
          rows, sdst)
    bench(tel, "scatter B rows into F",
          lambda n, r, s: n.at[s].set(r), nxt, rows, sdst)
    bench(tel, "donate scatter B rows into F",
          lambda n, r, s: n.at[s].set(r), nxt, rows, sdst)
    bench(tel, "dyn_update_slice B rows",
          lambda n, r: jax.lax.dynamic_update_slice(n, r, (0, 0)), nxt, rows)
    bench(tel, "donate dyn_update_slice",
          lambda n, r: jax.lax.dynamic_update_slice(n, r, (0, 0)), nxt, rows)

    # masked compact scatter (the nxt append pattern)
    def append(n, r, s):
        spos = jnp.cumsum(s) - 1
        dst = jnp.where(s & (spos < F), spos, F - 1)
        return n.at[dst].set(r)
    bench(tel, "donate masked append scatter", append, nxt, rows, sel)
    # take_along_axis variant
    bench(tel, "take_along_axis 2B rows",
          lambda r, g: jnp.take_along_axis(
              r, g[:, None].astype(jnp.int32), axis=0), rows, gidx)

    print()
    print(render_sites(tel.summary()))
    if flight:
        print(f"\nflight log: {flight} "
              f"(python -m dslabs_tpu.tpu.telemetry report {flight})")
    tel.close()


if __name__ == "__main__":
    main()
