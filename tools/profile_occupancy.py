"""Measure per-state valid-event occupancy by BFS level on the bench
config: how many of the net_cap + nn*timer_cap event slots are actually
deliverable?  Sets the budget for occupancy-compacted enumeration.

A thin client of the telemetry API (tpu/telemetry.py): each level's
occupancy scalars become telemetry level records (and flight-log lines
under ``--flight <path>``) and the chunk work is spanned, replacing the
old hand-rolled timing scaffold.  Dev tool, not part of the suite."""

import sys
import time

import jax

from dslabs_tpu.tpu import compile_cache

compile_cache.setup()
import jax.numpy as jnp
import numpy as np

from dslabs_tpu.tpu.engine import SENTINEL, timer_deliverable_mask
from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
from dslabs_tpu.tpu.telemetry import Telemetry, render_sites


def main():
    flight = None
    if "--flight" in sys.argv:
        flight = sys.argv[sys.argv.index("--flight") + 1]
    tel = Telemetry(flight_log=flight, engine_hint="profile_occupancy")

    protocol = make_paxos_protocol(n=3, n_clients=2, w=1, max_slots=3,
                                   net_cap=64, timer_cap=6)
    import dataclasses
    protocol = dataclasses.replace(protocol, goals={})
    mesh = make_mesh(len(jax.devices()))
    search = ShardedTensorSearch(
        protocol, mesh, chunk_per_device=256, frontier_cap=1 << 16,
        visited_cap=1 << 22, max_depth=1, strict=False)
    tel.attach(search)      # chunk/promote dispatches become spans

    def stats(carry):
        cur, cur_n = carry["cur"], carry["cur_n"][0]
        states = search.unflatten_rows(cur)
        valid_state = jnp.arange(cur.shape[0]) < cur_n
        msg_occ = states["net"][:, :, 0] != SENTINEL          # [F, cap]
        tmask = jax.vmap(jax.vmap(timer_deliverable_mask))(
            states["timers"])                                  # [F, nn, tc]
        nev = (jnp.sum(msg_occ, axis=1)
               + jnp.sum(tmask, axis=(1, 2))).astype(jnp.int32)
        nev = jnp.where(valid_state, nev, 0)
        hist = jnp.bincount(nev, weights=valid_state.astype(jnp.int32),
                            length=search._num_events() + 1)
        return (hist, jnp.max(nev), jnp.sum(nev),
                jnp.sum(valid_state.astype(jnp.int32)),
                jnp.max(jnp.sum(msg_occ, axis=1) * valid_state),
                jnp.max(jnp.sum(tmask, axis=(1, 2)) * valid_state))

    jstats = jax.jit(stats)

    with mesh:
        state = search.initial_state()
        carry = search._init_carry(state)
        t0 = time.time()
        max_n = 1
        depth = 0
        while max_n > 0 and depth < 24 and time.time() - t0 < 400:
            depth += 1
            t_lvl = time.time()
            n_chunks = -(-(max_n + search.n_devices - 1) // search.cpd)
            for _ in range(n_chunks):
                carry = search._chunk_step(carry)
            _, _, _, drops, max_n, _ = search._sync_checks(carry, depth,
                                                           t0)
            carry = search._finish_level(carry)
            hist, mx, tot, n, mmx, tmx = jax.tree.map(np.asarray,
                                                      jstats(carry))
            if n == 0:
                break
            mean = tot / max(int(n), 1)
            c = np.cumsum(hist)
            p99 = int(np.searchsorted(c, 0.99 * c[-1]))
            p90 = int(np.searchsorted(c, 0.90 * c[-1]))
            # The occupancy scalars become one telemetry level record
            # per depth — the report CLI renders the series, and the
            # live print below is just a view of the same record.
            rec = {"depth": int(depth),
                   "wall": round(time.time() - t_lvl, 4),
                   "explored": int(tot), "unique": int(n),
                   "next_frontier": int(max_n),
                   "ev_mean": round(float(mean), 2),
                   "ev_p90": p90, "ev_p99": p99, "ev_max": int(mx),
                   "msgs_max": int(mmx), "timers_max": int(tmx),
                   "drops": int(drops)}
            tel.on_level("occupancy", rec)
            print(f"lvl {depth:2d} n={int(n):6d} mean={mean:5.1f} "
                  f"p90={p90} p99={p99} max={int(mx)} "
                  f"msgs_max={int(mmx)} tmax={int(tmx)} drops={drops}",
                  flush=True)

    print()
    print(render_sites(tel.summary()))
    if flight:
        print(f"\nflight log: {flight} "
              f"(python -m dslabs_tpu.tpu.telemetry report {flight})")
    tel.close()


if __name__ == "__main__":
    main()
