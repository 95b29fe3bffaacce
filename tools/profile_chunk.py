"""Decompose the TPU chunk-step cost: which stage dominates?

Times jitted sub-programs of the bench configuration's expand pipeline
on whatever accelerator is present — a thin client of the telemetry
API (tpu/telemetry.py): each stage is a compile span + N steady spans
and the output is the shared per-site latency table (the old hand-rolled
``bench_fn`` stopwatch scaffold is gone).  Not part of the test suite —
a dev tool."""

import jax

from dslabs_tpu.tpu import compile_cache

compile_cache.setup()
import jax.numpy as jnp

from dslabs_tpu.tpu.engine import (TensorSearch, canonicalize_net,
                                   insert_messages, state_fingerprints,
                                   append_timers, flatten_state)
from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol
from dslabs_tpu.tpu.telemetry import Telemetry, render_sites

TEL = Telemetry(engine_hint="profile_chunk")


def timed(name, fn, *args, iters=5):
    """One compile span + ``iters`` steady spans through the telemetry
    recorder; returns the steady mean seconds (for derived rates)."""
    fn = jax.jit(fn)
    with TEL.span(f"profile.{name}.compile"):
        jax.block_until_ready(fn(*args))
    for _ in range(iters):
        with TEL.span(f"profile.{name}"):
            jax.block_until_ready(fn(*args))
    h = TEL.registry.histogram(f"dispatch_secs.profile.{name}")
    return h.total / max(h.count, 1)


def main():
    protocol = make_paxos_protocol(n=3, n_clients=2, w=1, max_slots=3,
                                   net_cap=64, timer_cap=6)
    C = 256
    search = TensorSearch(protocol, chunk=C)
    state = search.initial_state()
    chunk_state = jnp.repeat(flatten_state(state), C, axis=0)
    chunk_valid = jnp.ones(C, bool)
    ne = search._num_events()
    n_pairs = C * ne
    print(f"chunk={C} events/state={ne} pairs={n_pairs} "
          f"lanes={flatten_state(state).shape[1]}")

    # full expand
    dt = timed("expand_chunk", search._expand_chunk, chunk_state,
               chunk_valid)
    print(f"full _expand_chunk -> {n_pairs/max(dt, 1e-9):,.0f} "
          "explored pairs/s")

    # pieces, over the flattened pair batch
    rep_state = jnp.repeat(chunk_state, ne, axis=0)
    ev = jnp.tile(jnp.arange(ne), C)

    timed("step_one", lambda rs, e: jax.vmap(search._step_one)(rs, e),
          rep_state, ev)

    p = protocol
    rep_states = search.unflatten_rows(rep_state)   # views into the rows
    live = p.max_live_sends or p.max_sends
    sends = jnp.full((n_pairs, live, p.msg_width), 2**31 - 1, jnp.int32)

    timed("insert_messages",
          lambda net, s: jax.vmap(insert_messages)(net, s),
          rep_states["net"], sends)
    timed("canonicalize_net",
          lambda net: jax.vmap(canonicalize_net)(net),
          rep_states["net"])

    new_t = jnp.full((n_pairs, p.max_sets, 1 + p.timer_width), 2**31 - 1,
                     jnp.int32)
    timed("append_timers",
          lambda t, nt: jax.vmap(append_timers)(t, nt),
          rep_states["timers"], new_t)

    from dslabs_tpu.tpu.engine import row_fingerprints

    timed("row_fingerprints", row_fingerprints, rep_state)

    # the in-chunk lexsort
    fp = row_fingerprints(rep_state)

    def sort_only(fp, valids):
        inv = ~valids
        order = jnp.lexsort((fp[:, 3], fp[:, 2], fp[:, 1], fp[:, 0], inv))
        fps = fp[order]
        first = jnp.ones(fps.shape[0], bool).at[1:].set(
            jnp.any(fps[1:] != fps[:-1], axis=1))
        return jnp.zeros_like(valids).at[order].set(first & valids)

    timed("lexsort_unique", sort_only, fp, jnp.ones(n_pairs, bool))

    # predicate flags
    rows_all = jax.vmap(search._step_one)(rep_state, ev)[0]

    def flags_only(rows):
        states = search.unflatten_rows(rows)
        out = {}
        for kind, preds in (("inv", p.invariants), ("goal", p.goals),
                            ("prune", p.prunes)):
            for name, fn in preds.items():
                out[f"{kind}:{name}"] = jax.vmap(fn)(states)
        return out

    timed("predicate_flags", flags_only, rows_all)

    print()
    print(render_sites(TEL.summary()))


if __name__ == "__main__":
    main()
