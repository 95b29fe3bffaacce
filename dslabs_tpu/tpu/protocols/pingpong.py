"""Tensorised twin of lab 0 ping-pong (SURVEY §8.3 — the minimum
end-to-end slice).

Object model being mirrored (dslabs_tpu/labs/pingpong/pingpong.py +
testing/client_worker.py): a stateless PingServer echoing Ping(i) -> Pong(i)
and a ClientWorker-wrapped PingClient walking a ``hi-%i`` workload of W
commands with a (10,10) retry timer.  The combined client state collapses to
one integer k: "waiting on command k" (k in 1..W) or done (W+1) — the
worker pumps the next command inside the same handler, so intermediate
states never appear in the search graph (ClientWorker.java:174-235).

Lanes:
  nodes  = [k]                                   (server is stateless)
  msg    = [tag, i]        tag 0 = PingRequest -> server, 1 = PongReply
  timer  = [tag, min, max, i]                    PingTimer(i), (10, 10)
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from dslabs_tpu.tpu.engine import SENTINEL, TensorProtocol

__all__ = ["make_pingpong_protocol", "make_exhaustive_pingpong",
           "SERVER", "CLIENT"]

SERVER, CLIENT = 0, 1
REQ, REPLY = 0, 1
PING_MS = 10


def make_pingpong_protocol(workload_size: int) -> TensorProtocol:
    w = workload_size
    mw, tw = 2, 4
    max_sends, max_sets = 1, 1

    # ---- object-twin decoders (tpu/trace.py): canonical parity config —
    # server "pingserver", client "client1", workload hi-{i}
    # (tests/test_tpu_engine.py).

    def decode_message(rec):
        from dslabs_tpu.core.address import LocalAddress
        from dslabs_tpu.labs.pingpong.pingpong import (Ping, PingRequest,
                                                       Pong, PongReply)

        tag, i = int(rec[0]), int(rec[1])
        server = LocalAddress("pingserver")
        client = LocalAddress("client1")
        if tag == REQ:
            return client, server, PingRequest(Ping(f"hi-{i}"))
        return server, client, PongReply(Pong(f"hi-{i}"))

    def decode_timer(node_idx, rec):
        from dslabs_tpu.core.address import LocalAddress
        from dslabs_tpu.labs.pingpong.pingpong import Ping, PingTimer

        return (LocalAddress("client1"), PingTimer(Ping(f"hi-{int(rec[3])}")),
                PING_MS, PING_MS)

    def init_nodes():
        return np.array([1], np.int32)  # waiting on command 1

    def init_messages():
        return np.array([[REQ, 1]], np.int32)

    def init_timers():
        return np.array([[CLIENT, 1, PING_MS, PING_MS, 1]], np.int32)

    def no_sends():
        return jnp.full((max_sends, mw), SENTINEL, jnp.int32)

    def no_sets():
        return jnp.full((max_sets, 1 + tw), SENTINEL, jnp.int32)

    def send_request(i):
        return jnp.stack([jnp.full((), REQ, jnp.int32), i])[None, :]

    def set_ping_timer(i):
        return jnp.stack([jnp.full((), CLIENT, jnp.int32),
                          jnp.full((), 1, jnp.int32),
                          jnp.full((), PING_MS, jnp.int32),
                          jnp.full((), PING_MS, jnp.int32), i])[None, :]

    def step_message(nodes, msg):
        k = nodes[0]
        tag, i = msg[0], msg[1]

        # PingRequest at the server: echo a PongReply (PingServer.java:26-31).
        is_req = tag == REQ
        req_sends = jnp.where(is_req,
                              jnp.stack([jnp.full((), REPLY, jnp.int32), i])[None, :],
                              no_sends())

        # PongReply at the client: if it answers the in-flight ping, the
        # worker records the result and pumps the next command.
        matches = (tag == REPLY) & (k == i) & (k <= w)
        k2 = jnp.where(matches, k + 1, k)
        has_next = matches & (k2 <= w)
        reply_sends = jnp.where(has_next, send_request(k2), no_sends())
        reply_sets = jnp.where(has_next, set_ping_timer(k2), no_sets())

        nodes2 = nodes.at[0].set(k2)
        sends = jnp.where(is_req, req_sends, reply_sends)
        sets = jnp.where(is_req, no_sets(), reply_sets)
        return nodes2, sends, sets

    def step_timer(nodes, node_idx, timer):
        k = nodes[0]
        i = timer[3]
        live = (node_idx == CLIENT) & (k == i) & (k <= w)
        sends = jnp.where(live, send_request(i), no_sends())
        sets = jnp.where(live, set_ping_timer(i), no_sets())
        return nodes, sends, sets

    def msg_dest(msg):
        return jnp.where(msg[0] == REQ, SERVER, CLIENT)

    def clients_done(state):
        return state["nodes"][0] == w + 1

    def results_ok(state):
        return jnp.full((), True)  # the echo protocol cannot mis-answer

    return TensorProtocol(
        name=f"pingpong-w{w}",
        n_nodes=2,
        node_width=1,
        msg_width=mw,
        timer_width=tw,
        net_cap=2 * w + 2,
        timer_cap=w + 2,
        max_sends=max_sends,
        max_sets=max_sets,
        init_nodes=init_nodes,
        init_messages=init_messages,
        init_timers=init_timers,
        step_message=step_message,
        step_timer=step_timer,
        msg_dest=msg_dest,
        invariants={"RESULTS_OK": results_ok},
        goals={"CLIENTS_DONE": clients_done},
        decode_message=decode_message,
        decode_timer=decode_timer,
    )


def make_exhaustive_pingpong(workload_size: int = 2) -> TensorProtocol:
    """The goal-pruned exhaustive variant: CLIENTS_DONE becomes a prune
    so a strict search measures full-space parity instead of a
    first-goal race — the canonical small JOB UNIT the checking
    service and its chaos-isolation soak submit (a
    ``"module:callable"`` factory spec that crosses the warden spawn
    boundary with no transform needed)."""
    import dataclasses

    p = make_pingpong_protocol(workload_size)
    return dataclasses.replace(
        p, goals={}, prunes={"CLIENTS_DONE": p.goals["CLIENTS_DONE"]})
