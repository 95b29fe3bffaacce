"""Device-sharded swarm explorer (ISSUE 5): diversified random-walk
fleets with shared dedup and replay-verified witnesses
(dslabs_tpu/tpu/swarm.py), proven on the virtual CPU mesh:

* seeded determinism — same seed, same witness, bit for bit;
* swarm-vs-BFS verdict parity on pingpong + lab1 (the host BFS loop is
  the parity oracle; a minimized swarm witness can never undercut the
  BFS's minimal violation depth);
* dedup sharing — walkers restarting from a mid-BFS checkpoint
  frontier (table pre-seeded with the BFS's keys) re-tread covered
  territory at a measurably lower rate than a root-started fleet;
* frontier-seeding resume parity — a swarm cut mid-flight resumes
  from its round checkpoint to the IDENTICAL witness;
* FaultPlan transient-retry inside a swarm dispatch (the `_dispatch`
  seam contract);
* loud walker-overflow accounting (the old rollout probe restarted
  capacity-truncated walkers silently);
* the portfolio acceptance: on a deep-narrow violation with a fixed
  wall-clock budget, BFS alone returns TIME_EXHAUSTED while
  ``SearchSupervisor(portfolio=True)`` returns the violation with a
  minimized, independently-replayed witness.

Deep-narrow paxos scenarios are marked ``slow`` + ``perf`` and run via
``make swarm-smoke``.
"""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dslabs_tpu.tpu.engine import (CapacityOverflow, SENTINEL,  # noqa: E402
                                   TensorProtocol, TensorSearch)
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import make_mesh  # noqa: E402
from dslabs_tpu.tpu.supervisor import (FaultPlan, RetryPolicy,  # noqa: E402
                                       SearchSupervisor, install_retry)
from dslabs_tpu.tpu import swarm as swarm_mod  # noqa: E402
from dslabs_tpu.tpu.swarm import SwarmSearch  # noqa: E402

pytestmark = pytest.mark.swarm


def _violating(proto):
    """Plant a reachable violation: the completion goal negated into an
    invariant (violated exactly at the done state — the deepest state
    of the space, which is what the walkers are for)."""
    done = proto.goals["CLIENTS_DONE"]
    return dataclasses.replace(
        proto, goals={},
        invariants={"NOT_DONE": lambda s, f=done: ~f(s)})


def _swarm(proto, **kw):
    kw.setdefault("mesh", make_mesh(2))
    kw.setdefault("walkers_per_device", 16)
    kw.setdefault("max_steps", 32)
    kw.setdefault("steps_per_round", 32)
    kw.setdefault("seed", 7)
    kw.setdefault("visited_cap", 1 << 12)
    return SwarmSearch(proto, **kw)


def make_lock_protocol(m=6, k=9, noise_bits=16):
    """The deep-narrow scenario: a combination lock.  ``m`` persistent
    digit messages (delivery never removes a message), progress
    advances only on the ONE correct next digit, and a noise register
    folds every delivered digit into the state — so the space branches
    ``m`` ways per step while the violation (``p == k``) sits at depth
    >= k down exactly one digit sequence.  BFS must breadth through
    ~m^d states per level; a random walker reaches depth k in ~m*k
    steps."""
    MW, TW = 2, 3
    mask = (1 << noise_bits) - 1

    def init_nodes():
        return np.array([0, 0], np.int32)

    def init_messages():
        return np.array([[d, 0] for d in range(m)], np.int32)

    def init_timers():
        return np.zeros((0, 1 + TW), np.int32)

    def step_message(nodes, msg):
        d = msg[0]
        p, noise = nodes[0], nodes[1]
        good = d == (p * 5 + 3) % m
        p2 = jnp.where(good, p + 1, p)
        noise2 = (noise * 31 + d + 1) & mask
        nodes2 = nodes.at[0].set(p2).at[1].set(noise2)
        return (nodes2, jnp.full((1, MW), SENTINEL, jnp.int32),
                jnp.full((1, 1 + TW), SENTINEL, jnp.int32))

    def step_timer(nodes, node_idx, timer):
        return (nodes, jnp.full((1, MW), SENTINEL, jnp.int32),
                jnp.full((1, 1 + TW), SENTINEL, jnp.int32))

    return TensorProtocol(
        name=f"lock-m{m}-k{k}-b{noise_bits}", n_nodes=1, node_width=2,
        msg_width=MW, timer_width=TW, net_cap=m, timer_cap=1,
        max_sends=1, max_sets=1, init_nodes=init_nodes,
        init_messages=init_messages, init_timers=init_timers,
        step_message=step_message, step_timer=step_timer,
        msg_dest=lambda msg: 0,
        invariants={"LOCK_HELD": lambda s, k=k: s["nodes"][0] < k})


# ------------------------------------------------------- determinism

def test_seeded_determinism_identical_witness():
    """Same seed => identical verdict, witness (raw AND minimized),
    and fleet counters — the PRNG state is the only nondeterminism
    source and it is fully seeded."""
    proto = _violating(make_pingpong_protocol(2))
    a = _swarm(proto).run()
    b = _swarm(proto).run()
    assert a.end_condition == b.end_condition == "INVARIANT_VIOLATED"
    assert a.predicate_name == b.predicate_name == "NOT_DONE"
    assert a.witness.raw_trace == b.witness.raw_trace
    assert a.witness.trace == b.witness.trace

    def counters(o):
        # Everything but the wall-clock-derived rates.
        return {k: v for k, v in o.swarm.items()
                if not k.endswith(("_per_sec", "_per_min"))}

    assert counters(a) == counters(b)


# --------------------------------------------------- verdict parity

@pytest.mark.parametrize("maker", [
    lambda: _violating(make_pingpong_protocol(2)),
    lambda: _violating(make_clientserver_protocol(n_clients=1, w=2)),
], ids=["pingpong", "lab1"])
def test_swarm_vs_bfs_verdict_parity(maker):
    """The swarm lands the same verdict + predicate as the host BFS
    parity oracle, its witness replays clean, and — BFS depth being
    the MINIMAL violation distance — the minimized witness can never
    be shorter than it."""
    proto = maker()
    bfs = TensorSearch(proto, chunk=64, use_host_visited=True).run()
    assert bfs.end_condition == "INVARIANT_VIOLATED"
    out = _swarm(proto, max_steps=48).run()
    assert out.end_condition == bfs.end_condition
    assert out.predicate_name == bfs.predicate_name
    w = out.witness
    assert w.replay_verified and w.minimized
    assert len(w.trace) <= len(w.raw_trace)
    assert len(w.trace) >= bfs.depth


def test_witness_trace_decodes_and_replays():
    """The witness rides the existing tpu/trace.py contract: the
    minimized event-id list decodes to concrete message/timer records,
    and re-applying it manually from the root reproduces the violating
    predicate result."""
    from dslabs_tpu.tpu.swarm import replay_events
    from dslabs_tpu.tpu.trace import decode_trace

    proto = _violating(make_pingpong_protocol(2))
    sw = _swarm(proto)
    out = sw.run()
    recs = decode_trace(sw, out)
    assert len(recs) == len(out.witness.trace)
    from dslabs_tpu.tpu.engine import flatten_state

    root = np.asarray(flatten_state(jax.tree.map(
        jnp.asarray, sw._trace_root)))[0]
    row, applied = replay_events(sw, root, out.witness.trace)
    assert applied == len(out.witness.trace)
    end = sw.unflatten_rows(jnp.asarray(row)[None])
    holds = bool(np.asarray(jax.vmap(
        proto.invariants["NOT_DONE"])(end))[0])
    assert not holds


# ---------------------------------------------------- dedup sharing

def test_dedup_sharing_frontier_seed_drops_revisit_rate(tmp_path):
    """Dedup sharing with BFS: seeding the fleet from a mid-BFS
    checkpoint (frontier restarts + table pre-seeded with the BFS's
    visited keys) makes walkers re-tread covered territory at a lower
    rate than a root-started fleet, whose walkers all funnel through
    the same shallow states.  The lock protocol (wide branching, no
    reachable violation here) makes the funnel measurable: every
    root-started walker's first step lands on one of six states."""
    proto = make_lock_protocol(m=6, k=10 ** 6, noise_bits=16)
    ckpt = str(tmp_path / "bfs.npz")
    cut = TensorSearch(proto, chunk=256, max_depth=4,
                       checkpoint_path=ckpt, checkpoint_every=1)
    assert cut.run().end_condition == "DEPTH_EXHAUSTED"
    kw = dict(walkers_per_device=16, max_steps=40, steps_per_round=40,
              max_rounds=1, seed=5)
    rooted = _swarm(proto, **kw).run()
    seeded = _swarm(proto, frontier_seed=ckpt, **kw).run()
    assert rooted.end_condition == seeded.end_condition \
        == "TIME_EXHAUSTED"

    def rate(o):
        return o.swarm["revisits"] / max(o.swarm["explored"], 1)

    assert rate(seeded) < rate(rooted)
    # Pre-seeded BFS keys are already in the table, so the seeded
    # fleet's unique count (fresh inserts) never re-counts them.
    assert seeded.swarm["vis_over"] == 0
    assert seeded.unique_states > 0


# ------------------------------------------------------- checkpoints

def test_frontier_seeding_resume_parity(tmp_path):
    """A frontier-seeded swarm cut mid-flight resumes from its round
    checkpoint (walker rows, histories, PRNG keys, seed pool, table)
    to a BIT-IDENTICAL continuation: same verdict, same witness, same
    counters as the uncut run.  A round is FOUR steps: the uncut run
    meets its violation at its eighth step, so the cut run's one round
    stops it halfway there and the resumed fleet has a round to run
    (at eight steps a round the cut run found the violation itself,
    and nothing was left to resume: red from the seed to PR 43)."""
    proto = _violating(make_pingpong_protocol(3))
    bfs_ck = str(tmp_path / "bfs.npz")
    TensorSearch(proto, chunk=64, max_depth=2, checkpoint_path=bfs_ck,
                 checkpoint_every=1).run()
    kw = dict(walkers_per_device=8, max_steps=24, steps_per_round=4,
              seed=3, frontier_seed=bfs_ck)
    full = _swarm(proto, **kw).run()
    assert full.end_condition == "INVARIANT_VIOLATED"
    assert full.swarm["rounds"] == 2
    sw_ck = str(tmp_path / "swarm.npz")
    cut = _swarm(proto, max_rounds=1, checkpoint_path=sw_ck,
                 checkpoint_every=1, **kw).run()
    assert cut.end_condition == "TIME_EXHAUSTED"
    assert os.path.exists(sw_ck)
    resumed = _swarm(proto, checkpoint_path=sw_ck, **kw)
    out = resumed.run(resume=True)
    assert out.end_condition == full.end_condition
    assert out.witness.raw_trace == full.witness.raw_trace
    assert out.witness.trace == full.witness.trace
    assert out.swarm["explored"] == full.swarm["explored"]
    assert out.resumed_from_depth == 1


def test_swarm_checkpoint_not_resumable_by_bfs(tmp_path):
    """Swarm dumps are their own fingerprint family: a BFS engine must
    refuse one loudly rather than resume walker rows as a frontier."""
    from dslabs_tpu.tpu import checkpoint as ckpt_mod

    # No reachable violation (goal pruned away), so the round runs to
    # its cap and the checkpoint actually lands.
    pp = make_pingpong_protocol(2)
    proto = dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})
    sw_ck = str(tmp_path / "swarm.npz")
    _swarm(proto, max_rounds=1, checkpoint_path=sw_ck,
           checkpoint_every=1).run()
    assert os.path.exists(sw_ck)
    bfs = TensorSearch(proto, chunk=64, checkpoint_path=sw_ck)
    assert not bfs.has_resumable_checkpoint()
    with pytest.raises(ckpt_mod.CheckpointMismatch):
        bfs.run(resume=True)


# ------------------------------------------------- dispatch seam

def test_faultplan_transient_retry_inside_swarm_dispatch():
    """The swarm rides the `_dispatch` seam: a transient fault injected
    into a swarm round dispatch retries in place with an identical
    witness — the supervisor/watchdog/warden contracts apply to swarm
    runs without modification."""
    proto = _violating(make_pingpong_protocol(2))
    base = _swarm(proto).run()
    faulted = _swarm(proto)
    boundary = install_retry(
        faulted, RetryPolicy(max_retries=2, backoff_base=0.001),
        FaultPlan().raise_at(2, count=1))
    out = faulted.run()
    assert boundary.retries == 1
    assert out.end_condition == base.end_condition
    assert out.witness.trace == base.witness.trace


# -------------------------------------------- overflow accounting

def test_walker_overflow_counted_and_warned():
    """The satellite bugfix: a capacity-truncated walker step restarts
    LOUDLY — counted on SearchOutcome.swarm_overflow (with
    walker_restarts alongside) and warned about past the threshold —
    where the old rollout probe restarted silently."""
    # net_cap 4 cannot hold the depth the walkers reach: truncated
    # steps are guaranteed.
    proto = _violating(make_clientserver_protocol(n_clients=2, w=3,
                                                  net_cap=4))
    sw = _swarm(proto, max_steps=48, steps_per_round=48, max_rounds=2)
    with pytest.warns(RuntimeWarning, match="capacity-truncated"):
        out = sw.run()
    assert out.swarm_overflow > 0
    assert out.walker_restarts > 0
    assert out.swarm["overflow_restarts"] == out.swarm_overflow


def test_strict_swarm_raises_on_truncation():
    """Strict swarms keep the PR-1 overflow contract's strict half: a
    truncated step raises CapacityOverflow instead of degrading."""
    proto = _violating(make_clientserver_protocol(n_clients=2, w=3,
                                                  net_cap=4))
    sw = _swarm(proto, max_steps=48, steps_per_round=48, max_rounds=2,
                strict=True)
    with pytest.raises(CapacityOverflow):
        sw.run()


# ------------------------------------------ the walk step in blocks

BLOCK_KW = dict(walkers_per_device=64, max_steps=24, steps_per_round=8,
                seed=11, visited_cap=1 << 13)


def _pb_crash():
    """Primary-backup under one crash: message, timer AND fault events
    are enabled a few steps from the root."""
    from dslabs_tpu.tpu.specs import pb_crash_spec

    p = pb_crash_spec().compile()
    return dataclasses.replace(p, goals={}, prunes=dict(p.goals))


def _one_and_blocked(monkeypatch, proto):
    """Two fleets of 64 walkers on one device: ONE block (the module's
    constants leave any CPU twin's fleet whole), and blocks of 8
    walkers."""
    one = _swarm(proto, mesh=make_mesh(1), **BLOCK_KW)
    monkeypatch.setattr(swarm_mod, "STEP_BLOCK_BYTES", 0)
    monkeypatch.setattr(swarm_mod, "STEP_BLOCK_ROWS", 8)
    blocked = _swarm(proto, mesh=make_mesh(1), **BLOCK_KW)
    assert (one.step_blocks, one.block_rows) == (1, 64)
    assert (blocked.step_blocks, blocked.block_rows) == (8, 8)
    return one, blocked


def _walk(search, rounds):
    """The carry after ``rounds`` rounds from the root, and each
    round's stats vector."""
    stats = []
    with search.mesh:
        carry = search._init_carry(search.initial_state())
        for _ in range(rounds):
            carry, st = search._round(
                carry, jnp.asarray(search.steps_per_round, jnp.int32))
            stats.append(np.asarray(st))
    return jax.tree.map(np.asarray, carry), stats


def test_step_rows_in_blocks_equals_one_block(monkeypatch):
    """``_step_rows`` over blocks of 8 walkers gives what it gives over
    the fleet at once, bit for bit — successor rows, validity,
    truncation — with message, timer and fault events among the
    picks, enabled and not."""
    proto = _pb_crash()
    one, blocked = _one_and_blocked(monkeypatch, proto)
    rows = jnp.asarray(_walk(one, 1)[0]["rows"])
    msg_ids, tmr_ids, flt_ids, _rem = (
        np.asarray(x) for x in one._event_tables(
            rows, jnp.ones((64,), bool)))
    tgrid = proto.n_nodes * proto.timer_cap
    kinds = [msg_ids, np.where(tmr_ids >= 0, tmr_ids + proto.net_cap, -1),
             np.where(flt_ids >= 0, flt_ids + proto.net_cap + tgrid, -1)]
    # walker i asks for an event of kind i % 3: its first enabled one,
    # else that kind's first grid slot, which is not enabled
    base = [0, proto.net_cap, proto.net_cap + tgrid]
    ev = np.zeros((64,), np.int32)
    for i in range(64):
        ids = kinds[i % 3][i]
        ev[i] = ids[ids >= 0][0] if (ids >= 0).any() else base[i % 3]
    want = jax.jit(one._step_rows)(rows, jnp.asarray(ev))
    got = jax.jit(blocked._step_rows)(rows, jnp.asarray(ev))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ok = np.asarray(want[1])
    for k in range(3):
        mine = ok[k::3]
        assert mine.any(), f"no enabled event of kind {k} was stepped"
    assert not ok.all()
    assert "while" in jax.jit(blocked._step_rows).lower(
        rows, jnp.asarray(ev)).as_text()


@pytest.mark.parametrize("maker", [
    _pb_crash, lambda: make_clientserver_protocol(n_clients=2, w=2),
], ids=["pb-crash", "lab1"])
def test_blocked_walk_equals_the_one_block_walk(monkeypatch, maker):
    """Whole-walk parity: after 6 rounds the blocked fleet's carry is
    the one-block fleet's leaf for leaf — rows, depths, histories,
    table, PRNG key, every counter, ``fresh_by_depth`` — and so is every
    round's stats vector: blocks change where the handlers and the merge
    run, not what the walk is."""
    one, blocked = _one_and_blocked(monkeypatch, maker())
    want, want_stats = _walk(one, 6)
    got, got_stats = _walk(blocked, 6)
    assert sorted(want) == sorted(got)
    for k in want:
        np.testing.assert_array_equal(want[k], got[k], err_msg=k)
    for a, b in zip(want_stats, got_stats):
        np.testing.assert_array_equal(a, b)
    assert int(want["restarts"][0]) > 0 and int(want["fresh"][0]) > 16
    assert blocked._stats_dict(got_stats[-1], 6, 1.0)["step_blocks"] == 8
    assert one._stats_dict(want_stats[-1], 6, 1.0)["step_blocks"] == 1


@pytest.mark.parametrize("walkers,net_cap,msg_width,rows", [
    (8192, 2048, 8, 128),    # the five-server probe twin: 64 blocks
    (2048, 2048, 8, 128),    # 128 MiB of network: sixteen blocks
    (1024, 2048, 8, 1024),   # 64 MiB, measured faster at once: one block
    (256, 2048, 8, 256),     # test26's lab fleet: one block
    (128, 8192, 32, 128),    # no wider than a block: one block
    (8192, 128, 8, 8192),    # the ladder's top rung at its widest, 32 MiB
    (4096, 128, 9, 4096),    # lab 4's widest messages, the lab's fleets
    (128, 128, 3, 128),      # a CPU's probe
    (8192 + 64, 2048, 8, 8192 + 64),     # a width 128 does not divide
], ids=["five-servers", "sixteen-blocks", "measured-at-once", "test26-lab",
        "one-block", "ladder-top-widest", "lab4-multi", "cpu-probe",
        "undivided"])
def test_the_block_rule(walkers, net_cap, msg_width, rows):
    """A fleet whose network operand (walkers x net_cap x msg_width x 4
    bytes) passes the module's STEP_BLOCK_BYTES walks in blocks of
    STEP_BLOCK_ROWS walkers, one lane tile; a smaller fleet, or one the
    block does not divide, is ONE block."""
    assert swarm_mod.step_block_rows(walkers, net_cap, msg_width) == rows
    assert walkers % rows == 0


def test_a_fleet_of_one_block_has_no_block_loop():
    """A fleet no wider than its block lowers ``_step_rows`` with no
    loop at all (no loop of one trip): the program it always was."""
    proto = make_clientserver_protocol(n_clients=1, w=2)
    sw = _swarm(proto, mesh=make_mesh(1), **BLOCK_KW)
    assert (sw.step_blocks, sw.block_rows) == (1, 64)
    rows = jnp.zeros((64, sw.lanes), jnp.int32)
    text = jax.jit(sw._step_rows).lower(
        rows, jnp.zeros((64,), jnp.int32)).as_text()
    assert "while" not in text
    assert text == jax.jit(sw._step_block).lower(
        rows, jnp.zeros((64,), jnp.int32)).as_text().replace(
            "_step_block", "_step_rows")


# ------------------------------------------------------- portfolio

def _lock_sup(proto, mesh, max_secs, **kw):
    return SearchSupervisor(
        proto, ladder=("sharded",), mesh=mesh, chunk=1024,
        frontier_cap=1 << 14, visited_cap=1 << 18, strict=False,
        max_secs=max_secs, **kw)


def test_portfolio_beats_bfs_on_deep_narrow():
    """The ISSUE 5 acceptance: on a deep-narrow violation with a fixed
    wall-clock budget, BFS alone returns TIME_EXHAUSTED;
    SearchSupervisor(portfolio=True) returns the violation through the
    swarm lane, the witness replays to the same predicate result, and
    the minimized trace is no longer than the raw one."""
    # Unsaturated noise (22 bits) keeps level sizes at the beam cap,
    # so the kept beam is the genealogically-leftmost subtree — the
    # golden path's append position (~slot0 * m^(d-1)) falls out of it
    # by level 5, and the BFS lane measurably stalls (depth 11 after
    # 60 s on the CPU mesh) while a walker reaches depth k in ~m*k
    # random steps.
    proto = make_lock_protocol(m=8, k=12, noise_bits=22)
    mesh = make_mesh(2)
    bfs = _lock_sup(proto, mesh, max_secs=2.5).run()
    assert bfs.end_condition == "TIME_EXHAUSTED"

    sup = _lock_sup(
        proto, mesh, max_secs=90.0, portfolio=True,
        swarm_kwargs=dict(mesh=mesh, walkers_per_device=24,
                          max_steps=240, steps_per_round=64, seed=0,
                          visited_cap=1 << 14))
    out = sup.run()
    assert out.end_condition == "INVARIANT_VIOLATED"
    assert out.engine == "swarm"
    assert out.predicate_name == "LOCK_HELD"
    w = out.witness
    assert w.replay_verified
    assert len(w.trace) <= len(w.raw_trace)
    # The lock needs exactly k good digits: the minimizer must land on
    # the true minimal witness.
    assert len(w.trace) == 12
    # The losing BFS lane was cancelled, not left to burn its budget.
    assert sup.lanes["bfs"].cancelled
    # Replay the minimized witness manually: same predicate result.
    from dslabs_tpu.tpu.engine import flatten_state
    from dslabs_tpu.tpu.swarm import replay_events

    sw = SwarmSearch(proto, mesh=mesh, walkers_per_device=8)
    root = np.asarray(flatten_state(sw.initial_state()))[0]
    row, applied = replay_events(sw, root, w.trace)
    assert applied == len(w.trace)
    end = sw.unflatten_rows(jnp.asarray(row)[None])
    assert int(np.asarray(end["nodes"])[0, 0]) == 12


def test_portfolio_exhaustive_bfs_verdict_wins():
    """With no violation in the space, the portfolio returns the BFS
    lane's exhaustive verdict (swarm TIME_EXHAUSTED never outranks
    SPACE_EXHAUSTED) and cancels the walkers."""
    pp = make_pingpong_protocol(2)
    proto = dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})
    mesh = make_mesh(2)
    base = SearchSupervisor(proto, ladder=("sharded",), mesh=mesh,
                            chunk=16, frontier_cap=1 << 8,
                            visited_cap=1 << 10).run()
    assert base.end_condition == "SPACE_EXHAUSTED"
    sup = SearchSupervisor(
        proto, ladder=("sharded",), mesh=mesh, chunk=16,
        frontier_cap=1 << 8, visited_cap=1 << 10, portfolio=True,
        max_secs=60.0,
        swarm_kwargs=dict(mesh=mesh, walkers_per_device=8,
                          max_steps=16, steps_per_round=16, seed=1,
                          visited_cap=1 << 10))
    out = sup.run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert out.unique_states == base.unique_states


# ------------------------------------------- deep-narrow, swarm-smoke

@pytest.mark.slow
@pytest.mark.perf
def test_portfolio_deep_narrow_paxos():
    """Deep-narrow on a REAL protocol twin (lab 3 paxos): completing
    two client commands through leader election + two Paxos instances
    sits far deeper than a seconds-budget BFS clears, but the
    portfolio's swarm lane lands it with a verified witness (`make
    swarm-smoke`)."""
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    proto = _violating(make_paxos_protocol(n=3, n_clients=1, w=2,
                                           max_slots=3))
    mesh = make_mesh(2)
    bfs = _lock_sup(proto, mesh, max_secs=3.0).run()
    assert bfs.end_condition == "TIME_EXHAUSTED"
    sup = _lock_sup(
        proto, mesh, max_secs=240.0, portfolio=True,
        swarm_kwargs=dict(mesh=mesh, walkers_per_device=64,
                          max_steps=192, steps_per_round=64, seed=0,
                          visited_cap=1 << 16))
    out = sup.run()
    assert out.end_condition == "INVARIANT_VIOLATED"
    assert out.engine == "swarm"
    assert out.witness.replay_verified
    assert len(out.witness.trace) <= len(out.witness.raw_trace)
    assert len(out.witness.trace) >= bfs.depth


@pytest.mark.slow
@pytest.mark.perf
def test_deep_narrow_lab4_shardstore_swarm():
    """Deep-narrow on the lab 4 shardstore twin: the swarm reaches the
    deep completion state a bounded BFS cannot (`make swarm-smoke`)."""
    from dslabs_tpu.tpu.specs_lab4 import \
        make_shardstore_protocol

    base = make_shardstore_protocol(groups_of=[1, 2])
    proto = _violating(base)
    sw = SwarmSearch(proto, mesh=make_mesh(2), walkers_per_device=64,
                     max_steps=192, steps_per_round=64, seed=0,
                     visited_cap=1 << 16, max_secs=240.0)
    out = sw.run()
    assert out.end_condition == "INVARIANT_VIOLATED"
    assert out.witness.replay_verified
