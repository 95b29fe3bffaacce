"""On-device level supersteps + persistent compile cache (ISSUE 3).

The superstep (sharded.py ``_level_superstep``: one shard_map program
whose ``lax.while_loop`` drains every device's own frontier shard) must
match the host-dedup reference (``TensorSearch(use_host_visited=True)``,
``run_host``: it shares the expand with the sharded engine and nothing
of the level loop, the exchange or the visited table) EXACTLY — end
verdict, unique, explored, depth — at no more than 2 host dispatches a
level (superstep + promote; the dispatch-counter tests assert it), on
eight devices and on one.  Mid-level time budgets keep their contract
at both widths: TIME_EXHAUSTED never masks a violation found in chunks
already completed.  The persistent compile cache
(tpu/compile_cache.py) plus AOT warm-up makes a
second identical construction's compile near-zero.

The heavier paxos/shardstore parity cases are marked ``perf`` AND
``slow``: ``make perf-smoke`` (-m perf) runs them as the dry-run
8-virtual-device parity gate, while the tier-1 suite (-m 'not slow')
keeps only the cheap pingpong cases.
"""

import dataclasses
import os

import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import sharded as sharded_mod  # noqa: E402
from dslabs_tpu.tpu.engine import TensorSearch  # noqa: E402
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh  # noqa: E402


def _pruned_pingpong():
    pp = make_pingpong_protocol(workload_size=2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def _run_pair(proto, max_depth=None, **kw):
    """The same config on the 8-device sharded engine and on the
    host-dedup reference; returns (sharded_outcome, host_outcome)."""
    kw.setdefault("chunk_per_device", 16)
    kw.setdefault("frontier_cap", 1 << 8)
    kw.setdefault("visited_cap", 1 << 10)
    sharded = ShardedTensorSearch(proto, make_mesh(8),
                                  max_depth=max_depth, **kw).run()
    host = TensorSearch(
        proto, chunk=kw["chunk_per_device"], max_depth=max_depth,
        frontier_cap=8 * kw["frontier_cap"],
        visited_cap=kw["visited_cap"], strict=kw.get("strict", True),
        use_host_visited=True).run()
    return sharded, host


def _assert_exact(a, b):
    assert a.end_condition == b.end_condition
    assert a.unique_states == b.unique_states
    assert a.states_explored == b.states_explored
    assert a.depth == b.depth
    assert a.dropped == b.dropped


# ------------------------------------------------------------- parity

@pytest.mark.perf
@pytest.mark.parametrize("strict", [True, False])
def test_superstep_vs_host_reference_parity_pingpong(strict):
    sharded, host = _run_pair(_pruned_pingpong(), strict=strict)
    assert sharded.end_condition == "SPACE_EXHAUSTED"
    _assert_exact(sharded, host)


@pytest.mark.perf
@pytest.mark.slow
def test_superstep_vs_host_reference_parity_paxos_d5():
    """The dry-run 8-device paxos rung of the perf-smoke parity gate
    (acceptance: exact verdict/unique/explored match at depth 5)."""
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    proto = make_paxos_protocol(n=3, n_clients=1, w=1, max_slots=2,
                                net_cap=16, timer_cap=4)
    sharded, host = _run_pair(proto, max_depth=5, chunk_per_device=64,
                              frontier_cap=1 << 12,
                              visited_cap=1 << 15)
    assert sharded.end_condition == "DEPTH_EXHAUSTED"
    _assert_exact(sharded, host)


@pytest.mark.perf
@pytest.mark.slow
def test_superstep_vs_host_reference_parity_shardstore_d4():
    """Second protocol family (lab 4 shardstore lane layout) through
    the same superstep machinery."""
    from dslabs_tpu.tpu.specs_lab4 import \
        make_shardstore_protocol

    proto = make_shardstore_protocol([[1], [2]])
    sharded, host = _run_pair(proto, max_depth=4, chunk_per_device=64,
                              frontier_cap=1 << 12,
                              visited_cap=1 << 15)
    assert sharded.end_condition == "DEPTH_EXHAUSTED"
    _assert_exact(sharded, host)


def test_superstep_ev_spill_parity():
    """Event-window spill inside the while_loop: a tiny budget re-steps
    spilled chunks (j held back keeps the drain condition true) with
    exact counts."""
    proto = _pruned_pingpong()
    mesh = make_mesh(8)
    full = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10).run()
    tiny = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10, ev_budget=(2, 1),
        ev_spill=True).run()
    _assert_exact(tiny, full)


# ------------------------------------------------- skipped event kinds
#
# A pass computes an event kind only if the pass's table holds an event
# of it (engine._kinds_live: a device branch around the kind's handlers
# and merge).  Two clients against one server: up to 2 live timers and
# 4 messages a state, goal CLIENTS_DONE at depth 4.

def _two_clients():
    return make_clientserver_protocol(n_clients=2, w=1, net_cap=8,
                                      timer_cap=2)


def _kinds_run(proto, n_devices=1, masks=None, **kw):
    search = ShardedTensorSearch(
        proto, make_mesh(n_devices), chunk_per_device=16,
        frontier_cap=1 << 9, visited_cap=1 << 12, **kw)
    if masks is not None:
        search.set_runtime_masks(*masks)
    return search.run()


def _never_skipping(monkeypatch):
    """The program as it was before the branch: both kinds always
    computed (and, so, no skip counted)."""
    import jax.numpy as jnp

    monkeypatch.setattr(
        TensorSearch, "_kinds_live",
        lambda self, msg_ids, tmr_ids: (jnp.bool_(True), jnp.bool_(True)))


def _per_level(out, *keys):
    return [tuple(rec[k] for k in keys) for rec in out.levels]


@pytest.fixture(scope="module")
def full_grid():
    return _kinds_run(_two_clients())


@pytest.mark.parametrize("window, spills, skips", [
    ((8, 1), "timers", True),
    ((2, 6), "messages", True),
    ((1, 1), "both", None),
    ((4, 2), "neither", False),
])
def test_a_restepped_chunk_skips_the_kind_that_did_not_spill(
        full_grid, window, spills, skips):
    """Whatever kind overflows its window, counts and verdict are the
    full grid's, level for level; the re-steps of a chunk whose OTHER
    kind spilled skip the kind with the empty table, and a window that
    holds every event skips nothing."""
    out = _kinds_run(_two_clients(), ev_budget=window, ev_spill=True)
    assert out.end_condition == full_grid.end_condition == "GOAL_FOUND"
    assert out.predicate_name == full_grid.predicate_name
    assert out.dropped == 0
    assert _per_level(out, "depth", "explored", "unique") == _per_level(
        full_grid, "depth", "explored", "unique")
    chunks = sum(rec["chunks"] for rec in out.levels)
    skipped = sum(rec["kind_skips"] for rec in out.levels)
    assert all(0 <= rec["kind_skips"] <= 2 * rec["chunks"]
               for rec in out.levels)
    if skips is True:
        # one kind's re-steps, each skipping the other kind
        assert skipped == chunks - len(out.levels) > 0
    elif skips is False:
        assert skipped == 0 and chunks == len(out.levels)
    assert _per_level(full_grid, "kind_skips") == [(0,)] * len(
        full_grid.levels)


def test_skipping_changes_nothing_but_the_counter(monkeypatch):
    """Under one window, the run that skips and the run that computes
    both kinds on every pass give the same level records (the dedup
    counters too) and the same witness trace."""
    kw = dict(ev_budget=(8, 1), ev_spill=True, record_trace=True)
    out = _kinds_run(_two_clients(), **kw)
    _never_skipping(monkeypatch)
    ref = _kinds_run(_two_clients(), **kw)
    keys = ("depth", "chunks", "explored", "unique", "next_frontier",
            "write_blocks", "probe_cols")
    assert _per_level(out, *keys) == _per_level(ref, *keys)
    assert out.end_condition == ref.end_condition == "GOAL_FOUND"
    assert out.trace is not None and out.trace == ref.trace
    assert sum(rec["kind_skips"] for rec in out.levels) > 0
    assert sum(rec["kind_skips"] for rec in ref.levels) == 0


def test_each_device_of_a_mesh_branches_for_itself(full_grid):
    """Two devices, the root on one of them: in level 1's steps the
    device without a row skips both kinds while the other computes —
    ``kind_skips`` is the count of the device that skipped most — and
    the counts are the one-device full grid's."""
    out = _kinds_run(_two_clients(), n_devices=2, ev_budget=(8, 1),
                     ev_spill=True)
    assert out.end_condition == "GOAL_FOUND"
    assert _per_level(out, "depth", "explored", "unique") == _per_level(
        full_grid, "depth", "explored", "unique")
    first = out.levels[0]
    assert first["explored"] > 0
    assert first["kind_skips"] == 2 * first["chunks"]
    assert sorted(first["per_device"]["explored"])[0] == 0


def test_frozen_timers_skip_the_timer_kind_on_the_full_grid(monkeypatch):
    """Every timer frozen by a runtime mask, no window: pass 0 alone,
    its timer table empty in every step — the timer kind is skipped
    once a chunk step and the counts are those of the program that
    computes it."""
    import numpy as np

    import jax.numpy as jnp

    proto = dataclasses.replace(
        _two_clients(), goals={},
        deliver_message_rt=lambda msg, marr: marr[0],
        deliver_timer_rt=lambda node, tarr: jnp.sum(
            jnp.where(jnp.arange(tarr.shape[0]) == node, tarr, False)))
    masks = (np.ones(1, bool), np.zeros(proto.n_nodes, bool))
    out = _kinds_run(proto, masks=masks)
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert _per_level(out, "kind_skips") == _per_level(out, "chunks")
    _never_skipping(monkeypatch)
    ref = _kinds_run(proto, masks=masks)
    _assert_exact(out, ref)
    assert _per_level(out, "explored", "unique") == _per_level(
        ref, "explored", "unique")
    # the same twin with its timers live explores more
    live = _kinds_run(proto, masks=(masks[0], ~masks[1]), max_depth=out.depth)
    assert live.states_explored > out.states_explored


def _lowered_superstep():
    import jax.numpy as jnp

    search = ShardedTensorSearch(
        _two_clients(), make_mesh(1), chunk_per_device=16,
        frontier_cap=1 << 9, visited_cap=1 << 12, ev_budget=(8, 1),
        ev_spill=True)
    return search._superstep.lower(search._carry_sds(),
                                   jnp.asarray(1, jnp.int32))


def _branches(lowered):
    text = lowered.as_text()
    return text.count("stablehlo.case") + text.count("stablehlo.if")


def test_the_superstep_holds_a_conditional_a_kind():
    """The skip is a device branch, not a select over both results: the
    superstep's module carries the two conditionals, lowered and
    compiled (a refactor that traced the kinds under ``vmap`` or a
    ``where`` would leave none)."""
    lowered = _lowered_superstep()
    assert _branches(lowered) >= 2, "no branch in the lowered superstep"
    assert lowered.compile().as_text().count(" conditional(") >= 2


# ---------------------------------------------------- dispatch counting

def _counted_run(proto, n_devices, **kw):
    kw.setdefault("chunk_per_device", 16)
    kw.setdefault("frontier_cap", 1 << 8)
    kw.setdefault("visited_cap", 1 << 10)
    search = ShardedTensorSearch(proto, make_mesh(n_devices), **kw)
    counts = {}

    def hook(tag, fn, *args):
        counts[tag] = counts.get(tag, 0) + 1
        return fn(*args)

    search._dispatch_hook = hook
    return search.run(), counts


@pytest.mark.parametrize("n_devices", [8, 1])
def test_superstep_host_dispatches_per_level_at_most_two(n_devices):
    """The acceptance bound: a level costs <= 2 host dispatches
    (superstep + promote; the stats vector rides inside the superstep
    program), and the carry initialiser is the only other dispatch of a
    run — on the mesh and on the one device a chip run has."""
    out, counts = _counted_run(_pruned_pingpong(), n_devices)
    levels = out.depth
    assert levels >= 3
    assert set(counts) == {"sharded.init", "sharded.superstep",
                           "sharded.promote"}
    assert counts["sharded.init"] == 1
    assert counts["sharded.superstep"] + counts["sharded.promote"] <= (
        2 * levels)


def test_retired_options_are_gone():
    """One design: the per-chunk driver, the promote-boundary exchange
    and work stealing left no constructor option behind (an unknown
    keyword is a TypeError, not a silently ignored knob) and no
    environment name in the package."""
    import inspect
    import pathlib

    from dslabs_tpu.tpu.supervisor import SearchSupervisor

    proto, mesh = _pruned_pingpong(), make_mesh(2)
    params = inspect.signature(ShardedTensorSearch.__init__).parameters
    for kw in ({"superstep": True}, {"row_exchange": True},
               {"steal_threshold": 1.5}):
        assert not set(kw) & set(params)
        with pytest.raises(TypeError):
            ShardedTensorSearch(proto, mesh, **kw)
    with pytest.raises(TypeError):
        SearchSupervisor(proto, mesh=mesh, row_exchange=True)
    retired = ["DSLABS_" + n for n in (
        "SHARDED_SUPERSTEP", "SHARDED_EXCHANGE", "MESH_STEAL_THRESHOLD",
        "SUPERSTEP_CHUNKS", "MESH_PACK")]
    pkg = pathlib.Path(sharded_mod.__file__).parents[1]
    named = [(str(f.relative_to(pkg)), n) for f in pkg.rglob("*.py")
             for n in retired if n in f.read_text()]
    assert named == []


# ------------------------------------------------------- level records

def test_level_records_on_outcome():
    """Satellite: structured per-level throughput records ride the
    outcome (depth/chunks/write_blocks/probe_cols/kind_skips/wall/
    explored/unique/next_frontier) — the bench emits them as its
    throughput series."""
    proto = _pruned_pingpong()
    mesh = make_mesh(8)
    out = ShardedTensorSearch(
        proto, mesh, chunk_per_device=16, frontier_cap=1 << 8,
        visited_cap=1 << 10).run()
    assert out.levels, "SearchOutcome.levels must carry per-level records"
    for i, rec in enumerate(out.levels):
        assert rec["depth"] == i + 1
        for key in ("chunks", "write_blocks", "probe_cols", "kind_skips",
                    "wall", "explored", "unique", "next_frontier"):
            assert key in rec, rec
        assert rec["chunks"] >= 1
    # Cumulative counters are monotone; the final record's totals match
    # the outcome's.
    uniq = [r["unique"] for r in out.levels]
    assert uniq == sorted(uniq)
    assert out.levels[-1]["explored"] == out.states_explored
    assert out.levels[-1]["unique"] == out.unique_states


# ------------------------------------------------------- write blocks

def _tree_protocol(branch=6, depth=4, goal=None):
    """A tree: delivering standing message ``i`` in state ``v`` gives
    ``v * branch + i + 1`` down to ``depth``, so every successor of a
    first visit is a state nobody has seen and a level numbers its
    states ``lo .. hi`` in grid order — the traffic of a search's first
    levels, at a width where one chunk step appends several blocks.
    ``goal``: the state whose discovery ends the search."""
    import jax.numpy as jnp
    import numpy as np

    from dslabs_tpu.tpu.engine import SENTINEL, TensorProtocol

    inner = sum(branch ** d for d in range(depth))
    no_send = jnp.full((1, 1), SENTINEL, jnp.int32)
    no_set = jnp.full((1, 2), SENTINEL, jnp.int32)

    def step_message(nodes, msg):
        v = nodes[0]
        return (nodes.at[0].set(jnp.where(
            v < inner, v * branch + msg[0] + 1, v)), no_send, no_set)

    return TensorProtocol(
        name=f"tree-b{branch}-d{depth}", n_nodes=1, node_width=1,
        msg_width=1, timer_width=1, net_cap=branch, timer_cap=1,
        max_sends=1, max_sets=1,
        init_nodes=lambda: np.zeros(1, np.int32),
        init_messages=lambda: np.arange(branch, dtype=np.int32)[:, None],
        init_timers=lambda: np.zeros((0, 2), np.int32),
        step_message=step_message,
        step_timer=lambda nodes, node_idx, timer: (nodes, no_send, no_set),
        msg_dest=lambda msg: jnp.int32(0),
        goals=({} if goal is None
               else {"LEAF": lambda s: s["nodes"][0] == goal}))


def _run_watched(proto, n_devices, **kw):
    """Run the sharded engine; returns ``(outcome, after)`` where
    ``after`` holds, for every superstep dispatch, host copies of each
    device's ``nxt[:nxt_n]`` (and ``tmeta[:nxt_n]``) and of the visited
    table."""
    import numpy as np

    search = ShardedTensorSearch(proto, make_mesh(n_devices), **kw)
    inner, after = search._superstep_call, []

    def watched(carry, budget):
        carry, stats = inner(carry, budget)
        counts = np.asarray(carry["nxt_n"]).reshape(-1)
        kept = {"visited": np.asarray(carry["visited"])}
        for name, width in (("nxt", search.plane), ("tmeta", 9)):
            if name in carry:
                # nxt: a device's log of packed words (rows past
                # f_cap are its slack); tmeta: [f_cap + 1, 9].
                buf = np.asarray(carry[name]).reshape(
                    n_devices, -1, width)
                kept[name] = [buf[d, :min(int(c), search.f_cap)]
                              for d, c in enumerate(counts)]
        after.append(kept)
        return carry, stats

    search._superstep_call = watched
    return search.run(), after, search


@pytest.mark.parametrize("record_trace", [False, True],
                         ids=["plain", "record-trace"])
@pytest.mark.parametrize("n_devices,chunk,frontier_cap,strict", [
    (1, 256, 1 << 11, True), (2, 256, 1 << 11, True),
    (1, 384, 768, False)],
    ids=["one-device", "two-devices", "a-block-crosses-frontier_cap"])
def test_append_blocks_equal_one_whole_batch_scatter(
        monkeypatch, n_devices, chunk, frontier_cap, strict, record_trace):
    """A chunk step whose fresh successors fill several write blocks
    (the tree's depth 4: 1,296 fresh rows of 216; ``K`` = 256 of 1,792
    grid slots, 448 of the 3,588 two devices receive, 336 of 2,688 at
    chunk 384, where the third block lies across row 768) leaves
    ``nxt[:nxt_n]``, ``tmeta[:nxt_n]`` and the visited table, dispatch
    after dispatch, exactly as ONE whole-batch scatter does (the engine
    until PR 34: ``visited.block_width`` widened to the batch), rows
    past ``frontier_cap`` dropped and counted as before; where nothing
    drops, counts equal the host-dedup reference's."""
    import numpy as np

    from dslabs_tpu.tpu import visited as visited_mod

    proto = _tree_protocol()
    kw = dict(chunk_per_device=chunk, frontier_cap=frontier_cap,
              visited_cap=1 << 14, strict=strict,
              record_trace=record_trace)
    out, after, search = _run_watched(proto, n_devices, **kw)
    with monkeypatch.context() as m:
        m.setattr(visited_mod, "block_width", lambda n: n)
        whole, whole_after, _ = _run_watched(proto, n_devices, **kw)
    _assert_exact(out, whole)
    assert len(after) == len(whole_after) >= 5
    for got, want in zip(after, whole_after):
        assert np.array_equal(got["visited"], want["visited"])
        for name in ("nxt", "tmeta")[:1 + record_trace]:
            for g, w in zip(got[name], want[name]):
                assert np.array_equal(g, w)
    # Several blocks a step where it matters, one where the batch is
    # the block.
    deep, deep_whole = out.levels[3], whole.levels[3]
    assert deep["chunks"] == deep_whole["chunks"] == 1
    assert search.f_cap == frontier_cap
    assert deep["write_blocks"] >= 2 * (
        1296 // n_devices // visited_mod.block_width(
            chunk * 7 if n_devices == 1 else 3588))
    assert deep_whole["write_blocks"] < deep["write_blocks"]
    if n_devices == 1:
        # Grid order IS level order on one device: the rows that landed
        # are the level's first, in order.
        rows = after[3]["nxt"][0]
        if search._pk is not None:
            rows = search._pk.unpack_np(rows)
        assert rows[:, 0].tolist() == list(
            range(259, 259 + min(1296, frontier_cap)))
        assert out.dropped == max(0, 1296 - frontier_cap)
    if strict:
        # (Past its frontier cap the host reference stops with
        # CAPACITY_EXHAUSTED instead of truncating.)
        _assert_exact(out, TensorSearch(
            proto, chunk=chunk, frontier_cap=n_devices * frontier_cap,
            visited_cap=1 << 14, use_host_visited=True).run())


# What the engine gave while ``nxt`` was ``[F + 1, plane]`` and the append
# a row scatter (the parent of PR 45, commit ade35e1), on the tree of
# branch 7 and depth 5 (levels of 7, 49, 343, 2,401 and 16,807 states)
# with the leaf 19,607 — the last child of level 4's last row — as goal
# where the trace is recorded: ``(chunk, frontier_cap)``, the run's end,
# unique, explored and dropped states, per level ``(explored, unique,
# next_frontier, write_blocks, chunks)``, the witness, and after every
# promote each device's ``cur_n`` and the CRC-32 of the promoted states,
# sorted, as int64.
_LOG_LEVELS = {
    1: [(7, 8, 7, 2, 1), (56, 57, 49, 2, 1), (399, 400, 343, 3, 1),
        (2800, 2801, 2401, 17, 1), (19607, 19608, 3200, 114, 7),
        (42007, 19608, 0, 0, 8)],
    4: [(7, 8, 4, 2, 1), (56, 57, 18, 2, 1), (399, 400, 94, 3, 1),
        (2800, 2801, 614, 5, 1), (19607, 19608, 1224, 26, 3),
        (53879, 19608, 0, 0, 4)]}
_LOG_FRONTS = {
    1: [([7], 1602182657), ([49], 4002130850), ([343], 4091600918),
        ([2401], 3287508765), ([3200], 1698545458), ([0], 0)],
    4: [([2, 4, 0, 1], 1602182657), ([18, 9, 12, 10], 4002130850),
        ([89, 94, 85, 75], 4091600918),
        ([605, 599, 583, 614], 3287508765),
        ([1224, 1224, 1224, 1224], 3049208788), ([0, 0, 0, 0], 0)]}
_LOG_PARENT = {
    # (n_devices, record_trace): shape, end, unique, explored, dropped
    (1, False): ((400, 3200), "SPACE_EXHAUSTED", 19608, 42007, 13607),
    (1, True): ((400, 3200), "GOAL_FOUND", 19608, 19607, 13607),
    (4, False): ((306, 1224), "SPACE_EXHAUSTED", 19608, 53879, 26567),
    (4, True): ((306, 1224), "GOAL_FOUND", 19608, 19607, 11911)}
# Strict mode raises at level 5's first dispatch that counts a drop.
_LOG_STRICT_DROPS = {1: 13607, 4: 11911}


@pytest.mark.parametrize("strict", [False, True], ids=["beam", "strict"])
@pytest.mark.parametrize("record_trace", [False, True],
                         ids=["plain", "record-trace"])
@pytest.mark.parametrize("n_devices", [1, 4])
def test_log_appends_equal_the_parents_row_scatter(
        n_devices, record_trace, strict):
    """The next frontier as a flat word log appended by contiguous
    block writes (PR 45) against the numbers of the parent commit, in a
    search whose levels append every shape of block.  One device, chunk
    400 (``K`` = 400 of 3,200 grid slots): level 4 appends 2,401 rows =
    6 ``K`` + ONE ROW MORE (a seventh block of one live row, then 399
    rows of garbage that level 5 must never read); level 5's first
    chunk step appends 2,800 = exactly 7 ``K``, its second crosses
    ``frontier_cap`` = 3,200 inside its first block (400 rows land, the
    rest falls in the log's slack), every later one starts past it.
    Four devices, chunk 306 (``K`` = 613 of the 4,904 rows a device
    receives): the owners' shares are the hash's — 605, 599, 583 and
    614 = ``K`` + 1 at level 4 — and level 5 crosses 1,224 rows a
    device.  Per-level counts, drops, the promoted ``cur[:cur_n]`` as a
    SET of states and the witness are the parent's; strict mode raises
    at the level that drops, the levels before it equal."""
    import zlib

    import numpy as np

    from dslabs_tpu.tpu.engine import CapacityOverflow

    (chunk, cap), end, unique, explored, dropped = _LOG_PARENT[
        n_devices, record_trace]
    search = ShardedTensorSearch(
        _tree_protocol(7, 5, goal=19607 if record_trace else None),
        make_mesh(n_devices), chunk_per_device=chunk, frontier_cap=cap,
        visited_cap=1 << 15, strict=strict, record_trace=record_trace)
    promote, fronts = search._finish_level, []

    def watched(carry):
        carry = promote(carry)
        counts = np.asarray(carry["cur_n"]).reshape(-1)
        rows = np.asarray(carry["cur"]).reshape(
            n_devices, -1, search.plane)
        rows = np.concatenate([rows[d, :c] for d, c in enumerate(counts)])
        if search._pk is not None:
            rows = search._pk.unpack_np(rows)
        fronts.append((counts.tolist(), zlib.crc32(
            np.sort(rows[:, 0]).astype(np.int64).tobytes())))
        return carry

    search._finish_level = watched
    if strict:
        with pytest.raises(CapacityOverflow, match=(
                f"{_LOG_STRICT_DROPS[n_devices]} capacity drops at "
                "depth 5")):
            search.run()
        assert fronts == _LOG_FRONTS[n_devices][:4]
        return
    out = search.run()
    assert (out.end_condition, out.unique_states, out.states_explored,
            out.dropped) == (end, unique, explored, dropped)
    levels = [(r["explored"], r["unique"], r["next_frontier"],
               r["write_blocks"], r["chunks"]) for r in out.levels]
    # (A goal ends the run inside level 5: its record is not kept.)
    want = _LOG_LEVELS[n_devices]
    assert levels == (want[:4] if record_trace else want)
    assert fronts == _LOG_FRONTS[n_devices][:len(levels)]
    if record_trace:
        assert out.trace == [6] * 5
        state = 0
        for event in out.trace:
            state = state * 7 + event + 1
        assert state == int(out.goal_state["nodes"].reshape(-1)[0]) == 19607


# ------------------------------------------------- mid-level time budget

class _DispatchClock:
    """Deterministic wall clock for time-budget tests: time() returns
    ``base + n_dispatches * step`` where the dispatch hook advances the
    counter — the budget then expires at an exact, chosen dispatch
    instead of a wall-clock race."""

    def __init__(self, step: float):
        self.base = 1_000_000.0
        self.step = step
        self.dispatches = 0

    def time(self) -> float:
        return self.base + self.dispatches * self.step

    def sleep(self, secs: float) -> None:  # pragma: no cover
        pass


def _violating_clientserver():
    p = make_clientserver_protocol(n_clients=1, w=1)
    done = p.goals["CLIENTS_DONE"]
    return dataclasses.replace(
        p, goals={}, invariants={"NEVER_DONE": lambda s, f=done: ~f(s)})


def _clocked_run(proto, n_devices, max_secs, clock, **kw):
    kw.setdefault("chunk_per_device", 32)
    kw.setdefault("frontier_cap", 1 << 9)
    kw.setdefault("visited_cap", 1 << 12)
    search = ShardedTensorSearch(proto, make_mesh(n_devices),
                                 max_secs=max_secs, **kw)

    def hook(tag, fn, *args):
        clock.dispatches += 1
        return fn(*args)

    search._dispatch_hook = hook
    return search.run()


@pytest.mark.parametrize("n_devices", [8, 1])
def test_time_budget_returns_time_exhausted_mid_run(n_devices,
                                                    monkeypatch):
    """Satellite: a tiny max_secs returns TIME_EXHAUSTED (with the
    partial counts, never a crash) on the mesh and on one device (what
    a time-boxed chip run is).  The fake clock charges one 'second' per
    dispatch, so the budget expires after the first level's work —
    deterministically."""
    proto = _pruned_pingpong()
    full = _clocked_run(proto, n_devices, None, _DispatchClock(0.0))
    assert full.end_condition == "SPACE_EXHAUSTED"

    clock = _DispatchClock(1.0)
    monkeypatch.setattr(sharded_mod, "time", clock)
    out = _clocked_run(proto, n_devices, 3.5, clock)
    assert out.end_condition == "TIME_EXHAUSTED"
    assert 0 < out.states_explored < full.states_explored
    assert out.unique_states >= 1


@pytest.mark.parametrize("n_devices", [8, 1])
def test_time_budget_never_masks_violation_in_completed_chunks(
        n_devices, monkeypatch):
    """Satellite: a violation found in chunks already completed must be
    reported even when the wall budget is ALREADY exhausted at the
    sync — the checks run before any TIME_EXHAUSTED return.  The fake
    clock makes the budget expire during the violation's own level."""
    proto = _violating_clientserver()
    base = _clocked_run(proto, n_devices, None, _DispatchClock(0.0))
    assert base.end_condition == "INVARIANT_VIOLATED"

    # The run takes `total` dispatches, the last being the one whose
    # sync finds the violation.  A budget of total - 0.5 dispatch-
    # "seconds" passes every check BEFORE that dispatch (elapsed <=
    # total - 1) but is exhausted at its sync (elapsed == total) — the
    # violation must still win.
    counting = _DispatchClock(0.0)
    _clocked_run(proto, n_devices, None, counting)
    total = counting.dispatches
    clock = _DispatchClock(1.0)
    monkeypatch.setattr(sharded_mod, "time", clock)
    out = _clocked_run(proto, n_devices, total - 0.5, clock)
    assert out.end_condition == "INVARIANT_VIOLATED", (
        "TIME_EXHAUSTED masked a violation found in completed chunks")
    assert out.predicate_name == base.predicate_name
    assert out.depth == base.depth


# ------------------------------------------- compile cache + AOT warm-up

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_CHILD = """
import json, os, sys
sys.path.insert(0, %r)
from dslabs_tpu.tpu import compile_cache
from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh
from tests.test_superstep import _pruned_pingpong

out = {"setup": compile_cache.setup()}
if "--build" in sys.argv:
    proto, mesh = _pruned_pingpong(), make_mesh(8)
    kw = dict(chunk_per_device=16, frontier_cap=1 << 8,
              visited_cap=1 << 10, aot_warmup=True)
    cold = ShardedTensorSearch(proto, mesh, **kw)
    out["populated"] = bool(os.listdir(out["setup"]))
    res = cold.run()
    warm = ShardedTensorSearch(proto, mesh, **kw)
    res2 = warm.run()
    out.update(cold=cold.compile_secs, warm=warm.compile_secs,
               end=res.end_condition, outcome_secs=res.compile_secs,
               same=res.unique_states == res2.unique_states,
               after=compile_cache.cache_dir())
print(json.dumps(out))
""" % _REPO


def _cache_child(cwd, cache_env, *argv):
    import json
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(cache_env)
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD, *argv], cwd=str(cwd),
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_env_var_places_it_and_second_aot_is_fast(tmp_path):
    """Contract, variable SET: the cache is where
    JAX_COMPILATION_CACHE_DIR says and the code sets no other
    directory; it is populated, and a second identical construction's
    recorded compile time drops (the AOT .lower().compile() hits the
    on-disk cache instead of XLA)."""
    cache = str(tmp_path / "xla-cache")
    out = _cache_child(tmp_path, {"JAX_COMPILATION_CACHE_DIR": cache},
                       "--build")
    assert out["setup"] == cache and out["after"] == cache
    assert out["populated"], "persistent cache dir not populated"
    assert out["end"] == "SPACE_EXHAUSTED" and out["same"]
    assert out["cold"] > 0
    assert out["outcome_secs"] == round(out["cold"], 3)
    # The XLA-compile half is served from disk; what remains is
    # tracing.  On CPU the margin is small, so assert a robust drop
    # rather than "near-zero".
    assert out["warm"] < out["cold"]


def test_compile_cache_default_is_checkout_from_any_cwd(tmp_path):
    """Contract, variable UNSET: ``<checkout>/.jax_cache``, computed
    from the package's location — the same path from two processes
    started in different working directories."""
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir()
    b.mkdir()
    want = os.path.join(_REPO, ".jax_cache")
    assert _cache_child(a, {})["setup"] == want
    assert _cache_child(b, {})["setup"] == want


def test_compile_cache_has_one_place():
    """No directory argument, no DSLABS_* name, no per-checkpoint
    default: with the variable set (conftest) setup() leaves JAX's
    directory alone whatever else the environment says."""
    import inspect

    from dslabs_tpu.tpu import checkpoint as ckpt_mod
    from dslabs_tpu.tpu import compile_cache

    assert not inspect.signature(compile_cache.setup).parameters
    assert not hasattr(compile_cache, "setup_for_checkpoint")
    assert "compile_cache" not in ckpt_mod.run_dir_layout("/x/s.npz")
    assert "DSLABS_" not in inspect.getsource(compile_cache)
    prev = compile_cache.cache_dir()
    assert prev == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert compile_cache.setup() == prev == compile_cache.cache_dir()
