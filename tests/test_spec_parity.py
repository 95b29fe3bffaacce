"""Replicated-protocol spec layer (ISSUE 20): the shipped lab3/lab4
protocols compile from ProtocolSpec (tpu/specs_lab3.py +
tpu/specs_lab4.py — Slots blocks, QuorumCount declarations, Fragment
composition); the retired hand twins live on UNSHIPPED in
tests/fixtures/hand_twins/ as parity ORACLES —

* generated-vs-hand parity matrix: identical unique-state counts at
  every pinned small depth for lab3 paxos and all four lab4 scopes
  (join, part-1 shardstore, 2PC tx, multi-server groups);
* init-vector equality where the generated layout is lane-identical to
  the hand twin (join, part-1 shardstore);
* compile gates: a STATIC slot index outside the declared block range
  and a quorum over an empty or unknown group refuse loudly
  (structured SpecError) at compile, never silently misread lanes;
* packed slot lanes roundtrip bit-exactly through the storage codec,
  the checkpoint format, and the mesh wire descriptor (the PR-18
  parity-oracle pattern: packed vs unpacked is assertion-exact);
* spec-declared domains reach the bit-packer: >= 2x bytes-per-state
  reduction on every generated lab3/lab4 spec.

Marked ``spec`` (``make spec-smoke``)."""

import dataclasses
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import packing as packing_mod  # noqa: E402
from dslabs_tpu.tpu.compiler import (Field, MessageType,  # noqa: E402
                                     NodeKind, ProtocolSpec, SpecError,
                                     TimerType)
from dslabs_tpu.tpu.engine import TensorSearch  # noqa: E402
from dslabs_tpu.tpu.quorum import QuorumCount  # noqa: E402
from dslabs_tpu.tpu.slots import SlotField, Slots  # noqa: E402
from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol  # noqa: E402
from dslabs_tpu.tpu.specs_lab4 import (make_join_protocol,  # noqa: E402
                                       make_shardstore_multi_protocol,
                                       make_shardstore_protocol,
                                       make_shardstore_tx_protocol)

# The hand twins are test fixtures now — ORACLES for this module, not
# shipped modules (the generated specs are the single source of truth).
_FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
if _FIXTURES not in sys.path:
    sys.path.insert(0, _FIXTURES)

from hand_twins.paxos import \
    make_paxos_protocol as hand_paxos  # noqa: E402
from hand_twins.shardmaster_join import \
    make_join_protocol as hand_join  # noqa: E402
from hand_twins.shardstore import \
    make_shardstore_protocol as hand_shardstore  # noqa: E402
from hand_twins.shardstore_multi import \
    make_shardstore_multi_protocol as hand_multi  # noqa: E402
from hand_twins.shardstore_tx import \
    make_shardstore_tx_protocol as hand_tx  # noqa: E402

pytestmark = pytest.mark.spec


def _count(proto, depth, chunk=256):
    out = TensorSearch(dataclasses.replace(proto, goals={}),
                       chunk=chunk, max_depth=depth).run()
    return out.unique_states


# ------------------------------------------- generated-vs-hand matrix

@pytest.mark.parametrize("depth,expect", [(1, 6), (2, 25), (3, 102)])
def test_parity_lab3_paxos(depth, expect):
    assert _count(make_paxos_protocol(), depth) == expect
    assert _count(hand_paxos(), depth) == expect


@pytest.mark.parametrize("g,depth,expect", [
    (1, 1, 3), (1, 3, 10), (2, 2, 6), (2, 3, 11),
])
def test_parity_lab4_join(g, depth, expect):
    assert _count(make_join_protocol(g), depth) == expect
    assert _count(hand_join(g), depth) == expect


@pytest.mark.parametrize("depth,expect", [(1, 6), (2, 23), (3, 74)])
def test_parity_lab4_shardstore(depth, expect):
    assert _count(make_shardstore_protocol([1, 1]), depth) == expect
    assert _count(hand_shardstore([1, 1]), depth) == expect


@pytest.mark.parametrize("depth,expect", [(1, 8), (2, 38)])
def test_parity_lab4_tx(depth, expect):
    assert _count(make_shardstore_tx_protocol(1), depth) == expect
    assert _count(hand_tx(1), depth) == expect


@pytest.mark.slow
@pytest.mark.parametrize("depth,expect", [(1, 8), (2, 42)])
def test_parity_lab4_multi(depth, expect):
    # The hand twin has no catch-up exchange (the spec's has, since
    # PR 40): it first fires 10 events down, far below these depths.
    assert _count(make_shardstore_multi_protocol(), depth,
                  chunk=512) == expect
    assert _count(hand_multi(), depth, chunk=512) == expect


@pytest.mark.parametrize("gen_fn,hand_fn", [
    (lambda: make_join_protocol(1), lambda: hand_join(1)),
    (lambda: make_shardstore_protocol([1, 1]),
     lambda: hand_shardstore([1, 1])),
])
def test_init_vectors_lane_identical(gen_fn, hand_fn):
    """Where the generated layout reproduces the hand twin's lanes
    one-for-one (join, part-1 shardstore), the initial node vector is
    BIT-IDENTICAL — the adapters' lane predicates carry over unedited."""
    gen, hand = gen_fn(), hand_fn()
    assert np.array_equal(np.asarray(gen.init_nodes()),
                          np.asarray(hand.init_nodes()))


# ------------------------------------------------------ compile gates

def _tiny_spec(slot_index=1, quorums=(), kinds=None):
    spec = ProtocolSpec(
        "spec-gate",
        nodes=kinds if kinds is not None else [
            NodeKind("proc", 3, (
                Field("x", hi=4),
                Slots("log", 2, (SlotField("cmd", hi=7),), base=1),
            ))],
        messages=[MessageType("GO", ())],
        timers=[TimerType("TICK", (), 10, 10)],
        net_cap=4, timer_cap=1, quorums=quorums)

    @spec.on("proc", "GO")
    def go(ctx, m):
        ctx.put("x", ctx.slot_get("log", "cmd", slot_index))

    spec.initial_messages.append(("GO", 0, 0, {}))
    spec.invariants["OK"] = lambda v: True
    return spec


def test_static_slot_index_out_of_range_refused():
    """slot_get/slot_put with a STATIC index outside [base, base+n)
    is a structured SpecError at compile — the off-by-one that would
    silently read the neighbouring lane in a hand twin."""
    _tiny_spec(slot_index=2).compile()          # in range: fine
    with pytest.raises(SpecError, match="outside declared range"):
        _tiny_spec(slot_index=3).compile()      # base=1, n=2 -> [1, 3)
    with pytest.raises(SpecError, match="outside declared range"):
        _tiny_spec(slot_index=0).compile()


def test_quorum_over_empty_or_unknown_group_refused():
    """A quorum over zero instances is vacuous at every threshold; a
    quorum over an undeclared kind is a typo.  Both refuse loudly at
    compile instead of deep inside a search."""
    _tiny_spec(quorums=(QuorumCount("q", over="proc"),)).compile()
    with pytest.raises(SpecError, match="unknown node kind"):
        _tiny_spec(quorums=(QuorumCount("q", over="procs"),)).compile()
    kinds = [
        NodeKind("proc", 3, (
            Field("x", hi=4),
            Slots("log", 2, (SlotField("cmd", hi=7),), base=1))),
        NodeKind("ghost", 0, (Field("y", hi=1),)),
    ]
    with pytest.raises(SpecError, match="EMPTY group"):
        _tiny_spec(kinds=kinds,
                   quorums=(QuorumCount("q", over="ghost"),)).compile()


# ------------------------------------- packed slot-lane roundtrips

def test_packed_slot_lanes_codec_roundtrip():
    """Random in-domain rows of the generated paxos spec — whose log /
    p2bv / votes lanes all come from Slots declarations — roundtrip
    bit-exactly through BOTH codecs the engine installs: the storage
    descriptor (frontier SoA, spill spool, checkpoints) and the mesh
    wire descriptor (delta=True), numpy and jnp agreeing."""
    proto = dataclasses.replace(make_paxos_protocol(), goals={})
    eng = TensorSearch(proto, chunk=64)
    doms, sents = packing_mod._flat_domains(proto)
    rng = np.random.default_rng(20)
    rows = np.zeros((64, eng.lanes), np.int32)
    from dslabs_tpu.tpu.engine import SENTINEL
    for i, (dom, s_cap) in enumerate(zip(doms, sents)):
        if dom is None:
            rows[:, i] = rng.integers(-2**31, 2**31 - 1, 64)
        elif isinstance(dom, tuple) and dom and dom[0] == "delta":
            rows[:, i] = rng.integers(0, 1 << int(dom[1]), 64)
        else:
            rows[:, i] = rng.integers(dom[0], dom[1] + 1, 64)
        if s_cap:
            rows[rng.random(64) < 0.3, i] = SENTINEL
    for delta in (False, True):
        pk = packing_mod.derive_packing(proto, eng.lanes, delta=delta)
        assert not pk.identity
        base = (np.zeros(eng.lanes, np.int32)
                if delta and pk.has_delta else None)
        kw = {"base": base} if base is not None else {}
        assert (pk.unpack_np(pk.pack_np(rows, **kw), **kw)
                == rows).all()
        rt = np.asarray(pk.unpack_jnp(
            pk.pack_jnp(jax.numpy.asarray(rows), **kw), **kw))
        assert (rt == rows).all()


@pytest.mark.parametrize("spec_fn", [
    lambda: make_join_protocol(1),
    lambda: make_shardstore_protocol([1, 1]),
])
def test_packed_vs_unpacked_search_parity(spec_fn):
    """The PR-18 parity-oracle pattern on the generated specs: the
    packed (default) and unpacked device loops land the identical
    unique/explored/verdict/depth."""
    kw = dict(chunk=128, frontier_cap=1 << 10, visited_cap=1 << 13,
              max_depth=4)
    packed = TensorSearch(
        dataclasses.replace(spec_fn(), goals={}), **kw).run()
    raw = TensorSearch(
        dataclasses.replace(spec_fn(), goals={}), packed=False,
        **kw).run()
    assert packed.end_condition == raw.end_condition
    assert packed.unique_states == raw.unique_states
    assert packed.states_explored == raw.states_explored
    assert packed.depth == raw.depth
    assert packed.bytes_per_state < packed.bytes_per_state_unpacked


def test_packed_checkpoint_resume_generated_paxos(tmp_path):
    """A packed checkpoint of the generated paxos spec (slot lanes
    stored PACKED) resumes to the exact straight-run counts."""
    path = str(tmp_path / "spec.ckpt.npz")
    proto = dataclasses.replace(make_paxos_protocol(), goals={})
    TensorSearch(proto, chunk=256, max_depth=2, checkpoint_path=path,
                 checkpoint_every=1).run()
    resumed = TensorSearch(proto, chunk=256, max_depth=3,
                           checkpoint_path=path,
                           checkpoint_every=1).run()
    straight = TensorSearch(proto, chunk=256, max_depth=3).run()
    assert resumed.unique_states == straight.unique_states
    assert resumed.depth == straight.depth


def test_mesh_wire_packed_parity_generated_join():
    """The packed mesh wire moves generated-spec slot lanes bit-exactly:
    width-2 sharded runs with the wire codec ON vs OFF (the parity
    oracle) agree on every count."""
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    def run(mesh_pack):
        proto = dataclasses.replace(make_join_protocol(2), goals={})
        return ShardedTensorSearch(
            proto, make_mesh(2), chunk_per_device=16,
            frontier_cap=1 << 8, visited_cap=1 << 10,
            mesh_pack=mesh_pack).run()

    on, off = run(True), run(False)
    assert on.end_condition == off.end_condition
    assert on.unique_states == off.unique_states
    assert on.states_explored == off.states_explored
    assert on.depth == off.depth


# ------------------------------------------------ bytes-per-state

@pytest.mark.parametrize("spec_fn", [
    make_paxos_protocol,
    lambda: make_join_protocol(1),
    lambda: make_shardstore_protocol([1, 1]),
    lambda: make_shardstore_tx_protocol(1),
])
def test_bytes_per_state_floor_generated_labs(spec_fn):
    """ACCEPTANCE: the spec-declared Field/Slots domains buy >= 2x
    smaller packed bytes-per-state on every generated lab3/lab4 spec
    (the hand twins declared nothing and derived identity)."""
    eng = TensorSearch(dataclasses.replace(spec_fn(), goals={}),
                       chunk=64)
    pk = eng._pk
    assert pk is not None and not pk.identity
    assert pk.pack_ratio >= 2.0, pk.descriptor()


@pytest.mark.slow
def test_bytes_per_state_floor_generated_multi():
    eng = TensorSearch(dataclasses.replace(
        make_shardstore_multi_protocol(), goals={}), chunk=64)
    pk = eng._pk
    assert pk is not None and pk.pack_ratio >= 2.0, pk.descriptor()


# -------------------------------- fault scenarios on generated twins

def _fault_pruned(proto):
    """Goals off (count the full bounded-depth space), reach goals kept
    as prunes, invariants live — the scenario-count discipline of
    tests/test_scenarios.py."""
    return dataclasses.replace(proto, goals={}, prunes=dict(proto.goals),
                               invariants=dict(proto.invariants))


def test_partition_on_generated_paxos_pinned_counts():
    """ISSUE 20 + ISSUE 19 composed: a Partition fault model declared
    ON THE GENERATED lab3 paxos spec (majority side {s0, s1} vs {s2})
    explores a pinned bounded-depth space — fault events included."""
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_partition_spec

    for depth, (unique, explored, pev) in {
            2: (32, 64, 7), 3: (133, 328, 31)}.items():
        proto = _fault_pruned(make_paxos_partition_spec(3).compile())
        out = TensorSearch(proto, chunk=256, max_depth=depth).run()
        assert out.end_condition == "DEPTH_EXHAUSTED"
        assert out.unique_states == unique
        assert out.states_explored == explored
        assert out.partition_events == pev
        assert out.fault_events == pev


def test_partition_witness_on_generated_paxos_names_fault_events():
    """A deliberately-falsifiable invariant (NO_HEAL: the cut never
    heals) yields a witness whose decoded trace NAMES the fault
    events — CUT then HEAL — on the generated spec."""
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_partition_spec
    from dslabs_tpu.tpu.trace import decode_trace

    spec = make_paxos_partition_spec(3)
    spec.invariants["NO_HEAL"] = lambda v: ~(
        (v.get("$fault", 0, "pcut") == 0)
        & (v.get("$fault", 0, "eras") == 1))
    proto = dataclasses.replace(spec.compile(), goals={})
    search = TensorSearch(proto, chunk=256, record_trace=True,
                          max_depth=6)
    out = search.run()
    assert out.end_condition == "INVARIANT_VIOLATED"
    assert out.predicate_name == "NO_HEAL"
    assert out.depth == 2
    labels = [a[0] for k, a in decode_trace(search, out)
              if k == "fault"]
    assert labels == ["CUT", "HEAL"]


def test_crash_on_generated_shardstore_pinned_counts():
    """Crash-recovery (durable samo, volatile everything else) on the
    GENERATED lab4 part-1 shardstore spec: pinned bounded-depth
    exhaustive counts, crash events included."""
    from dslabs_tpu.tpu.specs_lab4 import make_shardstore_crash_spec

    for depth, (unique, explored, cev) in {
            2: (30, 43, 7), 3: (103, 200, 29)}.items():
        proto = _fault_pruned(
            make_shardstore_crash_spec([1, 1]).compile())
        out = TensorSearch(proto, chunk=256, max_depth=depth).run()
        assert out.end_condition == "DEPTH_EXHAUSTED"
        assert out.unique_states == unique
        assert out.states_explored == explored
        assert out.crash_events == cev
        assert out.fault_events == cev


def test_crash_witness_on_generated_shardstore_names_fault_event():
    """NO_CRASH (no server ever crashes) is falsified in one step; the
    decoded witness names which instance went down."""
    from dslabs_tpu.tpu.specs_lab4 import make_shardstore_crash_spec
    from dslabs_tpu.tpu.trace import decode_trace

    spec = make_shardstore_crash_spec([1, 1])
    spec.invariants["NO_CRASH"] = \
        lambda v: v.get("$fault", 0, "crashes") == 0
    proto = dataclasses.replace(spec.compile(), goals={})
    search = TensorSearch(proto, chunk=256, record_trace=True,
                          max_depth=4)
    out = search.run()
    assert out.end_condition == "INVARIANT_VIOLATED"
    assert out.predicate_name == "NO_CRASH"
    assert out.depth == 1
    labels = [a[0] for k, a in decode_trace(search, out)
              if k == "fault"]
    assert labels == ["CRASH(server[0])"]
