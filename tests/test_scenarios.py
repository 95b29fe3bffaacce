"""Checkable fault scenarios (ISSUE 19, ``make scenario-smoke``).

The fault plane's contract, tested bottom-up:

* **fault-free parity / overhead guard**: a spec with ``fault=None``
  AND a spec with a zero-budget :class:`FaultModel` both produce the
  verdict/explored/unique of the plain spec on BOTH engines — the
  fault lanes are pure declaration until an era/crash/drop budget is
  actually spent;
* **acceptance workloads**: paxos partition-then-heal explores every
  interleaving of CUT/HEAL with protocol events and proves the quorum
  invariant (exact pinned counts); the broken-quorum variant yields an
  INVARIANT_VIOLATED witness whose decoded trace NAMES the heal event;
  the crash/restart primary-backup spec wipes volatile fields to their
  inits and keeps durable ones;
* **carrier parity**: because fault state is ordinary bounded node
  lanes, bit-packing, symmetry canonicalization, the spill tier, and
  checkpoint/resume (including SIGKILL-mid-scenario) carry it with
  exact verdict parity, and a fault-model mismatch between dump and
  resume is refused loudly (the fault signature is part of the
  checkpoint fingerprint);
* **hygiene**: structural misdeclarations (split symmetry groups,
  unknown kinds/fields, negative budgets) raise SpecError at the
  compile gate, and conformance rule C6 flags handlers that read or
  branch on the ``$fault`` controller's internals;
* **chaos bridge**: the seeded engine-chaos soak runs a partitioned
  scenario job with exact verdict parity (model faults and engine
  faults compose).

docs/scenarios.md is the field guide.
"""

import dataclasses
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from dslabs_tpu.analysis.conformance import lint_source
from dslabs_tpu.tpu import checkpoint as ckpt_mod
from dslabs_tpu.tpu.compiler import SpecError
from dslabs_tpu.tpu.engine import TensorSearch, flatten_state
from dslabs_tpu.tpu.faults import Crash, FaultModel, Partition
from dslabs_tpu.tpu.specs import (paxos_partition_spec, paxos_spec,
                                  pb_crash_spec)
from dslabs_tpu.tpu.trace import decode_trace, replay_on_object

pytestmark = pytest.mark.scenario

# Small-knob config shared by every search here so the suite reuses a
# handful of XLA programs (same discipline as LAB1_KW in test_chaos).
KW = dict(chunk=64, frontier_cap=1 << 13, visited_cap=1 << 16)

# Pinned ground truth, established by exhaustive runs on both engines:
# plain 3-acceptor paxos (goal moved to prune) and its one-era
# proposer/acceptor partition variant.
PLAIN = dict(end="SPACE_EXHAUSTED", explored=1548, unique=202, depth=11)
PART = dict(end="SPACE_EXHAUSTED", explored=3416, unique=564, depth=13,
            partition_events=320)


def _pruned(p):
    """Move goals to prunes: run the full space, keep invariants."""
    return dataclasses.replace(p, goals={}, prunes=dict(p.goals),
                               invariants=dict(p.invariants))


def _plain_paxos():
    return _pruned(paxos_spec(3).compile())


def _part_paxos():
    return _pruned(paxos_partition_spec(3).compile())


def _assert_exact(a, b):
    assert a.end_condition == b.end_condition, (a, b)
    assert a.unique_states == b.unique_states, (a, b)
    assert a.states_explored == b.states_explored, (a, b)
    assert a.depth == b.depth, (a, b)


@pytest.fixture(scope="module")
def plain_base():
    out = TensorSearch(_plain_paxos(), **KW).run()
    assert out.end_condition == PLAIN["end"]
    return out


@pytest.fixture(scope="module")
def part_base():
    out = TensorSearch(_part_paxos(), **KW).run()
    assert out.end_condition == PART["end"]
    return out


# ------------------------------------------- fault-free parity guard

def test_zero_budget_fault_model_is_parity_oracle(plain_base):
    """OVERHEAD GUARD: a declared-but-zero-budget fault model adds
    lanes and zero valid fault events — verdict, explored, and unique
    are EQUAL to the plain spec on both engines, and every fault
    counter stays zero."""
    fm0 = FaultModel(partition=Partition(
        blocks=(("proposer",), ("acceptor",)), max_eras=0))
    proto = _pruned(paxos_spec(3, fault=fm0).compile())
    for host in (False, True):
        out = TensorSearch(proto, use_host_visited=host, **KW).run()
        _assert_exact(plain_base, out)
        assert out.fault_events == 0
        assert out.partition_events == 0
        assert out.crash_events == 0
        assert out.drop_events == 0
        assert out.dup_events == 0


def test_plain_paxos_pins(plain_base):
    """The oracle itself is pinned — if the base model drifts, every
    parity assertion in this file is re-baselined consciously."""
    assert plain_base.end_condition == PLAIN["end"]
    assert plain_base.states_explored == PLAIN["explored"]
    assert plain_base.unique_states == PLAIN["unique"]
    assert plain_base.depth == PLAIN["depth"]
    # fault=None lowers with no fault plumbing at all.
    assert plain_base.fault_events == 0


def test_fault_controller_is_hidden_last_node():
    """The ``$fault`` controller is appended LAST (user node indices
    are stable) and the partition-only event segment is CUT+HEAL."""
    spec = paxos_spec(3, fault=FaultModel(partition=Partition(
        blocks=(("proposer",), ("acceptor",)))))
    proto = spec.compile()
    assert spec.nodes[-1].name == "$fault"
    assert proto.fault is not None
    assert proto.fault.n_events == 2
    assert proto.fault.event_label(0) == "CUT"
    assert proto.fault.event_label(1) == "HEAL"
    # Plain spec carries no descriptor at all (byte-identity gate).
    assert _plain_paxos().fault is None


# ------------------------------------------------ acceptance: paxos

def test_paxos_partition_safety_exact(part_base):
    """ACCEPTANCE: one proposer/acceptor partition era over 3-acceptor
    paxos — the full interleaving space of CUT/HEAL with protocol
    events is explored (pinned counts), the quorum invariant HOLDS,
    and the device and host engines agree exactly, fault counters
    included."""
    assert part_base.end_condition == PART["end"]
    assert part_base.states_explored == PART["explored"]
    assert part_base.unique_states == PART["unique"]
    assert part_base.depth == PART["depth"]
    assert part_base.partition_events == PART["partition_events"]
    assert part_base.fault_events == PART["partition_events"]
    host = TensorSearch(_part_paxos(), use_host_visited=True,
                        **KW).run()
    _assert_exact(part_base, host)
    assert host.partition_events == PART["partition_events"]


def test_broken_quorum_witness_names_the_partition_event():
    """ACCEPTANCE: quorum=1 + initial_cut makes deciding without a
    majority reachable only after the heal — the search returns an
    INVARIANT_VIOLATED witness whose decoded trace contains the HEAL
    fault record, replay-verified step by step in tensor space."""
    proto = paxos_partition_spec(3, broken=True).compile()
    search = TensorSearch(proto, record_trace=True, **KW)
    out = search.run()
    assert out.end_condition == "INVARIANT_VIOLATED"
    assert out.predicate_name == "DECIDE_HAS_QUORUM"
    assert out.depth == 5
    # decode_trace replays every event through _step_one and asserts
    # per-step deliverability — reaching the end IS the verification.
    records = decode_trace(search, out)
    assert len(records) == out.depth
    labels = [a[0] for k, a in records if k == "fault"]
    assert labels == ["HEAL"]
    assert records[0][0] == "fault"
    assert all(k == "message" for k, _ in records[1:])
    # The object twin has no fault controller: scenario witnesses are
    # tensor-replay only, refused loudly (not silently skipped).
    search.p = dataclasses.replace(
        search.p, decode_message=lambda rec: None,
        decode_timer=lambda node, rec: None)
    with pytest.raises(NotImplementedError, match="fault event"):
        replay_on_object(search, out, None)


# --------------------------------------- acceptance: crash / restart

def test_pb_crash_volatile_wiped_durable_kept():
    """ACCEPTANCE: a CRASH event resets every volatile lane of the
    crashed node to its declared init and leaves the durable (``amo``)
    lanes untouched — checked directly on ``_step_one`` against a
    deliberately dirtied row."""
    import jax
    import jax.numpy as jnp

    proto = pb_crash_spec().compile()
    search = TensorSearch(proto, chunk=256, frontier_cap=1 << 15,
                          visited_cap=1 << 18, max_depth=6)
    fl = proto.fault
    assert fl.n_crashable > 0
    row = np.asarray(flatten_state(
        jax.tree.map(jnp.asarray, search.initial_state())))[0]
    nodes0 = np.asarray(search._slice_state(row)["nodes"]).copy()
    k = 0
    wipe = np.asarray(fl.wipe[k])
    keep = ~wipe
    assert wipe.any() and keep.any()
    dirty = nodes0.copy()
    dirty[wipe] = 7
    row2 = row.copy()
    row2[:dirty.shape[0]] = dirty
    tgrid = proto.n_nodes * proto.timer_cap
    ev = proto.net_cap + tgrid + fl.seg_crash + k
    succ, ok, _ = jax.jit(search._step_one)(
        jnp.asarray(row2), jnp.asarray(ev))
    assert bool(ok), "CRASH event not deliverable from the dirty state"
    succ = np.asarray(succ)[:dirty.shape[0]]
    init = np.asarray(fl.init_vec)
    # Exact successor: volatile lanes back to init, the controller's
    # down flag raised and crash counter bumped, EVERYTHING else —
    # durable lanes included — untouched.
    n = int(fl.crash_nodes[k])
    expected = dirty.copy()
    expected[wipe] = init[wipe]
    expected[int(fl.down_off[n])] = 1
    expected[fl.crashes_off] = dirty[fl.crashes_off] + 1
    assert (succ[wipe] == init[wipe]).all(), "volatile lanes not wiped"
    assert (succ == expected).all(), "durable lanes touched"
    # And the whole crash/restart interleaving space runs: counters
    # move, verdict reached.
    out = search.run()
    assert out.crash_events > 0
    assert out.fault_events >= out.crash_events


# ------------------------------------- carriers: pack/symmetry/spill

@pytest.mark.slow
def test_fault_lanes_survive_packing_symmetry_and_spill(part_base):
    """Fault lanes are ordinary bounded node lanes: the bit-packed
    frontier encoding and the host-RAM spill tier reproduce the
    partition scenario EXACTLY (verdict, counts, fault counters), and
    symmetry canonicalization keeps the verdict while never splitting
    the partition blocks (host/device agree on the reduced space)."""
    packed = TensorSearch(_part_paxos(), packed=True, **KW).run()
    _assert_exact(part_base, packed)
    assert packed.partition_events == PART["partition_events"]

    # visited_cap 256 << 564 unique forces tier eviction, while one
    # 32-row chunk's unique successors still fit an empty table.
    spilled = TensorSearch(_part_paxos(), spill=True,
                           chunk=32, frontier_cap=1 << 13,
                           visited_cap=1 << 8).run()
    _assert_exact(part_base, spilled)
    assert spilled.dropped_states == 0

    sym_dev = TensorSearch(_part_paxos(), symmetry=True, **KW).run()
    sym_host = TensorSearch(_part_paxos(), symmetry=True,
                            use_host_visited=True, **KW).run()
    _assert_exact(sym_dev, sym_host)
    assert sym_dev.end_condition == PART["end"]
    assert 0 < sym_dev.unique_states <= part_base.unique_states


# -------------------------------------------- carriers: checkpoints

def test_checkpoint_resume_mid_scenario_parity(part_base, tmp_path):
    """A partition-scenario run checkpointed per level resumes from a
    depth-6 partial dump to the identical verdict and exact counts
    (in-process half of the kill/resume contract)."""
    pth = str(tmp_path / "part.ckpt")
    partial = TensorSearch(_part_paxos(), max_depth=6,
                           checkpoint_path=pth, checkpoint_every=1,
                           **KW).run()
    assert partial.end_condition == "DEPTH_EXHAUSTED"
    out = TensorSearch(_part_paxos(), checkpoint_path=pth,
                       checkpoint_every=1, **KW).run(resume=True)
    _assert_exact(part_base, out)


def test_checkpoint_refuses_fault_model_mismatch(tmp_path):
    """The fault signature is part of the checkpoint fingerprint: a
    dump written WITHOUT a fault model is refused by the partition
    scenario (and vice versa) with a loud CheckpointMismatch — never
    resumed silently."""
    pth = str(tmp_path / "plain.ckpt")
    TensorSearch(_plain_paxos(), max_depth=4, checkpoint_path=pth,
                 checkpoint_every=1, **KW).run()
    with pytest.raises(ckpt_mod.CheckpointMismatch):
        TensorSearch(_part_paxos(), checkpoint_path=pth,
                     checkpoint_every=1, **KW).run(resume=True)
    pth2 = str(tmp_path / "part.ckpt")
    TensorSearch(_part_paxos(), max_depth=4, checkpoint_path=pth2,
                 checkpoint_every=1, **KW).run()
    with pytest.raises(ckpt_mod.CheckpointMismatch):
        TensorSearch(_plain_paxos(), checkpoint_path=pth2,
                     checkpoint_every=1, **KW).run(resume=True)


@pytest.mark.slow
def test_sigkill_mid_scenario_resume_parity(part_base, tmp_path):
    """ACCEPTANCE: the partition scenario SIGKILLed mid-search (dumps
    on disk) resumes from the checkpoint to the identical verdict and
    exact counts."""
    pth = str(tmp_path / "kill.ckpt")
    child_src = (
        "import dataclasses\n"
        "from dslabs_tpu.tpu.engine import TensorSearch\n"
        "from dslabs_tpu.tpu.specs import paxos_partition_spec\n"
        "p = paxos_partition_spec(3).compile()\n"
        "p = dataclasses.replace(p, goals={},"
        " prunes=dict(p.goals), invariants=dict(p.invariants))\n"
        f"TensorSearch(p, chunk=64, frontier_cap={1 << 13},"
        f" visited_cap={1 << 16}, checkpoint_path={pth!r},"
        " checkpoint_every=1).run()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR="/tmp/jaxcache-cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", child_src], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            d = ckpt_mod.peek_depth(pth)
            if d is not None and d >= 6:
                break
            if proc.poll() is not None:
                break
            time.sleep(0.05)
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert ckpt_mod.peek_depth(pth) is not None
    out = TensorSearch(_part_paxos(), checkpoint_path=pth,
                       checkpoint_every=1, **KW).run(resume=True)
    _assert_exact(part_base, out)


# --------------------------------------------- compile-gate hygiene

def test_fault_model_structural_red_fixtures():
    """Misdeclared fault models die at the compile gate with
    structured SpecErrors — unknown kinds/fields, split symmetry
    groups, and nonsense budgets never reach the engine."""
    with pytest.raises(SpecError, match="unknown node kind"):
        paxos_spec(3, fault=FaultModel(partition=Partition(
            blocks=(("proposer",), ("nonesuch",))))).compile()
    with pytest.raises(SpecError, match="symmetry group"):
        paxos_spec(3, fault=FaultModel(partition=Partition(
            blocks=((("acceptor", 0),), (("acceptor", 1),
                                         ("acceptor", 2)))))).compile()
    with pytest.raises(SpecError, match="initial_cut"):
        paxos_spec(3, fault=FaultModel(partition=Partition(
            blocks=(("proposer",), ("acceptor",)),
            max_eras=0, initial_cut=True))).compile()
    with pytest.raises(SpecError, match="not declared"):
        paxos_spec(3, fault=FaultModel(crash=Crash(
            durable={"acceptor": ("nonesuch",)}))).compile()
    with pytest.raises(SpecError, match=">= 2 blocks"):
        paxos_spec(3, fault=FaultModel(partition=Partition(
            blocks=(("acceptor",),)))).compile()


# ------------------------------------------- conformance: C6 fixtures

def test_c6_handler_reading_fault_internals_flagged():
    src = textwrap.dedent("""
        class FooNode(Node):
            def handle_Req(self, message, sender):
                if self.view.get("pcut", 0):          # finding
                    return
                down = self.view.get_at("down_server", 0)  # finding
                kind = "$fault"                        # finding
                self.state.put("drops", 1)             # finding
    """)
    c6 = [f for f in lint_source(src, "fixture.py") if f.code == "C6"]
    assert len(c6) == 4
    msgs = " ".join(f.message for f in c6)
    assert "pcut" in msgs and "down_server" in msgs
    assert "$fault" in msgs and "drops" in msgs
    assert all(f.leg == "conformance" for f in c6)


def test_c6_clean_handler_no_findings():
    """Protocol-owned fields that merely resemble nothing of the
    controller's stay clean — C6 keys on the reserved names only."""
    src = textwrap.dedent("""
        class FooNode(Node):
            def handle_Req(self, message, sender):
                amo = self.state.get("amo", 0)
                seq = self.state.get_at("seq", 1)
                self.state.put("dec", 1)
    """)
    assert [f for f in lint_source(src, "fixture.py")
            if f.code == "C6"] == []


# -------------------------------------------------- telemetry wiring

def test_fault_counters_reach_telemetry_and_status(tmp_path):
    """The schema-pinned ``faults`` block flows end to end: outcome
    counters -> telemetry record -> STATUS.json -> report renderer."""
    from dslabs_tpu.tpu.telemetry import (Telemetry, build_report,
                                          render_report)

    flight = str(tmp_path / "flight.jsonl")
    tel = Telemetry(flight_log=flight)
    out = TensorSearch(_part_paxos(), telemetry=tel, **KW).run()
    assert out.partition_events == PART["partition_events"]
    st = tel._status
    assert st.get("faults") is not None
    assert st["faults"]["partition_events"] == PART["partition_events"]
    assert st["faults"]["fault_events"] == PART["partition_events"]
    for key in ("partition_events", "crash_events", "drop_events",
                "dup_events", "fault_events"):
        assert key in st["faults"]
    import json
    with open(flight) as f:
        records = [json.loads(line) for line in f if line.strip()]
    report = build_report(records)
    assert report["faults"]["partition_events"] == \
        PART["partition_events"]
    assert "faults:" in render_report(report)


def test_fault_counters_in_warden_scalar_fields():
    """The supervisor's merged-outcome accounting carries the fault
    counters (a failover mustn't silently zero them)."""
    from dslabs_tpu.tpu.warden import _SCALAR_FIELDS

    for key in ("fault_events", "partition_events", "crash_events",
                "drop_events", "dup_events"):
        assert key in _SCALAR_FIELDS


# ------------------------------------------------------ chaos bridge

@pytest.mark.slow
def test_chaos_soak_partitioned_scenario_job(tmp_path):
    """Engine chaos x model faults: the seeded injection soak runs the
    partitioned-scenario job on the virtual mesh with EXACT verdict
    parity against its own fault-free baseline."""
    from dslabs_tpu.tpu import chaos as chaos_mod
    from dslabs_tpu.tpu.sharded import make_mesh

    report = chaos_mod.soak(
        chaos_mod._protocol("paxos-partition"),
        spec=chaos_mod.ChaosSpec(seed=7, faults=12),
        supervisor_kwargs=dict(mesh=make_mesh(8), chunk=64,
                               frontier_cap=1 << 9,
                               visited_cap=1 << 12),
        checkpoint_path=str(tmp_path / "soak.npz"),
        min_fired=8, min_sites=2)
    assert report["parity"] is True
    assert report["chaos"]["dropped_states"] == 0
