"""Soundness sanitizer (ISSUE 10, dslabs_tpu/analysis/).

The contract under test:

* **red fixtures** — every rule (C1-C4 conformance, J0-J5 jaxpr) has a
  deliberately-violating fixture asserting the EXACT finding code, so
  a rule that silently stops firing is a test failure, not quiet rot;
* **clean pins** — the shipped tree lints clean (zero unwaived
  conformance findings over specs/protocols/adapters/labs) and the
  pingpong superstep + promote programs audit clean on BOTH engines
  under JAX_PLATFORMS=cpu;
* **compile gate** — malformed ProtocolSpecs raise structured
  ``SpecError`` naming the handler and field at ``compile()`` time
  (the bare-KeyError shape is retired);
* **waivers + CLI** — the waiver file suppresses (but still reports)
  findings; the CLI exits 1 on unwaived findings, 0 otherwise;
* **build-time hook** — ``DSLABS_SANITIZE=1`` audits at engine build
  and records telemetry events; off is off (the overhead guard in
  tests/test_telemetry.py pins zero added dispatches/transfers).

``make analysis-smoke`` runs this file plus the CLI end to end.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dslabs_tpu.analysis import (apply_waivers, load_waivers,  # noqa: E402
                                 run_conformance)
from dslabs_tpu.analysis import main as analysis_main  # noqa: E402
from dslabs_tpu.analysis.conformance import (check_spec,  # noqa: E402
                                             lint_source)
from dslabs_tpu.analysis.jaxpr_audit import (audit_search,  # noqa: E402
                                             audit_sites)
from dslabs_tpu.tpu.compiler import (Field, MessageType,  # noqa: E402
                                     NodeKind, ProtocolSpec, SpecError,
                                     TimerType)
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402

pytestmark = pytest.mark.analysis

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(findings):
    return sorted({f.code for f in findings})


# ------------------------------------------------- red fixtures: C1-C3

def test_c1_payload_mutation_object_and_spec_handlers():
    src = textwrap.dedent("""
        class FooNode(Node):
            def handle_Req(self, message, sender):
                message.seq = 1
                message.entries.append(3)
            def on_Tick(self, timer):
                timer.count += 1

        @spec.on("server", "REQ")
        def srv(ctx, m):
            m["i"] = 3
    """)
    found = lint_source(src, "fixture.py")
    c1 = [f for f in found if f.code == "C1"]
    assert len(c1) == 4
    assert {f.obj for f in c1} == {"FooNode.handle_Req",
                                   "FooNode.on_Tick", "srv"}
    assert all(f.leg == "conformance" for f in c1)


def test_c1_alias_mutable_state_into_send_and_copy_exemption():
    src = textwrap.dedent("""
        class FooNode(Node):
            def __init__(self, address):
                self.log = []
                self.acks: Dict[int, int] = {}
            def handle_Req(self, message, sender):
                self.send(Reply(self.log), sender)          # finding
                self.send(Reply(list(self.log)), sender)    # copied: ok
                self.send(Reply(clone(self.acks)), sender)  # cloned: ok
                self.broadcast(Reply(self.acks), sender)    # finding
    """)
    c1 = [f for f in lint_source(src, "fixture.py") if f.code == "C1"]
    assert len(c1) == 2
    assert all("aliases mutable node state" in f.message for f in c1)


def test_c2_nondeterminism_variants():
    src = textwrap.dedent("""
        import random, time
        class FooNode(Node):
            def __init__(self, address):
                self.peers = set()
            def handle_Req(self, message, sender):
                a = random.randint(0, 3)
                b = time.time()
                c = id(message)
                for p in self.peers:
                    self.send(Reply(1), p)
                for p in sorted(self.peers):   # canonical order: ok
                    pass
    """)
    c2 = [f for f in lint_source(src, "fixture.py") if f.code == "C2"]
    assert len(c2) == 4
    msgs = " ".join(f.message for f in c2)
    assert "randomness" in msgs and "wall clock" in msgs
    assert "identity" in msgs and "unordered set" in msgs


def test_c3_hash_hostile_state_public_only():
    src = textwrap.dedent("""
        import numpy as np
        class FooNode(Node):
            def __init__(self, address):
                self.weights = np.zeros(4)     # finding
                self.pick = lambda x: x        # finding
                self._scratch = np.zeros(4)    # private: excluded
    """)
    c3 = [f for f in lint_source(src, "fixture.py") if f.code == "C3"]
    assert {f.obj for f in c3} == {"FooNode.weights", "FooNode.pick"}


# -------------------------------------- red fixtures: C4 compile gate

def _bad_field_spec():
    sp = ProtocolSpec("bad", nodes=[NodeKind("n", 1, (Field("x"),))],
                      messages=[MessageType("M", ("i",))], timers=[])

    @sp.on("n", "M")
    def h(ctx, m):
        ctx.put("y", m["i"])
    return sp


def test_c4_compile_raises_structured_spec_error_undeclared_field():
    sp = _bad_field_spec()
    with pytest.raises(SpecError) as ei:
        sp.compile()
    e = ei.value
    assert e.code == "C4" and e.handler == "h" and e.field == "y"
    assert e.kind == "n" and e.line
    assert "undeclared field 'y'" in str(e)


def test_c4_compile_raises_on_unknown_message_registration():
    sp = ProtocolSpec("bad2", nodes=[NodeKind("n", 1, ())],
                      messages=[MessageType("M", ())], timers=[])

    @sp.on("n", "NOPE")
    def h(ctx, m):
        pass
    with pytest.raises(SpecError, match="unknown message 'NOPE'"):
        sp.compile()


def test_c4_compile_raises_on_unknown_kind_and_payload_read():
    sp = ProtocolSpec("bad3", nodes=[NodeKind("n", 1, ())],
                      messages=[MessageType("M", ("i",))], timers=[])

    @sp.on("ghost", "M")
    def h(ctx, m):
        pass
    with pytest.raises(SpecError, match="unknown node kind 'ghost'"):
        sp.compile()

    sp2 = ProtocolSpec("bad4", nodes=[NodeKind("n", 1, ())],
                       messages=[MessageType("M", ("i",))], timers=[])

    @sp2.on("n", "M")
    def h2(ctx, m):
        _ = m["zz"]
    with pytest.raises(SpecError, match="not declared by 'M'") as ei:
        sp2.compile()
    assert ei.value.handler == "h2"


def test_c4_send_of_undeclared_message_and_fields():
    sp = ProtocolSpec("bad5", nodes=[NodeKind("n", 1, ())],
                      messages=[MessageType("M", ("i",))],
                      timers=[TimerType("T", ())])

    @sp.on("n", "M")
    def h(ctx, m):
        ctx.send("GHOST", 0, i=1)
    with pytest.raises(SpecError, match="undeclared message 'GHOST'"):
        sp.compile()

    sp2 = ProtocolSpec("bad6", nodes=[NodeKind("n", 1, ())],
                       messages=[MessageType("M", ("i",))], timers=[])

    @sp2.on("n", "M")
    def h2(ctx, m):
        ctx.send("M", 0, i=1, zz=2)
    with pytest.raises(SpecError, match="unknown fields \\['zz'\\]"):
        sp2.compile()


def test_c4_check_spec_reports_unhandled_declared_types():
    sp = ProtocolSpec(
        "soft", nodes=[NodeKind("n", 1, ())],
        messages=[MessageType("M", ()), MessageType("DEAD", ())],
        timers=[TimerType("TICK", ())])

    @sp.on("n", "M")
    def h(ctx, m):
        pass
    found = check_spec(sp, origin="fixture")
    assert _codes(found) == ["C4"]
    msgs = " ".join(f.message for f in found)
    assert "'DEAD' has no handler" in msgs
    assert "'TICK' has no handler" in msgs
    sp.compile()          # soft findings do NOT fail the compile gate


# ------------------------------------------- red fixtures: C5 symmetry

_C5_RED = textwrap.dedent("""
    from dslabs_tpu.tpu.compiler import (Field, MessageType, NodeKind,
                                         ProtocolSpec)

    spec = ProtocolSpec(
        "sym", nodes=[NodeKind("acceptor", 3, (Field("b"),))],
        messages=[MessageType("M", ())], timers=[],
        symmetry=("acceptor",))

    @spec.on("acceptor", "M")
    def h(ctx, m):
        me = ctx.node_index()
        ctx.put("b", 1, when=me == 1)        # member-specific branch
""")


def test_c5_symmetric_kind_branching_on_node_id():
    """ISSUE 15 red fixture: a handler on a kind inside a declared
    symmetry group comparing node_index() (here through a tainted
    local) against a constant is flagged C5."""
    c5 = [f for f in lint_source(_C5_RED, "fixture.py")
          if f.code == "C5"]
    assert len(c5) == 1
    assert c5[0].obj == "h"
    assert "interchangeable" in c5[0].message


def test_c5_clean_counterparts():
    """The symmetry-safe styles stay clean: identifying peers via
    _from, comparing tainted values against payloads (not constants),
    and the same constant-branching handler on a kind OUTSIDE the
    symmetry declaration."""
    clean = _C5_RED.replace("me == 1", 'm["_from"] == me')
    assert [f.code for f in lint_source(clean, "f.py")] == []
    outside = _C5_RED.replace('symmetry=("acceptor",)', "symmetry=()")
    assert [f.code for f in lint_source(outside, "f.py")] == []


def test_c5_direct_comparison_and_rules_catalog():
    src = _C5_RED.replace(
        "me = ctx.node_index()\n"
        "    ctx.put(\"b\", 1, when=me == 1)        "
        "# member-specific branch",
        "ctx.put(\"b\", 1, when=ctx.node_index() == 2)")
    c5 = [f for f in lint_source(src, "fixture.py") if f.code == "C5"]
    assert len(c5) == 1
    from dslabs_tpu.analysis.core import RULES

    assert "C5" in RULES and "symmetry" in RULES["C5"]


def test_c5_compile_gate_guards_group_declarations():
    """The compile gate's half of C5: unknown group kinds and
    malformed index_group declarations raise structured SpecErrors."""
    sp = ProtocolSpec("s1", nodes=[NodeKind("n", 2, ())],
                      messages=[MessageType("M", ())], timers=[],
                      symmetry=("ghost",))
    with pytest.raises(SpecError, match="unknown node kind 'ghost'"):
        sp.compile()
    sp2 = ProtocolSpec(
        "s2",
        nodes=[NodeKind("p", 1, (Field("x", size=3,
                                       index_group="a"),)),
               NodeKind("a", 2, ())],
        messages=[MessageType("M", ())], timers=[], symmetry=("a",))
    with pytest.raises(SpecError, match="size 3 but index_group"):
        sp2.compile()


# ------------------- red fixtures: C5 slot/quorum reads (ISSUE 20)

_C5_REP_RED = textwrap.dedent("""
    from dslabs_tpu.tpu.compiler import (Field, MessageType, NodeKind,
                                         ProtocolSpec)
    from dslabs_tpu.tpu.quorum import QuorumCount

    spec = ProtocolSpec(
        "rep",
        nodes=[NodeKind("proposer", 1,
                        (Field("seen", size=3,
                               index_group="acceptor"),
                         Field("bv", hi=7))),
               NodeKind("acceptor", 3, (Field("b"),))],
        messages=[MessageType("M", ())], timers=[],
        symmetry=("acceptor",),
        quorums=(QuorumCount("q", over="acceptor",
                             threshold="majority"),))

    @spec.on("proposer", "M")
    def h(ctx, m):
        ctx.put_at("seen", 2, 1)          # fixed member's element
""")


def test_c5_constant_index_into_symmetric_group_array():
    """ISSUE 20 red fixture: get_at/put_at of an index_group array
    over a symmetric kind at an integer-constant index is
    member-specific — flagged C5 even though the handler's own kind
    is outside the symmetry group."""
    c5 = [f for f in lint_source(_C5_REP_RED, "fixture.py")
          if f.code == "C5"]
    assert len(c5) == 1
    assert c5[0].obj == "h"
    assert "index_group" in c5[0].message
    assert "'acceptor'" in c5[0].message


def test_c5_quorum_constant_bitmask():
    """ISSUE 20 red fixture: met_bits/count_bits of a quorum over a
    symmetric kind fed a constant bitmask names members by bit."""
    src = _C5_REP_RED.replace(
        'ctx.put_at("seen", 2, 1)          # fixed member\'s element',
        'ctx.put("bv", ctx.quorum("q").met_bits(5))')
    c5 = [f for f in lint_source(src, "fixture.py") if f.code == "C5"]
    assert len(c5) == 1
    assert "bitmask" in c5[0].message and "'q'" in c5[0].message


def test_c5_slot_quorum_clean_counterparts():
    """The symmetric-safe styles stay clean: indexing the group array
    by the sender, feeding the quorum reducer the protocol's own
    vote-bit field, a constant index into a NON-group array, and the
    same red bodies with the symmetry declaration removed."""
    by_from = _C5_REP_RED.replace(
        'ctx.put_at("seen", 2, 1)', 'ctx.put_at("seen", m["_from"], 1)')
    assert [f.code for f in lint_source(by_from, "f.py")] == []
    own_bits = _C5_REP_RED.replace(
        'ctx.put_at("seen", 2, 1)',
        'ctx.put("bv", ctx.quorum("q").met_bits(ctx.get("bv")))')
    assert [f.code for f in lint_source(own_bits, "f.py")] == []
    non_group = _C5_REP_RED.replace(
        'ctx.put_at("seen", 2, 1)', 'ctx.put_at("bv", 0, 1)')
    assert [f.code for f in lint_source(non_group, "f.py")] == []
    asym = _C5_REP_RED.replace('symmetry=("acceptor",),', "")
    assert [f.code for f in lint_source(asym, "f.py")] == []


def test_c4_check_spec_flags_untouched_slots_and_quorums():
    """ISSUE 20 soft C4: the budget dry-run records which Slots blocks
    and quorums handlers touch; declared-but-unreached ones are dead
    lanes in every packed row.  Touching both clears the findings."""
    from dslabs_tpu.tpu.quorum import QuorumCount
    from dslabs_tpu.tpu.slots import SlotField, Slots

    def build(touch):
        sp = ProtocolSpec(
            "dead", nodes=[NodeKind("n", 3, (
                Field("x", hi=3),
                Slots("log", 2, base=1,
                      fields=(SlotField("cmd", hi=3),))))],
            messages=[MessageType("M", ())], timers=[],
            quorums=(QuorumCount("q", over="n",
                                 threshold="majority"),))

        @sp.on("n", "M")
        def h(ctx, m):
            if touch:
                ctx.slot_put("log", "cmd", 1, 2)
                ctx.put("x", ctx.quorum("q").met_bits(ctx.get("x")))
            else:
                ctx.put("x", 1)
        return sp

    found = check_spec(build(False), origin="fixture")
    assert _codes(found) == ["C4"]
    msgs = " ".join(f.message for f in found)
    assert "Slots block 'log'" in msgs and "dead lanes" in msgs
    assert "quorum 'q'" in msgs and "never read" in msgs
    assert check_spec(build(True), origin="fixture") == []


def test_c4_unhandled_but_sent_message_is_dead_letter_clean():
    """The dead-letter idiom (a message some handler sends to an
    address that ignores it — the lab4 reconfig-debris rows) is NOT
    an unhandled-message finding; an unsent+unhandled one still is
    (see test_c4_check_spec_reports_unhandled_declared_types)."""
    sp = ProtocolSpec(
        "dl", nodes=[NodeKind("n", 2, (Field("x"),))],
        messages=[MessageType("M", ()), MessageType("DEBRIS", ())],
        timers=[])

    @sp.on("n", "M")
    def h(ctx, m):
        ctx.send("DEBRIS", to=1)
    assert check_spec(sp, origin="fixture") == []


# ------------------------------------------- red fixtures: jaxpr J0-J5

def _entry(fn, args, donate=(), multi=False, builder=None):
    return dict(fn=fn, args=args, donate=donate, multi=multi,
                builder=builder)


def test_j0_unregistered_site_and_unlowerable_program():
    fn = jax.jit(lambda x: x + 1)
    sds = jax.ShapeDtypeStruct((4,), jnp.int32)
    found = audit_sites({"bogus.site": _entry(fn, (sds,))}, "Fixture")
    assert _codes(found) == ["J0"]
    assert "DISPATCH_SITES" in found[0].message

    def broken(x):
        raise RuntimeError("trace bomb")
    found = audit_sites(
        {"device.promote": _entry(jax.jit(broken), (sds,))}, "Fixture")
    assert _codes(found) == ["J0"]
    assert "failed to lower" in found[0].message


def test_j1_host_callback_in_program():
    def prog(x):
        jax.debug.print("leak {}", x[0])
        return x + 1
    sds = jax.ShapeDtypeStruct((4,), jnp.int32)
    found = audit_sites(
        {"device.step": _entry(jax.jit(prog), (sds,))}, "Fixture")
    assert "J1" in _codes(found)
    assert "host callback" in found[0].message


def test_j2_float64_upcast():
    def prog(x):
        return x.astype(jnp.float64) * 1.5
    sds = jax.ShapeDtypeStruct((4,), jnp.int32)
    with jax.enable_x64():
        found = audit_sites(
            {"device.promote": _entry(jax.jit(prog), (sds,))},
            "Fixture")
    assert _codes(found) == ["J2"]


def test_j3_large_carry_not_donated():
    big = jax.ShapeDtypeStruct((512, 512), jnp.int32)   # 1 MiB
    fn = jax.jit(lambda c: c * 2)                        # NO donation
    found = audit_sites(
        {"device.step": _entry(fn, (big,), donate=(0,))}, "Fixture")
    assert _codes(found) == ["J3"]
    assert "no input/output aliasing" in found[0].message
    # The genuinely-donated twin of the same program audits clean.
    ok = jax.jit(lambda c: c * 2, donate_argnums=0)
    assert audit_sites(
        {"device.step": _entry(ok, (big,), donate=(0,))},
        "Fixture") == []


def test_j4_collective_in_single_device_program():
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("d",))
    fn = jax.jit(shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                           in_specs=P("d"), out_specs=P()))
    sds = jax.ShapeDtypeStruct((8, 4), jnp.int32)
    found = audit_sites(
        {"device.promote": _entry(fn, (sds,), multi=False)}, "Fixture")
    assert _codes(found) == ["J4"]
    assert "all_reduce" in found[0].message
    # The same program declared multi-device audits clean.
    assert audit_sites(
        {"sharded.promote": _entry(fn, (sds,), multi=True)},
        "Fixture") == []


def test_j4_collective_leaking_into_width1_build():
    """ISSUE 12 red fixture: the REAL width-1 sharded superstep build
    still lowers its mesh collectives (identity all_to_all/psum become
    all_reduce over a one-element group) — registered as a
    single-device program (multi=False) it must be a loud J4, which is
    exactly the drift J4 exists to catch: a program built against the
    wrong mesh scope leaking collectives into the single-chip bench
    path.  Registered honestly (the registry's multi=True for
    sharded.*), the same build audits clean."""
    import dataclasses

    from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    pp = make_pingpong_protocol(workload_size=2)
    proto = dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})
    search = ShardedTensorSearch(proto, make_mesh(1),
                                 chunk_per_device=16,
                                 frontier_cap=1 << 8,
                                 visited_cap=1 << 10)
    sites = search.dispatch_site_programs()
    entry = dict(sites["sharded.superstep"], multi=False)
    found = audit_sites({"width1.superstep": entry}, "Fixture")
    assert "J4" in _codes(found)            # the red shape
    # The honest registration (registry multi=True) is clean end to
    # end — the standing zero-findings pin covers it, re-asserted here
    # for the fused-exchange build specifically.
    assert [f for f in audit_sites(sites, "ShardedTensorSearch")
            if f.code == "J4"] == []


def test_j5_retrace_hazard_fresh_constants_per_build():
    sds = jax.ShapeDtypeStruct((8,), jnp.float32)

    def churning_builder():
        consts = np.random.rand(8).astype(np.float32)   # fresh/build
        return jax.jit(lambda x: x + consts)

    found = audit_sites(
        {"device.promote": _entry(churning_builder(), (sds,),
                                  builder=churning_builder)},
        "Fixture", deep=True)
    assert _codes(found) == ["J5"]

    stable = np.ones(8, np.float32)

    def stable_builder():
        return jax.jit(lambda x: x + stable)

    assert audit_sites(
        {"device.promote": _entry(stable_builder(), (sds,),
                                  builder=stable_builder)},
        "Fixture", deep=True) == []


# ----------------------------------------------------- clean-pass pins

def test_shipped_tree_conformance_clean():
    """ACCEPTANCE: the shipped specs/protocols/adapters/labs lint
    clean modulo the documented waiver file."""
    findings = run_conformance()
    live = [f for f in findings if not f.waived]
    assert live == [], "\n".join(f.render() for f in live)


def test_jaxpr_zero_findings_pingpong_both_engines():
    """ACCEPTANCE: the pingpong superstep+promote (sharded) and
    step+promote (single-device) programs audit clean under
    JAX_PLATFORMS=cpu."""
    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    proto = make_pingpong_protocol(workload_size=2)
    dev = TensorSearch(proto, max_depth=8, frontier_cap=1 << 8,
                       visited_cap=1 << 10)
    assert audit_search(dev) == []
    sh = ShardedTensorSearch(proto, make_mesh(8), chunk_per_device=16,
                             frontier_cap=1 << 8, visited_cap=1 << 10,
                             max_depth=8)
    sites = sh.dispatch_site_programs()
    assert {"sharded.superstep", "sharded.promote"} <= set(sites)
    assert audit_sites(sites, "ShardedTensorSearch") == []


@pytest.mark.slow
def test_jaxpr_deep_retrace_clean_pingpong():
    """The J5 double-trace on the real engines: rebuilding the
    superstep/step/promote programs lowers bit-identically, so warden
    children and failover rungs keep hitting the compile cache."""
    from dslabs_tpu.tpu.engine import TensorSearch
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    proto = make_pingpong_protocol(workload_size=2)
    assert audit_search(
        TensorSearch(proto, max_depth=8, frontier_cap=1 << 8,
                     visited_cap=1 << 10), deep=True) == []
    assert audit_search(
        ShardedTensorSearch(proto, make_mesh(2), chunk_per_device=16,
                            frontier_cap=1 << 8, visited_cap=1 << 10,
                            max_depth=8), deep=True) == []


# ------------------------------------------------------ waivers + CLI

def test_waiver_file_suppresses_but_reports(tmp_path):
    wf = tmp_path / "waivers"
    wf.write_text("# test waivers\n"
                  "C1 fixture.py::FooNode.* known-shared reply buffer\n")
    src = textwrap.dedent("""
        class FooNode(Node):
            def handle_Req(self, message, sender):
                message.seq = 1
    """)
    found = apply_waivers(lint_source(src, "fixture.py"),
                          load_waivers(str(wf)))
    assert len(found) == 1 and found[0].waived
    assert found[0].waiver == "known-shared reply buffer"


def test_waiver_file_malformed_line_is_loud(tmp_path):
    wf = tmp_path / "waivers"
    wf.write_text("C1 only-two-fields\n")
    with pytest.raises(ValueError, match="waiver needs"):
        load_waivers(str(wf))
    wf.write_text("Q9 x::y reason\n")
    with pytest.raises(ValueError, match="unknown rule code"):
        load_waivers(str(wf))


def test_cli_rc_contract(tmp_path, capsys):
    # conformance over an explicit violating file -> rc 1 + findings
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        class FooNode(Node):
            def handle_Req(self, message, sender):
                message.seq = 1
    """))
    rc = analysis_main(["conformance", "--paths", str(bad), "--json"])
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 1 and out["findings"] == 1
    assert out["detail"][0]["code"] == "C1"
    # same file, waived -> rc 0, finding still reported
    wf = tmp_path / "waivers"
    wf.write_text(f"C1 {bad}::* justified for the fixture\n")
    rc = analysis_main(["conformance", "--paths", str(bad),
                        "--waivers", str(wf), "--json"])
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 0 and out["findings"] == 0 and out["waived"] == 1


@pytest.mark.slow
def test_cli_all_subprocess_clean():
    """ACCEPTANCE: `python -m dslabs_tpu.analysis all` exits 0 on the
    shipped tree (modulo documented waivers)."""
    proc = subprocess.run(
        [sys.executable, "-m", "dslabs_tpu.analysis", "all", "--json"],
        capture_output=True, text=True, cwd=ROOT, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert data["findings"] == 0
    assert data["conformance"] == 0 and data["jaxpr"] == 0


# ------------------------------------------------- build-time sanitize

def test_sanitize_hook_records_telemetry_events(monkeypatch):
    """DSLABS_SANITIZE=1 audits at engine build time and records
    findings as telemetry events (fixture: hide a tag from the site
    registry so the audit has something to find)."""
    from dslabs_tpu.tpu import telemetry as tel_mod
    from dslabs_tpu.tpu.engine import TensorSearch

    monkeypatch.setenv("DSLABS_SANITIZE", "1")
    sites = {k: v for k, v in tel_mod.DISPATCH_SITES.items()
             if k != "device.promote"}
    monkeypatch.setattr(tel_mod, "DISPATCH_SITES", sites)
    tel = tel_mod.Telemetry()
    with pytest.warns(RuntimeWarning, match="jaxpr-audit finding"):
        TensorSearch(make_pingpong_protocol(2), max_depth=8,
                     frontier_cap=1 << 8, visited_cap=1 << 10,
                     telemetry=tel)
    evs = [e for e in tel.events if e.get("kind") == "sanitizer_finding"]
    assert evs and evs[0]["code"] == "J0"
    assert evs[0]["site"] == "device.promote"


def test_sanitize_off_is_off(monkeypatch):
    """No DSLABS_SANITIZE -> the hook is one env read: no audit, no
    events, no warning (the dispatch/transfer half of this guarantee
    is pinned by the test_telemetry overhead guard)."""
    from dslabs_tpu.tpu import telemetry as tel_mod
    from dslabs_tpu.tpu.engine import TensorSearch

    monkeypatch.delenv("DSLABS_SANITIZE", raising=False)
    called = []
    import dslabs_tpu.analysis.jaxpr_audit as ja

    monkeypatch.setattr(ja, "audit_search",
                        lambda *a, **k: called.append(1) or [])
    tel = tel_mod.Telemetry()
    TensorSearch(make_pingpong_protocol(2), max_depth=8,
                 frontier_cap=1 << 8, visited_cap=1 << 10,
                 telemetry=tel)
    assert not called
    assert not [e for e in tel.events
                if e.get("kind") == "sanitizer_finding"]


# ------------------------------------------------------ run_tests --lint

def test_run_tests_lint_flag(tmp_path, capsys):
    """run_tests.py --lint runs the conformance pass before the labs
    and passes on the (clean) shipped tree."""
    sys.path.insert(0, ROOT)
    try:
        import run_tests as rt

        rc = rt.main(["--lint", "--replay-traces"])
    finally:
        sys.path.remove(ROOT)
    out = capsys.readouterr().out
    assert rc == 0
    assert "conformance lint" in out
