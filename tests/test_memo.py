"""Cross-job memoization parity suite (ISSUE 16, ``make memo-smoke``).

The reuse layer's one inviolable contract: a memoized answer is
BIT-IDENTICAL to the cold answer it replaced, or it is not given.
Covered bottom-up:

* structural fingerprints: rename-only/whitespace resubmits map to the
  SAME signature, a one-handler edit maps to a different one, and both
  the admission cache and the verdict cache key on that identity
  (satellite: they can never disagree about what a spec IS);
* HostVisitedTier persistence: versioned save/load with CRC + .prev
  rotation, loud refusal on foreign pack-descriptor or symmetry-flag
  mismatch (never a silently poisoned visited set);
* the divergence bound: tag-reachability over the union effect table
  lower-bounds the first level a handler edit can touch;
* service-level reuse: exact-key hit (zero dispatches, memo_hit
  journaled, ~0 COSTS device_secs), warm-start parity vs a cold run,
  incremental re-check after a one-handler edit finding the same
  violation with an identical witness digest, stale-verdict
  impossibility (an edited spec never returns a cached verdict),
  SIGKILL-mid-warm-start resume parity, a 3-tenant drain where the
  identical resubmit bills <10% of the cold run, and the memo-OFF
  overhead guard (no memo dir, no memo events, verdicts unchanged).
"""

import json
import os
import textwrap

import numpy as np
import pytest

from dslabs_tpu.service import CheckServer
from dslabs_tpu.service import memo as memo_mod
from dslabs_tpu.tpu import spill as spill_mod

pytestmark = [pytest.mark.service, pytest.mark.memo]

CHILD_ENV = {"JAX_COMPILATION_CACHE_DIR": "/tmp/jaxcache-cpu"}
FACTORY = ("dslabs_tpu.tpu.protocols.pingpong:"
           "make_exhaustive_pingpong")
SMALL = dict(factory_kwargs={"workload_size": 2}, chunk=64,
             frontier_cap=1 << 8, visited_cap=1 << 12)
GRACES = {"boot_grace": 120.0, "first_grace": 120.0,
          "steady_grace": 3.0, "idle_grace": 60.0, "grace_slack": 1.0}


def _server(root, **kw):
    kw.setdefault("admission", False)
    kw.setdefault("elastic", False)
    kw.setdefault("env", CHILD_ENV)
    kw.setdefault("warden_kwargs", dict(GRACES))
    return CheckServer(str(root), **kw)


def _same_verdict(a: dict, b: dict):
    for key in ("end", "unique", "explored", "depth"):
        assert a[key] == b[key], (key, a, b)


def _journal(root):
    path = os.path.join(str(root), "journal.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _events(root, kind):
    return [e for e in _journal(root) if e.get("t") == kind]


def _costs(root, tenant):
    path = os.path.join(str(root), "COSTS.jsonl")
    rows = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("tenant") == tenant:
                rows.append(rec)
    return rows


# --------------------------------------------- spec fixture modules

# A 3-stage message chain: S1 -> S2 -> S3, x walks 0..FINAL.  The
# final stage's write is the ONE knob the incremental tests edit —
# FINAL=3 is invariant-clean (SPACE_EXHAUSTED), FINAL=4 fires NO_FOUR
# at depth 3.  Field bounds are identical in both versions so the
# structural base (nodes/domains/messages) matches and only the S3
# handler hash differs.
CHAIN_MODULE = textwrap.dedent("""
    from dslabs_tpu.tpu.compiler import (Field, MessageType, NodeKind,
                                         ProtocolSpec, TimerType)


    def make_chain():
        spec = ProtocolSpec(
            "memo-chain",
            nodes=[NodeKind("proc", 1, (Field("x", init=0, hi=4),))],
            messages=[MessageType("S1", ()), MessageType("S2", ()),
                      MessageType("S3", ())],
            timers=[TimerType("TICK", (), 10, 10)],
            net_cap=4, timer_cap=1)

        @spec.on("proc", "S1")
        def h1(ctx, m):
            ctx.put("x", 1)
            ctx.send("S2", 0)

        @spec.on("proc", "S2")
        def h2(ctx, m):
            ctx.put("x", 2)
            ctx.send("S3", 0)

        @spec.on("proc", "S3")
        def h3(ctx, m):
            ctx.put("x", {final})

        spec.initial_messages.append(("S1", 0, 0, {{}}))

        def no_four(v):
            return v.get("proc", 0, "x") != 4

        spec.invariants["NO_FOUR"] = no_four
        return spec.compile()
""")

# Rename-only variant of the pingpong-spec factory: different module
# name, different factory-function name, extra comments/whitespace/
# docstring — structurally the SAME protocol.
SPEC_PP = textwrap.dedent("""
    from dslabs_tpu.tpu.specs import pingpong_spec


    def make(workload_size=2):
        return pingpong_spec(workload_size).compile()
""")

SPEC_PP_RENAMED = textwrap.dedent('''
    # A cosmetic rewrite of the same submission: renamed module,
    # renamed factory, reflowed whitespace.  Structurally identical.
    from dslabs_tpu.tpu.specs import pingpong_spec


    def build(workload_size=2):
        """Same lab-0 spec, different spelling."""

        return pingpong_spec(workload_size).compile()
''')


def _write_chain(tmp_path, name, final):
    (tmp_path / f"{name}.py").write_text(
        CHAIN_MODULE.format(final=final))
    return f"{name}:make_chain"


CHAIN = dict(chunk=64, frontier_cap=1 << 8, visited_cap=1 << 12)


# -------------------------------------------- fingerprint identity


def test_rename_only_same_fingerprint(tmp_path):
    """Whitespace/rename-only edits hash to the SAME structural
    fingerprint; a handler edit hashes to a different one."""
    (tmp_path / "fp_a.py").write_text(SPEC_PP)
    (tmp_path / "fp_b.py").write_text(SPEC_PP_RENAMED)
    extra = [str(tmp_path)]
    a = memo_mod.introspect_child("fp_a:make", {"workload_size": 2},
                                  None, extra_sys_path=extra)
    b = memo_mod.introspect_child("fp_b:build", {"workload_size": 2},
                                  None, extra_sys_path=extra)
    assert a["ok"] and b["ok"], (a, b)
    assert not a["weak"] and not b["weak"]
    assert a["spec_fp"] == b["spec_fp"]
    assert a["base_fp"] == b["base_fp"]
    # Different workload -> different structure (domains change).
    c = memo_mod.introspect_child("fp_a:make", {"workload_size": 3},
                                  None, extra_sys_path=extra)
    assert c["ok"] and c["spec_fp"] != a["spec_fp"]
    # One-handler edit -> different spec_fp, same base/predicates,
    # exactly one differing handler hash (the incremental precondition).
    v1 = _write_chain(tmp_path, "fp_v1", 3)
    v2 = _write_chain(tmp_path, "fp_v2", 4)
    i1 = memo_mod.introspect_child(v1, {}, None, extra_sys_path=extra)
    i2 = memo_mod.introspect_child(v2, {}, None, extra_sys_path=extra)
    assert i1["ok"] and i2["ok"]
    assert i1["kind"] == "spec" and not i1["weak"]
    assert i1["spec_fp"] != i2["spec_fp"]
    assert i1["base_fp"] == i2["base_fp"]
    assert i1["predicates"] == i2["predicates"]
    diff = [k for k in i1["handlers"]
            if i1["handlers"][k] != i2["handlers"][k]]
    assert diff == ["m:proc:S3"]


REP_MODULE = textwrap.dedent("""
    from dslabs_tpu.tpu.compiler import (Field, MessageType, NodeKind,
                                         ProtocolSpec, TimerType)
    from dslabs_tpu.tpu.quorum import QuorumCount
    from dslabs_tpu.tpu.slots import SlotField, Slots


    def {factory}():
        spec = ProtocolSpec(
            "memo-rep",
            nodes=[NodeKind("proc", 3, (
                Field("x", hi=7),
                Slots("log", {n}, (SlotField("cmd", hi=7,
                                             clear={clear}),),
                      base=1),
            ))],
            messages=[MessageType("GO", ())],
            timers=[TimerType("TICK", (), 10, 10)],
            net_cap=4, timer_cap=1,
            quorums=(QuorumCount("q", over="proc",
                                 threshold={threshold!r}),))

        @spec.on("proc", "GO")
        def go(ctx, m):
            met = ctx.quorum("q").met_bits(ctx.get("x"))
            ctx.slot_put("log", "cmd", 1, 2, when=met)
            ctx.slot_clear_upto("log", 2, when=~met)

        spec.initial_messages.append(("GO", 0, 0, {{}}))
        spec.invariants["OK"] = lambda v: True
        return spec.compile()
""")


def _write_rep(tmp_path, name, factory="make_rep", n=2, clear=0,
               threshold="majority"):
    (tmp_path / f"{name}.py").write_text(REP_MODULE.format(
        factory=factory, n=n, clear=clear, threshold=threshold))
    return f"{name}:{factory}"


def test_slot_quorum_rename_vs_resize_fingerprints(tmp_path):
    """ISSUE 20 satellite: the Slots/Quorum declarations participate in
    the structural fingerprint.  A factory rename is cosmetic (same
    fp); resizing the slot block, changing a SlotField ``clear``, or
    moving the quorum threshold — all invisible to the expanded node
    fields and the handler ASTs — each change the base fingerprint."""
    extra = [str(tmp_path)]

    def introspect(ref):
        out = memo_mod.introspect_child(ref, {}, None,
                                        extra_sys_path=extra)
        assert out["ok"] and not out["weak"], out
        return out

    base = introspect(_write_rep(tmp_path, "rep_a"))
    renamed = introspect(_write_rep(tmp_path, "rep_b",
                                    factory="build_replicated"))
    assert renamed["spec_fp"] == base["spec_fp"]
    assert renamed["base_fp"] == base["base_fp"]
    resized = introspect(_write_rep(tmp_path, "rep_c", n=3))
    cleared = introspect(_write_rep(tmp_path, "rep_d", clear=1))
    rethresh = introspect(_write_rep(tmp_path, "rep_e",
                                     threshold="all"))
    fps = {v["base_fp"] for v in (base, resized, cleared, rethresh)}
    assert len(fps) == 4, fps
    # The handler ASTs never changed — only the declarations did.
    assert resized["handlers"] == base["handlers"]
    assert cleared["handlers"] == base["handlers"]
    assert rethresh["handlers"] == base["handlers"]


def test_duck_typed_slot_block_marks_weak():
    """A partially-spec'd protocol (a slot declaration that is not a
    real Slots block) fingerprints WEAK, so the store refuses to
    memoize it rather than guess at its identity."""
    from dslabs_tpu.tpu.compiler import (Field, MessageType, NodeKind,
                                         ProtocolSpec, TimerType)
    from dslabs_tpu.tpu.slots import SlotField, Slots

    spec = ProtocolSpec(
        "memo-duck",
        nodes=[NodeKind("proc", 1, (
            Field("x", hi=4),
            Slots("log", 2, (SlotField("cmd", hi=7),), base=1)))],
        messages=[MessageType("GO", ())],
        timers=[TimerType("TICK", (), 10, 10)],
        net_cap=4, timer_cap=1)

    @spec.on("proc", "GO")
    def go(ctx, m):
        ctx.put("x", ctx.slot_get("log", "cmd", 1))

    spec.initial_messages.append(("GO", 0, 0, {}))
    spec.invariants["OK"] = lambda v: True
    proto = spec.compile()
    info = memo_mod.introspect_protocol(proto)
    assert not info["weak"]

    class DuckBlock:
        # Enough surface for the Ctx slot ops (the effect-table trace
        # still runs), but no ``name``/``fields`` — the declaration
        # fingerprint cannot see inside it.
        base, n = 1, 2

        def lane(self, field):
            return f"log.{field}"

    spec.slot_blocks[("proc", "log")] = DuckBlock()
    assert memo_mod.introspect_protocol(proto)["weak"]


def test_divergence_bound_chain(tmp_path):
    """Tag-reachability lower-bounds the first level a changed handler
    can fire: editing S3 in the 3-stage chain shares levels 0..2."""
    extra = [str(tmp_path)]
    v1 = _write_chain(tmp_path, "div_v1", 3)
    i1 = memo_mod.introspect_child(v1, {}, None, extra_sys_path=extra)
    assert i1["ok"]
    eff, init = i1["effects"], i1["initial"]
    assert memo_mod.divergence_depth(eff, init, {"m:proc:S3"}) == 2
    assert memo_mod.divergence_depth(eff, init, {"m:proc:S2"}) == 1
    assert memo_mod.divergence_depth(eff, init, {"m:proc:S1"}) == 0
    # A handler whose trigger is unreachable diverges nowhere.
    assert memo_mod.divergence_depth(
        eff, ["m1"], {"m:proc:S1"}) >= memo_mod._INF


# ------------------------------------- visited-tier save/load/refuse


def _tier_arrays(n=64, seed=7):
    rng = np.random.default_rng(seed)
    h1 = rng.integers(0, 1 << 63, size=n, dtype=np.uint64)
    h2 = rng.integers(0, 1 << 63, size=n, dtype=np.uint64)
    return h1, h2


def test_tier_roundtrip_and_prev_rotation(tmp_path):
    path = str(tmp_path / "tier.npz")
    h1, h2 = _tier_arrays()
    spill_mod.save_tier(path, h1, h2, {"pack": "p1", "sym": 0})
    r1, r2, meta = spill_mod.load_tier(
        path, expect_meta={"pack": "p1", "sym": 0})
    assert np.array_equal(r1, h1) and np.array_equal(r2, h2)
    assert meta["fmt"] == spill_mod.TIER_FORMAT
    # Second save rotates .prev; a torn main file falls back to it.
    g1, g2 = _tier_arrays(seed=8)
    spill_mod.save_tier(path, g1, g2, {"pack": "p1", "sym": 0})
    assert os.path.exists(path + ".prev")
    with open(path, "wb") as f:
        f.write(b"torn")
    f1, _, _ = spill_mod.load_tier(path,
                                   expect_meta={"pack": "p1", "sym": 0})
    assert np.array_equal(f1, h1)  # .prev holds the FIRST save
    # Both gone/torn -> loud corruption, never empty arrays.
    with open(path + ".prev", "wb") as f:
        f.write(b"also-torn")
    with pytest.raises(spill_mod.TierCorrupt):
        spill_mod.load_tier(path, expect_meta={"pack": "p1", "sym": 0})


def test_tier_refuses_foreign_pack_and_symmetry(tmp_path):
    """Satellite: the two refusal paths are LOUD — a tier saved under
    one pack descriptor or symmetry flag never loads under another."""
    path = str(tmp_path / "tier.npz")
    h1, h2 = _tier_arrays()
    spill_mod.save_tier(path, h1, h2, {"pack": "pack-v1:abcd", "sym": 0})
    with pytest.raises(spill_mod.TierMismatch, match="pack"):
        spill_mod.load_tier(path,
                            expect_meta={"pack": "pack-v2:ffff",
                                         "sym": 0})
    with pytest.raises(spill_mod.TierMismatch, match="sym"):
        spill_mod.load_tier(path,
                            expect_meta={"pack": "pack-v1:abcd",
                                         "sym": 6})


# ------------------------------------------- service-level reuse


def test_exact_hit_zero_dispatch(tmp_path):
    """ISSUE 16 acceptance leg (a): the identical resubmit returns the
    cached verdict with ZERO device dispatches — journaled memo_hit,
    cached=true, ~0 COSTS device_secs."""
    srv = _server(tmp_path)
    srv.submit(FACTORY, tenant="alice", **SMALL)
    srv.drain()
    cold = [v for v in srv.results if v["tenant"] == "alice"][0]
    assert cold["status"] == "done"

    res = srv.submit(FACTORY, tenant="bob", **SMALL)
    srv.close()
    assert res.get("memo") == "hit"
    hit = res["verdict"]
    assert hit["cached"] is True
    _same_verdict(hit, cold)
    assert hit["witness"] == cold["witness"]
    assert len(_events(tmp_path, "memo_hit")) == 1
    bob = _costs(tmp_path, "bob")[-1]
    assert bob["device_secs"] == 0.0 and bob["dispatches"] == 0
    st = srv.server_status()
    assert st["memo"]["hits"] == 1
    assert st["memo"]["device_secs_saved"] > 0


def test_warm_start_parity(tmp_path):
    """Leg (b): budget grew, signature matched — the new job resumes
    from the archived frontier and lands counts bit-identical to a
    cold run at the same depth."""
    ref_srv = _server(tmp_path / "ref", memo=False)
    ref_srv.submit(FACTORY, tenant="ref", **SMALL)
    ref = ref_srv.drain()["results"][0]
    ref_srv.close()

    srv = _server(tmp_path / "svc")
    srv.submit(FACTORY, tenant="a", max_depth=3, **SMALL)
    srv.drain()
    srv.submit(FACTORY, tenant="b", **SMALL)
    srv.drain()
    srv.close()
    warm = [v for v in srv.results if v["tenant"] == "b"][0]
    _same_verdict(warm, ref)
    assert warm["resumed_from_depth"] > 0
    ev = [e for e in _events(tmp_path / "svc", "memo")
          if e.get("mode") == "warm"]
    assert len(ev) == 1 and ev[0]["seed_depth"] > 0
    assert srv.server_status()["memo"]["warm_starts"] == 1


def test_incremental_recheck_and_stale_impossibility(tmp_path):
    """Leg (c) + stale-verdict impossibility, via the true hazard: the
    module is edited IN PLACE under the same factory path.  The edited
    spec must never return the old cached verdict; it completes via
    incremental re-check (levels_skipped >= 1) with a verdict and
    witness digest bit-identical to its own cold run."""
    extra = [str(tmp_path)]
    ref_root = tmp_path / "ref"
    _write_chain(tmp_path, "chain_cold", 4)
    ref_srv = _server(ref_root, extra_sys_path=extra, memo=False)
    ref_srv.submit("chain_cold:make_chain", tenant="ref", **CHAIN)
    ref = ref_srv.drain()["results"][0]
    ref_srv.close()
    assert ref["end"] == "INVARIANT_VIOLATED"
    assert ref["predicate"] == "NO_FOUR"

    factory = _write_chain(tmp_path, "chain", 3)
    srv = _server(tmp_path / "svc", extra_sys_path=extra)
    srv.submit(factory, tenant="v1", **CHAIN)
    v1 = srv.drain()["results"][0]
    assert v1["end"] == "SPACE_EXHAUSTED"

    _write_chain(tmp_path, "chain", 4)      # the one-handler edit
    srv.submit(factory, tenant="v2", **CHAIN)
    srv.drain()
    srv.close()
    v2 = [v for v in srv.results if v["tenant"] == "v2"][0]
    # Stale-verdict impossibility: the edit was SEEN (no memo_hit, no
    # SPACE_EXHAUSTED replay) …
    assert _events(tmp_path / "svc", "memo_hit") == []
    assert v2["end"] == "INVARIANT_VIOLATED"
    # … and the re-check was incremental yet bit-identical to cold.
    _same_verdict(v2, ref)
    assert v2["predicate"] == ref["predicate"]
    assert v2["witness"] == ref["witness"]
    ev = [e for e in _events(tmp_path / "svc", "memo")
          if e.get("mode") == "incremental"]
    assert len(ev) == 1
    assert ev[0]["levels_skipped"] >= 1
    st = srv.server_status()
    assert st["memo"]["incremental"] == 1
    assert st["memo"]["levels_skipped"] >= 1


def test_rename_only_resubmit_hits_both_caches(tmp_path):
    """Satellite: admission and memoization share ONE spec identity —
    a rename-only resubmit is an admission-cache hit AND a verdict-
    cache hit."""
    (tmp_path / "ren_a.py").write_text(SPEC_PP)
    (tmp_path / "ren_b.py").write_text(SPEC_PP_RENAMED)
    srv = _server(tmp_path / "svc", admission=True,
                  extra_sys_path=[str(tmp_path)])
    res = srv.submit("ren_a:make", tenant="alice",
                     factory_kwargs={"workload_size": 2}, chunk=64,
                     frontier_cap=1 << 8, visited_cap=1 << 12)
    assert res.get("accepted"), res
    srv.drain()
    res2 = srv.submit("ren_b:build", tenant="bob",
                      factory_kwargs={"workload_size": 2}, chunk=64,
                      frontier_cap=1 << 8, visited_cap=1 << 12)
    srv.close()
    assert res2.get("memo") == "hit"
    adm = _events(tmp_path / "svc", "admission")
    assert [e["cached"] for e in adm] == [False, True]
    _same_verdict(res2["verdict"],
                  [v for v in srv.results if v["tenant"] == "alice"][0])


def test_memo_off_overhead_guard(tmp_path, monkeypatch):
    """Memo OFF (constructor or DSLABS_MEMO=0) leaves the existing
    service path untouched: no memo dir, no memo events, no intro
    children, verdicts unchanged."""
    monkeypatch.setenv("DSLABS_MEMO", "0")
    srv = _server(tmp_path / "env_off")
    assert srv.memo is None
    srv.submit(FACTORY, tenant="a", **SMALL)
    srv.drain()
    srv.submit(FACTORY, tenant="b", **SMALL)
    srv.drain()
    srv.close()
    monkeypatch.delenv("DSLABS_MEMO")
    a, b = srv.results[0], srv.results[1]
    assert a["status"] == "done" and b["status"] == "done"
    _same_verdict(a, b)
    assert not os.path.isdir(os.path.join(str(tmp_path / "env_off"),
                                          "memo"))
    ev = _journal(tmp_path / "env_off")
    assert not [e for e in ev if e.get("t") in ("memo", "memo_hit")]
    assert srv.server_status()["memo"] == {"enabled": False}
    # Default-ON contract for the service path.
    srv_on = _server(tmp_path / "on")
    assert srv_on.memo is not None
    srv_on.close()


def test_three_tenant_drain_resubmit_bills_under_ten_percent(tmp_path):
    """Satellite acceptance: in a 3-tenant drain, tenant B's identical
    resubmit of tenant A's job bills <10% of A's cold device_secs in
    COSTS (here: exactly zero — the hit never dispatches)."""
    srv = _server(tmp_path, workers=1)
    srv.submit(FACTORY, tenant="alice", **SMALL)
    srv.submit(FACTORY, tenant="bob", **SMALL)
    srv.submit(FACTORY, tenant="carol",
               factory_kwargs={"workload_size": 3}, chunk=64,
               frontier_cap=1 << 8, visited_cap=1 << 12)
    summary = srv.drain()
    srv.close()
    assert summary["completed"] == 3
    va = [v for v in srv.results if v["tenant"] == "alice"][0]
    vb = [v for v in srv.results if v["tenant"] == "bob"][0]
    _same_verdict(va, vb)
    ca = _costs(tmp_path, "alice")[-1]
    cb = _costs(tmp_path, "bob")[-1]
    assert ca["device_secs"] > 0
    assert cb["device_secs"] < 0.10 * ca["device_secs"]
    assert len(_events(tmp_path, "memo_hit")) == 1
    assert summary["memo"]["hits"] == 1


@pytest.mark.slow
def test_sigkill_mid_warm_start_resume_parity(tmp_path):
    """A SIGKILL landing mid-warm-start is survived by the normal
    resume path: the seeded job's final verdict is bit-identical to
    the cold full run, and the fault never lands a cached verdict."""
    ref_srv = _server(tmp_path / "ref", memo=False)
    ref_srv.submit(FACTORY, tenant="ref", **SMALL)
    ref = ref_srv.drain()["results"][0]
    ref_srv.close()

    srv = _server(tmp_path / "svc", workers=1)
    srv.submit(FACTORY, tenant="a", max_depth=3, ladder=("device",),
               **SMALL)
    srv.drain()
    # The seeded checkpoint exists BEFORE the child boots, so
    # after_ckpt arms immediately and the kill lands on the very
    # first warm dispatch — mid-warm-start by construction.
    srv.submit(FACTORY, tenant="b", ladder=("device",),
               fault={"kind": "die", "at": 1, "after_ckpt": True},
               **SMALL)
    srv.drain()
    srv.close()
    out = [v for v in srv.results if v["tenant"] == "b"][0]
    assert out["status"] == "done"
    _same_verdict(out, ref)
    assert out["attempts"] >= 2           # the fault really fired
    assert out.get("cached") is not True
    warm = [e for e in _events(tmp_path / "svc", "memo")
            if e.get("mode") == "warm"]
    assert len(warm) == 1                 # seeded before the SIGKILL


@pytest.mark.slow
@pytest.mark.parametrize("strict", [True, False])
@pytest.mark.parametrize("packed", ["1", "0"])
def test_warm_parity_sweep(tmp_path, strict, packed):
    """Warm-start exactness across the engine's encoding matrix:
    strict and beam, packed frontier on and off (lab-0 spec factory +
    the lab-1 clientserver knob ride the same compiled path)."""
    env = dict(CHILD_ENV, DSLABS_PACKED=packed)
    (tmp_path / "sw.py").write_text(SPEC_PP)
    extra = [str(tmp_path)]
    kw = dict(factory_kwargs={"workload_size": 2}, strict=strict,
              chunk=64, frontier_cap=1 << 8, visited_cap=1 << 12)
    ref_srv = _server(tmp_path / "ref", env=env, memo=False,
                      extra_sys_path=extra)
    ref_srv.submit("sw:make", tenant="ref", **kw)
    ref = ref_srv.drain()["results"][0]
    ref_srv.close()
    assert ref["status"] == "done"

    srv = _server(tmp_path / "svc", env=env, extra_sys_path=extra)
    srv.submit("sw:make", tenant="a", max_depth=3, **kw)
    srv.drain()
    srv.submit("sw:make", tenant="b", **kw)
    srv.drain()
    srv.close()
    warm = [v for v in srv.results if v["tenant"] == "b"][0]
    _same_verdict(warm, ref)
    assert srv.server_status()["memo"]["warm_starts"] == 1
