"""Owner-sharded multi-chip superstep (ISSUE 12): the in-superstep
row exchange, first-class carry placement, and the Pallas bucket-probe
kernel.

The exchange routes successor ROWS through the same owner-hashed
``all_to_all`` as their fingerprints, so fresh states land on their
owner's frontier shard as they are produced and the level promote is a
local buffer swap.  This suite is the acceptance matrix:

* exact unique/explored/verdict/depth parity between the sharded engine
  and the host-dedup reference (``TensorSearch(use_host_visited=True)``,
  ``run_host``) at n_devices in {1, 2, 4, 8} on pingpong + lab1, and
  on pingpong against the object checker, which shares no code with
  ``tpu/``;
* per-level host dispatches stay within the PR 3 budget (<= 2/level)
  and the promote program carries ZERO collectives;
* per-device lanes at width 4: what ``imbalance_max.mesh4`` reads;
* Pallas-vs-jnp visited-table parity — bit-exact tables, insert flags,
  and the unresolved/overflow contract — standalone and through a full
  sharded search (``DSLABS_VISITED_PALLAS=interpret``);
* cross-width checkpoint resume 8 -> 4 -> 2 -> 1 stays exact on the new
  exchange path (owner re-hashing at each narrower width);
* the supervisor's transient-retry boundary covers the fused dispatch.

Marked ``mesh`` (``make mesh-smoke`` runs exactly this suite on the
CPU virtual 8-device mesh); the heavier combinations are additionally
``slow`` so tier-1 keeps only the cheap ones.
"""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dslabs_tpu.tpu import visited as visited_mod  # noqa: E402
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import (CARRY_PARTITION_RULES,  # noqa: E402
                                    ShardedTensorSearch, make_mesh,
                                    match_partition_rules)

pytestmark = pytest.mark.mesh

_COLLECTIVES = ("all-to-all", "all_to_all", "all-reduce", "all_reduce",
                "all-gather", "all_gather", "collective-permute",
                "collective_permute", "reduce-scatter", "reduce_scatter")


def _pruned_pingpong():
    pp = make_pingpong_protocol(workload_size=2)
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def _pruned_lab1():
    cs = make_clientserver_protocol(n_clients=1, w=2)
    return dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]})


def _build(proto, n_devices, **kw):
    kw.setdefault("chunk_per_device", 16)
    kw.setdefault("frontier_cap", 1 << 8)
    kw.setdefault("visited_cap", 1 << 10)
    return ShardedTensorSearch(proto, make_mesh(n_devices), **kw)


def _host_reference(proto, **kw):
    """``run_host``: device expand, host sort-unique dedup — none of
    the sharded engine's level loop, exchange or visited table."""
    from dslabs_tpu.tpu.engine import TensorSearch

    return TensorSearch(proto, chunk=16, frontier_cap=1 << 11,
                        visited_cap=1 << 10, use_host_visited=True,
                        **kw).run()


def _assert_exact(a, b):
    assert a.end_condition == b.end_condition
    assert a.unique_states == b.unique_states
    assert a.states_explored == b.states_explored
    assert a.depth == b.depth
    assert a.dropped == b.dropped


# --------------------------------------------------- width-parity matrix

@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_width_parity_matrix_pingpong(n_devices):
    """Acceptance: the sharded engine matches the host-dedup reference
    EXACTLY at every mesh width."""
    proto = _pruned_pingpong()
    out = _build(proto, n_devices).run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    _assert_exact(out, _host_reference(proto))


@pytest.mark.parametrize("n_devices", [1, 2, 4, 8])
def test_width_parity_matrix_pingpong_vs_object_checker(n_devices):
    """The same matrix held to the INDEPENDENT reference: the object
    checker (search/search.py BFS — no code shared with ``tpu/``)
    exhausts the same pruned space with the same count."""
    from dslabs_tpu.search.results import EndCondition
    from tests.test_tpu_engine import object_search

    obj = object_search(2, prune_done=True)
    assert obj.end_condition == EndCondition.SPACE_EXHAUSTED
    out = _build(_pruned_pingpong(), n_devices).run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert out.unique_states == obj.discovered_count
    assert out.dropped == out.visited_overflow == 0


@pytest.mark.parametrize("n_devices", [1, 8])
def test_width_parity_matrix_lab1(n_devices):
    proto = _pruned_lab1()
    out = _build(proto, n_devices).run()
    assert out.end_condition == "SPACE_EXHAUSTED"
    _assert_exact(out, _host_reference(proto))


@pytest.mark.slow
@pytest.mark.parametrize("n_devices", [2, 4])
def test_width_parity_matrix_lab1_mid_widths(n_devices):
    proto = _pruned_lab1()
    _assert_exact(_build(proto, n_devices).run(), _host_reference(proto))


@pytest.mark.slow
def test_width_parity_strict_vs_beam():
    """The exchange is verdict-preserving in BOTH capacity modes."""
    proto = _pruned_pingpong()
    for strict in (True, False):
        _assert_exact(_build(proto, 8, strict=strict).run(),
                      _host_reference(proto, strict=strict))


def test_per_device_lanes_width_4_lab1():
    """What ``imbalance_max.mesh4`` reads: at width 4 every level
    record's ``per_device.explored`` has one entry a device, they sum to
    the level's explored, and at the widest level the owner hash gave
    every device work."""
    cs = make_clientserver_protocol(n_clients=2, w=3)   # lab1-entry's
    out = _build(dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]}),
        4, frontier_cap=1 << 9, visited_cap=1 << 12).run()
    assert (out.end_condition, out.unique_states) == (
        "SPACE_EXHAUSTED", 255)
    before = 0
    for rec in out.levels:
        lanes = rec["per_device"]["explored"]
        assert len(lanes) == 4
        assert sum(lanes) == rec["explored"] - before, rec
        before = rec["explored"]
    widest = max(out.levels, key=lambda r: sum(r["per_device"]["explored"]))
    assert all(e > 0 for e in widest["per_device"]["explored"]), widest


# ---------------------------------------------- dispatch budget + promote

def test_fused_exchange_dispatch_budget():
    """The dispatch-counter pin (PR 3 budget): a level spends <= 2 host
    dispatches (superstep + thin promote), and the promote program
    moves ZERO rows over ICI — its lowering contains no collective at
    width 8."""
    proto = _pruned_pingpong()
    search = _build(proto, 8)
    counts = {}

    def hook(tag, fn, *args):
        counts[tag] = counts.get(tag, 0) + 1
        return fn(*args)

    search._dispatch_hook = hook
    out = search.run()
    assert out.depth >= 3
    assert (counts["sharded.superstep"] + counts["sharded.promote"]
            <= 2 * out.depth)

    text = search._finish_level.lower(search._carry_sds()).as_text()
    assert not any(c in text for c in _COLLECTIVES), (
        "the promote must be a local buffer swap")


# --------------------------------------------------- carry placement (b)

def test_partition_rules_cover_every_carry_leaf():
    """Every carry leaf (base + trace + spill variants) resolves
    through CARRY_PARTITION_RULES; an undeclared leaf is loud."""
    names = ["cur", "cur_n", "j", "evp", "noapp", "nxt", "nxt_n",
             "visited", "vis_n", "explored", "overflow", "vis_over",
             "drops", "flag_cnt", "flag_rows", "tmeta", "flag_meta",
             "f_full"]
    specs = match_partition_rules(CARRY_PARTITION_RULES, names,
                                  "search")
    assert set(specs) == set(names)
    from jax.sharding import PartitionSpec as P
    assert specs["cur"] == P("search")
    assert specs["visited"] == P("search")
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules(CARRY_PARTITION_RULES, ["mystery"],
                              "search")


def test_carry_placement_is_first_class():
    """The rule-derived NamedShardings feed every placement consumer:
    shard_map specs, the init program's outputs, and the AOT
    ShapeDtypeStructs agree leaf for leaf — and survive a width
    change (the elastic-ladder contract)."""
    from jax.sharding import NamedSharding

    proto = _pruned_pingpong()
    for width in (8, 2):
        search = _build(proto, width)
        shards = search._carry_shardings()
        specs = search._carry_specs()
        sds = search._carry_sds()
        assert set(shards) == set(specs) == set(sds)
        for k, s in shards.items():
            assert isinstance(s, NamedSharding)
            assert s.spec == specs[k]
            assert sds[k].sharding == s
        carry = search._init_carry(search.initial_state())
        for k, v in carry.items():
            assert v.sharding.is_equivalent_to(shards[k], v.ndim), k


# ------------------------------------------------ Pallas bucket kernel (c)

def _key_batch(n=300, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2 ** 32, size=(n, 4), dtype=np.uint32)
    keys[50:60] = keys[0:10]            # in-batch duplicates
    keys[99] = np.uint32(0xFFFFFFFF)    # the all-MAX collider
    valid = rng.random(n) > 0.2
    return jnp.asarray(keys), jnp.asarray(valid)


def test_pallas_vs_jnp_insert_bitexact():
    """The kernel body is the SAME traced algorithm as the jnp oracle:
    tables, insert flags, and unresolved flags are bit-identical."""
    keys, valid = _key_batch()
    table = visited_mod.empty_table(1 << 9)
    tj, ij, uj = visited_mod.insert_jnp(table, keys, valid)
    tp, ip, up = visited_mod.pallas_insert(table, keys, valid,
                                           interpret=True)
    assert (np.asarray(tj) == np.asarray(tp)).all()
    assert (np.asarray(ij) == np.asarray(ip)).all()
    assert (np.asarray(uj) == np.asarray(up)).all()


def test_pallas_overflow_contract_parity():
    """Table-full overflow (ISSUE 1 contract): the unresolved set — the
    keys a strict driver raises CapacityOverflow on — is identical
    between the kernel and the oracle on a saturated table."""
    keys, valid = _key_batch()
    tiny = visited_mod.empty_table(visited_mod.BKT * 2)
    tj, ij, uj = visited_mod.insert_jnp(tiny, keys, valid)
    tp, ip, up = visited_mod.pallas_insert(tiny, keys, valid,
                                           interpret=True)
    assert int(np.asarray(uj).sum()) > 0        # genuinely overflowed
    assert (np.asarray(uj) == np.asarray(up)).all()
    assert (np.asarray(ij) == np.asarray(ip)).all()
    assert (np.asarray(tj) == np.asarray(tp)).all()


def test_pallas_mode_knob():
    os.environ["DSLABS_VISITED_PALLAS"] = "0"
    try:
        assert visited_mod.pallas_mode() == "off"
        assert visited_mod._pallas_interpret() is None
    finally:
        os.environ["DSLABS_VISITED_PALLAS"] = "interpret"
    try:
        assert visited_mod.pallas_mode() == "interpret"
        assert visited_mod._pallas_interpret() is True
    finally:
        del os.environ["DSLABS_VISITED_PALLAS"]
    # Default on every backend: the jnp path (Mosaic refuses the
    # kernel, so it is never picked unasked).
    assert visited_mod.pallas_mode() == "off"
    assert visited_mod._pallas_interpret() is None


def test_pallas_on_never_degrades(monkeypatch):
    """``DSLABS_VISITED_PALLAS=on`` off-TPU raises: an explicit request
    for the compiled kernel is never answered by the interpreter or
    the jnp path."""
    monkeypatch.setenv("DSLABS_VISITED_PALLAS", "on")
    assert visited_mod.pallas_mode() == "on"
    table = visited_mod.empty_table(visited_mod.BKT * 2)
    keys = jnp.zeros((4, 4), jnp.uint32)
    valid = jnp.ones((4,), jnp.bool_)
    with pytest.raises(RuntimeError, match="DSLABS_VISITED_PALLAS=on"):
        visited_mod.insert(table, keys, valid)
    monkeypatch.setenv("DSLABS_VISITED_PALLAS", "sometimes")
    with pytest.raises(ValueError, match="DSLABS_VISITED_PALLAS"):
        visited_mod.pallas_mode()


def test_pallas_engine_parity(monkeypatch):
    """A full fused-exchange search with the table probe forced through
    the Pallas interpreter matches the jnp-path run exactly — the
    CapacityOverflow/visited_overflow contract is unchanged."""
    proto = _pruned_pingpong()
    base = _build(proto, 2).run()
    monkeypatch.setenv("DSLABS_VISITED_PALLAS", "interpret")
    out = _build(proto, 2).run()
    _assert_exact(out, base)


def test_pallas_site_registered_and_clean():
    """The bucket kernel is a canonical dispatch site: registered in
    telemetry.DISPATCH_SITES (hot), present in both engines' site maps,
    and its lowering audits clean."""
    from dslabs_tpu.analysis.jaxpr_audit import audit_sites
    from dslabs_tpu.tpu.telemetry import DISPATCH_SITES

    assert "visited.insert" in DISPATCH_SITES
    assert DISPATCH_SITES["visited.insert"]["hot"]
    proto = _pruned_pingpong()
    search = _build(proto, 2)
    sites = search.dispatch_site_programs()
    assert "visited.insert" in sites
    findings = audit_sites(
        {"visited.insert": sites["visited.insert"]},
        "ShardedTensorSearch")
    assert findings == []


# ------------------------------------------------- cross-width resilience

def test_cross_width_resume_8_4_2_1(tmp_path):
    """Satellite: a fused-exchange checkpoint re-shards exactly onto
    every narrower width (owner re-hash at the new D) — the elastic
    ladder's resume contract holds on the new exchange path."""
    proto = _pruned_pingpong()
    oracle = _build(proto, 8).run()
    assert oracle.end_condition == "SPACE_EXHAUSTED"

    path = str(tmp_path / "mesh.ckpt")
    out = _build(proto, 8, checkpoint_path=path,
                 checkpoint_every=1, max_depth=2).run()
    assert out.end_condition == "DEPTH_EXHAUSTED"
    for width, depth in ((4, 3), (2, 4), (1, None)):
        search = _build(proto, width, checkpoint_path=path,
                        checkpoint_every=1, max_depth=depth)
        out = search.run(resume=True)
    assert out.end_condition == oracle.end_condition
    assert out.unique_states == oracle.unique_states
    assert out.states_explored == oracle.states_explored
    assert out.depth == oracle.depth


def test_fused_exchange_transient_retry():
    """The supervisor's retry boundary covers the fused dispatch: a
    transient raise inside a superstep retries in place with an
    identical verdict (fault site = sharded.superstep, the fused
    exchange's dispatch tag in DISPATCH_SITES)."""
    from dslabs_tpu.tpu.supervisor import (FaultPlan, RetryPolicy,
                                           SearchSupervisor)

    proto = _pruned_pingpong()

    def sup(**kw):
        return SearchSupervisor(
            proto, mesh=make_mesh(8), chunk=16, frontier_cap=1 << 8,
            visited_cap=1 << 10, **kw)

    base = sup().run()
    assert base.end_condition == "SPACE_EXHAUSTED"
    out = sup(fault_plan=FaultPlan().raise_at(2, count=2),
              policy=RetryPolicy(max_retries=3,
                                 backoff_base=0.001)).run()
    assert out.end_condition == base.end_condition
    assert out.unique_states == base.unique_states
    assert out.states_explored == base.states_explored
    assert out.retries == 2
    assert out.failovers == 0
