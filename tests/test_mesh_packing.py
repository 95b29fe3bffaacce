"""Packed-wire mesh exchange (ISSUE 18): the sharded carry moves PACKED
rows through the owner-hashed ``all_to_all`` —

* the wire descriptor cuts bytes-per-state >= 8x on the generated lab1
  and paxos specs (13.7x / 13.5x measured — asserted from the
  descriptor the engine actually installs);
* packed-vs-raw exchange (``mesh_pack=False`` = the codec's
  reference) is BIT-IDENTICAL
  (unique/explored/verdict/depth/dropped) across widths {1, 2, 4, 8},
  strict and beam, and across a cross-width resume chain 8 -> 4 -> 2
  -> 1 through the packed checkpoint format;
* delta-from-level-base lanes (``Field(delta=)``, the varint lane for
  view-number-style unbounded fields) pack the pb spec and stay exact;
* the spill spool rides the packed encoding: 1/8-capacity strict runs
  keep exact parity with the full-table oracle;
* the promote still lowers with ZERO collectives under packing
  (raw-lane repack at the boundary is elementwise);
* pack/decode are first-class dispatch sites (DISPATCH_SITES +
  ``dispatch_site_programs()``) and their jaxprs audit clean;
* a mesh job that runs UNPACKED (hand twin -> identity codec, or
  ``mesh_pack=False``) is loud: a ``mesh_unpacked`` telemetry event,
  never silence.

Marked ``mesh`` (``make mesh-smoke`` runs this suite too).
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import packing as packing_mod  # noqa: E402
from dslabs_tpu.tpu.engine import TensorSearch  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.sharded import (ShardedTensorSearch,  # noqa: E402
                                    make_mesh)
from dslabs_tpu.tpu.specs import (clientserver_spec,  # noqa: E402
                                  paxos_spec, pb_spec, pingpong_spec)
from dslabs_tpu.tpu.telemetry import Telemetry  # noqa: E402

pytestmark = pytest.mark.mesh

_COLLECTIVES = ("all-to-all", "all_to_all", "all-reduce", "all_reduce",
                "all-gather", "all_gather", "collective-permute",
                "collective_permute", "reduce-scatter", "reduce_scatter")


def _pruned(p):
    name = next(iter(p.goals))
    return dataclasses.replace(p, goals={},
                               prunes={name: p.goals[name]})


def _pingpong():
    return _pruned(pingpong_spec(2).compile())


def _lab1_small():
    return _pruned(clientserver_spec(1, 2).compile())


def _build(proto, n_devices, **kw):
    kw.setdefault("chunk_per_device", 16)
    kw.setdefault("frontier_cap", 1 << 8)
    kw.setdefault("visited_cap", 1 << 10)
    return ShardedTensorSearch(proto, make_mesh(n_devices), **kw)


def _assert_exact(a, b):
    assert a.end_condition == b.end_condition
    assert a.unique_states == b.unique_states
    assert a.states_explored == b.states_explored
    assert a.depth == b.depth
    assert a.dropped == b.dropped


# ------------------------------------------------------ wire descriptor

@pytest.mark.parametrize("spec_fn,floor", [
    (lambda: clientserver_spec(3, 4).compile(), 8.0),
    (lambda: paxos_spec(3).compile(), 8.0),
])
def test_wire_bytes_per_state_floor(spec_fn, floor):
    """ACCEPTANCE: the mesh wire descriptor (same derivation the
    sharded engine installs: delta=True) cuts bytes-per-state >= 8x on
    the lab1 and packed-paxos specs."""
    proto = dataclasses.replace(spec_fn(), goals={})
    lanes = TensorSearch(proto, chunk=8).lanes
    pk = packing_mod.derive_packing(proto, lanes, delta=True)
    assert pk is not None and not pk.identity
    assert pk.pack_ratio >= floor, pk.descriptor()
    assert pk.words * 4 * floor <= lanes * 4


def test_engine_installs_packed_wire_by_default():
    """``mesh_pack`` defaults ON: a generated spec gets the
    non-identity codec, the carry plane shrinks to the packed word
    count, and the verdict stamps the ratio (satellite: pack_ratio on
    SearchOutcome + levels)."""
    search = _build(_pingpong(), 2)
    assert search.mesh_pack and search._pk is not None
    assert search.plane == search._pk.words < search.lanes
    out = search.run()
    assert out.pack_ratio == pytest.approx(
        search.lanes * 4 / (search.plane * 4), rel=0.01)
    assert out.levels
    assert all(lv["pack_ratio"] > 1.0 for lv in out.levels)
    raw = _build(_pingpong(), 2, mesh_pack=False)
    assert raw._pk is None and raw.plane == raw.lanes


# ------------------------------------------------------- parity matrix

@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_packed_vs_raw_parity_pingpong(width):
    """ACCEPTANCE: bit-identical verdicts between the packed wire and
    the raw parity oracle at every mesh width."""
    proto = _pingpong()
    packed = _build(proto, width).run()
    raw = _build(proto, width, mesh_pack=False).run()
    assert packed.end_condition == "SPACE_EXHAUSTED"
    _assert_exact(packed, raw)


@pytest.mark.parametrize("strict", [True, False])
def test_packed_vs_raw_parity_lab1(strict):
    """Lab1 (generated, 13.7x codec) at width 8, strict AND beam: the
    beam run truncates at a deliberately small frontier cap and the
    drop count must match bit-for-bit too."""
    proto = _pruned(clientserver_spec(2, 2).compile())
    width = 8 if strict else 2
    kw = dict(frontier_cap=1 << 8 if strict else 4,
              visited_cap=1 << 12, strict=strict, max_depth=8)
    if not strict:
        # f_cap floors at chunk_per_device; 4 rows/device truncates
        # this fixture's levels (per-device occupancy peaks at 7).
        kw["chunk_per_device"] = 4
    packed = _build(proto, width, **kw).run()
    raw = _build(proto, width, mesh_pack=False, **kw).run()
    _assert_exact(packed, raw)
    if not strict:
        assert packed.dropped > 0   # the beam really truncated


def test_delta_lane_parity_pb():
    """The varint lane (ISSUE 18b): pb's view-number fields carry
    ``Field(delta=)`` domains, so the wire codec packs them against a
    per-level base instead of falling back to identity — and the
    delta-packed run matches the raw oracle exactly."""
    proto = _pruned(pb_spec(2, 1, 1).compile())
    search = _build(proto, 2, max_depth=3, frontier_cap=1 << 9,
                    visited_cap=1 << 12)
    assert search._pk is not None and search._pk.has_delta
    assert search._mesh_delta
    assert {"pb_cur", "pb_nxt"} <= set(search._carry_names())
    packed = search.run()
    raw = _build(proto, 2, mesh_pack=False, max_depth=3,
                 frontier_cap=1 << 9, visited_cap=1 << 12).run()
    _assert_exact(packed, raw)
    assert packed.states_explored > 0


def test_cross_width_resume_packed_8_4_2_1(tmp_path):
    """A packed-wire checkpoint re-shards exactly onto every narrower
    width: the dump stores packed rows + the encoding marker + (for
    delta specs) the pack base, and each resume re-hashes owners at
    the new D."""
    proto = _pingpong()
    oracle = _build(proto, 8).run()
    assert oracle.end_condition == "SPACE_EXHAUSTED"

    path = str(tmp_path / "mesh-packed.ckpt")
    out = _build(proto, 8, checkpoint_path=path,
                 checkpoint_every=1, max_depth=2).run()
    assert out.end_condition == "DEPTH_EXHAUSTED"
    for width, depth in ((4, 3), (2, 4), (1, None)):
        search = _build(proto, width, checkpoint_path=path,
                        checkpoint_every=1, max_depth=depth)
        assert search._pk is not None   # the packed wire, end to end
        out = search.run(resume=True)
    assert out.end_condition == oracle.end_condition
    assert out.unique_states == oracle.unique_states
    assert out.states_explored == oracle.states_explored
    assert out.depth == oracle.depth


# ------------------------------------------------------- spill + promote

def test_packed_spill_parity_eighth_capacity():
    """ACCEPTANCE: the spill spool rides the packed encoding — a
    strict run with the visited table capped at ~1/8 of the reachable
    count keeps exact parity with the full-table oracle through
    drain/evict/reinject of PACKED spool segments."""
    proto = _lab1_small()
    base = _build(proto, 2, frontier_cap=1 << 9,
                  visited_cap=1 << 13, max_depth=8).run()
    cap = 1 << max(3, int(np.floor(
        np.log2(max(base.unique_states // 8, 8)))))
    out = _build(proto, 2, frontier_cap=1 << 9, visited_cap=cap,
                 max_depth=8, spill=True).run()
    _assert_exact(base, out)
    assert out.dropped_states == 0
    assert out.spilled_keys > 0          # the tier really engaged


@pytest.mark.parametrize("spec_fn", [_pingpong,
                                     lambda: _pruned(
                                         pb_spec(2, 1, 1).compile())])
def test_fused_promote_zero_collectives_under_packing(spec_fn):
    """ACCEPTANCE pin: the fused promote stays a LOCAL buffer swap
    under the packed wire — including the delta repack (pb spec),
    which re-bases rows elementwise against the replicated pb vector
    and must not reintroduce a boundary collective."""
    search = _build(spec_fn(), 8)
    assert search._pk is not None
    text = search._finish_level.lower(search._carry_sds()).as_text()
    assert not any(c in text for c in _COLLECTIVES), (
        "packed fused-exchange promote must stay collective-free")


# ------------------------------------------------------- observability

def test_dispatch_sites_cover_pack_decode():
    """CI satellite: pack/decode are canonical dispatch sites —
    registered in DISPATCH_SITES, emitted by the sharded engine's
    dispatch_site_programs(), and their jaxprs audit clean (J1-J5)."""
    from dslabs_tpu.analysis.jaxpr_audit import audit_sites
    from dslabs_tpu.tpu.telemetry import DISPATCH_SITES

    for site in ("packing.pack", "packing.unpack"):
        assert site in DISPATCH_SITES
    search = _build(_pingpong(), 2)
    sites = search.dispatch_site_programs()
    picked = {k: v for k, v in sites.items()
              if k in ("packing.pack", "packing.unpack")}
    assert set(picked) == {"packing.pack", "packing.unpack"}
    assert audit_sites(picked, "ShardedTensorSearch") == []


def test_mesh_unpacked_event_is_loud():
    """Satellite: a mesh job shipping RAW lanes is loud — the hand
    twin (identity codec) and ``mesh_pack=False`` both emit a
    ``mesh_unpacked`` event; the packed default emits none."""
    def run(proto, **kw):
        tel = Telemetry()
        _build(proto, 2, telemetry=tel, max_depth=4, **kw).run()
        return [e for e in tel.events
                if e.get("t") == "event"
                and e.get("kind") == "mesh_unpacked"]

    hand = dataclasses.replace(
        make_pingpong_protocol(2), goals={})
    ev = run(hand)
    assert ev and ev[0]["reason"] == "identity descriptor"
    ev = run(_pingpong(), mesh_pack=False)
    assert ev and ev[0]["reason"] == "knob"
    assert run(_pingpong()) == []


def test_status_skew_agg_block_and_watch(tmp_path, capsys):
    """Bugfix satellite: STATUS.json carries a schema-pinned skew
    aggregate (imbalance_max/mean/cv live from the per-level lanes)
    and ``telemetry watch`` renders it during a run."""
    import json

    from dslabs_tpu.tpu import telemetry as tel_mod

    ck = str(tmp_path / "search.ckpt")
    tel = Telemetry.for_checkpoint(ck)
    search = _build(_pruned(clientserver_spec(3, 4).compile()), 8,
                    chunk_per_device=4,
                    frontier_cap=1 << 9, visited_cap=1 << 13,
                    max_depth=6, telemetry=tel)
    search.run()
    tel.close()

    st = json.loads((tmp_path / "STATUS.json").read_text())
    assert "skew_agg" in st              # schema-pinned
    agg = st["skew_agg"]
    for key in ("imbalance_max", "imbalance_mean", "cv_max", "levels"):
        assert key in agg
    assert agg["levels"] > 0
    assert agg["imbalance_max"] >= agg["imbalance_mean"] > 0

    assert tel_mod.main(["watch", str(tmp_path), "--once"]) == 0
    text = capsys.readouterr().out
    assert "skew agg:" in text
    assert "imbalance_max=" in text
