"""Bit-packed frontier encoding (ISSUE 15 leg (a), tpu/packing.py):
the packed path is ON by default and BIT-EXACT —

* descriptor round-trip: pack(unpack) is the identity over in-domain
  rows incl. SENTINEL lanes, jnp and numpy codecs agree bit-for-bit;
* hand twins (no declared domains) derive the IDENTITY descriptor, so
  the default-on path cannot perturb the pinned lab counts;
* bytes_per_state >= 2x reduction pinned on the generated lab1 and
  paxos specs (13.7x / 13.5x measured — asserted from the descriptor);
* packed-vs-unpacked EXACT parity (unique/explored/verdict/depth) on
  pingpong + lab1, strict and beam(strict=False), device loop vs the
  host-dedup oracle;
* a strict run at a frontier_cap sized in PACKED bytes completes a
  depth the unpacked layout provably cannot fit in the same HBM;
* out-of-domain live values are a loud CapacityOverflow (a wrong spec
  bound must never silently corrupt stored states);
* checkpoints store packed rows + the encoding marker: SIGKILL-mid-run
  resume parity on a packed dump, loud packed->raw cross-resume
  CONVERSION, and loud refusal of a foreign-descriptor dump.

Marked ``capacity2`` (``make capacity2-smoke``)."""

import dataclasses
import os
import signal
import subprocess
import sys
import time
import zlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import checkpoint as ckpt_mod  # noqa: E402
from dslabs_tpu.tpu import packing as packing_mod  # noqa: E402
from dslabs_tpu.tpu.engine import (SENTINEL, CapacityOverflow,  # noqa: E402
                                   TensorSearch, flatten_state)
from dslabs_tpu.tpu import specs_lab4  # noqa: E402
from dslabs_tpu.tpu.specs import (clientserver_spec,  # noqa: E402
                                  paxos_spec, pb_spec, pingpong_spec)

pytestmark = pytest.mark.capacity2


def _pruned(p):
    name = next(iter(p.goals))
    return dataclasses.replace(p, goals={},
                               prunes={name: p.goals[name]})


def _lab1():
    return _pruned(clientserver_spec(3, 4).compile())


def _assert_exact(a, b):
    assert a.end_condition == b.end_condition
    assert a.unique_states == b.unique_states
    assert a.states_explored == b.states_explored
    assert a.depth == b.depth


# --------------------------------------------------------- descriptor

def test_roundtrip_with_sentinels_and_negatives():
    """pack/unpack are exact inverses over in-domain values, SENTINEL
    lanes, and negative domains; jnp and numpy codecs agree."""
    proto = _lab1()
    eng = TensorSearch(proto, chunk=64)
    pk = eng._pk
    assert pk is not None and not pk.identity
    rng = np.random.default_rng(0)
    rows = np.zeros((64, pk.lanes), np.int32)
    doms, sents = packing_mod._flat_domains(proto)
    for i, (dom, s_cap) in enumerate(zip(doms, sents)):
        if dom is None:
            rows[:, i] = rng.integers(-2**31, 2**31 - 1, 64)
        else:
            rows[:, i] = rng.integers(dom[0], dom[1] + 1, 64)
        if s_cap:
            mask = rng.random(64) < 0.3
            rows[mask, i] = SENTINEL
    rt_np = pk.unpack_np(pk.pack_np(rows))
    assert (rt_np == rows).all()
    rt_jnp = np.asarray(pk.unpack_jnp(pk.pack_jnp(
        jax.numpy.asarray(rows))))
    assert (rt_jnp == rows).all()
    assert (np.asarray(pk.pack_jnp(jax.numpy.asarray(rows)))
            == pk.pack_np(rows)).all()


def test_hand_twin_derives_identity():
    """No declared domains -> identity descriptor -> the default-on
    packed path cannot touch the hand twins' traced programs."""
    from dslabs_tpu.tpu.protocols.pingpong import make_pingpong_protocol

    eng = TensorSearch(make_pingpong_protocol(2), chunk=64)
    assert eng._pk is None
    assert eng.plane == eng.lanes
    pk = packing_mod.derive_packing(eng.p, eng.lanes)
    assert pk.identity and pk.signature() == "raw"
    rows = np.arange(2 * eng.lanes, dtype=np.int32).reshape(2, -1)
    assert (pk.pack_np(rows) == rows).all()
    assert (pk.unpack_np(rows) == rows).all()


@pytest.mark.parametrize("proto,floor", [
    (clientserver_spec(3, 4).compile(), 2.0),
    (paxos_spec(3).compile(), 2.0),
])
def test_bytes_per_state_reduction_floor(proto, floor):
    """ACCEPTANCE: >= 2x bytes/state reduction on the lab1 and paxos
    specs, asserted from the packing descriptor itself."""
    eng = TensorSearch(dataclasses.replace(proto, goals={}), chunk=64)
    pk = eng._pk
    assert pk is not None
    assert pk.pack_ratio >= floor, pk.descriptor()
    assert pk.bytes_per_state * floor <= pk.bytes_per_state_unpacked


# ------------------------------------------------- device codec == oracle

def _synthetic_protocol():
    """Raw 32-bit lanes beside 1-bit lanes: a word boundary after every
    raw lane, 1-bit fields filling the words between them."""
    import types

    # On a sentinel-capable lane one bit holds the domain {0} and the
    # reserved all-ones code.
    bit, flag, raw = (0, 1), (0, 0), None
    return types.SimpleNamespace(
        name="synthetic", n_nodes=1, net_cap=3, timer_cap=2,
        node_width=9, msg_width=4, timer_width=3,
        lane_domains={
            "nodes": [bit, raw, bit, bit, bit, raw, raw, bit, (-3, 3)],
            "msg": [flag, raw, flag, (0, 6)],
            "timer": [raw, flag, flag],
            "exc": bit})


def _lab3(net_cap, max_slots):
    from dslabs_tpu.tpu.specs_lab3 import make_paxos_protocol

    return make_paxos_protocol(n=3, n_clients=2, w=1,
                               max_slots=max_slots, net_cap=net_cap,
                               timer_cap=6)


# name -> (protocol, delta lanes on, (lanes, words), signature AS THE
# PARENT OF ISSUE 32 DERIVED IT — the encoding's identity: checkpoints
# and the mesh wire ride on it, so the strings are pinned, not computed).
_DESCRIPTORS = {
    "flagship-842-to-217": (
        lambda: _lab3(64, 3), False, (842, 217), "packed:217w:6c75e8a3"),
    # the lab 3 adapter's first rung: paxos3-suite's 115-word rows
    "suite-net-cap-32": (
        lambda: _lab3(32, 2), False, (503, 115), "packed:115w:a7d21a0a"),
    "lab0-pingpong": (
        lambda: pingpong_spec(2).compile(), False, (66, 4),
        "packed:4w:11956876"),
    "lab1-clientserver": (
        lambda: clientserver_spec(3, 4).compile(), False, (151, 11),
        "packed:11w:3a3ce4b8"),
    "lab2-pb-raw-lanes": (
        lambda: pb_spec(2, 1, 1).compile(), False, (316, 84),
        "packed:84w:4bdc941f"),
    "lab2-pb-delta-lanes": (
        lambda: pb_spec(2, 1, 1).compile(), True, (316, 79),
        "packed:79w:d0cdcd28"),
    "lab3-paxos-spec": (
        lambda: paxos_spec(3).compile(), False, (81, 6),
        "packed:6w:6f6de3be"),
    "lab4-join": (
        lambda: specs_lab4.make_join_protocol(1), False, (85, 9),
        "packed:9w:2a9748fd"),
    "lab4-shardstore": (
        lambda: specs_lab4.make_shardstore_protocol((1, 1)), False,
        (376, 117), "packed:117w:5b9a55b6"),
    "lab4-shardstore-tx": (
        lambda: specs_lab4.make_shardstore_tx_protocol(1), False,
        (430, 136), "packed:136w:635a2640"),
    "lab4-shardstore-multi": (
        specs_lab4.make_shardstore_multi_protocol, False,
        (1272, 448), "packed:448w:7782eaa4"),
    "synthetic-raw-beside-1-bit": (
        _synthetic_protocol, False, (28, 16), "packed:16w:9dcf3d72"),
}


def _codes(pk):
    """Per lane, how many codes the CODEC takes as in-domain: 2^width,
    less the reserved all-ones code on a sentinel-capable lane."""
    return (1 << pk.width.astype(np.int64)) - pk.sent.astype(np.int64)


def _in_domain_rows(pk, base, rng, n):
    """Random rows inside every lane's code range (SENTINEL on ~30 % of
    the sentinel-capable lanes) followed by the edges: all-SENTINEL,
    raw lanes at 0xFFFFFFFF and at INT32_MIN, every field at its
    largest non-sentinel code, every field at code 0."""
    lo = pk._lo_eff_np(base).astype(np.int64)
    codes = _codes(pk)
    rows = lo[None, :] + rng.integers(0, codes[None, :],
                                      (n, pk.lanes), dtype=np.int64)
    rows = np.where(pk.raw[None, :],
                    rng.integers(-2**31, 2**31, (n, pk.lanes)), rows)
    rows = np.where(pk.sent[None, :] & (rng.random((n, pk.lanes)) < 0.3),
                    int(SENTINEL), rows)
    zero = lo.copy()
    top = np.where(pk.raw, -1, lo + codes - 1)
    edges = [np.where(pk.sent, int(SENTINEL), zero),
             np.where(pk.raw, -1, zero),
             np.where(pk.raw, -2**31, zero),
             top, zero,
             np.where(pk.sent, int(SENTINEL), top)]
    return np.concatenate([rows, np.stack(edges)]).astype(np.int32)


@pytest.mark.parametrize("name", list(_DESCRIPTORS))
def test_device_codec_is_bit_identical_to_the_host_oracle(name):
    """ISSUE 32: ``pack_jnp`` (one dense lanes -> words contraction per
    byte plane) gives ``pack_np``'s rows bit for bit and
    ``unpack_jnp`` inverts it, on random in-domain rows and the edges;
    ``count_bad`` equals a reckoning in int64; the descriptor's
    ``signature()`` is the parent's."""
    build, delta, shape, signature = _DESCRIPTORS[name]
    proto = build()
    doms, _ = packing_mod._flat_domains(proto)
    pk = packing_mod.derive_packing(proto, len(doms), delta=delta)
    assert not pk.identity and pk.has_delta == delta
    assert (pk.lanes, pk.words) == shape
    assert pk.signature() == signature
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    base = None
    if delta:
        base = np.where(pk.dlt, rng.integers(-50, 5000, pk.lanes),
                        0).astype(np.int32)
    rows = _in_domain_rows(pk, base, rng, 250)
    want = pk.pack_np(rows, base)
    jbase = None if base is None else jax.numpy.asarray(base)
    pack = jax.jit(lambda r: pk.pack_jnp(r, jbase, count_bad=True))
    got, bad = pack(jax.numpy.asarray(rows))
    assert (np.asarray(got) == want).all()
    assert not np.asarray(bad).any()
    assert (pk.unpack_np(want, base) == rows).all()
    back = jax.jit(lambda w: pk.unpack_jnp(w, jbase))(got)
    assert (np.asarray(back) == rows).all()

    # Out of domain: one below the bias, one past the last code, and
    # far past it, on a random third of the bounded lanes.
    lo = pk._lo_eff_np(base).astype(np.int64)
    codes = _codes(pk)
    wrong = np.stack([lo - 1, lo + codes, lo + codes + 12345])
    wrong = wrong[rng.integers(0, 3, (64, pk.lanes)),
                  np.arange(pk.lanes)[None, :]]
    hit = (rng.random((64, pk.lanes)) < 0.33) & ~pk.raw[None, :]
    out = np.where(hit, wrong, rows[:64]).astype(np.int32)
    d = out.astype(np.int64) - lo[None, :]
    reckoned = (~pk.raw[None, :] & (out != SENTINEL)
                & ((d < 0) | (d >= codes[None, :]))).sum(axis=1)
    assert reckoned.any()
    _, bad = pack(jax.numpy.asarray(out))
    assert (np.asarray(bad) == reckoned).all()


def test_the_synthetic_descriptor_puts_raw_words_beside_1_bit_fields():
    """What the synthetic case is there for, held to the descriptor."""
    proto = _synthetic_protocol()
    pk = packing_mod.derive_packing(
        proto, len(packing_mod._flat_domains(proto)[0]))
    assert pk.raw.sum() == 8 and (pk.width == 1).sum() >= 12
    assert pk.sent[~pk.raw].any() and not pk.sent[pk.raw].any()
    # a raw lane owns its word; 1-bit lanes share theirs
    per_word = np.bincount(pk.word, minlength=pk.words)
    assert (per_word[pk.word[pk.raw]] == 1).all() and per_word.max() >= 3


def test_pack_lowers_to_one_contraction_not_a_column_per_word():
    """ISSUE 32, so that the column form cannot return unnoticed: the
    lowering of ``pack_jnp`` for the flagship descriptor at one chunk
    step's 49,152 rows has no reduction per word (217 before), no
    ``concatenate`` of more than 8 operands (217 ``[N, 1]`` columns
    before: each padded 128x by the TPU's (8, 128) tile) and no ``[N]``
    or ``[N, 1]`` operand feeding one."""
    import re

    n = 49152
    pk = packing_mod.derive_packing(_lab3(64, 3), 842)
    text = jax.jit(lambda r: pk.pack_jnp(r, count_bad=True)).lower(
        jax.ShapeDtypeStruct((n, pk.lanes), jax.numpy.int32)).as_text()
    assert len(re.findall(r"stablehlo\.dot_general", text)) == 4
    assert len(re.findall(r"stablehlo\.reduce\b", text)) < 8
    for line in text.splitlines():
        if "stablehlo.concatenate" not in line:
            continue
        operands = re.findall(r"tensor<([^>]*)>",
                              line.split(":", 1)[1].split("->")[0])
        assert len(operands) <= 8, line
        for t in operands:
            dims = t.split("x")[:-1]
            assert dims not in ([str(n)], [str(n), "1"]), line


# ------------------------------------------------------------- parity

@pytest.mark.parametrize("spec_fn", [
    lambda: _pruned(pingpong_spec(2).compile()),
    _lab1,
])
@pytest.mark.parametrize("strict", [True, False])
def test_packed_vs_unpacked_exact_parity(spec_fn, strict):
    """ACCEPTANCE: bit-identical unique/explored/verdict between the
    packed (default) and unpacked device loops, strict AND beam."""
    kw = dict(chunk=128, frontier_cap=1 << 12, visited_cap=1 << 14,
              strict=strict, max_depth=11)
    packed = TensorSearch(spec_fn(), **kw).run()
    raw = TensorSearch(spec_fn(), packed=False, **kw).run()
    _assert_exact(packed, raw)
    assert packed.visited_overflow == raw.visited_overflow
    assert packed.dropped == raw.dropped
    # The accounting tells the truth about the encoding in force.
    assert packed.bytes_per_state < packed.bytes_per_state_unpacked
    assert raw.bytes_per_state == raw.bytes_per_state_unpacked


def test_packed_device_matches_host_oracle():
    """The packed device loop against the legacy host-dedup parity
    oracle (which keeps raw in-memory rows by design)."""
    kw = dict(chunk=128, frontier_cap=1 << 12, visited_cap=1 << 14,
              max_depth=8)
    dev = TensorSearch(_lab1(), **kw).run()
    host = TensorSearch(_lab1(), use_host_visited=True, **kw).run()
    _assert_exact(dev, host)


def test_packed_capacity_fits_deeper():
    """ACCEPTANCE: at a FIXED HBM byte budget, the packed layout
    completes a depth the unpacked layout provably cannot fit.  lab1's
    depth-9 frontier peaks at 206 rows; the budget holds 256 packed
    rows but only ~18 unpacked ones."""
    eng = TensorSearch(_lab1(), chunk=64)
    pk = eng._pk
    budget_bytes = 256 * pk.bytes_per_state
    raw_rows = budget_bytes // pk.bytes_per_state_unpacked
    assert raw_rows < 206 < 256
    packed = TensorSearch(_lab1(), chunk=64, frontier_cap=256,
                          visited_cap=1 << 14, max_depth=9).run()
    assert packed.end_condition == "DEPTH_EXHAUSTED"
    assert packed.depth == 9
    raw = TensorSearch(_lab1(), chunk=64, packed=False,
                       frontier_cap=max(raw_rows, 1),
                       visited_cap=1 << 14, max_depth=9).run()
    assert raw.end_condition == "CAPACITY_EXHAUSTED"


def test_out_of_domain_is_loud():
    """A live value outside its declared domain is a CapacityOverflow,
    never silent corruption: shrink the client counter's declared
    domain below its real range and run."""
    proto = _pruned(pingpong_spec(2).compile())
    ld = dict(proto.lane_domains)
    nodes = list(ld["nodes"])
    assert nodes[0] == (0, 3)      # client k walks 1..3
    nodes[0] = (0, 1)
    proto = dataclasses.replace(proto,
                                lane_domains=dict(ld, nodes=nodes))
    with pytest.raises(CapacityOverflow):
        TensorSearch(proto, chunk=64, max_depth=8).run()


# -------------------------------------------------------- checkpoints

def test_packed_checkpoint_rows_and_resume(tmp_path):
    """Checkpoint rows are stored PACKED (plane-wide + encoding
    marker) and resume to the identical verdict and counts."""
    pth = str(tmp_path / "packed.ckpt")
    kw = dict(chunk=64, frontier_cap=1 << 11, visited_cap=1 << 14,
              checkpoint_path=pth, checkpoint_every=1)
    full = TensorSearch(_lab1(), chunk=64, frontier_cap=1 << 11,
                        visited_cap=1 << 14, max_depth=9).run()
    partial = TensorSearch(_lab1(), max_depth=5, **kw).run()
    assert partial.depth == 5
    with np.load(pth) as z:
        eng = TensorSearch(_lab1(), chunk=64)
        assert z["frontier"].shape[1] == eng.plane
        assert eng.plane < eng.lanes
        assert "extra__frontier_encoding" in z.files
    eng2 = TensorSearch(_lab1(), max_depth=9, **kw)
    out = eng2.run(resume=True)
    _assert_exact(full, out)
    assert eng2._resumed_from_depth == 5


def test_cross_encoding_resume_loud_conversion(tmp_path):
    """packed dump -> unpacked engine converts with a LOUD warning;
    unpacked dump -> packed engine resumes cleanly; a dump whose
    descriptor this protocol cannot derive is REFUSED."""
    pth = str(tmp_path / "cross.ckpt")
    kw = dict(chunk=64, frontier_cap=1 << 11, visited_cap=1 << 14,
              checkpoint_path=pth, checkpoint_every=1)
    full = TensorSearch(_lab1(), chunk=64, frontier_cap=1 << 11,
                        visited_cap=1 << 14, max_depth=9).run()
    TensorSearch(_lab1(), max_depth=5, **kw).run()
    with pytest.warns(RuntimeWarning, match="PACKED checkpoint"):
        out = TensorSearch(_lab1(), packed=False, max_depth=9,
                           **kw).run(resume=True)
    _assert_exact(full, out)
    # raw dump -> packed engine (re-packs on load, no warning needed).
    pth2 = str(tmp_path / "raw.ckpt")
    kw2 = dict(kw, checkpoint_path=pth2)
    TensorSearch(_lab1(), packed=False, max_depth=5, **kw2).run()
    out2 = TensorSearch(_lab1(), max_depth=9, **kw2).run(resume=True)
    _assert_exact(full, out2)
    # Foreign descriptor: same protocol SHAPE, different declared
    # domains -> different packing signature -> loud refusal.
    TensorSearch(_lab1(), max_depth=5, **kw).run()
    alt = _pruned(clientserver_spec(3, 4).compile())
    ld = dict(alt.lane_domains)
    ld["nodes"] = [None] * len(ld["nodes"])
    alt = dataclasses.replace(alt, lane_domains=ld)
    eng = TensorSearch(alt, max_depth=9, **kw)
    with pytest.raises(ckpt_mod.CheckpointMismatch):
        eng.run(resume=True)


@pytest.mark.fault
def test_sigkill_mid_packed_run_resume_parity(tmp_path):
    """ACCEPTANCE: a packed run SIGKILLed mid-search resumes from its
    packed dump to the identical verdict and exact counts."""
    pth = str(tmp_path / "kill.ckpt")
    full = TensorSearch(_lab1(), chunk=16, frontier_cap=1 << 11,
                        visited_cap=1 << 14, max_depth=9).run()
    child_src = (
        "import dataclasses\n"
        "from dslabs_tpu.tpu.engine import TensorSearch\n"
        "from dslabs_tpu.tpu.specs import clientserver_spec\n"
        "cs = clientserver_spec(3, 4).compile()\n"
        "cs = dataclasses.replace(cs, goals={},"
        " prunes={'CLIENTS_DONE': cs.goals['CLIENTS_DONE']})\n"
        f"TensorSearch(cs, chunk=16, max_depth=9,"
        f" visited_cap=1 << 14, frontier_cap=2048,"
        f" checkpoint_path={pth!r}, checkpoint_every=1).run()\n")
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
            if d is not None and d >= 4:
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
    out = TensorSearch(_lab1(), chunk=16, max_depth=9,
                       visited_cap=1 << 14, frontier_cap=2048,
                       checkpoint_path=pth,
                       checkpoint_every=1).run(resume=True)
    _assert_exact(full, out)


# ------------------------------------------------- spill interaction

def test_packed_spill_exact_parity():
    """Packed + host-RAM spill tier + async drain together: exact
    counts at a capped table, rows spooled in the packed encoding."""
    base = TensorSearch(_lab1(), chunk=128, frontier_cap=1 << 12,
                        visited_cap=1 << 14, max_depth=8).run()
    sp = TensorSearch(_lab1(), chunk=16, frontier_cap=1 << 12,
                      visited_cap=256, spill=True, max_depth=8).run()
    _assert_exact(base, sp)
    assert sp.dropped_states == 0
    assert sp.spilled_keys > 0


def test_engine_reuse_across_runs_resets_spill_tier():
    """The warm-up-then-measure reuse pattern: run 2 on the same
    engine must not refilter against run 1's tier (the latent reuse
    bug the capacity2 bench phase exposed)."""
    eng = TensorSearch(_lab1(), chunk=16, frontier_cap=1 << 12,
                       visited_cap=256, spill=True, max_depth=4)
    w = eng.run()
    assert w.spilled_keys >= 0
    eng.max_depth = 8
    out = eng.run()
    base = TensorSearch(_lab1(), chunk=128, frontier_cap=1 << 12,
                        visited_cap=1 << 14, max_depth=8).run()
    _assert_exact(base, out)
