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

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import checkpoint as ckpt_mod  # noqa: E402
from dslabs_tpu.tpu import packing as packing_mod  # noqa: E402
from dslabs_tpu.tpu.engine import (SENTINEL, CapacityOverflow,  # noqa: E402
                                   TensorSearch, flatten_state)
from dslabs_tpu.tpu.specs import (clientserver_spec,  # noqa: E402
                                  paxos_spec, pingpong_spec)

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
