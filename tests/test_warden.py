"""Process-isolated dispatch warden (ISSUE 4): the deterministic
kill/hang/crash matrix, on CPU, no broken hardware required:

* a child SIGKILLed mid-search (injected ``die`` fault) is reaped,
  classified, and the next rung's child RESUMES from the durable
  checkpoint to the identical verdict/unique/explored counts as an
  unfaulted run — strict pingpong AND lab1, the tier-1 acceptance;
* a hung child (injected uninterruptible ``hang``) is SIGKILLed within
  its announced heartbeat grace — seconds, not a leaked thread;
* exit-code classification is pinned (wedge / oom / crash / failed);
* the checkpoint ``.prev`` rotation + content checksum make a SIGKILL
  landing mid-checkpoint-write recoverable: a truncated main dump
  falls back to the rotated previous dump with a loud warning and
  resumes to verdict parity.

Marked ``fault`` (``make fault-smoke`` runs the whole matrix); the
slowest spawn-heavy variants are additionally ``slow`` so the tier-1
gate keeps only the fast CPU warden tests.
"""

import dataclasses
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from dslabs_tpu.tpu import checkpoint as ckpt_mod  # noqa: E402
from dslabs_tpu.tpu.engine import TensorSearch  # noqa: E402
from dslabs_tpu.tpu.protocols.clientserver import \
    make_clientserver_protocol  # noqa: E402
from dslabs_tpu.tpu.protocols.pingpong import \
    make_pingpong_protocol  # noqa: E402
from dslabs_tpu.tpu.supervisor import (EngineFailure,  # noqa: E402
                                       SearchSupervisor,
                                       SupervisorExhausted)
from dslabs_tpu.tpu.warden import (CHILD_RC_FAILED, Warden,  # noqa: E402
                                   classify_death)

pytestmark = pytest.mark.fault

# Children are fresh processes: share the suite's persistent compile
# cache (tests/conftest.py) or every spawn pays a cold XLA build.
CHILD_ENV = {"JAX_COMPILATION_CACHE_DIR": "/tmp/jaxcache-cpu"}


# Module-level so warden children can import them by reference
# ("tests.test_warden:prune_pingpong") — closures cannot cross the
# spawn boundary.

def prune_pingpong(pp):
    return dataclasses.replace(
        pp, goals={}, prunes={"CLIENTS_DONE": pp.goals["CLIENTS_DONE"]})


def prune_clientserver(cs):
    return dataclasses.replace(
        cs, goals={}, prunes={"CLIENTS_DONE": cs.goals["CLIENTS_DONE"]})


PINGPONG = {
    "factory":
        "dslabs_tpu.tpu.protocols.pingpong:make_pingpong_protocol",
    "factory_kwargs": {"workload_size": 2},
    "transform": "tests.test_warden:prune_pingpong",
}
LAB1 = {
    "factory":
        "dslabs_tpu.tpu.protocols.clientserver:"
        "make_clientserver_protocol",
    "factory_kwargs": {"n_clients": 1, "w": 2},
    "transform": "tests.test_warden:prune_clientserver",
}


def _warden(refs, **kw):
    kw.setdefault("chunk", 64)
    kw.setdefault("frontier_cap", 1 << 8)
    kw.setdefault("visited_cap", 1 << 12)
    kw.setdefault("env", CHILD_ENV)
    return Warden(**refs, **kw)


def _base_pingpong():
    return TensorSearch(prune_pingpong(make_pingpong_protocol(2)),
                        chunk=64).run()


def _base_lab1():
    return TensorSearch(
        prune_clientserver(make_clientserver_protocol(n_clients=1, w=2)),
        chunk=64).run()


def _same_verdict(a, b):
    assert a.end_condition == b.end_condition
    assert a.unique_states == b.unique_states
    assert a.states_explored == b.states_explored


# ------------------------------------------------- exit-code taxonomy

def test_exit_code_classification_pinned():
    """The death taxonomy is part of the warden's contract: a warden
    SIGKILL is a wedge, an unprompted SIGKILL is the OOM killer or an
    external kill, CHILD_RC_FAILED is a reported in-child failure,
    everything else is a crash."""
    import signal

    assert classify_death(-signal.SIGKILL, True) == "wedge"
    assert classify_death(-signal.SIGKILL, False) == "oom"
    assert classify_death(-signal.SIGSEGV, False) == "crash"
    assert classify_death(-signal.SIGTERM, False) == "crash"
    assert classify_death(CHILD_RC_FAILED, False) == "failed"
    assert classify_death(1, False) == "crash"
    assert classify_death(86, False) == "crash"


# --------------------------------------- SIGKILL mid-search -> resume

def test_child_sigkill_mid_search_resumes_strict_pingpong(tmp_path):
    """ACCEPTANCE: a child SIGKILLed mid-search (dispatch 8 of the
    device rung — wave 3, after checkpoints have landed) produces the
    IDENTICAL strict pingpong verdict as an unfaulted run, resumed
    from the durable checkpoint by the next rung's child."""
    base = _base_pingpong()
    assert base.end_condition == "SPACE_EXHAUSTED"
    w = _warden(PINGPONG, ladder=("device", "host"),
                checkpoint_path=str(tmp_path / "pp.npz"),
                checkpoint_every=1,
                fault={"kind": "die", "at": 8, "engine": "device",
                       "after_ckpt": True})
    out = w.run()
    _same_verdict(out, base)
    assert out.engine == "host"
    assert out.failovers == 1
    assert out.child_restarts == 1
    assert out.resumed_from_depth > 0
    assert [d.kind for d in w.deaths] == ["oom"]
    # The heartbeat protocol carried the dispatch seam's state out of
    # the dead child: tag, index, live depth, durable-resume depth.
    hb = w.deaths[0].last_hb
    assert hb is not None and hb["tag"].startswith("device.")
    for key in ("n", "depth", "ckpt_depth"):
        assert key in hb


def test_child_sigkill_mid_search_resumes_strict_lab1(tmp_path):
    """ACCEPTANCE: same SIGKILL-resume parity on the lab1 strict
    clientserver BFS (a deeper space; more checkpoints survive)."""
    base = _base_lab1()
    assert base.end_condition == "SPACE_EXHAUSTED"
    w = _warden(LAB1, ladder=("device", "host"),
                checkpoint_path=str(tmp_path / "cs.npz"),
                checkpoint_every=1,
                fault={"kind": "die", "at": 11, "engine": "device",
                       "after_ckpt": True})
    out = w.run()
    _same_verdict(out, base)
    assert out.engine == "host"
    assert out.child_restarts == 1
    assert out.resumed_from_depth > 0


# --------------------------------------------------- hang -> SIGKILL

def test_hung_child_is_reaped_within_deadline(tmp_path):
    """A child that wedges mid-dispatch (uninterruptible hang — the
    shape the in-process watchdog can only abandon) is SIGKILLed
    within its announced heartbeat grace and the search completes on
    the next rung.  The whole recovery must take seconds, not the
    3600 s the hang would run."""
    base = _base_pingpong()
    t0 = time.time()
    w = _warden(PINGPONG, ladder=("device", "host"),
                checkpoint_path=str(tmp_path / "hang.npz"),
                checkpoint_every=1,
                boot_grace=120.0, first_grace=120.0, steady_grace=3.0,
                idle_grace=60.0, grace_slack=1.0,
                fault={"kind": "hang", "at": 8, "engine": "device"})
    out = w.run()
    elapsed = time.time() - t0
    _same_verdict(out, base)
    assert [d.kind for d in w.deaths] == ["wedge"]
    assert out.killed_dispatches == 1
    assert out.child_restarts == 1
    # Generous bound for a loaded 1-core CI box; the hang itself was
    # cut at steady_grace + slack = 4 s.
    assert elapsed < 90.0, f"hung child reaped too slowly ({elapsed:.0f}s)"


# ------------------------------------------------ crash / failed rungs

@pytest.mark.slow
def test_abrupt_child_exit_classified_crash_and_failed_over(tmp_path):
    """An abrupt os._exit mid-search is a ``crash``; the ladder
    recovers on the next rung with verdict parity."""
    base = _base_pingpong()
    w = _warden(PINGPONG, ladder=("device", "host"),
                checkpoint_path=str(tmp_path / "crash.npz"),
                checkpoint_every=1,
                fault={"kind": "exit", "at": 8, "engine": "device"})
    out = w.run()
    _same_verdict(out, base)
    assert [d.kind for d in w.deaths] == ["crash"]
    assert w.deaths[0].exitcode == 86


@pytest.mark.slow
def test_in_child_fatal_error_reported_and_exhausts_ladder():
    """A classified in-child failure (injected fatal raise) is reported
    over the pipe (``failed``, CHILD_RC_FAILED) and a single-rung
    ladder surfaces it as a loud SupervisorExhausted with the per-rung
    chain — never a silent empty exit."""
    w = _warden(PINGPONG, ladder=("device",),
                fault={"kind": "raise", "at": 3, "engine": "device"})
    with pytest.raises(SupervisorExhausted) as ei:
        w.run()
    assert len(ei.value.failures) == 1
    f = ei.value.failures[0]
    assert isinstance(f, EngineFailure)
    assert f.engine == "device" and f.kind == "failed"
    assert w.deaths[0].exitcode == CHILD_RC_FAILED


@pytest.mark.slow
def test_last_rung_forces_cpu_runtime():
    """The last rung's child env pins JAX_PLATFORMS=cpu (plus the
    config re-pin): when the accelerator runtime itself is broken, the
    final rung must not touch it."""
    w = _warden(PINGPONG, ladder=("host",))
    out = w.run()
    assert out.engine == "host"
    assert out.end_condition == "SPACE_EXHAUSTED"
    assert w.last_platform == "cpu"


# -------------------------------------- supervisor process-isolation

def test_supervisor_process_isolation_mode_verdict_parity():
    """SearchSupervisor(process_isolation=True) rides the warden with
    identical verdict semantics and the extended recovery accounting
    fields present on the outcome."""
    base = _base_pingpong()
    sup = SearchSupervisor(
        None, ladder=("device",), chunk=64, frontier_cap=1 << 8,
        visited_cap=1 << 12, process_isolation=True,
        protocol_factory=PINGPONG["factory"],
        factory_kwargs=PINGPONG["factory_kwargs"],
        protocol_transform=PINGPONG["transform"],
        warden_kwargs={"env": CHILD_ENV})
    out = sup.run()
    _same_verdict(out, base)
    assert out.engine == "device"
    assert (out.failovers, out.child_restarts,
            out.killed_dispatches) == (0, 0, 0)


def test_process_isolation_requires_factory():
    sup = SearchSupervisor(None, ladder=("device",),
                           process_isolation=True)
    with pytest.raises(ValueError, match="protocol_factory"):
        sup.run()


# ------------------------------- checkpoint torn-write robustness

def _mini_ckpt(fingerprint, depth):
    return ckpt_mod.SearchCheckpoint(
        fingerprint=fingerprint, depth=depth, explored=10 * depth,
        elapsed=1.0 * depth,
        frontier=np.full((2, 3), depth, np.int32),
        visited_keys=np.full((4, 4), depth, np.uint32))


def test_checkpoint_save_rotates_prev(tmp_path):
    """Every save rotates the previous dump to ``.prev``: after two
    saves both generations are on disk and checksum-verified."""
    path = str(tmp_path / "rot.npz")
    ckpt_mod.save(path, _mini_ckpt("fp", 1))
    assert not os.path.exists(path + ".prev")
    ckpt_mod.save(path, _mini_ckpt("fp", 2))
    assert os.path.exists(path + ".prev")
    assert ckpt_mod.load(path, "fp").depth == 2
    assert ckpt_mod.load(path + ".prev", "fp").depth == 1
    assert ckpt_mod.peek_depth(path) == 2


def test_truncated_main_falls_back_to_prev_with_loud_warning(tmp_path):
    """A torn main dump (truncation — the SIGKILL-mid-write shape)
    fails its read/checksum and the loader falls back to the rotated
    previous dump WITH a RuntimeWarning, never a crash or a silent
    root restart."""
    path = str(tmp_path / "torn.npz")
    ckpt_mod.save(path, _mini_ckpt("fp", 1))
    ckpt_mod.save(path, _mini_ckpt("fp", 2))
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 3])         # torn mid-write
    with pytest.warns(RuntimeWarning, match="falling back"):
        ck = ckpt_mod.load(path, "fp")
    assert ck.depth == 1                        # the rotated dump
    # peek_* must track what the loader would resume.
    assert ckpt_mod.peek_fingerprint(path) == "fp"
    assert ckpt_mod.peek_depth(path) == 1


def test_corrupt_payload_detected_by_checksum(tmp_path):
    """A bit-flip that keeps the zip READABLE is caught by the content
    checksum; with no ``.prev`` to fall back to the loader raises a
    loud CheckpointCorrupt instead of resuming garbage."""
    path = str(tmp_path / "flip.npz")
    ckpt_mod.save(path, _mini_ckpt("fp", 3))
    with open(path, "r+b") as f:
        blob = bytearray(f.read())
        # Flip a byte inside the frontier ARRAY PAYLOAD (npz members
        # are stored uncompressed, so the fill pattern is findable);
        # either the zip member CRC or the content checksum must
        # refuse the dump — never a silent resume of garbage.
        payload = np.full((2, 3), 3, np.int32).tobytes()
        off = blob.find(payload)
        assert off > 0, "frontier payload not found in npz"
        blob[off] ^= 0xFF
        f.seek(0)
        f.write(blob)
    with pytest.warns(RuntimeWarning):
        with pytest.raises(ckpt_mod.CheckpointCorrupt):
            ckpt_mod.load(path, "fp")


def test_sigkill_mid_checkpoint_write_resume_parity(tmp_path):
    """End-to-end resume parity across the rotation: a checkpointed
    run is cut at depth 2, the NEXT dump is 'killed mid-write'
    (rotation done, main torn), and a fresh engine resumes from the
    rotated dump to the identical verdict as an uninterrupted run."""
    proto = prune_pingpong(make_pingpong_protocol(2))
    full = TensorSearch(proto, chunk=64).run()
    path = str(tmp_path / "kill.npz")
    cut = TensorSearch(proto, chunk=64, max_depth=2,
                       checkpoint_path=path, checkpoint_every=1)
    assert cut.run().end_condition == "DEPTH_EXHAUSTED"
    # Simulate the torn write: the good depth-2 dump was rotated to
    # .prev and the in-flight replacement died mid-write.
    with open(path, "rb") as f:
        blob = f.read()
    os.replace(path, path + ".prev")
    with open(path, "wb") as f:
        f.write(blob[:200])
    resumed = TensorSearch(proto, chunk=64, checkpoint_path=path)
    with pytest.warns(RuntimeWarning, match="falling back"):
        r = resumed.run(resume=True)
    _same_verdict(r, full)
    assert resumed._resumed_from_depth == 2
