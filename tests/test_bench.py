"""bench.py contract smoke tests: whatever happens — wedged runtime,
exhausted deadline, external kill, healthy run — the bench prints
exactly one parseable JSON line on stdout, and its exit code is 0 only
when a measured phase produced a number.  A failed pre-flight is
reported as what it is (named error, non-zero exit): no CPU number
stands in for the device."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench.py")


def _env(**extra):
    # The children run CPU-pinned (JAX_PLATFORMS=cpu is all it takes);
    # drop the test harness's CPU-mesh flags so the bench's own phase
    # children see a clean slate.
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("XLA_FLAGS", None)
    return env


def _run(env_extra, timeout, rc=0):
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=timeout, env=_env(**env_extra), cwd=ROOT)
    assert proc.returncode == rc, (proc.returncode, proc.stderr[-2000:])
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, proc.stdout
    out = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in out, out
    return out


def test_bench_exhausted_deadline_still_emits_json():
    """With a deadline too small for any phase, the bench must skip
    phases (never race an external killer) and still land the JSON
    line with an attributable error — and, having no number, exit
    non-zero."""
    out = _run({"DSLABS_BENCH_DEADLINE_SECS": "1"}, timeout=240, rc=1)
    assert out["value"] == 0.0
    assert "error" in out


def test_bench_external_kill_still_emits_json():
    """ACCEPTANCE: an external ``timeout``-style SIGTERM mid-run must
    still produce a parsable last-line JSON naming the signal — never
    an empty tail — and exit non-zero when no phase had a number."""
    proc = subprocess.Popen(
        [sys.executable, BENCH], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
        env=_env(DSLABS_BENCH_DEADLINE_SECS="400"), cwd=ROOT)
    # Let the run get into its first phase, then kill like a driver
    # timeout would.
    t0 = time.time()
    for line in proc.stderr:
        if "phase preflight: start" in line or time.time() - t0 > 60:
            break
    time.sleep(1.0)
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 1
    lines = [ln for ln in out.strip().splitlines() if ln]
    assert len(lines) == 1, out
    parsed = json.loads(lines[0])
    assert "error" in parsed and "SIGTERM" in parsed["error"], parsed
    assert "total_secs" in parsed


def _assert_no_stand_in(out):
    assert out["value"] == 0.0, out
    assert "error" in out and "wedged" in out["error"], out
    assert "pre-flight" in out["error"], out
    for key in ("backend", "cpu_fallback", "mesh", "beam", "strict"):
        assert key not in out, (key, out)


def test_bench_wedged_preflight_fast_kill_exits_nonzero():
    """ACCEPTANCE: a preflight that hangs SILENTLY
    (DSLABS_BENCH_FAKE_WEDGE=hang) is SIGKILLed at the
    heartbeat-silence budget — seconds, not the phase budget — and the
    bench exits NON-ZERO with the named error: no CPU answer, no
    measured phase, the wedge diagnosed on the last line."""
    out = _run({"DSLABS_BENCH_FAKE_WEDGE": "hang",
                "DSLABS_BENCH_PREFLIGHT_SILENCE_SECS": "8",
                "DSLABS_BENCH_DEADLINE_SECS": "400"}, timeout=200, rc=1)
    _assert_no_stand_in(out)
    assert out["wedge_diagnostics"][0]["phase"] == "preflight"
    # The kill must be silence-driven (fast).
    assert out["total_secs"] < 90, out


def test_bench_failed_preflight_exits_nonzero():
    """A preflight that FAILS outright (the fast wedge shape) ends the
    run the same way: named error, non-zero exit, no stand-in."""
    out = _run({"DSLABS_BENCH_FAKE_WEDGE": "1",
                "DSLABS_BENCH_DEADLINE_SECS": "400"}, timeout=200, rc=1)
    _assert_no_stand_in(out)


@pytest.mark.skipif(not os.environ.get("DSLABS_SLOW_TESTS"),
                    reason="runs a real (small) CPU beam rung")
def test_bench_cpu_smoke_lands_a_rate():
    """The healthy-path contract on the CPU backend: preflight, one
    beam rung, a nonzero rate, compile_secs reported."""
    out = _run({"DSLABS_BENCH_DEADLINE_SECS": "400"}, timeout=450)
    assert out["value"] > 0, out
    assert out["beam"]["dropped"] >= 0
    assert "compile_secs" in out["beam"]
