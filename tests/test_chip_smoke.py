"""Rehearsals of ``chip_smoke.py`` without the chip.

Rehearsal 1 (control flow): every phase function runs end to end at
tiny caps on the CPU backend.  Rehearsal 2 (sharding): the four-chip
phase runs on four of the eight virtual CPU devices.  And the contract
the driver holds the script to: ``main()`` refuses a backend that is
not a TPU with a non-zero exit and no result line, and the last line
of a passing run is exactly the ``ok`` object.
"""

import json

import pytest

jax = pytest.importorskip("jax")

import chip_smoke  # noqa: E402

TINY = dict(chunk=64, frontier_cap=1 << 12, visited_cap=1 << 15)
# Unique states of the bench protocol at depths 3 / 4 — the same series
# chip_smoke.FLAGSHIP_UNIQUE pins at 6 / 7.
UNIQUE = {3: 162, 4: 713}


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["default", "four-chips"])
def test_main_refuses_cpu_backend(argv, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main(argv) != 0
    cap = capsys.readouterr()
    assert cap.out == ""                  # no result line, no phase ran
    assert "not a TPU" in cap.err


def test_last_line_is_exactly_the_ok_object(monkeypatch, capsys):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ran = []
    monkeypatch.setattr(chip_smoke, "_device", lambda: dict(dev))
    monkeypatch.setattr(chip_smoke, "lab_phase",
                        lambda: ran.append("lab"))
    monkeypatch.setattr(
        chip_smoke, "flagship_phase",
        lambda **kw: ran.append(("flagship", kw)) or {"rec": 1})
    monkeypatch.setattr(chip_smoke, "warm_phase",
                        lambda **kw: ran.append(("warm", kw)))
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": dev}
    assert list(json.loads(last)) == ["ok", "device"]
    # main() drives the phases at the REAL caps, in order.
    assert ran[0] == "lab"
    assert ran[1][1] == dict(chip_smoke.FLAGSHIP, expect_unique=15102)
    assert ran[2][1] == dict(chip_smoke.FLAGSHIP, first={"rec": 1})

    # --four-chips: that phase and nothing else; too few chips refuses.
    ran.clear()
    monkeypatch.setattr(chip_smoke, "four_chip_phase",
                        lambda **kw: ran.append(("four", kw)))
    assert chip_smoke.main(["--four-chips"]) != 0 and not ran
    dev["count"] = 4
    assert chip_smoke.main(["--four-chips"]) == 0
    assert ran == [("four", dict(chip_smoke.FLAGSHIP,
                                 expect_unique=69673))]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["device"]["count"] == 4


def test_lab_phase_rehearsal(capsys):
    rec = chip_smoke.lab_phase()
    assert rec["verdict"] == "SPACE_EXHAUSTED"
    assert rec["unique"] == rec["object_unique"] == rec["dfs"]["unique"]
    assert rec["violation"]["verdict"] == "INVARIANT_VIOLATED"
    assert rec["dfs"]["probe_secs"] > 0
    assert rec["platform"] == "cpu" and rec["device_kind"] == "cpu"
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]
                      )["phase"] == "lab"


def test_flagship_and_warm_phase_rehearsal(capsys):
    rec = chip_smoke.flagship_phase(
        **TINY, depth=3, expect_unique=UNIQUE[3], deep_depth=4,
        deep_secs=120.0)
    assert rec["unique"] == UNIQUE[3] and rec["dropped"] == 0
    assert rec["bytes_per_state"] * 3 < rec["bytes_per_state_unpacked"]
    phases = [json.loads(ln)["phase"] for ln in
              capsys.readouterr().out.strip().splitlines()]
    assert phases == ["flagship", "flagship-deep"]
    # The warm construction must hit the persistent cache (JAX's own
    # events); the tests' on-disk cache may have been warm for the
    # first one too, and then the seconds are printed, not compared.
    assert rec["cache_hits"] + rec["cache_misses"] > 0
    warm = chip_smoke.warm_phase(**TINY, first=rec)
    assert warm["cache_hits"] > 0 and warm["cache_entries"] > 0
    # ... and the assertion is live: against a cold first construction
    # that took no time, no warm one is fast enough.
    with pytest.raises(AssertionError, match="persistent cache"):
        chip_smoke.warm_phase(
            **TINY, first={"compile_secs": 0.0, "cache_hits": 0})


def test_four_chip_phase_rehearsal_on_virtual_devices():
    assert len(jax.devices()) >= 4
    rec = chip_smoke.four_chip_phase(**TINY, depth=4,
                                     expect_unique=UNIQUE[4])
    assert rec["unique"] == rec["one_device"]["unique"] == UNIQUE[4]
    for leaf in ("visited", "cur", "nxt"):
        assert len(set(rec["layout_device_ids"][leaf])) == 4
    assert all(e > 0 for e in rec["explored_per_device"])


def test_flagship_protocol_is_the_benchmark_configuration():
    """``chip_smoke.flagship_protocol()`` and the `protocol` block of
    ``benchmark/configs/lab3-paxos-n3c2.json`` build one program: two
    engines at the same caps share a store key, and a changed kwarg
    does not."""
    from benchmark.drivers.timeboxed_bfs import build_protocol
    from benchmark.harness import manifest
    from dslabs_tpu.tpu.sharded import ShardedTensorSearch, make_mesh

    spec = manifest.load_cell(
        chip_smoke.ROOT, "paxos3-deep").config["protocol"]
    assert spec["strip_goals"]
    other = dict(spec, kwargs=dict(spec["kwargs"], net_cap=32))

    def key(protocol):
        return ShardedTensorSearch(
            protocol, make_mesh(1), chunk_per_device=TINY["chunk"],
            frontier_cap=TINY["frontier_cap"],
            visited_cap=TINY["visited_cap"], strict=True,
            ev_budget=chip_smoke.EV_BUDGET).store_key()

    smoke = chip_smoke.flagship_protocol()
    assert smoke.goals == {}
    smoke_key = key(smoke)
    assert smoke_key is not None
    assert smoke_key == key(build_protocol(spec)) != key(
        build_protocol(other))
