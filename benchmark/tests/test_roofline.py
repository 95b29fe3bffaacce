"""The byte function on hand-worked numbers, and the table of peaks."""

import pytest

from benchmark.harness import device, roofline

from helpers import ROOT
import os

BENCH = os.path.join(ROOT, "benchmark")


def test_necessary_bytes_by_hand():
    # 1,000 successors of 868 + 32 bytes each, from 50 rows of 868 read
    assert roofline.necessary_bytes(1000, 50, 868) == 900_000 + 43_400
    assert roofline.necessary_bytes(0, 0, 868) == 0


def test_roofline_share_by_hand():
    # 819e9 bytes in one second on one chip is the whole roofline ...
    explored = 819_000_000
    bps = 1000 - 32
    assert roofline.least_seconds(explored, 0, bps, 819e9, 1) == 1.0
    assert roofline.roofline_pct(explored, 0, bps, 1.0, 819e9, 1) == 100.0
    # ... four chips side by side need a quarter of the time, so the
    # same device time per chip is a quarter of the roofline
    assert roofline.roofline_pct(explored, 0, bps, 1.0, 819e9, 4) == 25.0
    # PR 24's level 8, roughly: 1.1 M successors from 54,571 rows in 7 s
    pct = roofline.roofline_pct(1_100_000, 54_571, 868, 7.0, 819e9, 1)
    assert pct == pytest.approx(0.0181, rel=0.02)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    assert device.peaks(BENCH, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", "_source"):
        with pytest.raises(KeyError):
            device.peaks(BENCH, kind)
