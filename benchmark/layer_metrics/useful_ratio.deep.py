"""Unique states over states explored, cumulative at the end of the
traced level, in percent: how much of the expand work found something
new.  Exact."""

from benchmark.harness.levels import traced_level


def compute(run: dict):
    got = traced_level(run)
    if got is None:
        return None
    lv = got[0]
    return 100.0 * int(lv["unique"]) / int(lv["explored"])
