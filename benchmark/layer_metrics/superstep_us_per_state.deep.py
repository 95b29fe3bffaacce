"""Device microseconds of the fused superstep program per state explored,
over the traced level (per chip: the chips work side by side)."""

from benchmark.harness.levels import superstep_secs, traced_level


def compute(run: dict):
    got, secs = traced_level(run), superstep_secs(run)
    if got is None or secs is None:
        return None
    return 1e6 * secs / got[1]
