"""Share of the HBM-bandwidth roofline the superstep reached in the
traced level: the least time for the bytes the level must move
(``harness/roofline.py``) over the program's device time.  Bytes bound
it; there is no operation count."""

from benchmark.harness.levels import superstep_secs, traced_level
from benchmark.harness.roofline import roofline_pct


def compute(run: dict):
    got, secs = traced_level(run), superstep_secs(run)
    if got is None or secs is None:
        return None
    _lv, explored, expanded = got
    return roofline_pct(explored, expanded,
                        int(run["outcome"]["bytes_per_state"]), secs,
                        run["peaks"]["hbm_bytes_per_s"], run["chips"])
