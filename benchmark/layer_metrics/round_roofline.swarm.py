"""Share of the HBM-bandwidth roofline the fleet's round reached in the
traced round: the least time for the bytes its advanced walker steps
must move (``harness/roofline_swarm.py``) over the round program's
device time.  Bytes bound it; there is no operation count."""

from benchmark.harness.roofline_swarm import roofline_pct
from benchmark.harness.walk_spans import round_device_secs, traced_round


def compute(run: dict):
    rnd, secs = traced_round(run), round_device_secs(run)
    if rnd is None or secs is None:
        return None
    return roofline_pct(rnd["explored"],
                        int(run["outcome"]["row_bytes"]), secs,
                        run["peaks"]["hbm_bytes_per_s"], run["chips"])
