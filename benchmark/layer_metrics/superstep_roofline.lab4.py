"""Share of the HBM-bandwidth roofline the traced lab call's searches
reached: the least time for the bytes its levels must move
(``harness/roofline.py``, the benchmark's one byte function), summed
over its ladder attempts — the states each explored, the frontier rows
of its levels that closed, at the packed bytes a state of the twin THAT
attempt bound (a higher rung's network is wider) — over the device's busy
seconds inside the call's ``dispatch.*`` spans."""

from benchmark.harness.lab_call_trace import attempts, dispatch_device_secs
from benchmark.harness.roofline import roofline_pct


def compute(run: dict):
    got = attempts(run)
    secs = dispatch_device_secs(run) if got else None
    if not secs or any(a["bytes_per_state"] is None for a in got):
        return None
    return sum(roofline_pct(a["explored"], a["expanded"],
                            a["bytes_per_state"], secs,
                            run["peaks"]["hbm_bytes_per_s"], run["chips"])
               for a in got)
