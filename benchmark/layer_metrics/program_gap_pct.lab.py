"""Of the seconds inside one lab call's module runs (the worst device's
``XLA Modules`` events that began inside the call), the share in which
no operation ran: the device program's own trickle, which no change to
the host can remove.  Mean per traced call."""

from benchmark.harness.idle_by_span import mean_per_call


def compute(run: dict):
    return mean_per_call(run, "program_gap_pct")
