"""Seconds of one lab call's ``entry.search`` + ``entry.warm_run``
that no ``dispatch.*`` span covers: the level loop's own host work
(the run's prologue, what a dispatch is handed, the checks, the trace
metadata's readback, the verdict).  Mean per traced call."""

from benchmark.harness.idle_by_span import mean_per_call


def compute(run: dict):
    return mean_per_call(run, "level_host_s")
