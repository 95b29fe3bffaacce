"""What a launch and a readback cost: per ``dispatch.*`` span of one
lab call, its milliseconds less the ``XLA Modules`` runs that began
inside it — lead (span start to the first run), between the runs, tail
(the last run's end to the span's end) — mean over the call's
dispatches, then over the traced calls."""

from benchmark.harness.idle_by_span import mean_per_call


def compute(run: dict):
    return mean_per_call(run, "dispatch_host_ms")
