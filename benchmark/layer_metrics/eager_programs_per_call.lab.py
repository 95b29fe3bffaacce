"""Module runs of one lab call that are NOT a dispatch site's own
program (``dispatch.<site>`` launches ``jit_<site>…``): eager
operations and jitted helpers called past the ``_dispatch`` seam (the
replay's compiled step among them), each a launch of its own.  Told by
name, not by where a run's start fell against the spans (every launch
is asynchronous), so exact for a seed's call; mean per traced call.
The reader's stderr line names the programs that ran most."""

from benchmark.harness.idle_by_span import mean_per_call


def compute(run: dict):
    return mean_per_call(run, "eager_programs")
