"""Dispatches of one lab call: the ``dispatch.<site>`` annotations that
carry its id (every dispatch of the searches it built, warm run
included), mean per call of the traced cycle.  Exact: the same on every
run of a seed's cycle."""

from benchmark.harness.program_spans import mean_per_call


def compute(run: dict):
    return mean_per_call(run, lambda c: float(c["dispatches"]))
