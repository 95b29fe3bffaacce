"""Rungs of the capacity ladder one lab call stood on: its
``entry.bind`` spans (one a ladder attempt; every further one follows an
``entry.capacity_retry`` mark and rebuilds protocol and engine), mean per
call of the traced slice.  Exact; 1.0 means no rung was climbed.  What
each mark says overflowed goes to stderr."""

import sys

from benchmark.harness.call_notes import mean_per_call


def _attempts(notes):
    for n in notes:
        if n["name"] == "entry.capacity_retry":
            print(f"info ladder: call {n.get('call')} left rung "
                  f"{n.get('attempt')}: {n.get('overflow')}",
                  file=sys.stderr, flush=True)
    return float(sum(n["name"] == "entry.bind" for n in notes)) or None


def compute(run: dict):
    return mean_per_call(run, _attempts)
