"""Seconds of one lab call that a climbed rung threw away: its
``entry.derive_root`` + ``entry.warm_run`` + ``entry.search`` on the
attempts that an ``entry.capacity_retry`` mark ended (the stages carry
``attempt`` since PR 33), mean per call of the traced slice.  0.0 where
``ladder_attempts_per_call.suite`` reads 1.0; a program whose stages do
not say their attempt gives None."""

from benchmark.harness.call_notes import mean_per_call
from benchmark.harness.lab_call_trace import wasted_seconds


def compute(run: dict):
    return mean_per_call(run, wasted_seconds)
