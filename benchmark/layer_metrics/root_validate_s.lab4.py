"""Seconds of one STAGED lab call under ``entry.root.validate``: the
binding holds the staged object state, field by field, to the canonical
root its twin's initial state bakes in (lab 4's joined root) instead of
replaying a provenance — mean per call of the traced slice that has such
a span (a program from before PR 33 has none)."""

from benchmark.harness.call_notes import mean_per_call
from benchmark.harness.program_spans import secs


def compute(run: dict):
    return mean_per_call(run, lambda notes: sum(
        secs(n) for n in notes
        if n["name"] == "entry.root.validate") or None)
