"""Seconds of one lab call after the search: ``entry.replay`` (the
witness replayed on the object twin, the original predicate checked on
it) and ``entry.recheck`` (sampled states re-checked for value-level
invariants), mean per call of the traced cycle."""

from benchmark.harness.program_spans import stage_seconds


def compute(run: dict):
    return stage_seconds(run, ("entry.replay", "entry.recheck"))
