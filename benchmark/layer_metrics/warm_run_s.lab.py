"""Seconds of one lab call in ``entry.warm_run``, the depth-2 run
``_run_tensor`` makes before a search that carries ``max_time`` (so that
compiling is not charged to the test's budget), mean per call of the
traced cycle."""

from benchmark.harness.program_spans import stage_seconds


def compute(run: dict):
    return stage_seconds(run, ("entry.warm_run",))
