"""Seconds of one lab call spent building what it searches with —
``entry.bind`` (settings to masks, predicates to lanes, the twin),
``entry.build_engine`` (``ShardedTensorSearch(...)``) and
``entry.derive_root`` — mean per call of the traced cycle, from the
program's own phases on the trace's clock."""

from benchmark.harness.program_spans import ENGINE_BUILD, stage_seconds


def compute(run: dict):
    return stage_seconds(run, ENGINE_BUILD)
