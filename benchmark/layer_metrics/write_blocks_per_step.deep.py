"""Scatter blocks the dedup layer wrote per chunk step of the traced
level: the ``write_blocks`` over the ``chunks`` that the program closes
onto the level's ``search.level`` span (``tpu/sharded.py``: visited-table
blocks plus frontier-append blocks, of the device that wrote most).  One
block a probe iteration and one an append is the floor; a value that
climbs says ``visited.block_width`` is too narrow for the traffic.
Exact.  None from a program that counts no blocks (before PR 34)."""

from benchmark.harness.program_spans import traced_level


def compute(run: dict):
    level = traced_level(run)
    if level is None or "write_blocks" not in level or not level.get(
            "chunks"):
        return None
    return float(level["write_blocks"]) / float(level["chunks"])
