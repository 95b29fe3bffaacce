"""Bucket columns the dedup layer's probes gathered per chunk step of the
traced level: the ``probe_cols`` over the ``chunks`` that the program
closes onto the level's ``search.level`` span (``tpu/sharded.py``: the
indices handed to the visited table's gather, full phase and tail
together, of the device that gathered most).  The chip pays a gather per
index it is handed, so this is the probe's read width as the traffic
sets it: a step's live blocks of ``visited.block_width``, not its
batch.  A value near the batch's width (49,152 on one chip, 98,312 on
four) says the valid keys lie scattered over the batch.  Exact.  None
from a program that counts no columns (before PR 37)."""

from benchmark.harness.program_spans import traced_level


def compute(run: dict):
    level = traced_level(run)
    if level is None or "probe_cols" not in level or not level.get(
            "chunks"):
        return None
    return float(level["probe_cols"]) / float(level["chunks"])
