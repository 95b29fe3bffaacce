"""Share of the traced level's (chunk step, event kind) pairs that the
expand did NOT compute, in percent: the ``kind_skips`` the level's
``search.level`` span closes with over 2 x its ``chunks`` — a chunk step
runs the message kind's and the timer kind's handlers and merge each
under a device branch on the pass's own event table, and skips a kind
whose table holds no event (``tpu/engine.py`` ``_expand_chunk``, which
counts the skip where it takes the branch).  0 where no chunk re-steps
and both kinds are live; 25 where every chunk steps twice and the second
pass holds one kind only (``event_resteps_pct.deep`` 50).  Exact on one
device.  On a mesh the count is the device's that skipped most, and a
device whose shard has run out skips both kinds in every step it waits
through: the number then says nothing of work saved, and the metric
lists no mesh cell.  None from a program whose span has no
``kind_skips`` (before PR 49)."""

from benchmark.harness.program_spans import traced_level


def compute(run: dict):
    level = traced_level(run)
    if level is None or "kind_skips" not in level or not level.get(
            "chunks"):
        return None
    return 100.0 * float(level["kind_skips"]) / (
        2.0 * float(level["chunks"]))
