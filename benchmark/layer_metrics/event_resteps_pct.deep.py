"""Share of the traced level's chunk steps that RE-RAN a chunk at a later
event window, in percent: of the ``chunks`` the level's ``search.level``
span closes with, those beyond ``ceil(frontier0 / chunk)`` — the steps
its ``frontier0`` rows need when every state's valid events fit the
configuration's event window (``engine.ev_budget``; a strict search
re-steps a chunk that holds a state with more, ``tpu/sharded.py``).  0
says the window is wide enough for the protocol; what it costs in masked
grid slots is ``grid_fill_pct.deep``'s to say.  Exact.  None from a
program whose span has no ``frontier0`` (before PR 36)."""

from benchmark.harness.program_spans import traced_level


def compute(run: dict):
    level = traced_level(run)
    if level is None or "frontier0" not in level or not level.get(
            "chunks"):
        return None
    chunk = int(run["config"]["engine"]["chunk"])
    needed = -(-int(level["frontier0"]) // chunk)
    return 100.0 * (int(level["chunks"]) - needed) / int(level["chunks"])
