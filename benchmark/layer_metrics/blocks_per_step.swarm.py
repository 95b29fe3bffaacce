"""Blocks of walkers the fleet's walk step ran its handlers and merge
over, in the traced round: the ``blocks`` field the program writes on
its ``swarm.round`` span (``SwarmSearch.step_blocks``: 1 = the whole
fleet at once; exact).  A program that writes no such field — every
commit before PR 44 — gives None."""

from benchmark.harness.walk_spans import traced_round


def compute(run: dict):
    rnd = traced_round(run)
    if rnd is None or rnd.get("blocks") is None:
        return None
    return float(rnd["blocks"])
