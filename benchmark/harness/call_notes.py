"""The annotations of each lab call of a traced slice, for per-layer
metrics that COUNT what the program wrote about a call (a span's fields,
how many spans of a name) where ``program_spans.calls`` sums seconds."""

from __future__ import annotations

import statistics
from typing import Callable, List, Optional

from benchmark.harness import program_spans


def mean_per_call(run: dict,
                  value: Callable[[List[dict]], Optional[float]]):
    """Mean of ``value(notes)`` over the entry-point calls of the slice,
    ``notes`` being the ``dslabs:`` annotations that carry the call's id;
    calls for which ``value`` gives None are left out, and None comes
    back when nothing is left (no slice, or a program that writes no
    such annotation)."""
    got = program_spans.load(run)
    if got is None:
        return None
    values = []
    for root in got["notes"]:
        if root["name"] not in ("entry.tensor_bfs", "entry.tensor_dfs"):
            continue
        v = value([n for n in got["notes"]
                   if n.get("call") == root.get("call")])
        if v is not None:
            values.append(v)
    return statistics.fmean(values) if values else None
