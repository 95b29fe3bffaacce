"""Shared by the ``.deep`` per-layer readers: the traced level's own record."""


def traced_level(run: dict):
    """``(level record, explored in the level, rows expanded in it)`` of
    the level the traced slice covered, or None if no whole level was
    traced (the run ended first, or the timer cut the slice)."""
    depth = run.get("traced_depth")
    if depth is None or run.get("trace_cut_by_timer"):
        return None
    by_depth = {int(lv["depth"]): lv for lv in run["levels"]}
    if depth not in by_depth or depth - 1 not in by_depth:
        return None
    lv, prev = by_depth[depth], by_depth[depth - 1]
    before = by_depth.get(depth - 2, {"unique": 1})
    explored = int(lv["explored"]) - int(prev["explored"])
    expanded = int(prev["unique"]) - int(before["unique"])
    return lv, explored, expanded


def superstep_secs(run: dict):
    """Device seconds (per chip) of the fused superstep program in the
    traced slice.  The program has no name of its own in the trace
    (``jit__lambda``), so it is found by its dispatch: the host blocks
    in the ``superstep`` site until the program's stats are read back,
    so the device time inside those spans is the program's."""
    return run["trace"]["busy_in_span"].get("superstep")
