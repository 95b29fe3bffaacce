"""Device microseconds of the fleet's round program per walker step
advanced, over the traced round (per chip).  The reader's stderr table
splits the same step by scope (``harness/walk_spans.py``)."""

from benchmark.harness.walk_spans import (round_device_secs, scope_table,
                                          traced_round)


def compute(run: dict):
    rnd, secs = traced_round(run), round_device_secs(run)
    if rnd is None or secs is None:
        return None
    scope_table(run)            # the stderr table
    return 1e6 * secs / rnd["explored"]
