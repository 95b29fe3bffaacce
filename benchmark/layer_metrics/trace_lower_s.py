"""Seconds the process spent tracing jaxprs (nested traces counted
once) and lowering them to MLIR, by JAX's own compile events
(``compile_cache.totals()``): the part of set-up no cache can load.  The
window of the cells that read it compiles nothing (``correct`` checks
that), so this is set-up's."""

import sys


def compute(run: dict):
    if not run.get("trace"):
        return None
    try:
        from dslabs_tpu.tpu import compile_cache

        totals = compile_cache.totals()
    except (ImportError, AttributeError):
        return None             # a program from before PR 25
    if totals.get("trace_folded_n"):
        print(f"info trace_lower_s may count nested traces twice: "
              f"{totals['trace_folded_n']} folds of the record of counted "
              f"traces", file=sys.stderr, flush=True)
    return totals["trace_s"] + totals["lower_s"]
