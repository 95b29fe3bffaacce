"""Of the process's lookups in the executable store
(``compile_cache.totals()``: ``exe_store_hit_n`` / ``exe_store_miss_n``),
the share that loaded an executable instead of tracing, lowering and
compiling it, in per cent.  Every lookup is set-up's: the window of the
cells that read it builds no engine (``correct`` checks that it compiles
nothing).  100 on a machine whose store holds this commit's programs, 0
on its first run there; nothing to read (None) on a program without the
store, or where no engine's key could be built (no lookup was made)."""


def compute(run: dict):
    if not run.get("trace"):
        return None
    try:
        from dslabs_tpu.tpu import compile_cache

        totals = compile_cache.totals()
        hits = totals["exe_store_hit_n"]
        lookups = hits + totals["exe_store_miss_n"]
    except (ImportError, AttributeError, KeyError):
        return None             # a program from before PR 41
    return 100.0 * hits / lookups if lookups else None
