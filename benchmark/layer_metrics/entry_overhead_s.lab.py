"""What the lab entry point adds around the search, per call (median):
the benchmark's span around ``tensor_bfs`` minus the search's own
elapsed and AOT-compile seconds — adapter, engine construction, tracing,
cache loads, root derivation, witness replay."""

import statistics


def compute(run: dict):
    calls = run.get("calls")
    if not calls:
        return None
    return statistics.median(c["wall_s"] - c["search_s"] - c["compile_s"]
                             for c in calls)
