"""The search itself, per call (median): ``tensor_outcome.elapsed_secs``
— the host's level loop over tiny levels."""

import statistics


def compute(run: dict):
    calls = run.get("calls")
    if not calls:
        return None
    return statistics.median(c["search_s"] for c in calls)
