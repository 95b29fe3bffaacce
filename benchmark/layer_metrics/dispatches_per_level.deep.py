"""Dispatches the host made in the traced level (all sites of the
``_dispatch`` seam, counted by the benchmark's recorder): exact, and the
same in every run, since the level's size is."""

from benchmark.harness.levels import traced_level


def compute(run: dict):
    if traced_level(run) is None:
        return None
    by_site = run["dispatches_by_level"][run["traced_depth"] - 1]
    return float(sum(by_site.values()))
