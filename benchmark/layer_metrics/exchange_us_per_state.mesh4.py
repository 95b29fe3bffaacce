"""Device microseconds per state explored, traced level, of the
superstep's operations scoped ``route`` (the owner sort and the bucket
gathers), ``exchange`` (the ``all_to_all``s) or ``level_sync`` (the
stats vector's reductions): what the exchange costs around the
collectives themselves, which ``collective_pct.mesh4`` does not see."""

from benchmark.harness.program_spans import scope_us_per_state


def compute(run: dict):
    if run["chips"] < 2:
        return None
    return scope_us_per_state(run, ("route", "exchange", "level_sync"))
