"""Device microseconds per state explored, traced level, of the
superstep's operations scoped ``expand.*`` (event tables, handlers,
network merge) or ``fingerprint`` — self time per chip, found through
the executable's own ``dslabs.<scope>`` metadata
(``harness/program_spans.py``)."""

from benchmark.harness.program_spans import scope_us_per_state


def compute(run: dict):
    return scope_us_per_state(run, ("expand.", "fingerprint"))
