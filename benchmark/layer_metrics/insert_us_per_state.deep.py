"""Device microseconds per state explored, traced level, of the
superstep's operations that name the scope ``visited_insert``
(``visited.insert_jnp``: the probe loop) or ``append`` (the scatter into
the next frontier).  The compiler's relayouts of the table around the
insert name no scope and are NOT in it (``scope_coverage_pct.deep``)."""

from benchmark.harness.program_spans import scope_us_per_state


def compute(run: dict):
    return scope_us_per_state(run, ("visited_insert", "append"))
