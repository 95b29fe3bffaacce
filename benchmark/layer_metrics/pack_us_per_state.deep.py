"""Device microseconds per state explored, traced level, of the
superstep's operations that name the scope ``pack``
(``LanePacking.unpack_jnp`` of the chunk's frontier rows and
``pack_jnp`` of its successors: the bit-packed codec).  The compiler's
relayouts around the codec name no scope and are NOT in it
(``scope_coverage_pct.deep``)."""

from benchmark.harness.program_spans import scope_us_per_state


def compute(run: dict):
    return scope_us_per_state(run, ("pack",))
