"""Device microseconds per state explored, in the traced lab call, of
the superstep's operations scoped ``expand.*`` (event tables, handlers,
network merge) or ``fingerprint`` — self time per chip over the states
ALL the call's ladder attempts explored (``harness/lab_call_trace.py``).
The 2PC twin's handlers, to be read beside ``expand_us_per_state.deep``
(lab 3's)."""

from benchmark.harness.lab_call_trace import counts, scope_seconds


def compute(run: dict):
    got = counts(run)
    named = scope_seconds(run) if got and got[0] else None
    if named is None:
        return None
    return 1e6 * sum(v for k, v in named.items() if k.startswith(
        "expand.") or k == "fingerprint") / got[0]
