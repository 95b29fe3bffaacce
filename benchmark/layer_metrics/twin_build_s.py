"""Seconds the process spent building tensor twins
(``compile_cache.totals()``: ``twin_build_s``, the sum of its
``compile.twin`` spans — one a ``ProtocolSpec.compile()``: layout,
the budget dry-run that runs every handler once, the step closures).
A deep cell builds one twin, by its configuration's factory in
set-up's ``construct``; a lab cell one a ladder rung and a binding, and
the counter sums them, as ``setup_s`` pays them (a twin the reference
or ``verify`` builds after the window is in the sum too).  Nothing to
read (None) on a program from before PR 48."""


def compute(run: dict):
    if not run.get("trace"):
        return None
    try:
        from dslabs_tpu.tpu import compile_cache

        return float(compile_cache.totals()["twin_build_s"])
    except (ImportError, AttributeError, KeyError):
        return None
