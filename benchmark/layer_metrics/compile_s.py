"""Seconds the set-up's construction spent in explicit AOT compilation
(``SearchOutcome.compile_secs``): compiling on a cold cache, loading on
a warm one.  Tracing and lowering are outside it; ``setup_s`` has all."""


def compute(run: dict):
    return run.get("compile_s")
