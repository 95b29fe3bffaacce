"""Seconds of one lab call inside JAX's compile machinery — tracing,
lowering, compiling or loading — by the ``compile.event`` marks that
carry the call's id: the time their intervals cover (traces nest and a
cache load lies inside its backend compile, so not their sum), mean per
call of the traced cycle."""

from benchmark.harness.program_spans import mean_per_call


def compute(run: dict):
    return mean_per_call(run, lambda c: c["compile_s"])
