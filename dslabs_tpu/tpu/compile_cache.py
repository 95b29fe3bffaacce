"""Persistent XLA compile-cache wiring (one place, one path).

The cache directory is part of the cache's key, so a directory that
moves never hits.  This module is the single seam:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
  and this module sets no directory at all;
* where it is not set, the cache lives at ``<checkout>/.jax_cache``,
  computed from this package's own location — the same path from every
  process and every working directory (``.gitignore`` lists it).

It is also where the program listens to JAX's own compile events
(``jax.monitoring``): :func:`setup` registers ONE listener for the life
of the process, which writes each event as a ``compile.event`` mark
(tpu/telemetry.py) — on the profiler's clock in a traced run, in the
flight log of a current recorder — and keeps the process totals
:func:`totals` reads.

Together with the engines' AOT warm-up (``ShardedTensorSearch
.aot_warmup``) the second construction of any config pays near-zero
compile: the warm-up's ``.lower().compile()`` hits the on-disk cache
instead of XLA.
"""

from __future__ import annotations

import os
import threading
import time

__all__ = ["setup", "cache_dir", "totals", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The persistent-compile-cache directory currently in effect."""
    import jax

    return jax.config.jax_compilation_cache_dir


# JAX's compile events, by the short name a ``compile.event`` mark
# carries as ``kind``.  ``trace`` events nest (tracing a function traces
# the jitted functions it calls), ``retrieve`` lies inside
# ``backend_compile`` (which times compile-or-load).
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieve",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
_lock = threading.Lock()
_listening = False
_totals = {k + "_s": 0.0 for k in _DURATIONS.values()}
_totals.update({k + "_n": 0 for k in _DURATIONS.values()})
_totals.update({k + "_n": 0 for k in _EVENTS.values()})
# [start, secs] of the trace events counted so far, oldest first: an
# event that began before them contains them, and takes their place.
# Held to _TRACE_KEEP entries by folding the two oldest into one, which
# miscounts only if a trace still open began between those two: each
# fold is counted (``trace_folded_n``), so a total that may be high
# says so.
_trace_counted: list = []
_TRACE_KEEP = 1 << 16
_totals["trace_folded_n"] = 0


def _on_duration(event, secs, **kw) -> None:
    kind = _DURATIONS.get(event)
    if kind is None:
        return
    from dslabs_tpu.tpu import telemetry

    secs = float(secs)
    with _lock:
        _totals[kind + "_n"] += 1
        _totals[kind + "_s"] += secs
        if kind == "trace":
            start = time.monotonic() - secs
            while _trace_counted and _trace_counted[-1][0] >= start:
                _totals["trace_s"] -= _trace_counted.pop()[1]
            _trace_counted.append([start, secs])
            if len(_trace_counted) > _TRACE_KEEP:
                _trace_counted[0][1] += _trace_counted.pop(1)[1]
                _totals["trace_folded_n"] += 1
    fields = {"kind": kind, "secs": round(secs, 6)}
    if kw.get("fun_name"):
        fields["fun"] = str(kw["fun_name"])
    telemetry.mark("compile.event", **fields)


def _on_event(event, **_kw) -> None:
    kind = _EVENTS.get(event)
    if kind is None:
        return
    from dslabs_tpu.tpu import telemetry

    with _lock:
        _totals[kind + "_n"] += 1
    telemetry.mark("compile.event", kind=kind, secs=0.0)


def totals() -> dict:
    """What the process has spent in JAX's compile machinery since
    :func:`setup` first ran: seconds and counts by kind — ``trace_s``
    (jaxpr tracing, nested traces counted once), ``lower_s`` (jaxpr to
    MLIR), ``backend_compile_s`` (XLA compile or persistent-cache load;
    ``retrieve_s`` is the loading part of it) — the persistent cache's
    ``cache_hit_n`` / ``cache_miss_n``, and ``trace_folded_n``: how
    often the record of counted traces was shortened (0: ``trace_s``
    is exact)."""
    with _lock:
        return dict(_totals)


def setup() -> str:
    """Enable JAX's persistent compilation cache and return its
    directory: the one ``JAX_COMPILATION_CACHE_DIR`` names (left to
    JAX), else :data:`DEFAULT_DIR`.  Idempotent — every engine
    constructor calls it."""
    import jax

    global _listening
    if not _listening:
        # JAX offers no stable way to take a listener out again, so
        # there is one, registered once.
        import jax.monitoring

        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            and jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        from jax.experimental.compilation_cache import \
            compilation_cache as cc

        os.makedirs(DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # The runtime holds a cache singleton initialised with the dir
        # at FIRST use — without a reset, a dir change after any cached
        # compile is silently ignored.
        cc.reset_cache()
    # Cache quick compiles too: the search programs are rebuilt by
    # every process that constructs an engine, and the key is
    # platform-specific anyway.  0.3 s and not 0.5: on the v5e lab 3's
    # ``_step_one``, which every staged call rebuilds, compiles in about
    # 0.5 s — under the floor a few times and over it later, so a cold
    # process cached it at a moment chance chose (PERF.md, PR 27); the
    # small programs every call rebuilds take 0.02-0.21 s there.
    if jax.config.jax_persistent_cache_min_compile_time_secs > 0.3:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.3)
    return jax.config.jax_compilation_cache_dir
