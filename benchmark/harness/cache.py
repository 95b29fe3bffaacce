"""JAX's own compile events, counted: the direct evidence of whether
something compiled, and when.

``misses`` are programs compiled and WRITTEN to the persistent cache
(JAX records the event in the write, so a compile shorter than
``jax_persistent_cache_min_compile_time_secs`` is not one), ``hits`` are
programs loaded from it, ``backend_compiles`` counts every run of the
backend's compiler, written or not."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Counts:
    hits: int = 0
    misses: int = 0
    backend_compiles: int = 0
    backend_compile_secs: float = 0.0

    def minus(self, other: "Counts") -> "Counts":
        return Counts(self.hits - other.hits, self.misses - other.misses,
                      self.backend_compiles - other.backend_compiles,
                      self.backend_compile_secs
                      - other.backend_compile_secs)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class CompileEvents:
    """Listens for the life of the process (JAX offers no way to take
    one listener out that is stable across versions); ``snapshot()``
    differences bound a phase."""

    def __init__(self):
        import jax.monitoring

        self._now = Counts()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._now.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._now.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._now.backend_compiles += 1
            self._now.backend_compile_secs += secs

    def snapshot(self) -> Counts:
        return dataclasses.replace(self._now)
