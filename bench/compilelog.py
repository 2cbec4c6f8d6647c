"""Compile accounting from JAX's own monitoring events (copied from the
program's ``chip_smoke.CompileLog``, so that the yardstick does not move
with the program).

Every executable JAX builds passes ``backend_compile_duration``, whether
the backend compiled it or the persistent cache handed it back, so
``programs`` counts both; ``hits`` counts the cache's share of them.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Counts:
    programs: int = 0
    seconds: float = 0.0
    hits: int = 0
    misses: int = 0

    def __sub__(self, other: "Counts") -> "Counts":
        return Counts(self.programs - other.programs,
                      self.seconds - other.seconds,
                      self.hits - other.hits, self.misses - other.misses)


class CompileLog:
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.counts = Counts()

        def on_duration(event, secs, **_):
            if event == self.COMPILE:
                self.counts.programs += 1
                self.counts.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.counts.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.counts.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Counts:
        return dataclasses.replace(self.counts)
