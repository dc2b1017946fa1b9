"""Backend compiles and their seconds, from JAX's own monitoring events."""
from __future__ import annotations

from typing import Tuple


class CompileMeter:
    """Counts backend compiles (and their seconds) and persistent-cache
    hits from the moment it is built."""

    #: the duration event JAX records for every backend compile
    EVENT = "/jax/core/compile/backend_compile_duration"
    #: the event JAX records for every persistent-cache hit
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax) -> None:
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> Tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits
