"""The benchmark's own host spans and its count of compiles.

A span is a ``jax.profiler.TraceAnnotation`` (so a traced run shows it on
the profiler's timeline beside the device) that also adds its host-clock
duration to a total per name.
"""

from __future__ import annotations

import contextlib
import time

import jax


class Spans:
    def __init__(self):
        self.total_s: dict[str, float] = {}
        self.count: dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        dt = time.perf_counter() - t0
        self.total_s[name] = self.total_s.get(name, 0.0) + dt
        self.count[name] = self.count.get(name, 0) + 1

    def reset(self) -> None:
        self.total_s.clear()
        self.count.clear()


class CompileCounter:
    """Counts backend compiles (cache misses) and persistent-cache loads
    from the moment it is created; nothing should compile in a window."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_loads += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_loads
