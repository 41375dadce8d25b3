"""Compile seconds and persistent-cache hits, from JAX's own monitoring
events (after ``chip_smoke.py``'s `CompileMeter`), and the host spans of a
run on one clock."""

from __future__ import annotations

import contextlib
import time


class CompileMeter:
    """Counts backend compiles. ``compiles`` is what the window may not move."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Spans:
    """Host spans ``(name, start_s, end_s)`` on ``time.perf_counter``; with a
    trace running each span is also a ``TraceAnnotation`` in the profile."""

    def __init__(self, annotate: bool = False):
        self.items: list[tuple[str, float, float]] = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(f"bench/{name}")
        t0 = time.perf_counter()
        with ctx:
            yield
        self.items.append((name, t0, time.perf_counter()))

    def total(self, name: str, lo: float = float("-inf"), hi: float = float("inf")) -> tuple[float, int]:
        """Summed seconds and count of the spans called ``name`` inside [lo, hi]."""
        took = [e - s for n, s, e in self.items if n == name and s >= lo and e <= hi]
        return sum(took), len(took)
