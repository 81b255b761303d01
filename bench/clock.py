"""Compile seconds and persistent-cache hits, read from ``jax.monitoring``.

A copy of the program's ``chip_smoke.py::CompileClock``: a persistent-cache
hit is timed as its retrieval, so a warm set-up reads far less than a cold
one."""
from __future__ import annotations


class CompileClock:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on_duration)
        self._jax.monitoring.unregister_event_listener(self._on_event)


class Aside:
    """Wall and compile seconds of set-up work that is not the system's own
    (the reference's labels and calibration), so that ``setup_s`` and
    ``compile_s`` can leave them out.  Use as ``with aside:`` around work
    whose inputs are ready, and make the work's outputs ready inside."""

    def __init__(self, clock: CompileClock | None = None):
        self.clock = clock
        self.seconds = 0.0
        self.compile_s = 0.0

    def __enter__(self):
        import time

        self._t0 = time.perf_counter()
        self._c0 = self.clock.seconds if self.clock is not None else 0.0
        return self

    def __exit__(self, *exc):
        import time

        self.seconds += time.perf_counter() - self._t0
        if self.clock is not None:
            self.compile_s += self.clock.seconds - self._c0
        return False
