"""Profiling hooks: the program's spans and counters, and the AOT split.

  * ``PhaseProfiler`` — the program's single source of spans and
    counters.  ``phase(name)`` opens a ``jax.profiler.TraceAnnotation``
    named ``"repro." + name`` around the region, so a device trace names
    the host work between device calls on its own clock, and adds the
    region's wall-clock seconds to ``totals``/``counts``.  ``count(name,
    n)`` accumulates ``counters``.  The numpy engine's per-round phases
    (plan / serve / transmit / fold) and the jax bridge's (prepare /
    precompute with upload / tier_fast / tier_slow / host_read / pad,
    scan, fold, record) go through it (docs/observability.md).
  * ``phase(prof, name)`` — the engines' guard: ``prof.phase(name)``, or a
    ``nullcontext`` when ``prof`` is ``None``.  The engines hold ``prof =
    None`` without telemetry, and then open no span, read no clock and
    count nothing.
  * ``aot_split`` — the compile-vs-steady split for jitted entry points
    (``fn.lower(*args).compile()`` timed as one explicit step), so
    ``compile_s`` is a measured wall-clock, never a first-call
    subtraction.  ``bench_fleet_control.py`` reports both numbers
    through it.
"""
from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext

__all__ = ["PhaseProfiler", "aot_split", "phase", "SPAN_PREFIX"]

SPAN_PREFIX = "repro."


class PhaseProfiler:
    """Named wall-clock accumulators (total seconds + call counts), named
    spans on the device trace's clock, and named counters."""

    def __init__(self):
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self.counters: dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        self.totals[name] = self.totals.get(name, 0.0) + float(seconds)
        self.counts[name] = self.counts.get(name, 0) + 1

    def count(self, name: str, n) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    @contextmanager
    def phase(self, name: str):
        """``with prof.phase("plan"): ...`` — one timed region, a
        ``repro.plan`` span on the profiler trace."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(SPAN_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    def __bool__(self) -> bool:
        return bool(self.totals or self.counters)

    def summarize(self) -> dict:
        """Per-phase ``{total_s, calls, mean_ms}``, the grand total, and the
        counters under ``"counters"``."""
        out = {}
        for name in self.totals:
            t, c = self.totals[name], self.counts[name]
            out[name] = {"total_s": round(t, 6), "calls": c,
                         "mean_ms": round(t / max(c, 1) * 1e3, 4)}
        if out:
            out["total_s"] = round(sum(self.totals.values()), 6)
        if self.counters:
            out["counters"] = dict(self.counters)
        return out

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()
        self.counters.clear()


def phase(prof: PhaseProfiler | None, name: str):
    """``with phase(prof, "fold"): ...`` — a span of ``prof``, or nothing
    at all when ``prof`` is ``None``."""
    return nullcontext() if prof is None else prof.phase(name)


def aot_split(fn, *args, profiler: PhaseProfiler | None = None):
    """AOT-compile a jitted callable and time the lower+compile step.

    Returns ``(compiled, compile_s)``.  The caller times steady-state
    executions of ``compiled`` itself (donated buffers make that
    caller-specific); when ``profiler`` is given the compile time is also
    folded in under ``"compile"``.
    """
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    if profiler is not None:
        profiler.add("compile", dt)
    return compiled, dt
