"""Fleet telemetry: per-round time series, frame tracing, profiling.

The observability layer behind ``MultiStreamServer(..., telemetry=...)``
— always available, zero-cost when off (the engines hold ``None`` and
skip every hook).  Three parts (docs/observability.md):

  * ``timeseries.FleetRecorder`` — per-round SoA time series of the
    control loop's observables (counters, bandwidth EWMA vs truth,
    cell/replica contention, occupancy, decision histograms); fed by the
    numpy engine inline and by the JAX engine through stacked ``lax.scan``
    outputs, backend-comparable under the exactness policy;
  * ``trace.FrameTracer`` — per-escalation lifecycle spans with
    cell/replica/batch ids, exported as Chrome trace-event / Perfetto
    JSON (numpy engine only);
  * ``profile.PhaseProfiler`` — the program's spans (wall-clock phase
    totals, and ``repro.*`` annotations on the device trace's clock) and
    counters, plus the AOT compile-vs-steady split for jitted entry
    points.

``Telemetry`` is the bundle the engines consume: pick the parts with
flags, the server binds dimensions at construction.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from repro.obs.profile import PhaseProfiler, aot_split
from repro.obs.timeseries import FleetRecorder, relock_lags
from repro.obs.trace import FrameTracer, export_chrome_trace

__all__ = ["Telemetry", "FleetRecorder", "FrameTracer", "PhaseProfiler",
           "aot_split", "export_chrome_trace", "relock_lags"]


@dataclass
class Telemetry:
    """What to observe: ``record`` (per-round series, cheap, default on),
    ``trace`` (per-frame lifecycle spans, numpy engine only), ``profile``
    (per-phase wall-clock).  Pass to ``MultiStreamServer(telemetry=...)``;
    the server calls ``bind`` with the fleet's dimensions and the parts
    materialize lazily (pre-built parts are kept)."""

    record: bool = True
    trace: bool = False
    profile: bool = False
    recorder: Optional[FleetRecorder] = None
    tracer: Optional[FrameTracer] = None
    profiler: Optional[PhaseProfiler] = None

    def bind(self, *, n_streams: int, n_cells: int, n_replicas: int,
             n_actions: int) -> "Telemetry":
        if self.record and self.recorder is None:
            self.recorder = FleetRecorder(n_streams, n_cells, n_replicas,
                                          n_actions)
        if self.trace and self.tracer is None:
            self.tracer = FrameTracer()
        if self.profile and self.profiler is None:
            self.profiler = PhaseProfiler()
        return self
