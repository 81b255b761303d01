"""One cell of the benchmark: set-up, the measured window, the check.

The window drives the served path as a camera fleet's edge server does:
``MultiStreamServer(backend="jax").process_streams(frames, labels)`` on
whole segments, back to back, each segment a fresh server replaying S
streams x N frames (the compiled engine starts every call from empty
backlogs, so a segment is its unit of work).  One client, closed loop:
the next segment is handed over when the previous one returns.  Frames
stay in host numpy arrays, as the server receives them.

Everything a configuration or a traffic mix sets comes from its file
(``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``); the
configuration's ``tiers`` names the module under ``bench/gen`` that makes
its tiers and frames.  A new configuration enters by new files and
appended ``BENCHMARK.json`` entries alone, and its cell reports every
per-layer metric whose reader finds what it reads, if its tiers module
keeps to this contract:

``build(conf, traffic, seed, aside)`` returns the system, an object with

* ``fast``, ``slow``: the tiers the server calls, each ``(n, H, W, 3)``
  float32 frames to ``(n, classes)`` logits, each through one jitted
  program named ``tier_fast`` or ``tier_slow`` (so the trace holds
  ``jit_tier_fast`` and ``jit_tier_slow``, which ``tier_device_ms`` reads);
* ``platt``: Platt's ``(a, b)`` for the fast tier's confidence, or None for
  the plain max-softmax;
* ``segment(i)``: segment ``i``'s ``(S, N, H, W, 3)`` host frames and
  ``(S, N)`` labels, the same for the same seed;
* ``ref_fast(x)``, ``ref_slow(x, res)``: the plain reference's logits, at
  the configuration's stated precision, the slow tier's on frames degraded
  to the rung ``res`` (``bench/check.py``); the reference imports nothing
  of the program;
* ``control_tiers()``: ``(fast, slow)``, the reference one precision step
  below the stated one, in the program's place; ``control_conf(logits)``:
  the gate's confidence at that precision;
* ``program_texts()`` (optional): ``{"jit_tier_fast": text,
  "jit_tier_slow": text}``, each tier program's compiled text at one
  round's input (S x B frames), so that a reader can split its device time
  by the model's own ``jax.named_scope`` names
  (``bench/program_trace.py::program_texts``).

Work that makes what the check compares against (labels, calibration)
runs inside ``aside``, which set-up leaves out.  The module may define
``tier_flops(conf) -> (fast, slow)``, the FLOPs of one forward of each
tier at the configuration's input size, which ``tier_mfu`` reads; without
it ``tier_mfu`` reads nothing.  A configuration with ``use_fused: true``
runs the fused calibrate+gate kernel (``jit_calib_gate``, read by
``calib_gate_roofline``) and needs ``platt``.  The round
(``round_device_ms``, ``plan_device_ms``, ``plan_walk_share``), the bridge
(``fold_ms``, ``bridge_idle_ms``, ``slow_useful_ratio``), ``idle_share``
and ``compile_s`` need nothing more than serving through this harness.

No per-layer metric lists its cells, so a traced line has to carry every
one of them.  A tiers module that keeps only part of this contract, as
``synthetic`` does (closed-form tiers, no fused gate, no ``tier_flops``),
leaves ``tier_device_ms``, ``tier_mfu`` and ``calib_gate_roofline``
unread, and its cell cannot enter by new files alone: those three entries
first need a ``workloads`` list of the cells that keep the contract.
"""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import statistics
import tempfile
import time


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str):
    """(cell, configuration file, traffic file) of ``workload``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = load_json(entry["file"])
    traffic = load_json(os.path.join("bench", "traffic", cell["traffic"] + ".json"))
    return cell, conf, traffic


def control_config(conf: dict, traffic: dict):
    from bench.ref.control import ControlConfig

    S = int(traffic["streams"])
    return ControlConfig(
        resolutions=tuple(conf["resolutions"]), acc_server=tuple(conf["acc_server"]),
        deadline=float(conf["deadline"]), frame_rate=float(conf["frame_rate"]),
        batch=int(conf["batch_size"]), cell_bps=float(conf["stream_mbps"]) * S * 1e6 / 8.0,
        latency=float(conf["latency"]), server_time=float(conf["server_time"]),
        t_fast=float(conf["fast_time"]) + float(conf["calib_time"]),
        max_backlog=int(conf["max_backlog"]))


class Capture:
    """The tiers handed to the server, and the compiled round, wrapped so
    that one segment's outputs can be held for the check.  Holding them
    keeps references to arrays the path made anyway; nothing is copied or
    synchronised while the window runs."""

    def __init__(self, fast, slow, control_conf=None, trace=False):
        self._fast, self._slow = fast, slow
        self._trace = trace
        self.current = None
        # the control also computes the gate's confidences, from this call's
        # fast-tier logits, in place of the program's kernel
        self.control_conf = control_conf
        self._fast_calls = []

    def fast(self, x):
        with _span(self._trace, "bench.tier_fast"):
            y = self._fast(x)
        if self.current is not None:
            self.current["fast"].append(y)
        if self.control_conf is not None:
            self._fast_calls.append(y)
        return y

    def slow(self, x):
        with _span(self._trace, "bench.tier_slow"):
            y = self._slow(x)
        if self.current is not None:
            self.current["slow"].append(y)
        return y

    def start(self):
        self.current = {"fast": [], "slow": [], "inputs": None}

    def stop(self):
        cap, self.current = self.current, None
        return cap

    @contextlib.contextmanager
    def rounds(self):
        """Record the round inputs the control plane receives."""
        import jax.numpy as jnp

        from repro.serving import engine_jax as ej

        orig = ej.simulate

        def simulate(spec, params, inputs, carry=None):
            if self.control_conf is not None:
                R, S, B = inputs.conf.shape
                conf = jnp.stack([self.control_conf(y).reshape(S, B) for y in self._fast_calls])
                inputs = inputs._replace(conf=conf.astype(inputs.conf.dtype))
                self._fast_calls = []
            if self.current is not None:
                self.current["inputs"] = inputs
            with _span(self._trace, "bench.control_plane"):
                return orig(spec, params, inputs, carry)

        ej.simulate = simulate
        try:
            yield
        finally:
            ej.simulate = orig


def make_server(conf, S, capture, platt, telemetry=None):
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric
    from repro.serving import MultiStreamServer, ServeConfig

    cfg = ServeConfig(deadline=float(conf["deadline"]), frame_rate=float(conf["frame_rate"]),
                      resolutions=tuple(conf["resolutions"]),
                      acc_server=tuple(conf["acc_server"]), batch_size=int(conf["batch_size"]),
                      fast_time=float(conf["fast_time"]), calib_time=float(conf["calib_time"]),
                      server_time=float(conf["server_time"]),
                      use_fused=bool(conf["use_fused"]), platt_ab=platt)
    # one cell whose uplink carries every stream's share
    fabric = EdgeFabric.degenerate(
        Uplink(bandwidth_bps=mbps(float(conf["stream_mbps"]) * S),
               latency=float(conf["latency"]), server_time=float(conf["server_time"])),
        n_streams=S)
    return MultiStreamServer(cfg, capture.fast, capture.slow, lambda s: s, None,
                             n_streams=S, fabric=fabric, policy=conf["policy"],
                             backend="jax", telemetry=telemetry)


def _counts(m) -> dict:
    return {"frames": int(m.n_frames), "offloads": int(m.n_offloaded),
            "misses": int(m.n_deadline_miss),
            "correct": int(round(m.accuracy * m.n_frames))}


def run_cell(conf: dict, traffic: dict, *, seed: int, seconds: float, trace: bool,
             t_process_start: float, limits: dict, device=None, control=False,
             fault=None) -> dict:
    """Set up, measure, check.  Returns every number the cell can report;
    the caller prints the ones ``BENCHMARK.json`` names for the cell.

    ``control=True`` (tests and readings only) puts the reference at the
    control precision in the program's place; ``fault`` (likewise, one of
    ``bench/faults.py``) breaks the timed path underneath."""
    import jax

    from bench import check as chk
    from bench.clock import Aside, CompileClock

    clock = CompileClock()
    aside = Aside(clock)  # the reference's share of set-up, left out of it
    system = importlib.import_module(f"bench.gen.{conf['tiers']}").build(conf, traffic, seed,
                                                                          aside)
    S, N = int(traffic["streams"]), int(traffic["frames"])
    pool = [system.segment(i) for i in range(int(traffic["pool"]))]
    if control:
        capture = Capture(*system.control_tiers(), control_conf=system.control_conf,
                          trace=trace)
    else:
        capture = Capture(system.fast, system.slow, trace=trace)
    from repro.obs import PhaseProfiler, Telemetry

    # the traced run times the bridge's phases (host precompute, scan,
    # fold); record=False keeps the compiled round the untraced one
    profiler = PhaseProfiler() if trace else None

    def fresh():
        tel = Telemetry(record=False, profile=True, profiler=profiler) if trace else None
        return make_server(conf, S, capture, system.platt, tel)

    ctl = control_config(conf, traffic)
    counts, segs = [], []
    with (fault() if fault is not None else contextlib.nullcontext()), capture.rounds():
        # warm-up: every shape the window uses, on fresh servers
        for i in range(int(traffic["warmup_segments"])):
            fresh().process_streams(*pool[i % len(pool)])
        jax.effects_barrier()
        compile_s = clock.seconds - aside.compile_s
        if profiler is not None:
            profiler.reset()
        setup_s = time.perf_counter() - t_process_start - aside.seconds
        # the checked segments: one drawn from the seed among the first few,
        # and the window's last
        first_checked = seed % 3
        held = {}
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        n_trace = int(traffic["trace_segments"])
        if trace:
            # user spans and device activity only: the Python tracer and the
            # runtime's own host events would slow the host path it measures
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level, opts.python_tracer_level = 1, 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window = jax.profiler.TraceAnnotation("bench.window") if trace else contextlib.nullcontext()
        compile_before = clock.seconds
        with window:
            t_start = time.perf_counter()
            k = 0
            while k < n_trace if trace else _another(segs, time.perf_counter() - t_start, seconds):
                with _span(trace, "bench.build_server"):
                    srv = fresh()
                frames, labels = pool[k % len(pool)]
                capture.start()
                t0 = time.perf_counter()
                with _span(trace, "bench.process_streams"):
                    m = srv.process_streams(frames, labels)
                t1 = time.perf_counter()
                cap = capture.stop()
                segs.append(t1 - t0)
                counts.append(_counts(m))
                if k == first_checked:
                    held["first"] = (k, cap)
                held["last"] = (k, cap)
                k += 1
            t_end = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
    window_compile_s = clock.seconds - compile_before
    clock.close()
    peak = None
    if device is not None:
        stats = device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
    # the check runs after the window and after the memory peak was read
    checked = dict(held.values()) if int(traffic["check_segments"]) > 1 else dict([held["last"]])
    readings = [chk.check_segment(system, ctl, *pool[k % len(pool)], cap, counts[k])
                for k, cap in sorted(checked.items())]
    numbers = chk.merge(readings)
    correct, rows = chk.verdict(numbers, limits)
    served = sum(c["frames"] for c in counts)
    window_s = t_end - t_start
    out = {
        "correct": correct, "check": rows, "attempted": len(segs) * S * N,
        "failed": len(segs) * S * N - served,
        "segments": len(segs), "segment_s": segs, "window_s": window_s,
        "frames_per_s": served / window_s, "setup_s": setup_s, "compile_s": compile_s,
        "window_compile_s": window_compile_s, "memory_peak_bytes": peak,
        "reference_setup_s": aside.seconds,
        "escalated": sum(c["offloads"] + c["misses"] for c in counts), "served": served,
        "counts": counts, "system": system, "profiler": profiler, "trace_dir": trace_dir,
    }
    out["segment_p90_ms"] = (statistics.quantiles(segs, n=10)[-1] if len(segs) > 1
                             else segs[0]) * 1e3
    return out


def _another(segs, elapsed, seconds) -> bool:
    """Start another segment while it should end inside the window: every
    run then lasts about ``seconds``, however long a segment takes."""
    return not segs or elapsed + statistics.fmean(segs) <= seconds


def _span(on: bool, name: str):
    import jax

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()
