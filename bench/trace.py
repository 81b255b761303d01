"""Reduce a profiler trace (``.xplane.pb``) to device busy time, device time
per compiled program, and idle gaps named by the benchmark's host spans.

Device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops`` line
holds one event per operation run and their ``XLA Modules`` line one event
per compiled program run (named ``jit_<function>(<id>)``).  Host spans are
the ``bench.*`` ``TraceAnnotation`` events on the host plane; ``bench.window``
bounds the traced window.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_ID = re.compile(r"\(\d+\)$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def op_name(text: str) -> str:
    """An op event's HLO instruction name (TPU traces name ops by their
    whole instruction text)."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclass
class Reduction:
    window_s: float = 0.0  # length of the traced window
    busy_s: float = 0.0  # union of op intervals in the window, mean over devices
    n_devices: int = 0
    module_s: dict = field(default_factory=dict)  # program name -> device s (all devices)
    module_calls: dict = field(default_factory=dict)  # program name -> runs
    op_s: dict = field(default_factory=dict)  # op name -> device self s
    idle_by_span: dict = field(default_factory=dict)  # host span -> idle device s

    def top_ops(self, n=10):
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_idle(self, n=10):
        return sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _self_times(evs):
    """(name, self seconds) per op event: an op that encloses others (a
    loop, a call) keeps only the time no enclosed op covers."""
    out, stack = [], []  # stack of [end, name, self ns]
    for s, e, name in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _, n, t = stack.pop()
            out.append((n, t * 1e-9))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out.extend((n, t * 1e-9) for _, n, t in stack)
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_planes(planes) -> Reduction:
    """``planes``: objects with ``name`` and ``lines``, lines with ``name``
    and ``events``, events with ``name``, ``start_ns`` and ``duration_ns``
    (``jax.profiler.ProfileData``'s shape)."""
    spans, devices = [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    red = Reduction(n_devices=len(devices))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows or not devices:
        return red
    lo, hi = windows[0]
    red.window_s = (hi - lo) * 1e-9
    inner = sorted(((n, s, e) for n, s, e in spans if n != WINDOW_SPAN),
                   key=lambda x: x[2] - x[1])  # innermost (shortest) first
    busy_total = 0.0
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
                       for ev in line.events
                       if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi]
                ops.extend((s, e) for s, e, _ in evs)
                for name, secs in _self_times(evs):
                    red.op_s[name] = red.op_s.get(name, 0.0) + secs
            elif line.name == "XLA Modules":
                for ev in line.events:
                    if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi:
                        name = MODULE_ID.sub("", ev.name)
                        red.module_s[name] = red.module_s.get(name, 0.0) + ev.duration_ns * 1e-9
                        red.module_calls[name] = red.module_calls.get(name, 0) + 1
        busy = _union(_clip(ops, lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        # idle gaps inside the window, each named by the innermost host
        # span that covers its midpoint
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            name = next((n for n, s, e in inner if s <= mid < e), "outside the bench spans")
            red.idle_by_span[name] = red.idle_by_span.get(name, 0.0) + (g1 - g0) * 1e-9
    red.busy_s = busy_total / len(devices)
    red.idle_by_span = {k: v / len(devices) for k, v in red.idle_by_span.items()}
    return red


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)
