"""The program's own spans and name scopes in a device trace.

The served path names its host work with ``repro.*`` spans (the program's
``PhaseProfiler``, under ``Telemetry(profile=True)``) and each stage of the
compiled round with a ``round.<stage>`` name scope (``serving/engine_jax.py``).
This module reduces a profiler trace against both:

* ``reduce_planes``: host spans of the ``bench.`` and ``repro.`` kinds, the
  seconds each name was open (``span_s``), device idle named by the innermost
  span of either kind (``idle_by_span``), device idle inside the union of the
  ``repro.*`` spans (``idle_in_program_s``), and op self time per enclosing
  compiled program (``module_op_s``: the ``XLA Ops`` of the ``XLA Modules``
  event they run in, so that same-named ops of two programs stay apart).
* ``scope_map``: TPU op events carry no scope, only their HLO instruction
  name, so a compiled program's text maps each instruction to the name
  scope in its ``metadata={op_name=...}`` that matches a pattern: the
  round's ``round.<stage>`` (``ROUND_SCOPE``) by default, or any other,
  such as a model's own ``jax.named_scope`` inside a tier program.
* ``program_trace``: the trace that the readers of these spans and scopes
  need.  The traced window's own trace is reduced by ``bench/trace.py`` and
  deleted before the readers run, and that reduction keeps neither these
  spans nor op times per program; so after the window this traces
  ``trace_segments`` more segments of the same served path, the profiler
  set as for the window, and compiles the round once more (a cache hit) for
  its text.  ``program_texts`` gives that text beside the tier programs'
  (the tiers object's ``program_texts()``, ``bench/harness.py``, compiled
  only when a reader asks), and ``scope_seconds`` splits the tier
  programs' device time by a model's own name scopes.  A program that
  opens no ``repro.*`` spans gets no such trace.
"""
from __future__ import annotations

import bisect
import re
import time
from dataclasses import dataclass, field

from bench.trace import DEVICE_PLANE, MODULE_ID, WINDOW_SPAN, _clip, _self_times, _union, op_name

PREFIXES = ("bench.", "repro.")
PROGRAM_PREFIX = "repro."
ROUND_SCOPE = r"^round\.[A-Za-z_]+$"
MODULE_NAME = re.compile(r"^HloModule ([^\s,]+)")
INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*\bmetadata=\{[^}]*\bop_name="([^"]*)"')


@dataclass
class ProgramReduction:
    window_s: float = 0.0
    busy_s: float = 0.0  # union of op intervals in the window, mean over devices
    n_devices: int = 0
    span_s: dict = field(default_factory=dict)  # span name -> seconds open in the window
    idle_by_span: dict = field(default_factory=dict)  # innermost span -> idle device s
    idle_in_program_s: float = 0.0  # device idle inside the union of repro.* spans
    idle_in_s: dict = field(default_factory=dict)  # span name -> idle device s inside it
    # span name -> idle device s inside it and inside some repro.* span
    idle_in_program_of: dict = field(default_factory=dict)
    module_op_s: dict = field(default_factory=dict)  # program -> {op -> self s}


def _intersect(a, b):
    """The intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _seconds(intervals):
    return sum(e - s for s, e in intervals) * 1e-9


def reduce_planes(planes) -> ProgramReduction:
    """``planes`` as for ``bench.trace.reduce_planes``."""
    spans, devices = [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    red = ProgramReduction(n_devices=len(devices))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows or not devices:
        return red
    lo, hi = windows[0]
    red.window_s = (hi - lo) * 1e-9
    inner = sorted(((n, s, e) for n, s, e in spans if n != WINDOW_SPAN),
                   key=lambda x: x[2] - x[1])  # innermost (shortest) first
    by_name = {}
    for n, s, e in inner:
        by_name.setdefault(n, []).append((s, e))
    merged = {n: _union(_clip(ivs, lo, hi)) for n, ivs in by_name.items()}
    # a span name open twice at once (nested) counts its time once
    red.span_s = {n: _seconds(ivs) for n, ivs in merged.items()}
    program = _union([iv for n, ivs in merged.items() if n.startswith(PROGRAM_PREFIX)
                      for iv in ivs])
    busy_total = 0.0
    for plane in devices:
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
                       for ev in line.events
                       if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi]
            elif line.name == "XLA Modules":
                modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  MODULE_ID.sub("", ev.name)) for ev in line.events)
        starts = [s for s, _, _ in modules]
        per_module = {}
        for s, e, name in ops:
            i = bisect.bisect_right(starts, s) - 1
            mod = modules[i][2] if i >= 0 and s < modules[i][1] else ""
            per_module.setdefault(mod, []).append((s, e, name))
        for mod, evs in per_module.items():
            acc = red.module_op_s.setdefault(mod, {})
            for name, secs in _self_times(evs):
                acc[name] = acc.get(name, 0.0) + secs
        busy = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_total += _seconds(busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]
        for g0, g1 in gaps:
            mid = 0.5 * (g0 + g1)
            name = next((n for n, s, e in inner if s <= mid < e), "outside the spans")
            red.idle_by_span[name] = red.idle_by_span.get(name, 0.0) + (g1 - g0) * 1e-9
        idle_program = _intersect(gaps, program)
        red.idle_in_program_s += _seconds(idle_program)
        for n, ivs in merged.items():
            red.idle_in_s[n] = red.idle_in_s.get(n, 0.0) + _seconds(_intersect(gaps, ivs))
            red.idle_in_program_of[n] = (red.idle_in_program_of.get(n, 0.0)
                                         + _seconds(_intersect(idle_program, ivs)))
    nd = len(devices)
    red.busy_s = busy_total / nd
    red.idle_in_program_s /= nd
    for d in (red.idle_by_span, red.idle_in_s, red.idle_in_program_of):
        for k in d:
            d[k] /= nd
    return red


def reduce_file(path: str) -> ProgramReduction:
    """Reduce a ``.xplane.pb`` file, or its xz (``.xplane.pb.xz``)."""
    import lzma

    from jax.profiler import ProfileData

    if path.endswith(".xz"):
        with lzma.open(path, "rb") as f:
            return reduce_planes(ProfileData.from_serialized_xspace(f.read()).planes)
    return reduce_planes(ProfileData.from_file(path).planes)


def _instruction_scopes(text: str, pattern: str):
    """(instruction name, the scopes matching ``pattern`` that its
    ``op_name`` names) per instruction of a compiled module's text that
    carries an ``op_name``."""
    scope = re.compile(pattern)
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            yield m.group(1), {part for part in m.group(2).split("/") if scope.match(part)}


def scope_map(text: str, pattern: str = ROUND_SCOPE) -> dict:
    """``{instruction name: scope}`` for each instruction whose ``op_name``
    holds exactly one scope matching ``pattern`` (one path component, such
    as ``round.plan`` or ``swin.attn``)."""
    return {name: next(iter(sc)) for name, sc in _instruction_scopes(text, pattern)
            if len(sc) == 1}


def scoped_instructions(text: str) -> tuple[int, int]:
    """(instructions whose ``op_name`` names any stage of the round, those
    that name exactly one)."""
    stages = [len(st) for _, st in _instruction_scopes(text, ROUND_SCOPE) if st]
    return len(stages), stages.count(1)


def stage_seconds(op_s: dict, smap: dict) -> dict:
    """Op self seconds (one program's, from ``module_op_s``) summed by the
    scope ``smap`` gives each op; ops of no scope under ``""``."""
    out = {}
    for name, secs in op_s.items():
        stage = smap.get(name, "")
        out[stage] = out.get(stage, 0.0) + secs
    return out


def program_spans_on() -> bool:
    """Whether the program under test opens ``repro.*`` spans."""
    from repro.obs import profile

    return getattr(profile, "SPAN_PREFIX", None) == PROGRAM_PREFIX


def round_text(spec, params, inputs) -> str:
    """The compiled round's text for the ``spec``, ``params`` and stacked
    round ``inputs`` that ``engine_jax.simulate`` was called with."""
    from repro.serving import engine_jax as ej
    from repro.sharding.axes import current_mesh

    engine = ej._cached_engine(spec, current_mesh())
    return engine.lower(params, ej.init_carry(spec, params), inputs).compile().as_text()


def trace_segments(conf, traffic, system, pool, n_segments, trace_dir):
    """Trace ``n_segments`` segments of the served path (``pool``'s
    segments, cycled), each a fresh server
    under ``Telemetry(record=False, profile=True)`` inside the harness's
    ``bench.*`` spans, with the profiler set as for the traced window.
    Returns the compiled round's text and each segment's wall seconds."""
    import jax

    from bench import harness
    from repro.obs import Telemetry
    from repro.serving import engine_jax as ej

    S = int(traffic["streams"])
    capture = harness.Capture(system.fast, system.slow, trace=True)
    called, seconds = {}, []
    simulate = ej.simulate

    def recorded(spec, params, inputs, carry=None):
        called.update(spec=spec, params=params, inputs=inputs)
        return simulate(spec, params, inputs, carry)

    ej.simulate = recorded
    try:
        with capture.rounds():
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level, opts.python_tracer_level = 1, 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    for k in range(n_segments):
                        with jax.profiler.TraceAnnotation("bench.build_server"):
                            srv = harness.make_server(conf, S, capture, system.platt,
                                                      Telemetry(record=False, profile=True))
                        t0 = time.perf_counter()
                        with jax.profiler.TraceAnnotation("bench.process_streams"):
                            srv.process_streams(*pool[k % len(pool)])
                        seconds.append(time.perf_counter() - t0)
            finally:
                jax.profiler.stop_trace()
    finally:
        ej.simulate = simulate
    return round_text(called["spec"], called["params"], called["inputs"]), seconds


def program_trace(ctx):
    """``(ProgramReduction, the round's scope map, segments)`` of a trace
    taken after the window, held in ``ctx`` for every reader; None where
    the program opens no spans or the run holds no system to serve.  The
    round's compiled text goes to ``ctx["round_text"]`` (``program_texts``)."""
    if "program_trace" not in ctx:
        ctx["program_trace"] = None
        out = ctx["out"]
        if out.get("system") is not None and ctx.get("trace") is not None and program_spans_on():
            import shutil
            import tempfile

            from bench.trace import find_xplane

            traffic, system = ctx["traffic"], out["system"]
            n = int(traffic["trace_segments"])
            pool = [system.segment(i) for i in range(min(int(traffic["pool"]), n))]
            d = tempfile.mkdtemp(prefix="bench_program_trace_")
            try:
                text, _ = trace_segments(ctx["conf"], traffic, system, pool, n, d)
                red = reduce_file(find_xplane(d))
            finally:
                shutil.rmtree(d, ignore_errors=True)
            ctx["round_text"] = text
            ctx["program_trace"] = (red, scope_map(text, ROUND_SCOPE), n)
    return ctx["program_trace"]


def program_texts(ctx) -> dict:
    """``{program name: compiled text}`` of the programs ``program_trace``
    traced: the round's (``jit_run``) and, where the tiers object hands them
    over, the tier programs' at one round's input, compiled (a cache hit)
    only when a reader first asks; empty where there is no such trace.
    Each text is keyed by the name its ``HloModule`` line gives.  On the
    TPU two tier programs that compile alike (one model in both tiers) run
    as one executable, and the trace shows both tiers' ops under one of the
    two names, so ``module_op_s`` may hold ``jit_tier_fast`` alone;
    ``scope_seconds`` reads whichever is there."""
    if program_trace(ctx) is None:
        return {}
    if "program_texts" not in ctx:
        tiers = getattr(ctx["out"]["system"], "program_texts", None)
        texts = [ctx["round_text"], *(tiers().values() if tiers is not None else ())]
        ctx["program_texts"] = {MODULE_NAME.match(t).group(1): t for t in texts}
    return ctx["program_texts"]


def scope_seconds(ctx, pattern: str):
    """Device seconds by name scope (``pattern``, as for ``scope_map``) of
    the traced tier programs (``jit_tier_*``), each program's ops mapped by
    its own compiled text; ops of no scope under ``""``.  None where there
    is no trace or no tier program ran."""
    got = program_trace(ctx)
    if got is None:
        return None
    red = got[0]
    ran = {name: text for name, text in program_texts(ctx).items()
           if name.startswith("jit_tier_") and red.module_op_s.get(name)}
    if not ran:
        return None
    out = {}
    for name, text in ran.items():
        for scope, secs in stage_seconds(red.module_op_s[name], scope_map(text, pattern)).items():
            out[scope] = out.get(scope, 0.0) + secs
    return out
