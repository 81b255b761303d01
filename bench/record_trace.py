"""Where a cell's served path spends a segment, by the program's own spans.

  python3 bench/record_trace.py --workload resnet50-paper.s16 --seed 7 --segments 4

Sets the cell up as ``bench/run.py`` does, then times ``--segments``
segments three ways, each a fresh server as in the window: without
telemetry (the measured path), under ``Telemetry(profile=True)`` with no
profiler session (spans opened, nothing recording them; their phase
totals give the untraced segment's host breakdown), and under
``Telemetry(profile=True)`` inside a profiler trace set as for the traced
window.  The traced segments are reduced by ``bench/program_trace.py``: the
seconds each ``bench.*``/``repro.*`` span was open, device idle by innermost
span and inside the program's spans, and the scan program's op self time by
``round.<stage>``.  The last line of standard output is one JSON object.

``--config`` and ``--traffic`` take other files than the cell's (a small
configuration for a recorded test fixture); ``--keep DIR`` writes the trace
(``trace.xplane.pb.xz``) and the compiled round's text (``round.hlo.txt.xz``)
there.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import lzma  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
XZ = 9 | lzma.PRESET_EXTREME  # a recorded trace is kept as a test fixture


def quartiles(xs):
    return statistics.quantiles(xs, n=4) if len(xs) > 1 else [xs[0]] * 3


def timed_segments(conf, traffic, system, pool, n, telemetry):
    """Wall seconds of ``n`` segments, each on a fresh server."""
    from bench import harness

    S = int(traffic["streams"])
    capture = harness.Capture(system.fast, system.slow)
    out = []
    for k in range(n):
        srv = harness.make_server(conf, S, capture, system.platt, telemetry())
        t0 = time.perf_counter()
        srv.process_streams(*pool[k % len(pool)])
        out.append(time.perf_counter() - t0)
    return out


def summary(red, smap, segments, rounds):
    from bench import program_trace as pt

    run = red.module_op_s.get("jit_run", {})
    stages = pt.stage_seconds(run, smap)
    run_s = sum(run.values())
    idle_ps = red.idle_in_s.get("bench.process_streams", 0.0)
    return {
        "window_s": red.window_s, "busy_s": red.busy_s, "segments": segments,
        "span_s": red.span_s,
        "idle_by_span": dict(sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])),
        "idle_in_program_s": red.idle_in_program_s,
        "idle_in_process_streams_s": idle_ps,
        "idle_in_process_streams_named_share": (
            red.idle_in_program_of.get("bench.process_streams", 0.0) / idle_ps
            if idle_ps else None),
        "jit_run_op_s": run_s,
        "jit_run_stage_s": dict(sorted(stages.items(), key=lambda kv: -kv[1])),
        "jit_run_scoped_share": (run_s - stages.get("", 0.0)) / run_s if run_s else None,
        "plan_device_ms": stages.get("round.plan", 0.0) / rounds * 1e3,
        "bridge_idle_ms": red.idle_in_program_s / segments * 1e3,
        "module_s": {m: sum(ops.values()) for m, ops in red.module_op_s.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--segments", type=int, default=4)
    ap.add_argument("--config", help="a configuration file in place of the cell's")
    ap.add_argument("--traffic", help="a traffic file in place of the cell's")
    ap.add_argument("--keep", help="directory to keep the trace and the round's text in")
    args = ap.parse_args(argv)

    import importlib

    import jax

    from bench import harness
    from bench import program_trace as pt
    from bench.clock import Aside
    from bench.run import configure_cache
    from bench.trace import find_xplane
    from repro.obs import PhaseProfiler, Telemetry

    _, conf, traffic = harness.cell_files(harness.load_json("BENCHMARK.json"), args.workload)
    if args.config:
        conf = json.load(open(args.config))
    if args.traffic:
        traffic = json.load(open(args.traffic))
    configure_cache()
    system = importlib.import_module(f"bench.gen.{conf['tiers']}").build(
        conf, traffic, args.seed, Aside())
    pool = [system.segment(i) for i in range(int(traffic["pool"]))]
    n = args.segments
    prof = PhaseProfiler()
    plain = lambda: None  # noqa: E731
    profiled = lambda: Telemetry(record=False, profile=True, profiler=prof)  # noqa: E731
    timed_segments(conf, traffic, system, pool, int(traffic["warmup_segments"]), profiled)
    jax.effects_barrier()
    setup_s = time.perf_counter() - T_START
    prof.reset()
    times = {"plain": timed_segments(conf, traffic, system, pool, n, plain),
             "profiled": timed_segments(conf, traffic, system, pool, n, profiled)}
    d = tempfile.mkdtemp(prefix="record_trace_")
    try:
        text, times["traced"] = pt.trace_segments(conf, traffic, system, pool, n, d)
        xplane = find_xplane(d)
        red = pt.reduce_file(xplane)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            with open(xplane, "rb") as src, lzma.open(
                    os.path.join(args.keep, "trace.xplane.pb.xz"), "wb", preset=XZ) as dst:
                shutil.copyfileobj(src, dst)
            with lzma.open(os.path.join(args.keep, "round.hlo.txt.xz"), "wt", preset=XZ) as f:
                f.write(text)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    scoped, one = pt.scoped_instructions(text)
    rounds = n * (int(traffic["frames"]) // int(conf["batch_size"]))
    result = {
        "workload": args.workload, "seed": args.seed, "setup_s": setup_s,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "segment_s": times,
        "segment_quartiles_s": {k: quartiles(v) for k, v in times.items()},
        "profiled_phases": prof.summarize(),
        "scoped_instructions": scoped, "scoped_to_one_stage": one,
        "trace": summary(red, pt.scope_map(text), n, rounds),
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
