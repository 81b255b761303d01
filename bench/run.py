"""Run one benchmark cell on the accelerator this process finds.

  python3 bench/run.py --workload resnet50-paper.s16 --seed 7 --seconds 30 --trace 0

``BENCHMARK.json`` names the cell's configuration file and traffic mix, and
the metrics it reports: with ``--trace 0`` its end-to-end metrics, with
``--trace 1`` its per-layer metrics (each read by ``bench/metrics/<name>.py``
from the profiler trace, the program's phase timers or its counters).  The
last line of standard output is one JSON object; the numbers the check
compared, each beside its limit, come last there and on standard error.
Off a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.  Compiled programs persist in ``.jax_cache`` in the
checkout, so only the first run of a cell there compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def configure_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it however quickly it compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def metrics_for(bench, cell, section):
    """The ``section`` metrics that apply to ``cell``: name -> unit."""
    return {m["name"]: m["unit"] for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    bench = harness.load_json("BENCHMARK.json")
    cell, conf, traffic = harness.cell_files(bench, args.workload)

    import jax

    configure_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr)
        return 1

    out = harness.run_cell(conf, traffic, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_process_start=T_START,
                           limits=conf["limits"], device=dev)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        from bench import trace as tr

        red = tr.reduce_file(tr.find_xplane(out["trace_dir"]))
        shutil.rmtree(out["trace_dir"], ignore_errors=True)
        ctx = {"out": out, "trace": red, "conf": conf, "traffic": traffic,
               "device_kind": dev.device_kind}
        metrics = {}
        for name, unit in metrics_for(bench, cell["name"], "per_layer").items():
            value = importlib.import_module(f"bench.metrics.{name}").read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": [list(x) for x in red.top_ops()],
                               "idle_gaps": [list(x) for x in red.top_idle()]}
    else:
        metrics = {name: {"value": out[name], "unit": unit}
                   for name, unit in metrics_for(bench, cell["name"], "end_to_end").items()}
    result["metrics"] = metrics
    result["device"] = device
    result["check"] = {name: {"value": v, "limit": lim} for name, v, lim in out["check"]}
    q = statistics.quantiles(out["segment_s"], n=4) if out["segments"] > 1 else [0.0] * 3
    print(f"bench: segment seconds quartiles {q[0]:.4f} {q[1]:.4f} {q[2]:.4f}, "
          f"min {min(out['segment_s']):.4f}, max {max(out['segment_s']):.4f}", file=sys.stderr)
    print(f"bench: {out['segments']} segments in {out['window_s']:.3f} s, "
          f"{out['window_compile_s']:.3f} s compiling in the window, "
          f"set-up {out['setup_s']:.3f} s of which compiling {out['compile_s']:.3f} s, "
          f"the reference's labels and calibration {out['reference_setup_s']:.3f} s apart",
          file=sys.stderr)
    for name, v, lim in out["check"]:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
