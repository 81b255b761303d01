"""The readings each limit of ``correct`` is set from, for one cell.

  python3 bench/readings.py --workload resnet50-paper.s16 --seeds 12 --base 1000 \
      --seconds 6 --control-seeds 3 --faults wrong_rung

For each seed it runs the cell as a benchmark run does (set-up, a short
window at the cell's load, the check of as many segments) as it stands;
on the first ``--control-seeds`` seeds also with the reference at one
precision step below the configuration's in the program's place (the
control), and once with each named fault of ``bench/faults.py`` planted.
It prints one JSON line per run with every compared number, and a summary:
per number the largest reading of the program (the lower reading) and the
smallest of the control and of each fault.  The benchmark's own runs never
run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base", type=int, default=0, help="first seed")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="", help="comma-separated names in bench/faults.py")
    args = ap.parse_args(argv)

    from bench import faults, harness
    from bench.run import configure_cache

    bench = harness.load_json("BENCHMARK.json")
    _, conf, traffic = harness.cell_files(bench, args.workload)
    import jax

    configure_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"readings: needs a TPU; JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    planted = [f for f in args.faults.split(",") if f]
    runs = []
    for i in range(args.seeds):
        seed = args.base + i
        modes = ["program"] + (["control"] + planted if i < args.control_seeds else [])
        for mode in modes:
            out = harness.run_cell(conf, traffic, seed=seed, seconds=args.seconds, trace=False,
                                   t_process_start=time.perf_counter(), limits=conf["limits"],
                                   control=mode == "control", fault=faults.FAULTS.get(mode))
            row = {"seed": seed, "mode": mode, "segments": out["segments"],
                   "counts": out["counts"], "setup_s": out["setup_s"],
                   "reference_setup_s": out["reference_setup_s"],
                   **{k: v for k, v, _ in out["check"]}}
            runs.append(row)
            print(json.dumps(row), flush=True)
    summary = {}
    for k, _, _ in out["check"]:
        summary[k] = {"lower": max(r[k] for r in runs if r["mode"] == "program")}
        for mode in ({r["mode"] for r in runs} - {"program"}):
            summary[k][mode] = min(r[k] for r in runs if r["mode"] == mode)
    print(json.dumps({"summary": summary, "device": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
