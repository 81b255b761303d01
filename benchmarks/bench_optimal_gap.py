"""Paper Fig. 14: Optimal accuracy over (bandwidth x frame rate), and the
Optimal-minus-CBO gap (the paper's claim: ~zero almost everywhere)."""
from __future__ import annotations

import json

import numpy as np

from benchmarks.approaches import NetCfg, build_trace, run_cbo, run_optimal
from benchmarks.common import build_stack, out_path


def run() -> dict:
    stack = build_stack()
    trace = build_trace(stack, max_frames=720)
    bws = (1, 2, 5, 10, 20)
    fps = (10, 20, 30)
    grid = []
    gaps = []
    for b in bws:
        for f in fps:
            net = NetCfg(bandwidth_mbps=b, frame_rate=f)
            a_opt = run_optimal(trace, net)
            a_cbo = run_cbo(trace, net)
            gap = round(a_opt - a_cbo, 4)
            gaps.append(gap)
            grid.append({"bandwidth_mbps": b, "frame_rate": f,
                         "optimal": round(a_opt, 4), "cbo": round(a_cbo, 4), "gap": gap})
            print(f"bench_optimal_gap,bw={b},fps={f},opt={a_opt:.4f},cbo={a_cbo:.4f},gap={gap}", flush=True)
    out = {"grid": grid, "mean_gap": round(float(np.mean(gaps)), 4), "max_gap": round(float(np.max(gaps)), 4)}
    with open(out_path("fig14_optimal_gap.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
