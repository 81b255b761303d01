"""Paper Figs. 11/12/13: accuracy vs bandwidth / frame rate / latency for all
seven approaches."""
from __future__ import annotations

import json

import numpy as np

from benchmarks.approaches import APPROACHES, NetCfg, build_trace
from benchmarks.common import build_stack, out_path


def _sweep(trace, cfgs: list[NetCfg], xkey: str) -> list[dict]:
    rows = []
    for net in cfgs:
        row = {xkey: getattr(net, xkey if xkey != "bandwidth" else "bandwidth_mbps")}
        for name, fn in APPROACHES.items():
            row[name] = round(fn(trace, net), 4)
        rows.append(row)
        print("bench_network," + ",".join(f"{k}={v}" for k, v in row.items()), flush=True)
    return rows


def run() -> dict:
    stack = build_stack()
    trace = build_trace(stack)

    fig11 = _sweep(trace, [NetCfg(bandwidth_mbps=b) for b in (0.25, 0.5, 1, 2, 5, 10, 20, 40)], "bandwidth")
    fig12 = _sweep(trace, [NetCfg(frame_rate=f) for f in (5, 10, 15, 20, 25, 30)], "frame_rate")
    fig13 = _sweep(trace, [NetCfg(latency=l) for l in (0.0, 0.05, 0.1, 0.15, 0.18)], "latency")

    out = {"fig11_bandwidth": fig11, "fig12_frame_rate": fig12, "fig13_latency": fig13}
    with open(out_path("fig11_12_13_network.json"), "w") as f:
        json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
