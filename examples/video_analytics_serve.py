"""End-to-end driver: CBO video-analytics serving (the paper's system).

Streams synthetic video through the CascadeServer: fast int8 tier answers
everything instantly; the CBO controller (Algorithm 1) adaptively escalates
low-confidence frames over a bandwidth-limited uplink; deadline-missed
escalations fall back to the fast answer (straggler mitigation).

  PYTHONPATH=src:benchmarks python examples/video_analytics_serve.py [--bw 5]
"""
import argparse
import sys

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bw", type=float, default=5.0, help="uplink Mbps")
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--latency", type=float, default=0.1)
    ap.add_argument("--frames", type=int, default=480)
    ap.add_argument("--policy", default="cbo",
                    help="offload policy registry name (see docs/policies.md)")
    args = ap.parse_args()

    import os

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import FAST_CFG, RESOLUTIONS, SLOW_CFG, build_stack

    from repro.core.netsim import Uplink, mbps
    from repro.models import api
    from repro.models.transformer import ParallelPlan
    from repro.serving.engine import CascadeServer, ServeConfig
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    stack = build_stack()
    fh = api.build(FAST_CFG, ParallelPlan(remat=False))
    sh = api.build(SLOW_CFG, ParallelPlan(remat=False))

    cfg = ServeConfig(
        frame_rate=args.fps,
        resolutions=RESOLUTIONS,
        acc_server=stack.acc_server_by_res,
    )
    uplink = Uplink(bandwidth_bps=mbps(args.bw), latency=args.latency, server_time=cfg.server_time)
    server = CascadeServer(
        cfg,
        fast_forward=lambda x: fh.forward(stack.fast_params, x),
        slow_forward=lambda x: sh.forward(stack.slow_params, x),
        calibrate=stack.platt,
        uplink=uplink,
        policy=args.policy,
    )
    frames = stack.test["frames"][: args.frames]
    labels = stack.test["labels"][: args.frames]
    metrics = server.process_stream(frames, labels)
    print(f"\n=== {args.policy} serving @ {args.bw} Mbps, {args.fps} fps, "
          f"L={args.latency*1e3:.0f} ms ===")
    for k, v in metrics.summary().items():
        print(f"  {k:22s} {v}")
    print(f"  (fast tier alone: {stack.acc_fast:.3f}; slow tier ceiling: {stack.acc_slow:.3f})")


if __name__ == "__main__":
    main()
