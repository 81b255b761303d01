"""End-to-end driver: N mobile clients sharing one edge uplink (CBO at scale).

Each client runs the paper's fast-tier/offload loop; all of them contend for
the same uplink and edge server. The MultiStreamServer batches every
stream's fast-tier inference into one call per round, aggregates the
low-confidence frames of all streams into one slow-tier batch, and
schedules transfers with weighted fair queueing.

``--churn`` turns the lockstep replay into a dynamic fleet: half the
clients join mid-run with ragged lifetimes, exercising the batched
``FleetRunner`` control plane's admit/retire path.

``--cells`` / ``--replicas`` / ``--placement`` / ``--trace`` put the fleet
behind an edge fabric (``src/repro/net/``): clients partitioned across C
radio cells (one serial uplink each, optionally replaying a synthetic
LTE/WiFi bandwidth trace), escalations sharded across K slow-tier replica
queues.  The defaults (1 cell, 1 replica, no trace) reproduce the legacy
single-uplink pipeline exactly.

``--trace-out trace.json`` turns on the frame-lifecycle tracer
(``repro.obs``) and exports every offloaded frame's span tree — device
pass, offload window, cell queue, upload, replica queue, batched service
— as Chrome trace-event JSON; load it in ui.perfetto.dev or
chrome://tracing.

  PYTHONPATH=src:benchmarks python examples/multi_client_serve.py --streams 8 --bw 5
  PYTHONPATH=src python examples/multi_client_serve.py --streams 8 --synthetic --churn
  PYTHONPATH=src python examples/multi_client_serve.py --streams 16 --synthetic \\
      --cells 4 --replicas 2 --placement jsq --trace lte
"""
import argparse
import os
import sys

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=8, help="number of concurrent clients")
    ap.add_argument("--bw", type=float, default=5.0, help="shared uplink Mbps")
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--latency", type=float, default=0.1)
    ap.add_argument("--frames", type=int, default=240, help="frames per stream")
    ap.add_argument("--scheduler", choices=("round_robin", "fifo"), default="round_robin")
    ap.add_argument("--policy", default="cbo",
                    help="offload policy name, or 'name0,name1,...' cycled "
                         "across streams for a heterogeneous fleet")
    ap.add_argument("--synthetic", action="store_true",
                    help="tiny synthetic tiers (no training) instead of the trained stack")
    ap.add_argument("--churn", action="store_true",
                    help="dynamic fleet: half the clients join mid-run with "
                         "ragged lifetimes (staggered join/leave)")
    ap.add_argument("--cells", type=int, default=1,
                    help="radio cells (one serial uplink each; streams "
                         "partitioned round-robin)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="slow-tier replicas (per-replica serial queues)")
    ap.add_argument("--placement", choices=("round_robin", "jsq", "least_land"),
                    default="round_robin", help="escalation -> replica placement")
    ap.add_argument("--trace", choices=("none", "lte", "wifi", "regime"),
                    default="none", help="per-cell synthetic bandwidth trace "
                                         "(scaled to --bw as the mean rate)")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="record every offloaded frame's lifecycle "
                         "(queued/uploaded/placed/batched/served) and export "
                         "a Chrome trace-event JSON — open in ui.perfetto.dev "
                         "or chrome://tracing")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

    from repro.core.netsim import Uplink, mbps
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import FairScheduler, MultiStreamServer, ServeConfig

    enable_compile_cache()
    if args.synthetic:
        from benchmarks.bench_multistream import synthetic_cfg, synthetic_streams, synthetic_tiers

        cfg = synthetic_cfg(argparse.Namespace(deadline=0.2, fps=args.fps))
        fast, slow, calibrate = synthetic_tiers()
        frames, labels = synthetic_streams(args.streams, args.frames)
        acc_note = ""
    else:
        from benchmarks.common import FAST_CFG, RESOLUTIONS, SLOW_CFG, build_stack

        from repro.models import api
        from repro.models.transformer import ParallelPlan

        stack = build_stack()
        fh = api.build(FAST_CFG, ParallelPlan(remat=False))
        sh = api.build(SLOW_CFG, ParallelPlan(remat=False))
        cfg = ServeConfig(frame_rate=args.fps, resolutions=RESOLUTIONS,
                          acc_server=stack.acc_server_by_res)
        fast = lambda x: fh.forward(stack.fast_params, x)
        slow = lambda x: sh.forward(stack.slow_params, x)
        calibrate = stack.platt
        # deal each client a phase-shifted slice of the test video set
        all_f, all_l = stack.test["frames"], stack.test["labels"]
        idx = (np.arange(args.streams)[:, None] * 131 + np.arange(args.frames)[None, :]) % len(all_l)
        frames, labels = all_f[idx], all_l[idx]
        acc_note = f"  (fast tier alone: {stack.acc_fast:.3f}; slow ceiling: {stack.acc_slow:.3f})"

    uplink = Uplink(bandwidth_bps=mbps(args.bw), latency=args.latency, server_time=cfg.server_time)
    fabric = None
    if args.cells > 1 or args.replicas > 1 or args.trace != "none":
        from repro.net import EdgeFabric, lte_trace, regime_shift_trace, wifi_trace

        make_trace = {
            "none": lambda c: None,
            "lte": lambda c: lte_trace(120.0, mean_mbps=args.bw, seed=c),
            "wifi": lambda c: wifi_trace(120.0, good_mbps=args.bw, bad_mbps=args.bw / 8, seed=c),
            "regime": lambda c: regime_shift_trace((args.bw, args.bw / 8), period=10.0),
        }[args.trace]
        fabric = EdgeFabric.build(
            n_streams=args.streams, n_cells=args.cells, n_replicas=args.replicas,
            bandwidth_bps=mbps(args.bw), latency=args.latency,
            server_time=cfg.server_time, placement=args.placement,
            traces=[make_trace(c) for c in range(args.cells)],
            serial_replicas=args.replicas > 1)
    names = args.policy.split(",")
    policy = names[0] if len(names) == 1 else (lambda s: names[s % len(names)])
    telemetry = None
    if args.trace_out:
        from repro.obs import Telemetry

        telemetry = Telemetry(record=True, trace=True)
    server = MultiStreamServer(cfg, fast, slow, calibrate,
                               uplink if fabric is None else None,
                               n_streams=args.streams,
                               scheduler=FairScheduler(args.scheduler), policy=policy,
                               fabric=fabric, telemetry=telemetry)
    schedule = None
    if args.churn:
        from benchmarks.bench_multistream import churn_schedule

        schedule = churn_schedule(args.streams, frames.shape[1], cfg)
    metrics = server.process_streams(frames, labels, schedule=schedule)

    print(f"\n=== {args.policy} multi-client serving: {args.streams} streams @ "
          f"{args.bw} Mbps shared, {args.fps} fps, L={args.latency*1e3:.0f} ms, "
          f"{args.scheduler} ===")
    for k, v in metrics.summary().items():
        print(f"  {k:22s} {v}")
    if acc_note:
        print(acc_note)
    print("\n  per-stream:")
    for s, m in enumerate(metrics.per_stream):
        print(f"    stream {s:3d}: acc={m.accuracy:.3f} offload={m.offload_frac:.3f} "
              f"miss={m.deadline_miss_frac:.3f}")
    if telemetry is not None:
        path = telemetry.tracer.export_chrome_trace(args.trace_out)
        att = telemetry.tracer.miss_attribution()
        print(f"\n  frame-lifecycle trace: {telemetry.tracer.n_frames} offloads "
              f"-> {path}")
        print(f"  miss attribution: {att['misses']} misses "
              f"({att['radio']} radio-dominant, {att['slow_tier']} slow-tier)")


if __name__ == "__main__":
    main()
