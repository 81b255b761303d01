"""Multi-stream CBO serving: aggregate accuracy / offload / deadline-miss vs
number of concurrent streams sharing one uplink.

Sweeps N ∈ {1, 4, 16, 64, 256, 1024} client streams through
``MultiStreamServer`` on a fixed uplink, so per-stream bandwidth shrinks
as 1/N and the contention / fairness regime opens up. The N=1 row is
cross-checked against the single-stream ``CascadeServer`` on the identical
workload (they must agree within tie-breaking noise — that equivalence is
the refactor's regression anchor).  ``--churn`` adds a dynamic-fleet
scenario at each N: half the streams join mid-run with ragged lifetimes
(``ArrivalSchedule.churn``) — the regime the batched ``FleetRunner``
control plane exists for.

Default stack is a tiny synthetic two-tier pair (runs in seconds, no
training); ``--stack models`` uses the trained int4/fp stack from
``benchmarks.common`` like the other paper benchmarks.

``--cells`` / ``--replicas`` / ``--placement`` rerun the sweep on an edge
fabric (``src/repro/net/``) instead of the legacy single uplink — see
``bench_fabric.py`` for the dedicated topology sweep.

  PYTHONPATH=src:benchmarks python benchmarks/bench_multistream.py
  PYTHONPATH=src:benchmarks python benchmarks/bench_multistream.py --streams 64,256,1024 --churn
  PYTHONPATH=src:benchmarks python benchmarks/bench_multistream.py --bw 0.5 --scheduler fifo
  PYTHONPATH=src:benchmarks python benchmarks/bench_multistream.py --cells 4 --replicas 4
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

STREAM_COUNTS = (1, 4, 16, 64, 256, 1024)


# synthetic stack: planted-signal images, weak fast tier, oracle-ish slow tier
# (canonical definition shared with tests — repro/serving/synthetic.py)
from repro.serving.synthetic import synthetic_streams, synthetic_tiers  # noqa: E402


def synthetic_cfg(args) -> "ServeConfig":
    from repro.core.netsim import png_size_model
    from repro.serving import ServeConfig

    # scale the PNG size model so the 8-px synthetic frames carry the same
    # bytes a full 224-px upload would — otherwise payloads are so small the
    # shared uplink never contends and the sweep is vacuous
    return ServeConfig(
        deadline=args.deadline, frame_rate=args.fps, batch_size=16,
        resolutions=(4, 8), acc_server=(0.9, 0.99),
        size_of=lambda r: png_size_model(r, base_res=16),
    )


def model_setup(args):
    from benchmarks.common import FAST_CFG, RESOLUTIONS, SLOW_CFG, build_stack
    from repro.models import api
    from repro.models.transformer import ParallelPlan
    from repro.serving import ServeConfig

    stack = build_stack()
    fh = api.build(FAST_CFG, ParallelPlan(remat=False))
    sh = api.build(SLOW_CFG, ParallelPlan(remat=False))
    cfg = ServeConfig(deadline=args.deadline, frame_rate=args.fps,
                      resolutions=RESOLUTIONS, acc_server=stack.acc_server_by_res)
    fast = lambda x: fh.forward(stack.fast_params, x)
    slow = lambda x: sh.forward(stack.slow_params, x)

    def streams(n_streams, n_frames):
        frames, labels = stack.test["frames"], stack.test["labels"]
        idx = (np.arange(n_streams)[:, None] * 131 + np.arange(n_frames)[None, :]) % len(labels)
        return frames[idx], labels[idx]

    return cfg, fast, slow, stack.platt, streams


def churn_schedule(S, n_frames, cfg, seed=0):
    """Half the fleet serves the whole run; the rest join mid-run with
    ragged lifetimes (joins staggered over the first half of the run)."""
    from repro.serving import ArrivalSchedule

    rng = np.random.default_rng(seed)
    even = np.arange(S) % 2 == 0
    join = np.where(even, 0, rng.integers(0, max(n_frames // 2, 1), size=S))
    ragged = np.minimum(n_frames - join,
                        rng.integers(max(n_frames // 4, 1), n_frames + 1, size=S))
    length = np.where(even, n_frames, ragged)
    return ArrivalSchedule.churn(S, n_frames, cfg.frame_rate, cfg.deadline,
                                 join=join, length=length)


def run(args=None) -> dict:
    from repro.core.netsim import Uplink, mbps
    from repro.serving import CascadeServer, FairScheduler, MultiStreamServer

    if args is None:
        args = parse_args([])

    if args.stack == "models":
        cfg, fast, slow, calibrate, make_streams = model_setup(args)
    else:
        cfg = synthetic_cfg(args)
        fast, slow, calibrate = synthetic_tiers()
        make_streams = lambda S, N: synthetic_streams(S, N, seed=args.seed)

    def fresh_uplink():
        return Uplink(bandwidth_bps=mbps(args.bw), latency=args.latency,
                      server_time=cfg.server_time, jitter=args.jitter, seed=args.seed)

    def fresh_fabric(S):
        """None when the topology is degenerate (legacy uplink path keeps
        its exact floats); an EdgeFabric otherwise."""
        if args.cells == 1 and args.replicas == 1:
            return None
        from repro.net import EdgeFabric

        return EdgeFabric.build(
            n_streams=S, n_cells=args.cells, n_replicas=args.replicas,
            bandwidth_bps=mbps(args.bw), latency=args.latency,
            server_time=cfg.server_time, placement=args.placement,
            jitter=args.jitter, seed=args.seed, serial_replicas=args.replicas > 1)

    rows = []
    single_row = None
    for S in args.streams:
        frames, labels = make_streams(S, args.frames)
        fab = fresh_fabric(S)
        srv = MultiStreamServer(cfg, fast, slow, calibrate,
                                fresh_uplink() if fab is None else None, n_streams=S,
                                scheduler=FairScheduler(args.scheduler), fabric=fab)
        m = srv.process_streams(frames, labels)
        row = {"n_streams": S, **m.summary()}
        rows.append(row)
        print("bench_multistream," + ",".join(f"{k}={v}" for k, v in row.items()), flush=True)

        if S == 1:  # cross-check: the old single-stream engine, same workload
            ref = CascadeServer(cfg, fast, slow, calibrate, fresh_uplink())
            mr = ref.process_stream(frames[0], labels[0])
            single_row = mr.summary()
            delta = abs(single_row["accuracy"] - row["accuracy"])
            print(f"bench_multistream,singlestream_ref_accuracy={single_row['accuracy']},"
                  f"delta={round(delta, 4)}", flush=True)

        if args.churn and S > 1:  # dynamic fleet: staggered join/leave
            sched = churn_schedule(S, frames.shape[1], cfg, seed=args.seed)
            fab = fresh_fabric(S)
            srv = MultiStreamServer(cfg, fast, slow, calibrate,
                                    fresh_uplink() if fab is None else None, n_streams=S,
                                    scheduler=FairScheduler(args.scheduler), fabric=fab)
            mc = srv.process_streams(frames, labels, schedule=sched)
            crow = {"n_streams": S, "scenario": "churn",
                    "served_frac": round(mc.n_frames / labels.size, 4), **mc.summary()}
            rows.append(crow)
            print("bench_multistream," + ",".join(f"{k}={v}" for k, v in crow.items()),
                  flush=True)

    out = {"config": {"bw_mbps": args.bw, "latency": args.latency, "fps": args.fps,
                      "deadline": args.deadline, "frames": args.frames,
                      "scheduler": args.scheduler, "stack": args.stack,
                      "cells": args.cells, "replicas": args.replicas,
                      "placement": args.placement},
           "sweep": rows, "single_stream_ref": single_row}
    from benchmarks.common import emit_bench_json

    emit_bench_json("BENCH_multistream.json", out, mirror="multistream_sweep.json")
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--streams", type=lambda s: tuple(int(x) for x in s.split(",")),
                    default=STREAM_COUNTS, help="comma-separated stream counts")
    ap.add_argument("--frames", type=int, default=256, help="frames per stream")
    ap.add_argument("--bw", type=float, default=2.0, help="shared uplink Mbps")
    ap.add_argument("--latency", type=float, default=0.05)
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--deadline", type=float, default=0.2)
    ap.add_argument("--jitter", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheduler", choices=("round_robin", "fifo"), default="round_robin")
    ap.add_argument("--stack", choices=("synthetic", "models"), default="synthetic")
    ap.add_argument("--churn", action="store_true",
                    help="also run a dynamic-fleet scenario per N (staggered "
                         "join/leave, ragged stream lifetimes)")
    ap.add_argument("--cells", type=int, default=1,
                    help="radio cells (edge fabric; 1 = legacy single uplink)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="slow-tier replicas (edge fabric; 1 = legacy fixed delay)")
    ap.add_argument("--placement", choices=("round_robin", "jsq", "least_land"),
                    default="round_robin")
    return ap.parse_args(argv)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(parse_args())
