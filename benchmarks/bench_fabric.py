"""Edge fabric: where does the fleet collapse, and how do cells/replicas move it?

The single-uplink sweeps (``bench_multistream``) show the N=64+ collapse:
per-stream offloads starve once one serial link and one implicit server
saturate.  This bench puts the same fleet behind an ``EdgeFabric``
(``src/repro/net/``) and sweeps the topology instead of the fleet:

  * **replica sweep** — S fixed (default 256), slow tier sharded across
    K ∈ {1, 2, 4, 8} serial replicas: the *contention-collapse point* —
    the smallest fleet size whose deadline-miss fraction crosses the
    collapse threshold — moves up monotonically with K;
  * **cell sweep** — streams partitioned across C ∈ {1, 2, 4} cells (one
    serial uplink each, same per-cell rate): aggregate radio capacity
    scales with C and the collapse point moves the same way;
  * **placement column** — round_robin / jsq / least_land at the largest
    sweep point, showing queue-aware placement's margin on tail latency.

``--smoke`` is the CI gate: asserts (1) the degenerate fabric (1 cell,
1 replica, constant bandwidth) reproduces ``tests/data/
multistream_snapshot.json`` bit-for-bit through the fabric code path, and
(2) batched ``Placement.assign`` equals the looped per-row reference for
every policy.

  PYTHONPATH=src:benchmarks python benchmarks/bench_fabric.py
  PYTHONPATH=src:benchmarks python benchmarks/bench_fabric.py --smoke
  PYTHONPATH=src:benchmarks python benchmarks/bench_fabric.py --replicas 1,4 --cells 1,2
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.serving.synthetic import synthetic_streams, synthetic_tiers  # noqa: E402

REPLICA_COUNTS = (1, 2, 4, 8)
CELL_COUNTS = (1, 2, 4)
FLEET_SIZES = (16, 32, 64, 128, 256)
COLLAPSE_MISS_FRAC = 0.05  # a fleet has collapsed when >5% of frames miss


def synthetic_cfg(args):
    from repro.core.netsim import png_size_model
    from repro.serving import ServeConfig

    # same scaling as bench_multistream: make the 8-px synthetic frames carry
    # full-frame bytes so the shared resources actually contend
    return ServeConfig(
        deadline=args.deadline, frame_rate=args.fps, batch_size=16,
        resolutions=(4, 8), acc_server=(0.9, 0.99),
        server_time=args.server_time,
        size_of=lambda r: png_size_model(r, base_res=16),
    )


def build_fabric(args, cfg, S, n_cells, n_replicas, placement="round_robin",
                 bw_mbps=None, het_replicas=False):
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric, ReplicaPool

    bw = mbps(args.bw if bw_mbps is None else bw_mbps)
    if not het_replicas:
        return EdgeFabric.build(
            n_streams=S, n_cells=n_cells, n_replicas=n_replicas,
            bandwidth_bps=bw, latency=args.latency,
            server_time=cfg.server_time, placement=placement,
            seed=args.seed, serial_replicas=True)
    # heterogeneous slow tier: service times spread geometrically over
    # [st/2, 2*st] — the regime where least_land and jsq actually differ
    st = cfg.server_time * np.geomspace(0.5, 2.0, n_replicas)
    ups = [Uplink(bandwidth_bps=bw, latency=args.latency,
                  server_time=cfg.server_time, seed=args.seed + c)
           for c in range(n_cells)]
    return EdgeFabric(ups, ReplicaPool(n_replicas, st), n_streams=S,
                      placement=placement)


def run_point(args, cfg, S, n_cells, n_replicas, placement="round_robin",
              bw_mbps=None, het_replicas=False):
    from repro.serving import FairScheduler, MultiStreamServer

    fast, slow, calibrate = synthetic_tiers()
    frames, labels = synthetic_streams(S, args.frames, seed=args.seed)
    fab = build_fabric(args, cfg, S, n_cells, n_replicas, placement,
                       bw_mbps=bw_mbps, het_replicas=het_replicas)
    srv = MultiStreamServer(cfg, fast, slow, calibrate, None, n_streams=S,
                            scheduler=FairScheduler(args.scheduler), fabric=fab)
    m = srv.process_streams(frames, labels)
    s = m.summary()
    return {
        "n_streams": S, "cells": n_cells, "replicas": n_replicas,
        "placement": placement,
        "accuracy": s["accuracy"], "offload_frac": s["offload_frac"],
        "deadline_miss_frac": s["deadline_miss_frac"],
        "p99_latency_ms": s["p99_latency_ms"],
        "offload_fairness": s["offload_fairness"],
        "replica_queued_s": round(float(fab.pool.queued_seconds.sum()), 2),
        "cell_queued_s": round(float(sum(c.uplink.queued_seconds for c in fab.cells)), 2),
    }


def collapse_point(rows):
    """Smallest fleet size whose miss fraction crosses the threshold
    (None = never collapsed within the sweep)."""
    for r in rows:
        if r["deadline_miss_frac"] > COLLAPSE_MISS_FRAC:
            return r["n_streams"]
    return None


def run(args=None) -> dict:
    if args is None:
        args = parse_args([])
    cfg = synthetic_cfg(args)

    out = {"config": {"bw_mbps": args.bw, "latency": args.latency, "fps": args.fps,
                      "deadline": args.deadline, "frames": args.frames,
                      "server_time": args.server_time, "scheduler": args.scheduler},
           "replica_sweep": [], "cell_sweep": [], "placement": []}

    # -- replica sweep: collapse point vs K (C fixed at 1) ----------------- #
    for K in args.replicas:
        rows = [run_point(args, cfg, S, 1, K) for S in args.fleets]
        cp = collapse_point(rows)
        out["replica_sweep"].append({"replicas": K, "collapse_at": cp, "rows": rows})
        for r in rows:
            print("bench_fabric,sweep=replica," +
                  ",".join(f"{k}={v}" for k, v in r.items()), flush=True)
        print(f"bench_fabric,replicas={K},collapse_at={cp}", flush=True)

    # -- cell sweep: collapse point vs C (K fixed at max sweep value, and a
    # lower per-cell rate so the *radio*, not the slow tier, binds) -------- #
    K = max(args.replicas)
    for C in args.cells:
        rows = [run_point(args, cfg, S, C, K, bw_mbps=args.cell_bw)
                for S in args.fleets]
        cp = collapse_point(rows)
        out["cell_sweep"].append({"cells": C, "collapse_at": cp, "rows": rows})
        for r in rows:
            print("bench_fabric,sweep=cell," +
                  ",".join(f"{k}={v}" for k, v in r.items()), flush=True)
        print(f"bench_fabric,cells={C},replicas={K},collapse_at={cp}", flush=True)

    # -- placement shoot-out: heterogeneous replicas at the hottest point -- #
    S = max(args.fleets)
    for pol in ("round_robin", "jsq", "least_land"):
        r = run_point(args, cfg, S, max(args.cells), K, placement=pol,
                      het_replicas=True)
        out["placement"].append(r)
        print("bench_fabric,sweep=placement," +
              ",".join(f"{k}={v}" for k, v in r.items()), flush=True)

    # monotonicity headline: more replicas never lowers successful offloads
    # at the largest fleet, and the collapse point never moves down
    heads = [next(r for r in e["rows"] if r["n_streams"] == max(args.fleets))
             for e in out["replica_sweep"]]
    out["monotone_offload_at_max_fleet"] = all(
        b["offload_frac"] >= a["offload_frac"] - 1e-9
        for a, b in zip(heads, heads[1:]))
    print("bench_fabric,monotone_offload_at_max_fleet="
          f"{out['monotone_offload_at_max_fleet']}", flush=True)

    from benchmarks.common import emit_bench_json

    # machine-readable CI name + legacy sweep filename
    emit_bench_json("BENCH_fabric.json", out, mirror="fabric_sweep.json")
    return out


# ---------------------------- smoke (CI gate) ------------------------------ #


def smoke() -> None:
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric, Placement, ReplicaPool, assign_looped
    from repro.serving import MultiStreamServer, ServeConfig

    # 1) degenerate fabric reproduces the recorded snapshot bit-for-bit
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "tests", "data", "multistream_snapshot.json")) as f:
        snapshot = json.load(f)
    fast, slow, cal = synthetic_tiers()
    cfg = ServeConfig(resolutions=(4, 8), acc_server=(0.7, 0.99), batch_size=16,
                      frame_rate=30.0, deadline=0.2)
    imgs, labels = synthetic_streams(4, 64)
    up = Uplink(bandwidth_bps=mbps(50.0), latency=0.05, server_time=cfg.server_time)
    fab = EdgeFabric.degenerate(up, n_streams=4)
    agg = MultiStreamServer(cfg, fast, slow, cal, None, n_streams=4,
                            fabric=fab).process_streams(imgs, labels)
    for m, ref in zip(agg.per_stream, snapshot["per_stream"]):
        assert m.accuracy == ref["accuracy"], (m.accuracy, ref["accuracy"])
        assert m.offload_frac == ref["offload_frac"]
        assert m.deadline_miss_frac == ref["deadline_miss_frac"]
    assert agg.n_offloaded == snapshot["n_offloaded"]
    print("bench_fabric,smoke=degenerate_snapshot,status=ok", flush=True)

    # 2) batched placement == looped reference, every policy
    rng = np.random.default_rng(0)
    for pol in ("round_robin", "jsq", "least_land"):
        for trial in range(10):
            K = int(rng.integers(1, 6))
            pool = ReplicaPool(K, rng.uniform(0.01, 0.2, K))
            pool.busy_until[:] = rng.uniform(0, 0.5, K)
            arrive = rng.uniform(0, 2, int(rng.integers(0, 40)))
            got = Placement(pol).assign(pool, arrive)
            want = assign_looped(pol, pool, arrive)
            assert np.array_equal(got, want), (pol, trial)
    print("bench_fabric,smoke=placement_equivalence,status=ok", flush=True)


def parse_args(argv=None):
    csv = lambda s: tuple(int(x) for x in s.split(","))
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fleets", type=csv, default=FLEET_SIZES,
                    help="comma-separated fleet sizes per sweep point")
    ap.add_argument("--replicas", type=csv, default=REPLICA_COUNTS)
    ap.add_argument("--cells", type=csv, default=CELL_COUNTS)
    ap.add_argument("--frames", type=int, default=128, help="frames per stream")
    ap.add_argument("--bw", type=float, default=80.0,
                    help="per-cell uplink Mbps (replica sweep: radio "
                         "overprovisioned so the slow tier binds)")
    ap.add_argument("--cell-bw", type=float, default=4.0,
                    help="per-cell uplink Mbps for the cell sweep (radio "
                         "scarce so the cell count binds)")
    ap.add_argument("--latency", type=float, default=0.05)
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--deadline", type=float, default=0.2)
    ap.add_argument("--server-time", type=float, default=0.020,
                    help="per-replica service time (s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheduler", choices=("round_robin", "fifo"), default="round_robin")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: degenerate==snapshot + placement equivalence")
    return ap.parse_args(argv)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = parse_args()
    if args.smoke:
        smoke()
    else:
        run(args)
