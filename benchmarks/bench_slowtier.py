"""Continuous-batching slow tier: where does batching move the collapse point?

``bench_fabric`` shows the contention-collapse point — the smallest fleet
whose deadline-miss fraction crosses 5% — moving right with more replicas
and cells.  This bench holds the topology fixed and changes the *replica
service discipline* instead (``src/repro/slowtier/``): each replica runs
TGI-style continuous batching with an admission window, and the batch
cost follows a calibrated latency curve f(n) = base + per_item*n instead
of the paper's constant T^o.  Because the marginal item cost is far below
the flat service time, a replica that coalesces its queue into batches
serves a congested fleet at a multiple of its serial throughput — so the
collapse point moves right, further with a longer admission window:

  * **window sweep** — K ∈ {1, 2, 4} serial replicas × admission window
    ∈ {none, 0 ms, 5 ms, 20 ms} ("none" = the serial FlatService
    baseline), fleet sizes swept until collapse;
  * the headline assertion: at every K, every batching column's collapse
    point is >= the FlatService baseline's.

The batching curve defaults to ``LinearBatch(base, per_item)`` with
coefficients matched to the sweep's ``--server-time`` (f(1) ~= T^o, so
an idle fleet behaves like the paper's model and only congestion changes
anything).  ``--coeffs-from`` loads coefficients fitted by
``bench_kernels.py --batch-sweep`` (results/bench/BENCH_kernels.json)
instead — the calibration recipe in docs/network.md.

``--smoke`` is the CI gate, no sweeps: asserts (1) vectorized
``form_batches`` equals the one-request-at-a-time looped reference
bit-for-bit on seeded fuzz workloads, (2) a *degenerate* batching config
(FlatService, window=0, cap=1) drives ``MultiStreamServer`` to the exact
per-stream metrics of the plain serial ``ReplicaPool``, and (3) that
degenerate path still reproduces ``tests/data/fabric_snapshot.json``
bit-for-bit.

  PYTHONPATH=src:benchmarks python benchmarks/bench_slowtier.py
  PYTHONPATH=src:benchmarks python benchmarks/bench_slowtier.py --smoke
  PYTHONPATH=src:benchmarks python benchmarks/bench_slowtier.py --replicas 1,2 --windows 0,0.02
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

REPLICA_COUNTS = (1, 2, 4)
WINDOWS_S = (0.0, 0.005, 0.020)
FLEET_SIZES = (8, 16, 32, 64, 128, 256)
COLLAPSE_MISS_FRAC = 0.05


def synthetic_cfg(args):
    from repro.core.netsim import png_size_model
    from repro.serving import ServeConfig

    return ServeConfig(
        deadline=args.deadline, frame_rate=args.fps, batch_size=16,
        resolutions=(4, 8), acc_server=(0.9, 0.99),
        server_time=args.server_time,
        size_of=lambda r: png_size_model(r, base_res=16),
    )


def latency_model(args):
    """The batching curve for the sweep: calibrated coefficients when
    ``--coeffs-from`` points at a ``bench_kernels --batch-sweep`` artifact,
    else a LinearBatch anchored at f(1) ~= T^o."""
    from repro.slowtier import LinearBatch, model_from_coeffs

    if args.coeffs_from:
        with open(args.coeffs_from) as f:
            fit = json.load(f)["batch_fit"]
        kind, coeffs = fit["kind"], fit["coeffs"]
        # rescale the kernel-time curve so f(1) lands on the sweep's T^o:
        # the *shape* (marginal item cost vs fixed cost) is the calibrated
        # part; the absolute scale belongs to the simulated server
        m = model_from_coeffs(kind, coeffs)
        scale = args.server_time / float(m.batch_latency(1))
        m = model_from_coeffs(kind, tuple(c * scale for c in coeffs))
        return m, {"kind": kind, "coeffs": [float(c) for c in coeffs],
                   "scale": scale, "source": args.coeffs_from}
    base = args.server_time * 0.8
    per_item = args.server_time * 0.2
    return (LinearBatch(base, per_item),
            {"kind": "linear", "coeffs": [base, per_item], "source": "default"})


def build_fabric(args, cfg, S, n_replicas, batching=None):
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric, ReplicaPool

    up = Uplink(bandwidth_bps=mbps(args.bw), latency=args.latency,
                server_time=cfg.server_time, seed=args.seed)
    pool = ReplicaPool(n_replicas, cfg.server_time, serial=True,
                       batching=batching)
    return EdgeFabric([up], pool, n_streams=S, placement="jsq")


def run_point(args, cfg, S, n_replicas, batching=None):
    from repro.serving import FairScheduler, MultiStreamServer

    fast, slow, calibrate = synthetic_tiers()
    frames, labels = synthetic_streams(S, args.frames, seed=args.seed)
    fab = build_fabric(args, cfg, S, n_replicas, batching=batching)
    srv = MultiStreamServer(cfg, fast, slow, calibrate, None, n_streams=S,
                            scheduler=FairScheduler("round_robin"), fabric=fab)
    m = srv.process_streams(frames, labels)
    s = m.summary()
    return {
        "n_streams": S, "replicas": n_replicas,
        "window_ms": None if batching is None else batching.window_s * 1e3,
        "accuracy": s["accuracy"], "offload_frac": s["offload_frac"],
        "deadline_miss_frac": s["deadline_miss_frac"],
        "p99_latency_ms": s["p99_latency_ms"],
        "avg_batch": round(float(fab.pool.avg_batch), 3),
        "replica_queued_s": round(float(fab.pool.queued_seconds.sum()), 2),
    }


def collapse_point(rows):
    for r in rows:
        if r["deadline_miss_frac"] > COLLAPSE_MISS_FRAC:
            return r["n_streams"]
    return None


def run(args=None) -> dict:
    from repro.slowtier import ContinuousBatching, model_coeffs

    if args is None:
        args = parse_args([])
    if args.smoke:
        smoke()
        return {"smoke": "ok"}
    cfg = synthetic_cfg(args)
    model, fit_info = latency_model(args)
    kind, coeffs = model_coeffs(model)

    out = {"config": {"bw_mbps": args.bw, "latency": args.latency,
                      "fps": args.fps, "deadline": args.deadline,
                      "frames": args.frames, "server_time": args.server_time,
                      "model": {"kind": kind, "coeffs": list(coeffs)},
                      "fit": fit_info},
           "window_sweep": []}

    shift_ok = True
    for K in args.replicas:
        cols = []
        # FlatService baseline: the paper's constant-T^o serial replica
        rows = [run_point(args, cfg, S, K) for S in args.fleets]
        base_cp = collapse_point(rows)
        cols.append({"window_ms": None, "collapse_at": base_cp, "rows": rows})
        for w in args.windows:
            b = ContinuousBatching(model, window_s=w, max_batch=args.max_batch)
            rows = [run_point(args, cfg, S, K, batching=b) for S in args.fleets]
            cp = collapse_point(rows)
            cols.append({"window_ms": w * 1e3, "collapse_at": cp, "rows": rows})
            # None = never collapsed in the sweep — treat as +inf
            if (cp or 10**9) < (base_cp or 10**9):
                shift_ok = False
        out["window_sweep"].append({"replicas": K, "columns": cols})
        for c in cols:
            for r in c["rows"]:
                print("bench_slowtier,sweep=window," +
                      ",".join(f"{k}={v}" for k, v in r.items()), flush=True)
            print(f"bench_slowtier,replicas={K},window_ms={c['window_ms']},"
                  f"collapse_at={c['collapse_at']}", flush=True)
    out["collapse_never_moves_left"] = shift_ok
    print(f"bench_slowtier,collapse_never_moves_left={shift_ok}", flush=True)

    from benchmarks.common import emit_bench_json

    emit_bench_json("BENCH_slowtier.json", out)
    return out


# ---------------------------- smoke (CI gate) ------------------------------ #


def smoke() -> None:
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric, ReplicaPool
    from repro.serving import MultiStreamServer, ServeConfig
    from repro.slowtier import (ContinuousBatching, FlatService, LinearBatch,
                                StepBatch, form_batches, form_batches_looped)

    # 1) vectorized batch formation == looped reference, bit-for-bit
    rng = np.random.default_rng(0)
    models = [FlatService(0.02), LinearBatch(0.015, 0.004),
              StepBatch(0.01, 0.008, page_size=4, max_pages=3)]
    n_cases = 0
    for trial in range(60):
        n = int(rng.integers(1, 40))
        arr = np.sort(rng.exponential(0.02, size=n).cumsum())
        if rng.random() < 0.3:  # coincident arrivals stress window ties
            arr = np.round(arr, 2)
        cfg_b = ContinuousBatching(
            models[trial % len(models)],
            window_s=float(rng.choice([0.0, 0.005, 0.02])),
            max_batch=int(rng.integers(1, 9)) if rng.random() < 0.5 else None)
        busy0 = float(rng.uniform(0.0, 0.1))
        got = form_batches(arr, cfg_b, busy0=busy0)
        ref = form_batches_looped(arr, cfg_b, busy0=busy0)
        for g, r in zip(got, ref):
            assert np.array_equal(g, r), (trial, cfg_b, arr, got, ref)
        n_cases += 1
    print(f"bench_slowtier,smoke=batch_formation,cases={n_cases},exact=True",
          flush=True)

    # 2) degenerate batching (FlatService, window=0, cap=1) == the plain
    # serial ReplicaPool through the full server, bit-for-bit
    fast, slow, cal = synthetic_tiers()
    cfg = ServeConfig(resolutions=(4, 8), acc_server=(0.7, 0.99), batch_size=16,
                      frame_rate=32.0, deadline=0.2)
    S = 12
    imgs, labels = synthetic_streams(S, 64)
    degen = ContinuousBatching(FlatService(cfg.server_time), window_s=0.0,
                               max_batch=1)
    assert degen.degenerate

    def run_server(batching):
        ups = [Uplink(bandwidth_bps=mbps(50.0 * 0.6), latency=0.05,
                      server_time=cfg.server_time, seed=c)
               for c in range(2)]
        pool = ReplicaPool(2, np.array([cfg.server_time, cfg.server_time * 1.5]),
                           serial=True, batching=batching)
        fab = EdgeFabric(ups, pool, n_streams=S, placement="jsq")
        srv = MultiStreamServer(cfg, fast, slow, cal, None, n_streams=S,
                                fabric=fab)
        return srv.process_streams(imgs, labels), fab

    agg_plain, fab_plain = run_server(None)
    agg_degen, fab_degen = run_server(degen)
    assert agg_plain.accuracy == agg_degen.accuracy
    assert agg_plain.n_offloaded == agg_degen.n_offloaded
    assert agg_plain.n_deadline_miss == agg_degen.n_deadline_miss
    assert np.array_equal(fab_plain.pool.busy_until, fab_degen.pool.busy_until)
    for a, b in zip(agg_plain.per_stream, agg_degen.per_stream):
        assert a.accuracy == b.accuracy and a.offload_frac == b.offload_frac
        assert a.deadline_miss_frac == b.deadline_miss_frac
    print("bench_slowtier,smoke=degenerate_pool,exact=True", flush=True)

    # 3) ... and that degenerate path still pins the recorded golden run
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "tests", "data",
                           "fabric_snapshot.json")) as f:
        snap = json.load(f)["fabric"]
    assert agg_degen.accuracy == snap["accuracy"]
    assert int(agg_degen.n_offloaded) == snap["n_offloaded"]
    assert int(agg_degen.n_deadline_miss) == snap["n_deadline_miss"]
    for m, ref in zip(agg_degen.per_stream, snap["per_stream"]):
        assert m.accuracy == ref["accuracy"]
    print("bench_slowtier,smoke=fabric_snapshot,exact=True", flush=True)
    print("bench_slowtier,smoke=ok  (batched==looped; degenerate==serial)")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fleets", type=lambda s: tuple(int(x) for x in s.split(",")),
                    default=FLEET_SIZES)
    ap.add_argument("--replicas", type=lambda s: tuple(int(x) for x in s.split(",")),
                    default=REPLICA_COUNTS)
    ap.add_argument("--windows", type=lambda s: tuple(float(x) for x in s.split(",")),
                    default=WINDOWS_S, help="admission windows (seconds)")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="occupancy cap per batch")
    ap.add_argument("--bw", type=float, default=80.0, help="uplink Mbps")
    ap.add_argument("--latency", type=float, default=0.05)
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--deadline", type=float, default=0.2)
    ap.add_argument("--server-time", type=float, default=0.020,
                    help="flat T^o; the batching curve is anchored at f(1)~=T^o")
    ap.add_argument("--frames", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--coeffs-from", type=str, default=None,
                    help="BENCH_kernels.json with a batch_fit entry")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: formation + degenerate-path exactness, no sweeps")
    return ap.parse_args(argv)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(parse_args())
