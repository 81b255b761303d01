"""Split-computation offloading: frame-only vs split-enabled action grids
under time-varying uplinks (LTE / WiFi traces).

The regime is the one the split subsystem exists for: the slow tier is
nearly as slow as the deadline (``server_time`` close to ``T``), so a
full-frame offload's rtt eats the window — at the default settings a
full-resolution frame needs ~12 Mbps to land in time, which neither trace
sustains.  A feature cut near the end of the network (Swin stage 4) ships
~3x the bytes but pays only a suffix-scaled rtt, so it lands from
~2.5 Mbps up.  The sweep runs the same fleet twice per trace — action grid
{local} ∪ {frame@r} vs {local} ∪ {frame@r} ∪ {features@cut k} — and
records accuracy / offload mix / deadline misses.

``--smoke`` is the CI gate: on small split grids the batched planner
(``cbo_plan_many``), the looped planner (``cbo_plan``), and a brute-force
enumeration of every action assignment must agree, and a *degenerate*
(frames-only) action table must reproduce the recorded pre-split fleet
snapshot (``tests/data/fabric_snapshot.json``) bit-for-bit.

  PYTHONPATH=src:benchmarks python benchmarks/bench_split.py
  PYTHONPATH=src:benchmarks python benchmarks/bench_split.py --smoke
  PYTHONPATH=src:benchmarks python benchmarks/bench_split.py --arch vit-s16 --bw 12
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core.netsim import Uplink, mbps, png_size_model  # noqa: E402
from repro.net import EdgeFabric, lte_trace, wifi_trace  # noqa: E402
from repro.policy.frontier import cbo_plan, cbo_plan_many  # noqa: E402
from repro.policy.types import ActionTable, Env, EnvBatch, Frame  # noqa: E402
from repro.serving import FairScheduler, MultiStreamServer, ServeConfig  # noqa: E402
from repro.serving.synthetic import synthetic_streams, synthetic_tiers  # noqa: E402
from repro.split import build_action_table, catalog_for  # noqa: E402


def make_cfg(args, actions=None) -> ServeConfig:
    # base_res=16 scaling as in bench_multistream: the 8-px synthetic frames
    # carry full-upload bytes so the uplink actually binds
    return ServeConfig(deadline=args.deadline, frame_rate=args.fps,
                       batch_size=16, resolutions=(4, 8),
                       acc_server=(0.7, 0.99), server_time=args.server_time,
                       size_of=lambda r: png_size_model(r, base_res=16),
                       actions=actions)


def split_table(cfg: ServeConfig, args) -> ActionTable:
    cat = catalog_for(args.arch, max_cuts=args.cuts)
    return build_action_table(cat, resolutions=cfg.resolutions,
                              size_of=cfg.size_of, acc_server=cfg.acc_server,
                              device_peak=args.npu_peak, acc_drop=args.acc_drop)


def run_one(trace, actions, args, nominal_mbps=None) -> dict:
    cfg = make_cfg(args, actions)
    fast, slow, cal = synthetic_tiers()
    # nominal = the link's rated capacity (the estimators' optimistic
    # prior); the trace modulates the actual rate underneath it
    up = Uplink(bandwidth_bps=mbps(nominal_mbps or args.bw), latency=args.latency,
                server_time=cfg.server_time, seed=args.seed, trace=trace)
    fab = EdgeFabric.degenerate(up, n_streams=args.streams)
    srv = MultiStreamServer(cfg, fast, slow, cal, None, n_streams=args.streams,
                            scheduler=FairScheduler("round_robin"), fabric=fab)
    n_frame_off = n_split_off = 0

    def hook(rec):
        nonlocal n_frame_off, n_split_off
        k = np.asarray(rec["off_kind"])
        n_split_off += int((k == 1).sum())
        n_frame_off += int((k == 0).sum())

    srv.round_hook = hook
    imgs, labels = synthetic_streams(args.streams, args.frames, seed=args.seed)
    m = srv.process_streams(imgs, labels)
    return {"grid": "split" if actions is not None and actions.has_splits
            else "frame_only",
            "n_frame_offloads_planned": n_frame_off,
            "n_split_offloads_planned": n_split_off, **m.summary()}


# --------------------------------------------------------------------------- #
# --smoke: planner triple-agreement + degenerate-table snapshot fidelity
# --------------------------------------------------------------------------- #

_SIZES = (2500.0, 60000.0)
_ACC = (0.7, 0.99)


def _smoke_table() -> ActionTable:
    base = ActionTable.frames_only(sizes=np.asarray(_SIZES), acc=np.asarray(_ACC))
    return ActionTable(
        kind=np.r_[base.kind, np.ones(2, dtype=np.int8)],
        res=np.r_[base.res, np.full(2, 1, dtype=np.int64)],
        cut=np.r_[base.cut, np.arange(2, dtype=np.int64)],
        sizes=np.r_[base.sizes, [30000.0, 8000.0]],
        acc=np.r_[base.acc, [0.98, 0.95]],
        t_dev=np.r_[base.t_dev, [0.002, 0.004]],
        srv_frac=np.r_[base.srv_frac, [0.5, 0.1]])


def brute_force_gain(frames, env: Env) -> float:
    """Enumerate every action assignment over the DP's domain (local, or
    one positive-gain action per frame), chaining uplink busy time in the
    planner's confidence-descending order; the max total gain is the
    oracle ``cbo_plan`` must match."""
    act = env.actions
    tx = act.sizes / env.bandwidth
    rtt = act.rtt(env.server_time, env.latency)
    order = sorted(range(len(frames)), key=lambda i: (-frames[i].conf, i))
    best = 0.0
    for assign in itertools.product(range(act.n_actions + 1), repeat=len(frames)):
        t, gain, ok = 0.0, 0.0, True
        for i in order:
            a = assign[i] - 1
            if a < 0:
                continue  # local
            dA = act.acc[a] - frames[i].conf
            if dA <= 0:
                ok = False
                break
            t = max(t, frames[i].arrival + act.t_dev[a]) + tx[a]
            if t + rtt[a] > frames[i].arrival + env.deadline:
                ok = False
                break
            gain += dA
        if ok and gain > best:
            best = gain
    return best


def smoke_planner(args) -> None:
    """Batched == looped == brute force on small split grids."""
    from repro.policy.fleet import FleetState

    table = _smoke_table()
    for seed in range(args.smoke_seeds):
        rng = np.random.default_rng(seed)
        k = int(rng.integers(1, 7))
        frames = [Frame(arrival=i / 32.0, conf=float(rng.integers(20, 99)) / 100.0,
                        sizes=_SIZES) for i in range(k)]
        env = Env(bandwidth=float(rng.uniform(3e4, 4e5)), latency=0.03,
                  server_time=0.1, deadline=0.2, acc_server=_ACC, actions=table)
        plan = cbo_plan(frames, env)
        oracle = brute_force_gain(frames, env)
        assert abs(plan.total_gain - oracle) < 1e-9, \
            f"seed {seed}: DP gain {plan.total_gain} != brute force {oracle}"

        # batched fleet of clones of this instance + fresh random streams
        S = 3
        state = FleetState(S, max_backlog=64)
        for s in range(S):
            kk = k if s == 0 else int(rng.integers(0, 7))
            if kk:
                conf = (frames if s == 0 else None)
                cvals = (np.asarray([f.conf for f in frames]) if s == 0
                         else rng.integers(20, 99, size=kk) / 100.0)
                state.extend(np.full(kk, s, dtype=np.int64),
                             np.arange(kk) / 32.0, np.asarray(cvals, dtype=np.float64))
        envb = EnvBatch(bandwidth=np.full(S, env.bandwidth), latency=0.03,
                        server_time=0.1, deadline=0.2, acc_server=_ACC,
                        sizes=np.asarray(_SIZES), actions=table)
        batch = cbo_plan_many(state, envb, np.zeros(S))
        offs = state.offsets
        for s in range(S):
            fr = [Frame(arrival=float(a), conf=float(c), sizes=_SIZES)
                  for a, c in zip(state.arrival[offs[s]:offs[s + 1]],
                                  state.conf[offs[s]:offs[s + 1]])]
            p = cbo_plan(fr, envb.for_stream(s))
            assert batch.plan(s).offloads == p.offloads, f"seed {seed} stream {s}"
    print(f"bench_split,smoke_planner,seeds={args.smoke_seeds},"
          f"batched==looped==brute_force", flush=True)


def smoke_snapshot(args) -> None:
    """A degenerate (frames-only) table through the full serving stack must
    pin the recorded pre-split snapshot bit-for-bit."""
    from repro.core.netsim import payload_sizes

    snap_path = os.path.join(os.path.dirname(__file__), "..", "tests", "data",
                             "fabric_snapshot.json")
    with open(snap_path) as f:
        snap = json.load(f)["degenerate"]
    cfg = ServeConfig(resolutions=(4, 8), acc_server=(0.7, 0.99), batch_size=16,
                      frame_rate=32.0, deadline=0.2,
                      actions=ActionTable.frames_only(
                          sizes=payload_sizes(png_size_model, np.asarray((4, 8))),
                          acc=np.asarray((0.7, 0.99))))
    fast, slow, cal = synthetic_tiers()
    up = Uplink(bandwidth_bps=mbps(50.0), latency=0.05, server_time=cfg.server_time)
    fab = EdgeFabric.degenerate(up, n_streams=4)
    imgs, labels = synthetic_streams(4, 64, seed=0)
    agg = MultiStreamServer(cfg, fast, slow, cal, None, n_streams=4,
                            fabric=fab).process_streams(imgs, labels)
    assert agg.accuracy == snap["accuracy"]
    assert int(agg.n_offloaded) == snap["n_offloaded"]
    assert int(agg.n_deadline_miss) == snap["n_deadline_miss"]
    for m, ref in zip(agg.per_stream, snap["per_stream"]):
        assert m.n_frames == ref["n_frames"]
        assert m.accuracy == ref["accuracy"]
        assert m.offload_frac == ref["offload_frac"]
        assert m.deadline_miss_frac == ref["deadline_miss_frac"]
    print("bench_split,smoke_snapshot,degenerate_table==fabric_snapshot",
          flush=True)


def run(args=None) -> dict:
    if args is None:
        args = parse_args([])
    if args.smoke:
        smoke_planner(args)
        smoke_snapshot(args)
        return {}

    cfg0 = make_cfg(args)
    table = split_table(cfg0, args)
    # two regimes, two stories: LTE never sustains what a full-frame
    # offload needs (the split grid is the ONLY way to the slow tier);
    # WiFi's good state admits frames but its interference bursts are
    # split-only (the bad rate clears the suffix-scaled window, not the
    # full-rtt one).  WiFi's nominal is the good-state rate — a rated-
    # capacity prior, so the frame grid gets a fair chance.
    traces = {
        "lte": (lte_trace(mean_mbps=args.bw, seed=args.seed), args.bw),
        "wifi": (wifi_trace(good_mbps=args.bw * 5, bad_mbps=args.bw * 2 / 3,
                            seed=args.seed), args.bw * 5),
    }
    out = {"config": {"arch": args.arch, "cuts": args.cuts, "bw_mbps": args.bw,
                      "latency": args.latency, "server_time": args.server_time,
                      "deadline": args.deadline, "fps": args.fps,
                      "streams": args.streams, "frames": args.frames,
                      "npu_peak": args.npu_peak, "acc_drop": args.acc_drop},
           "actions": [{"name": n, "bytes": float(b), "t_dev": float(t),
                        "srv_frac": float(f)}
                       for n, b, t, f in zip(("thumb", "full") + table.names,
                                             table.sizes, table.t_dev,
                                             table.srv_frac)],
           "traces": {}}
    for name, (trace, nominal) in traces.items():
        frame_row = run_one(trace, None, args, nominal)
        split_row = run_one(trace, table, args, nominal)
        out["traces"][name] = {"frame_only": frame_row, "split": split_row,
                               "delta_accuracy": round(split_row["accuracy"]
                                                       - frame_row["accuracy"], 4)}
        for row in (frame_row, split_row):
            print(f"bench_split,{name}," + ",".join(
                f"{k}={v}" for k, v in row.items()
                if k in ("grid", "accuracy", "offload_frac", "deadline_miss_frac",
                         "n_frame_offloads_planned", "n_split_offloads_planned")),
                flush=True)
    from benchmarks.common import emit_bench_json

    emit_bench_json("BENCH_split.json", out)
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="swin-b",
                    help="catalog family for the split actions "
                         "(vit-s16 / resnet-50 / swin-b)")
    ap.add_argument("--cuts", type=int, default=4, help="max cut points kept")
    ap.add_argument("--bw", type=float, default=6.0,
                    help="nominal uplink Mbps (trace mean)")
    ap.add_argument("--latency", type=float, default=0.03)
    ap.add_argument("--server-time", type=float, default=0.16,
                    help="full-model slow-tier seconds (close to the deadline "
                         "— the regime where only suffix offloads fit)")
    ap.add_argument("--deadline", type=float, default=0.2)
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--streams", type=int, default=2)
    ap.add_argument("--frames", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--npu-peak", type=float, default=7e12)
    ap.add_argument("--acc-drop", type=float, default=0.0,
                    help="int8 feature-degradation penalty on split accuracy")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: batched == looped == brute force on small "
                         "split grids; degenerate table == fabric snapshot")
    ap.add_argument("--smoke-seeds", type=int, default=8)
    return ap.parse_args(argv)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(parse_args())
