"""Fleet control plane: batched ``FleetRunner.plan_all`` vs S looped
``PolicyRunner.plan`` calls on identical backlogs.

The data plane has been one batched call per round since the multi-stream
engine landed; this benchmark measures the *decision* plane — the part
that was still O(S) Python — before/after the struct-of-arrays refactor.
For each fleet size S it builds S random ragged backlogs in the paper's
link regime (0.5-10 Mbps per-stream estimates, 200 ms deadline) with
per-stream bandwidth estimates, plans them both ways, asserts the batched
plans equal the looped ones (offload schedules, theta, r° — exactly;
gains to 1e-9), and reports interleaved best-of wall-clock speedup.
Target is >=10x at S=256; measured speedup is hardware-dependent (the
batched planner trades ~30x fewer interpreter dispatches for more raw
element traffic, so narrow containers land lower than wide hosts).

  PYTHONPATH=src:benchmarks python benchmarks/bench_fleet_control.py
  PYTHONPATH=src:benchmarks python benchmarks/bench_fleet_control.py --smoke

``--backend jax`` benchmarks the compiled round loop instead
(``serving/engine_jax.py``): after an exact-integer parity gate at small S
(both ``MultiStreamServer`` backends replay the same workload and must
agree on every offload/schedule/miss count — and, under ``--devices N``,
the mesh-sharded jax run must agree with both), it scans synthetic
``RoundInputs`` through the jitted ``lax.scan`` engine at fleet sizes up
to S=10^6 (max_backlog=8 — the CPU-feasible regime the paper's fleets
run in) and reports rounds/sec and frames/sec.  The engine is AOT-lowered
(``lower().compile()``) so ``compile_s`` and ``steady_s`` are measured
separately, never inferred by subtraction.  Results land in
``results/bench/BENCH_fleet.json``.

``--devices N`` forces N XLA host devices (the flag must land before jax
imports, so pass it on the command line, not from a REPL that already
imported jax) and runs the scan with the ``"streams"`` axis sharded over
an (N, 1) mesh; ``--streams`` overrides the fleet-size sweep.

  PYTHONPATH=src:benchmarks python benchmarks/bench_fleet_control.py --backend jax
  PYTHONPATH=src:benchmarks python benchmarks/bench_fleet_control.py --smoke --backend jax
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/bench_fleet_control.py --backend jax --devices 8 --streams 1000000
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FLEET_SIZES = (16, 64, 256, 1024)
JAX_FLEET_SIZES = (1000, 10000, 100000, 1000000)


def _force_host_devices(n: int) -> None:
    """Make sure this process sees >= n XLA devices.  The host-platform
    device count only takes effect before jax initializes, so set the flag
    when jax is not yet imported and fail with a recipe when it is."""
    if n <= 1:
        return
    flag = f"--xla_force_host_platform_device_count={n}"
    if "jax" in sys.modules:
        import jax

        if len(jax.devices()) < n:
            raise SystemExit(
                f"--devices {n}: jax is already initialized with "
                f"{len(jax.devices())} device(s); relaunch with "
                f"XLA_FLAGS={flag}")
        return
    cur = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in cur:
        os.environ["XLA_FLAGS"] = (cur + " " + flag).strip()


def build_fleet(policy: str, S: int, seed: int, backlog: int = 16):
    """One FleetRunner plus S equivalent PolicyRunners, same backlogs."""
    from repro.core.netsim import mbps, png_size_model
    from repro.policy import BandwidthEstimator, FleetRunner, PolicyRunner
    from repro.policy.registry import make_policy

    rng = np.random.default_rng(seed)
    resolutions = (45, 90, 134, 179, 224)
    acc = (0.6, 0.75, 0.85, 0.92, 0.96)
    kw = dict(resolutions=resolutions, acc_server=acc, deadline=0.2,
              latency=0.05, server_time=0.037, size_of=png_size_model)
    fleet = FleetRunner([make_policy(policy) for _ in range(S)], bw_init=1.0, **kw)
    runners = [PolicyRunner(make_policy(policy),
                            bw=BandwidthEstimator(estimate_bps=1.0), **kw)
               for _ in range(S)]
    bw = rng.uniform(mbps(0.5), mbps(10.0), size=S)
    fleet.bw_est[:] = bw
    lens = rng.integers(backlog // 2, backlog + 1, size=S)
    for s in range(S):
        runners[s].bw.estimate_bps = bw[s]
        for i in range(int(lens[s])):
            a, c = i / 30.0, float(rng.uniform(0.2, 0.99))
            runners[s].add_frame(a, c)
            fleet.add_frame(s, a, c)
    return fleet, runners


def check_equal(batch, runners, now: float) -> None:
    for s, runner in enumerate(runners):
        ref = runner.plan(now=now)
        got = batch.plan(s)
        assert got.offloads == ref.offloads, (s, got.offloads, ref.offloads)
        assert got.theta == ref.theta and got.resolution == ref.resolution, s
        assert abs(got.total_gain - ref.total_gain) <= 1e-9, s


def bench_one(policy: str, S: int, seed: int, repeats: int, backlog: int = 16) -> dict:
    fleet, runners = build_fleet(policy, S, seed, backlog=backlog)
    now = np.zeros(S)
    # correctness first: batched == looped on this instance
    batch = fleet.plan_all(now)
    check_equal(batch, runners, 0.0)

    # interleaved best-of: per-pass pairs resist scheduler noise better
    # than two long back-to-back loops
    t_batched, t_looped = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fleet.plan_all(now)
        t_batched.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for r in runners:
            r.plan(now=0.0)
        t_looped.append(time.perf_counter() - t0)

    tb, tl = min(t_batched), min(t_looped)
    return {"policy": policy, "n_streams": S, "backlog": backlog,
            "looped_ms": round(tl * 1e3, 3),
            "batched_ms": round(tb * 1e3, 3),
            "speedup": round(tl / max(tb, 1e-12), 2)}


def check_jax_parity(S: int = 4, n_frames: int = 64, seed: int = 0,
                     devices: int = 1) -> dict:
    """Exact-integer gate: both ``MultiStreamServer`` backends replay the
    same seeded workload and must agree on every aggregate decision count
    (frame_rate=32 — the tie-free grid, see tests/_diff.py).  With
    ``devices > 1`` the jax backend runs a THIRD time under a streams mesh
    and must match decision-for-decision too."""
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric
    from repro.serving import MultiStreamServer, ServeConfig
    from repro.serving.synthetic import synthetic_streams, synthetic_tiers

    fast, slow, cal = synthetic_tiers()
    cfg = ServeConfig(resolutions=(4, 8), acc_server=(0.7, 0.99), batch_size=16,
                      frame_rate=32.0, deadline=0.2)
    imgs, labels = synthetic_streams(S, n_frames, seed=seed)

    def run(backend, mesh=None):
        from repro.sharding.axes import sharding_ctx

        fab = EdgeFabric.degenerate(
            Uplink(bandwidth_bps=mbps(50.0), latency=0.05,
                   server_time=cfg.server_time), n_streams=S)
        srv = MultiStreamServer(cfg, fast, slow, cal, None, n_streams=S,
                                fabric=fab, backend=backend)
        with sharding_ctx(mesh) if mesh is not None else contextlib.nullcontext():
            return srv.process_streams(imgs, labels)

    runs = {"numpy": run("numpy"), "jax": run("jax")}
    if devices > 1:
        from repro.launch.mesh import make_streams_mesh

        runs[f"jax@{devices}dev"] = run("jax", make_streams_mesh(devices))
    mn = runs["numpy"]
    for name, mj in runs.items():
        for k in ("n_frames", "n_offloaded", "n_deadline_miss"):
            assert getattr(mn, k) == getattr(mj, k), (
                name, k, getattr(mn, k), getattr(mj, k))
        assert mn.accuracy == mj.accuracy, (name, mn.accuracy, mj.accuracy)
    return {"parity": "exact", "runs": "==".join(runs), "n_streams": S,
            "n_frames": int(mn.n_frames), "n_offloaded": int(mn.n_offloaded)}


def bench_jax_one(S: int, n_rounds: int, seed: int, backlog: int = 8,
                  batch: int = 8, devices: int = 1, collect: str = "none",
                  telemetry: bool = False, repeats: int = 1) -> dict:
    """Round-loop throughput of the jitted engine on synthetic inputs.

    ``collect="none"`` (the default) so the scan carries nothing per round
    beyond the fleet state — the S=1e6 regime the numpy loop cannot reach;
    ``collect="metrics"`` / ``telemetry=True`` measure the cost of the
    per-round outputs (the ``--telemetry`` overhead gate compares them).
    With ``devices > 1`` the (S,) stream arrays are placed sharded over an
    (N, 1) mesh (S rounds up to a device multiple) and the jitted scan
    runs SPMD.  The engine is AOT-compiled (``repro.obs.profile
    .aot_split``) so the reported ``compile_s`` is the real lower+compile
    wall-clock, not a first-call subtraction; ``repeats`` takes the best
    of N steady-state executions (fresh carry each — the scan donates)."""
    import jax
    import jax.numpy as jnp

    from repro.core.netsim import mbps, payload_sizes, png_size_model
    from repro.launch.mesh import make_streams_mesh
    from repro.obs.profile import aot_split
    from repro.policy.fleet_jax import spec_for_policy
    from repro.policy.registry import make_policy
    from repro.serving import engine_jax as ej
    from repro.sharding.axes import host_shard, sharding_ctx

    S = -(-S // devices) * devices  # pad to a whole number of shards
    ctx = (sharding_ctx(make_streams_mesh(devices)) if devices > 1
           else contextlib.nullcontext())
    resolutions = (4, 8)
    sizes = payload_sizes(png_size_model, np.asarray(resolutions))
    pspec = spec_for_policy(make_policy("cbo", max_backlog=backlog),
                            sizes=sizes, acc_server=(0.7, 0.99), deadline=0.2,
                            latency=0.05, server_time=0.037)
    spec = ej.EngineSpec(n_streams=S, batch=batch, n_cells=1, n_replicas=1,
                         planner=pspec, collect=collect, telemetry=telemetry)
    bw = mbps(6.0)
    rng = np.random.default_rng(seed)
    fr = 32.0
    base = (np.arange(n_rounds * batch, dtype=np.float32) / fr).reshape(
        n_rounds, 1, batch)
    m = len(resolutions)
    with ctx:
        params = ej.EngineParams(
            sizes=jnp.asarray(sizes, dtype=jnp.float32),
            cell_bw=jnp.asarray([bw], dtype=jnp.float32),
            cell_of=host_shard(jnp.zeros(S, dtype=jnp.int32), "streams"),
            replica_st=jnp.asarray([0.037], dtype=jnp.float32),
            stream_bw=host_shard(jnp.full((S,), bw, dtype=jnp.float32),
                                 "streams"),
            weights=host_shard(jnp.ones(S, dtype=jnp.float32), "streams"),
            bw_init=host_shard(jnp.full((S,), bw, dtype=jnp.float32),
                               "streams"))
        inputs = ej.RoundInputs(
            arr=host_shard(jnp.asarray(np.broadcast_to(base, (n_rounds, S, batch))),
                           None, "streams", None),
            valid=host_shard(jnp.ones((n_rounds, S, batch), dtype=bool),
                             None, "streams", None),
            conf=host_shard(jnp.asarray(rng.uniform(0.0, 1.0, (n_rounds, S, batch)),
                                        dtype=jnp.float32),
                            None, "streams", None),
            fast_ok=host_shard(jnp.asarray(rng.random((n_rounds, S, batch)) < 0.7),
                               None, "streams", None),
            slow_ok=host_shard(jnp.asarray(rng.random((n_rounds, S, batch, m)) < 0.9),
                               None, "streams", None, None))

        step = ej.make_engine(spec)
        carry0 = ej.init_carry(spec, params)
        jax.block_until_ready((params, carry0, inputs))
        compiled, t_compile = aot_split(step, params, carry0, inputs)
        # the engine donates its carry buffers (make_engine, donate_argnums):
        # each call needs a freshly built carry, rebuilt outside the timed
        # region; one warm-up execution absorbs first-dispatch costs, but
        # at >10^7 frames a run is minutes long and dwarfs dispatch noise,
        # so the warm-up pass is skipped rather than doubling the wall-clock
        if n_rounds * S * batch <= 20_000_000:
            carry, _ = compiled(params, carry0, inputs)
            jax.block_until_ready(carry)
            carry0 = ej.init_carry(spec, params)
            jax.block_until_ready(carry0)
        times, ys = [], None
        for r in range(max(int(repeats), 1)):
            t0 = time.perf_counter()
            carry, ys = compiled(params, carry0, inputs)
            jax.block_until_ready(carry)
            times.append(time.perf_counter() - t0)
            if r + 1 < repeats:
                carry0 = ej.init_carry(spec, params)
                jax.block_until_ready(carry0)
        t_steady = min(times)
    # rounds actually emitted through the ys pytree — the telemetry gate
    # asserts this equals the requested round count
    rounds_emitted = None
    if ys is not None:
        col = ys.ts_bw_est if telemetry else ys.off_counts
        rounds_emitted = int(col.shape[0])
    return {"backend": "jax", "n_streams": S, "devices": devices,
            "rounds": n_rounds, "batch": batch, "backlog": backlog,
            "collect": collect, "telemetry": bool(telemetry),
            "rounds_emitted": rounds_emitted,
            "compile_s": round(t_compile, 3),
            "steady_s": round(t_steady, 4),
            "rounds_per_s": round(n_rounds / max(t_steady, 1e-12), 2),
            "frames_per_s": round(n_rounds * S * batch / max(t_steady, 1e-12), 1)}


def _telemetry_server(backend, S, cfg, fab, telemetry):
    from repro.serving import MultiStreamServer
    from repro.serving.synthetic import synthetic_tiers

    fast, slow, cal = synthetic_tiers()
    return MultiStreamServer(cfg, fast, slow, cal, None, n_streams=S,
                             fabric=fab, backend=backend, telemetry=telemetry)


def check_telemetry_parity(S: int = 8, n_frames: int = 64, seed: int = 0) -> dict:
    """Recorder gate: both backends replay one seeded workload with the
    recorder on; the recorded series must agree round-for-round under the
    exactness policy (integer series bit-equal, floats at tolerance)."""
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric
    from repro.obs import Telemetry
    from repro.serving import ServeConfig
    from repro.serving.synthetic import synthetic_streams

    cfg = ServeConfig(resolutions=(4, 8), acc_server=(0.7, 0.99), batch_size=16,
                      frame_rate=32.0, deadline=0.2)
    imgs, labels = synthetic_streams(S, n_frames, seed=seed)

    def run(backend):
        tel = Telemetry(record=True)
        fab = EdgeFabric.degenerate(
            Uplink(bandwidth_bps=mbps(50.0), latency=0.05,
                   server_time=cfg.server_time), n_streams=S)
        _telemetry_server(backend, S, cfg, fab, tel).process_streams(imgs, labels)
        return tel.recorder

    rec_np, rec_jx = run("numpy"), run("jax")
    expected = n_frames // cfg.batch_size
    assert rec_np.n_rounds == rec_jx.n_rounds == expected, (
        rec_np.n_rounds, rec_jx.n_rounds, expected)
    rec_np.assert_close(rec_jx, ctx="telemetry parity")
    return {"telemetry_parity": "exact", "n_streams": S,
            "rounds": rec_np.n_rounds, "series": len(rec_np.as_dict())}


def bench_telemetry_overhead(S: int, n_rounds: int, seed: int) -> dict:
    """Recorder-on vs recorder-off steady-state cost of the compiled round
    loop at identical collect level.  The gate allows 5% relative plus a
    50 ms absolute slack (CI scheduler noise on sub-second runs); best of
    two executions each side."""
    base = bench_jax_one(S, n_rounds, seed, collect="metrics", repeats=2)
    tele = bench_jax_one(S, n_rounds, seed, collect="metrics",
                         telemetry=True, repeats=2)
    assert tele["rounds_emitted"] == n_rounds, tele["rounds_emitted"]
    limit = base["steady_s"] * 1.05 + 0.05
    assert tele["steady_s"] <= limit, (
        f"telemetry overhead: {tele['steady_s']}s vs off "
        f"{base['steady_s']}s (limit {limit:.4f}s)")
    over = tele["steady_s"] / max(base["steady_s"], 1e-12) - 1.0
    return {"n_streams": S, "rounds": n_rounds,
            "steady_off_s": base["steady_s"], "steady_on_s": tele["steady_s"],
            "overhead_pct": round(over * 100.0, 2), "gate": "<=5% + 50ms"}


def telemetry_fairness_demo(S: int = 64, n_frames: int = 128,
                            seed: int = 0) -> dict:
    """The N=64 fairness collapse as a recorded trajectory: one shared
    starved cell (0.12 Mbps for 64 streams), Jain's index over cumulative
    landed offloads per round.  The end-of-run scalar only says fairness
    degraded; the series shows WHEN the collapse sets in (round 1, Jain
    ~0.46 on the canonical seed) and the partial recovery as the bandwidth
    EWMAs learn the contended share and the policies back off."""
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric
    from repro.obs import Telemetry
    from repro.serving import ServeConfig
    from repro.serving.synthetic import synthetic_streams

    cfg = ServeConfig(resolutions=(4, 8), acc_server=(0.7, 0.99), batch_size=16,
                      frame_rate=32.0, deadline=0.2)
    imgs, labels = synthetic_streams(S, n_frames, seed=seed)
    tel = Telemetry(record=True)
    fab = EdgeFabric.degenerate(
        Uplink(bandwidth_bps=mbps(0.12), latency=0.05,
               server_time=cfg.server_time), n_streams=S)
    _telemetry_server("numpy", S, cfg, fab, tel).process_streams(imgs, labels)
    jain = tel.recorder.jain_series()
    onset = next((int(i) for i, j in enumerate(jain) if j < 0.9), None)
    return {"n_streams": S, "rounds": int(tel.recorder.n_rounds),
            "jain_trajectory": [round(float(j), 4) for j in jain],
            "onset_round": onset,
            "jain_first": round(float(jain[0]), 4),
            "jain_last": round(float(jain[-1]), 4)}


def telemetry_relock_demo(S: int = 8, seed: int = 0) -> dict:
    """EWMA re-lock lag on the square-wave regime trace: the recorded
    ``bw_est`` vs ``bw_true`` series make the estimator's recovery time
    after each 20<->2 Mbps shift a measured number (``relock_lags``)."""
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric
    from repro.net.traces import regime_shift_trace
    from repro.obs import Telemetry, relock_lags
    from repro.serving import ServeConfig
    from repro.serving.synthetic import synthetic_streams

    cfg = ServeConfig(resolutions=(4, 8), acc_server=(0.7, 0.99), batch_size=16,
                      frame_rate=32.0, deadline=0.2)
    n_frames = 256  # 16 rounds x 0.5 s — two shifts per 4 s period leg
    imgs, labels = synthetic_streams(S, n_frames, seed=seed)
    tel = Telemetry(record=True)
    trace = regime_shift_trace((20.0, 2.0), period=4.0)
    fab = EdgeFabric.degenerate(
        Uplink(bandwidth_bps=mbps(20.0), latency=0.05,
               server_time=cfg.server_time, trace=trace), n_streams=S)
    _telemetry_server("numpy", S, cfg, fab, tel).process_streams(imgs, labels)
    rec = tel.recorder
    lags = relock_lags(rec, rtol=0.25, shift_rtol=0.2)
    err = rec.bw_error()
    return {"n_streams": S, "rounds": int(rec.n_rounds),
            "trace": "regime_shift 20<->2 Mbps, 4 s period",
            "shifts": [{"round": int(r), "relock_lag_rounds": lag}
                       for r, lag in lags],
            "mean_bw_err_per_round": [
                round(float(np.nanmean(row)), 4) for row in err]}


def run_telemetry(args) -> dict:
    """--telemetry: recorder parity + overhead gates, then the two recorded
    scenarios (fairness collapse, EWMA re-lock); merges under the
    ``"telemetry"`` key of BENCH_fleet.json so the throughput rows survive."""
    import json

    gate = check_telemetry_parity(seed=args.seed)
    print("bench_fleet_control," +
          ",".join(f"{k}={v}" for k, v in gate.items()), flush=True)
    S_over = 256 if args.smoke else 10_000
    overhead = bench_telemetry_overhead(S_over, n_rounds=4 if args.smoke else 16,
                                        seed=args.seed)
    print("bench_fleet_control,telemetry_overhead," +
          ",".join(f"{k}={v}" for k, v in overhead.items()), flush=True)
    fairness = telemetry_fairness_demo(seed=args.seed)
    print(f"bench_fleet_control,fairness_collapse,onset_round="
          f"{fairness['onset_round']},jain_last={fairness['jain_last']}",
          flush=True)
    relock = telemetry_relock_demo(seed=args.seed)
    print(f"bench_fleet_control,ewma_relock,shifts={relock['shifts']}",
          flush=True)
    block = {"parity_gate": gate, "overhead": overhead,
             "fairness_collapse": fairness, "ewma_relock": relock,
             "smoke": bool(args.smoke)}
    from benchmarks.common import emit_bench_json, out_path

    path = out_path("BENCH_fleet.json")
    payload = {}
    if os.path.exists(path):
        with open(path) as fh:
            payload = json.load(fh)
    payload["telemetry"] = block
    emit_bench_json("BENCH_fleet.json", payload)
    if args.smoke:
        print("bench_fleet_control,telemetry_smoke=ok  "
              "(recorder series numpy == jax; overhead within gate)")
    return block


def run_jax(args) -> dict:
    gate = check_jax_parity(seed=args.seed, devices=args.devices)
    print("bench_fleet_control,backend=jax," +
          ",".join(f"{k}={v}" for k, v in gate.items()), flush=True)
    sizes = (256,) if args.smoke else args.sizes
    if sizes == FLEET_SIZES:  # backend-appropriate default scale
        sizes = JAX_FLEET_SIZES
    if args.streams:
        sizes = args.streams
    n_rounds = 4 if args.smoke else args.rounds
    rows = []
    for S in sizes:
        row = bench_jax_one(S, n_rounds, seed=args.seed, devices=args.devices)
        rows.append(row)
        print("bench_fleet_control," + ",".join(f"{k}={v}" for k, v in row.items()),
              flush=True)
    out = {"backend": "jax", "devices": args.devices, "parity_gate": gate,
           "rows": rows, "smoke": bool(args.smoke)}
    from benchmarks.common import emit_bench_json

    emit_bench_json("BENCH_fleet.json", out)
    if args.smoke:
        who = ("jax+mesh decisions == jax decisions == numpy decisions"
               if args.devices > 1 else "jax decisions == numpy decisions")
        print(f"bench_fleet_control,smoke=ok  ({who})")
    return out


def run(args=None) -> dict:
    if args is None:
        args = parse_args([])
    if args.telemetry:
        return run_telemetry(args)
    if args.backend == "jax":
        _force_host_devices(args.devices)
        return run_jax(args)
    sizes = (64,) if args.smoke else args.sizes
    repeats = 1 if args.smoke else args.repeats
    rows = []
    for policy in args.policies:
        for S in sizes:
            row = bench_one(policy, S, seed=args.seed, repeats=repeats)
            rows.append(row)
            print("bench_fleet_control," + ",".join(f"{k}={v}" for k, v in row.items()),
                  flush=True)
    if args.smoke:
        print("bench_fleet_control,smoke=ok  (batched plans == looped plans)")
        return {"smoke": "ok", "rows": rows}
    ref = [r for r in rows if r["policy"] == "cbo" and r["n_streams"] == 256]
    if ref and ref[0]["speedup"] < 10.0:
        print(f"bench_fleet_control,WARNING: cbo S=256 speedup {ref[0]['speedup']} < 10x")
    out = {"backend": "numpy", "rows": rows}
    from benchmarks.common import emit_bench_json

    emit_bench_json("BENCH_fleet.json", out, mirror="fleet_control.json")
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", type=lambda s: tuple(int(x) for x in s.split(",")),
                    default=FLEET_SIZES, help="comma-separated fleet sizes")
    ap.add_argument("--policies", type=lambda s: tuple(s.split(",")),
                    default=("cbo", "threshold"), help="policies to bench")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                    help="numpy: batched-vs-looped planner; jax: compiled round loop")
    ap.add_argument("--rounds", type=int, default=16,
                    help="rounds per lax.scan run (--backend jax)")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the streams axis over N forced XLA host "
                         "devices (--backend jax; must be set before jax "
                         "initializes — pass on the CLI, or export "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--streams", type=lambda s: tuple(int(x) for x in s.split(",")),
                    default=(), help="fleet sizes for the jax round-loop sweep "
                                     "(overrides --sizes; e.g. 1000000)")
    ap.add_argument("--telemetry", action="store_true",
                    help="telemetry mode: recorder parity + overhead gates "
                         "plus the recorded fairness-collapse and EWMA "
                         "re-lock scenarios (merges under the 'telemetry' "
                         "key of BENCH_fleet.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small S, single pass, exact parity gates")
    return ap.parse_args(argv)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(parse_args())
