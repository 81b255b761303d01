"""Differential gate for the JAX backend: jax == numpy, decision-for-decision.

Three layers, all driven through ``tests/_diff.py``:

* planner parity — ``FleetRunner(backend="jax")._plan_all_jax`` against the
  numpy ``plan_all`` on identical fuzzed backlogs (four policies, active
  masks, tie-heavy confidences): every integer field of the ``PlanBatch``
  bit-equal, floats at float32 tolerance;
* round-loop parity — ``run_differential`` replays seeded workloads through
  both ``MultiStreamServer`` backends with the ``round_hook`` attached and
  asserts every round record (S in {1, 3, 17}, degenerate + C2/K2 fabric,
  cbo/threshold, round_robin/fifo, churn on/off, jsq/least_land);
* golden pins — BOTH backends must reproduce
  ``tests/data/fabric_snapshot.json`` (frame_rate=32, the tie-free grid).

Plus a sharding smoke: the jax engine under ``sharding_ctx(make_local_mesh())``
must agree with its own off-mesh run (``shard`` constraints are layout
hints, never semantics).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from _diff import (THETA_ATOL, assert_fleet_equal, make_server,
                   run_differential)

DATA = os.path.join(os.path.dirname(__file__), "data")


# --------------------------------------------------------------------- #
# planner parity: FleetRunner(backend="jax") vs numpy plan_all
# --------------------------------------------------------------------- #

def make_runner(backend, policy_name, S, mb=12):
    from repro.core.netsim import png_size_model
    from repro.policy.fleet import FleetRunner
    from repro.policy.registry import make_policy

    kw = {"max_backlog": mb}
    if policy_name == "server":
        kw["frame_interval"] = 1.0 / 32.0
    return FleetRunner([make_policy(policy_name, **kw) for _ in range(S)],
                       resolutions=(4, 8), acc_server=(0.7, 0.99), deadline=0.2,
                       latency=0.05, server_time=0.037, size_of=png_size_model,
                       bw_init=50e6 / 8, backend=backend)


def fuzz_backlog(S, mb, seed, conf_grid=None):
    """One seeded ragged workload: per-stream ascending arrivals on the
    1/32 grid (exactly representable in f32 — tie-free prune compares),
    confidences either uniform or drawn from a coarse tie-heavy grid."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, mb + 1, size=S)
    stream = np.repeat(np.arange(S), lens)
    t0 = rng.integers(0, 64, size=S) / 32.0
    pos = np.concatenate([np.arange(n) for n in lens]) if lens.sum() else np.zeros(0)
    arrival = t0[stream] + pos / 32.0
    if conf_grid is None:
        conf = rng.uniform(0.05, 0.95, size=lens.sum())
    else:
        conf = np.asarray(conf_grid)[rng.integers(0, len(conf_grid), size=lens.sum())]
    # plan a fraction of a frame after each stream's newest arrival
    now = t0 + (lens + 0.5) / 32.0
    bw = rng.uniform(2e5, 1e7, size=S)
    active = rng.random(S) < 0.8
    now = np.where(active, now, np.inf)
    return stream, arrival, conf, now, bw, active


def assert_plan_equal(pn, pj, ctx=""):
    for k in ("resolution", "n_offloads", "n_frames", "off_stream", "off_pos",
              "off_res", "planned"):
        assert np.array_equal(getattr(pn, k), getattr(pj, k)), (
            f"{ctx}: {k}: numpy={getattr(pn, k)!r} jax={getattr(pj, k)!r}")
    np.testing.assert_allclose(pj.theta, pn.theta, atol=THETA_ATOL,
                               err_msg=f"{ctx}: theta")
    np.testing.assert_allclose(pj.total_gain, pn.total_gain, atol=1e-4,
                               err_msg=f"{ctx}: total_gain")
    np.testing.assert_allclose(pj.base_acc, pn.base_acc, atol=1e-4,
                               err_msg=f"{ctx}: base_acc")


@pytest.mark.parametrize("policy", ["cbo", "threshold", "local", "server",
                                    "greedy-rate"])
@pytest.mark.parametrize("S", [1, 3, 17])
def test_planner_parity(policy, S):
    for seed in range(4):
        rn = make_runner("numpy", policy, S)
        rj = make_runner("jax", policy, S)
        stream, arrival, conf, now, bw, active = fuzz_backlog(S, 12, 100 * S + seed)
        for r in (rn, rj):
            r.observe_frames(stream, arrival, conf)
            r.bw_est[:] = bw
        pn = rn.plan_all(now, active)
        pj = rj.plan_all(now, active)
        assert_plan_equal(pn, pj, ctx=f"{policy} S={S} seed={seed}")
        assert_fleet_equal(rn.state, rj.state)  # post-prune state agrees too


@pytest.mark.parametrize("policy", ["cbo", "threshold"])
def test_planner_parity_tie_heavy(policy):
    # coarse confidence grid => many exact ties; stable tie-breaking in the
    # DP / threshold selection must match the numpy reference bit-for-bit
    for seed in range(4):
        rn = make_runner("numpy", policy, 9)
        rj = make_runner("jax", policy, 9)
        stream, arrival, conf, now, bw, active = fuzz_backlog(
            9, 12, 7000 + seed, conf_grid=(0.3, 0.5, 0.5, 0.7))
        for r in (rn, rj):
            r.observe_frames(stream, arrival, conf)
            r.bw_est[:] = bw
        assert_plan_equal(rn.plan_all(now, active), rj.plan_all(now, active),
                          ctx=f"tie-heavy {policy} seed={seed}")


def test_planner_parity_heterogeneous():
    # mixed fleet: three policy kinds with DIFFERENT max_backlogs, so the
    # jax path must pad every group to the widest L and trim per stream
    from repro.core.netsim import png_size_model
    from repro.policy.fleet import FleetRunner
    from repro.policy.registry import make_policy

    mix = (("cbo", 12), ("threshold", 8), ("greedy-rate", 10))

    def runner(backend, S):
        pols = [make_policy(name, max_backlog=mb)
                for name, mb in (mix[i % len(mix)] for i in range(S))]
        return FleetRunner(pols, resolutions=(4, 8), acc_server=(0.7, 0.99),
                           deadline=0.2, latency=0.05, server_time=0.037,
                           size_of=png_size_model, bw_init=50e6 / 8,
                           backend=backend)

    for S in (3, 9):
        for seed in range(3):
            rn, rj = runner("numpy", S), runner("jax", S)
            stream, arrival, conf, now, bw, active = fuzz_backlog(
                S, 12, 4200 + 10 * S + seed)
            for r in (rn, rj):
                r.observe_frames(stream, arrival, conf)
                r.bw_est[:] = bw
            pn = rn.plan_all(now, active)
            pj = rj.plan_all(now, active)
            assert_plan_equal(pn, pj, ctx=f"het S={S} seed={seed}")
            assert_fleet_equal(rn.state, rj.state)


# --------------------------------------------------------------------- #
# the planners' backlog walk stops at the deepest live backlog
# --------------------------------------------------------------------- #

DEPTH_PLANNERS = ("cbo", "cbo-split", "threshold", "greedy-rate")
# "mix" stays below the pad, so the walk is really cut short; "full"
# puts one stream at L, so the bound is the pad itself
DEPTH_LENGTHS = ("empty", "zero-one", "mix", "full")


def _depth_spec(planner, L):
    from _diff import canonical_actions
    from repro.core.netsim import payload_sizes, png_size_model
    from repro.policy.fleet_jax import spec_for_policy
    from repro.policy.registry import make_policy

    kind = "cbo" if planner == "cbo-split" else planner
    return spec_for_policy(
        make_policy(kind, max_backlog=L),
        sizes=payload_sizes(png_size_model, np.asarray((4, 8))),
        acc_server=(0.7, 0.99), deadline=0.2, latency=0.05, server_time=0.037,
        actions=canonical_actions() if planner == "cbo-split" else None)


def _depth_fleet(lengths, S, L, seed):
    """A padded fleet whose backlog lengths follow ``lengths``: arrivals on
    the 1/128 grid (a backlog spans under the deadline, so the frontier
    DP has feasible work at every depth), empty streams planned at +inf
    as the compiled round plans them."""
    import jax.numpy as jnp

    from repro.policy.fleet_jax import pad_fleet

    rng = np.random.default_rng(seed)
    lens = {"empty": np.zeros(S, dtype=int),
            "zero-one": np.r_[0, 1, rng.integers(0, 2, size=S - 2)],
            "mix": np.r_[0, 1, rng.integers(0, L, size=S - 2)],
            "full": np.r_[0, 1, rng.integers(0, L, size=S - 3), L]}[lengths]
    t0 = rng.integers(0, 64, size=S) / 32.0
    stream = np.repeat(np.arange(S), lens)
    pos = np.concatenate([np.arange(n) for n in lens]) if lens.sum() else np.zeros(0)
    arrival = t0[stream] + pos / 128.0
    conf = rng.uniform(0.05, 0.95, size=lens.sum())
    now = np.where(lens > 0, t0 + lens / 128.0, np.inf)
    bw = rng.uniform(2e5, 1e7, size=S)
    fleet = pad_fleet(arrival, conf, lens, L)
    return fleet, jnp.asarray(now, jnp.float32), jnp.asarray(bw, jnp.float32), lens


@pytest.mark.parametrize("lengths", DEPTH_LENGTHS)
@pytest.mark.parametrize("planner", DEPTH_PLANNERS)
def test_plan_depth_bound_matches_full_walk(planner, lengths):
    """``plan_fleet`` walks the deepest live backlog, not the pad; every
    ``PlanOut`` field equals the full ``L`` walk's bit for bit."""
    import jax

    from repro.policy import fleet_jax as fj

    S, L = 7, 12
    spec = _depth_spec(planner, L)
    bounded = jax.jit(lambda f, now, bw: fj.plan_fleet(spec, f, now, bw))
    full = jax.jit(lambda f, now, bw, d: fj._plan_to_depth(spec, f, now, bw, None, d))
    for seed in range(3):
        fleet, now, bw, lens = _depth_fleet(lengths, S, L, 9100 + seed)
        got = bounded(fleet, now, bw)
        ref = full(fleet, now, bw, L)
        assert int(got.depth) == lens.max() and int(ref.depth) == L
        assert lengths == "empty" or np.any(np.asarray(ref.dec) >= 0)
        for k in fj.PlanOut._fields:
            if k != "depth":
                assert np.array_equal(np.asarray(getattr(got, k)),
                                      np.asarray(getattr(ref, k))), (
                    f"{planner}/{lengths} seed={seed}: {k}")


@pytest.mark.parametrize("policy", ["cbo", "mixed"])
def test_round_reports_plan_steps(policy):
    """The compiled round reports the planners' walk: ``plan_steps`` sums
    each round's deepest pruned backlog (per policy group), 0 in the first
    round, where every backlog is empty; ``plan_steps_padded`` is R·L a
    planner walk."""
    from repro.obs import Telemetry
    from repro.serving.synthetic import synthetic_streams

    mix = ("cbo", "threshold", "greedy-rate")
    pol = "cbo" if policy == "cbo" else (lambda i: mix[i % len(mix)])
    S = 6
    imgs, labels = synthetic_streams(S, 64, seed=4)
    tel = Telemetry(record=False, profile=True)
    srv, _ = make_server("jax", S=S, policy=pol, telemetry=tel)
    recs = []
    srv.round_hook = recs.append
    srv.process_streams(imgs, labels)
    groups = [np.asarray(ss) for _, ss in srv.fleet.groups]
    L = max(p.max_backlog for p, _ in srv.fleet.groups)
    depths = [sum(int(r["n_frames"][ss].max()) for ss in groups) for r in recs]
    assert depths[0] == 0 and max(depths) > 0
    assert tel.profiler.counters["plan_steps"] == sum(depths)
    assert tel.profiler.counters["plan_steps_padded"] == len(recs) * L * len(groups)


def test_runner_backend_validation():
    from repro.core.netsim import png_size_model
    from repro.policy.fleet import FleetRunner
    from repro.policy.registry import make_policy

    common = dict(resolutions=(4, 8), acc_server=(0.7, 0.99), deadline=0.2,
                  latency=0.05, server_time=0.037, size_of=png_size_model)
    with pytest.raises(ValueError, match="backend"):
        FleetRunner([make_policy("cbo", max_backlog=8)], backend="torch", **common)
    # heterogeneous fleets segment per policy group: supported since the
    # sharded scale-out, so mixing plannable kinds must construct cleanly
    FleetRunner([make_policy("cbo", max_backlog=8),
                 make_policy("threshold", max_backlog=8)],
                backend="jax", **common)
    # unbounded backlogs cannot be padded to fixed shapes
    with pytest.raises(ValueError, match="max_backlog"):
        FleetRunner([make_policy("cbo", max_backlog=None)], backend="jax", **common)
    # a policy with no JAX planner AND no bound: the error lists EVERY
    # reason (the "optimal" offline DP trips both at once)
    with pytest.raises(ValueError) as ei:
        FleetRunner([make_policy("optimal")], backend="jax", **common)
    assert "no JAX planner" in str(ei.value)
    assert "max_backlog" in str(ei.value)


# --------------------------------------------------------------------- #
# round-loop parity: MultiStreamServer(backend="jax") vs numpy
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("S", [1, 3, 17])
def test_round_loop_parity_degenerate(S):
    run_differential(S=S, topology="degenerate", seed=S)


@pytest.mark.parametrize("placement", ["jsq", "least_land", "round_robin"])
def test_round_loop_parity_fabric(placement):
    run_differential(S=3, topology="fabric", placement=placement)


def test_round_loop_parity_threshold_fifo():
    run_differential(S=3, policy="threshold", scheduler="fifo")


@pytest.mark.parametrize("topology", ["degenerate", "fabric"])
def test_round_loop_parity_churn(topology):
    run_differential(S=3, topology=topology, churn=True, seed=5)


def test_round_loop_parity_heterogeneous():
    # per-stream policy factory => >1 group => the engine's segmented
    # per-group planning must match the numpy group-merge path round-for-round
    mix = ("cbo", "threshold", "greedy-rate")
    run_differential(S=6, policy=lambda i: mix[i % len(mix)], seed=11)


def test_round_loop_parity_heterogeneous_fabric():
    mix = ("cbo", "threshold")
    run_differential(S=4, policy=lambda i: mix[i % len(mix)],
                     topology="fabric", seed=12)


@pytest.mark.parametrize("topology", ["degenerate", "fabric"])
def test_round_loop_parity_jitter(topology):
    # counter-mode jitter: the PRNG-keyed factors are drawn inside the scan
    # and must reproduce the host rng's draws bit-for-bit (same fold_in
    # chain), so integer decisions stay exact
    run_differential(S=3, topology=topology, jitter=0.3,
                     jitter_mode="counter", seed=7)


def test_round_loop_parity_trace():
    # square-wave trace with a 1.5 s loop period: the ~2 s workload crosses
    # regime boundaries AND wraps the loop, all inside the compiled scan
    from repro.net.traces import regime_shift_trace

    tr = regime_shift_trace(levels_mbps=(20.0, 4.0), period=0.75, loop=True)
    run_differential(S=3, traces=[tr], seed=13)


def test_round_loop_parity_trace_fabric():
    # two cells on different traces; one also jittered — trace lookup and
    # counter jitter compose multiplicatively in-scan
    from repro.net.traces import regime_shift_trace

    trs = [regime_shift_trace(levels_mbps=(25.0, 6.0), period=0.75, loop=True),
           regime_shift_trace(levels_mbps=(12.0, 30.0, 8.0), period=0.5,
                              loop=True)]
    run_differential(S=4, topology="fabric", traces=trs, seed=14)
    run_differential(S=3, topology="fabric", traces=trs, jitter=0.2,
                     jitter_mode="counter", seed=15)


def test_post_run_fleet_state_parity():
    # after a full replay, the residual backlog state (rebuilt from the
    # padded arrays by the jax engine's fold-back) matches the numpy one
    from repro.serving.synthetic import synthetic_streams

    imgs, labels = synthetic_streams(3, 48, seed=9)
    states = {}
    for backend in ("numpy", "jax"):
        srv, _ = make_server(backend, S=3)
        srv.process_streams(imgs, labels)
        states[backend] = srv.fleet.state
    assert_fleet_equal(states["numpy"], states["jax"])


def test_server_backend_fail_fast():
    # unsupported fabric configs must raise at construction, not mid-run —
    # and the shared ``supports_jax`` predicate must agree with the raise
    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric
    from repro.serving import MultiStreamServer, ServeConfig
    from repro.serving.engine_jax import jax_unsupported, supports_jax
    from repro.serving.synthetic import synthetic_tiers

    fast, slow, cal = synthetic_tiers()
    cfg = ServeConfig(resolutions=(4, 8), acc_server=(0.7, 0.99),
                      frame_rate=32.0, deadline=0.2)

    def server(backend, **up_kw):
        up = Uplink(bandwidth_bps=mbps(50.0), latency=0.05,
                    server_time=cfg.server_time, seed=0, **up_kw)
        return MultiStreamServer(cfg, fast, slow, cal, None, n_streams=2,
                                 fabric=EdgeFabric.degenerate(up, n_streams=2),
                                 backend=backend)

    # legacy "pcg" jitter draws from a host rng the compiled scan cannot
    # reproduce — construction must raise and name the fix
    with pytest.raises(ValueError, match="jitter_mode"):
        server("jax", jitter=0.3)
    # ...but the numpy backend still accepts it, and the predicate reports
    # the same verdict the constructor enforces
    srv = server("numpy", jitter=0.3)
    assert not supports_jax(srv)
    assert any("counter" in r for r in jax_unsupported(srv))
    # counter-mode jitter is expressible in-scan: constructs fine
    srv = server("jax", jitter=0.3, jitter_mode="counter")
    assert supports_jax(srv) and jax_unsupported(srv) == []


# --------------------------------------------------------------------- #
# golden snapshot: both backends pin tests/data/fabric_snapshot.json
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("topology,S", [("degenerate", 4), ("fabric", 12)])
def test_fabric_snapshot(backend, topology, S):
    from repro.serving.synthetic import synthetic_streams

    with open(os.path.join(DATA, "fabric_snapshot.json")) as f:
        snap = json.load(f)[topology]
    imgs, labels = synthetic_streams(S, 64)
    srv, _ = make_server(backend, S=S, topology=topology)
    agg = srv.process_streams(imgs, labels)
    assert agg.accuracy == pytest.approx(snap["accuracy"], abs=1e-12)
    assert int(agg.n_offloaded) == snap["n_offloaded"]
    assert int(agg.n_deadline_miss) == snap["n_deadline_miss"]
    for m, ref in zip(agg.per_stream, snap["per_stream"]):
        assert m.n_frames == ref["n_frames"]
        assert m.accuracy == pytest.approx(ref["accuracy"], abs=1e-12)
        assert m.offload_frac == pytest.approx(ref["offload_frac"], abs=1e-12)
        assert m.deadline_miss_frac == pytest.approx(ref["deadline_miss_frac"],
                                                     abs=1e-12)


# --------------------------------------------------------------------- #
# sharding smoke: the streams axis under a local mesh
# --------------------------------------------------------------------- #

def test_engine_under_local_mesh():
    from repro.launch.mesh import make_local_mesh
    from repro.serving.synthetic import synthetic_streams
    from repro.sharding.axes import sharding_ctx

    imgs, labels = synthetic_streams(4, 32, seed=3)

    def run():
        srv, _ = make_server("jax", S=4)
        return srv.process_streams(imgs, labels)

    base = run()
    with sharding_ctx(make_local_mesh()):
        meshed = run()
    assert meshed.n_frames == base.n_frames
    assert meshed.n_offloaded == base.n_offloaded
    assert meshed.n_deadline_miss == base.n_deadline_miss
    assert meshed.accuracy == base.accuracy


# --------------------------------------------------------------------- #
# multi-device parity: 8 forced host devices, streams axis really sharded
# --------------------------------------------------------------------- #

REPO = os.path.join(os.path.dirname(__file__), "..")

# subprocess because --xla_force_host_platform_device_count must land
# before jax imports (conftest pins the parent to a single CPU device)
MULTI_DEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import sys
sys.path.insert(0, "tests")
import jax
assert len(jax.devices()) == 8, jax.devices()
from _diff import make_server
from repro.launch.mesh import make_streams_mesh
from repro.sharding.axes import sharding_ctx
from repro.serving.synthetic import synthetic_streams

S = 6  # NOT a multiple of 8: exercises stream padding under the mesh
imgs, labels = synthetic_streams(S, 32, seed=3)

def run(backend, mesh=None, **kw):
    srv, _ = make_server(backend, S=S, topology="fabric", **kw)
    if mesh is None:
        agg = srv.process_streams(imgs, labels)
    else:
        with sharding_ctx(mesh):
            agg = srv.process_streams(imgs, labels)
    return dict(n_frames=int(agg.n_frames), n_off=int(agg.n_offloaded),
                n_miss=int(agg.n_deadline_miss), acc=float(agg.accuracy))

out = {"numpy": run("numpy"), "jax1": run("jax"),
       "jax8": run("jax", make_streams_mesh(8))}
jit = dict(jitter=0.25, jitter_mode="counter")
out["numpy_jit"] = run("numpy", **jit)
out["jax8_jit"] = run("jax", make_streams_mesh(8), **jit)
print("JSON" + json.dumps(out))
"""


def test_multi_device_round_loop_parity():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                          capture_output=True, text=True, env=env, cwd=REPO,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    payload = [l for l in proc.stdout.splitlines() if l.startswith("JSON")][0][4:]
    out = json.loads(payload)
    # multi-device == single-device == numpy, decision-for-decision
    assert out["jax8"] == out["jax1"] == out["numpy"], out
    # ...and with in-scan counter jitter active under the mesh
    assert out["jax8_jit"] == out["numpy_jit"], out
