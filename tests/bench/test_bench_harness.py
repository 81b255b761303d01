"""The benchmark's harness on the CPU: trace reduction, counts from shapes,
the files ``BENCHMARK.json`` names, the refusal off a TPU, the last line,
and the check that decides ``correct`` (the control and the faults it has
to catch).  A run's device metrics need the chip; nothing here measures
one."""
from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT]

from bench import check, faults, flops, harness, trace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench_json():
    return harness.load_json("BENCHMARK.json")


# --------------------------------------------------------------------------- #
# trace reduction
# --------------------------------------------------------------------------- #


def test_reduction_of_a_recorded_v5e_trace():
    """A trace recorded on one TPU v5e chip: two jitted programs run three
    times inside ``bench.window``, between host spans."""
    red = trace.reduce_file(FIXTURE)
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(0.00867281, rel=1e-6)
    assert red.busy_s == pytest.approx(4.495e-6, rel=1e-6)
    assert red.module_calls == {"jit__lambda": 3}
    assert red.module_s["jit__lambda"] == pytest.approx(4.513e-6, rel=1e-6)
    assert set(red.idle_by_span) == {"bench.build_server", "bench.process_streams"}
    # every idle second of the window is named, and busy + idle is the window
    assert red.busy_s + sum(red.idle_by_span.values()) == pytest.approx(red.window_s, rel=1e-9)
    assert red.top_ops(1)[0][0] == "convolution_tanh_fusion"


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def test_reduction_unions_overlaps_and_names_gaps():
    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(name="python", events=[
        _ev("bench.window", 0, 1000), _ev("bench.process_streams", 100, 400),
        _ev("bench.tier_fast", 150, 50), _ev("other", 0, 1000)])])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Ops", events=[
            _ev("%a = f32[] add()", 200, 100), _ev("%b = f32[] mul()", 250, 100),
            _ev("%c = f32[] sub()", 900, 200)]),  # clipped at the window's end
        SimpleNamespace(name="XLA Modules", events=[_ev("jit_run(7)", 200, 150),
                                                    _ev("jit_run(7)", 900, 200)])])
    red = trace.reduce_planes([host, SimpleNamespace(name="/device:TPU_NON_CORE", lines=[]), dev])
    assert red.window_s == pytest.approx(1e-6)
    assert red.busy_s == pytest.approx(250e-9)  # [200, 350) and [900, 1000)
    assert red.module_calls == {"jit_run": 2}
    assert red.op_s["a"] == pytest.approx(50e-9)  # the part no later op covers
    # gaps: [0,200) mid 100 -> process_streams; [350,900) mid 625 -> outside
    assert red.idle_by_span["bench.process_streams"] == pytest.approx(200e-9)
    assert red.idle_by_span["outside the bench spans"] == pytest.approx(550e-9)


def test_op_times_are_self_times():
    """A loop op that encloses others keeps only its own time."""
    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(name="p", events=[
        _ev("bench.window", 0, 1000)])])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[SimpleNamespace(name="XLA Ops", events=[
        _ev("%while.1 = (s32[]) while()", 100, 500), _ev("%f.1 = f32[] fusion()", 150, 100),
        _ev("%f.2 = f32[] fusion()", 300, 200), _ev("%g = f32[] add()", 700, 50)])])
    red = trace.reduce_planes([host, dev])
    assert red.op_s == pytest.approx({"while.1": 200e-9, "f.1": 100e-9, "f.2": 200e-9,
                                      "g": 50e-9})
    assert red.busy_s == pytest.approx(550e-9)


# --------------------------------------------------------------------------- #
# counts from shapes, and the peak table
# --------------------------------------------------------------------------- #


def test_resnet_flops_match_a_hand_count_at_smoke_size():
    # ResNet SMOKE: 32 px, one bottleneck in each of two stages, width 16,
    # 10 classes.  Stem 7x7 stride 2: 16x16 out; max pool: 8x8.
    stem = 2 * 16 * 16 * 49 * 3 * 16
    # stage 0 (8x8, mid 16, out 64, projection): 1x1 16->16, 3x3 16->16,
    # 1x1 16->64, projection 1x1 16->64
    s0 = 2 * 64 * (16 * 16 + 9 * 16 * 16 + 16 * 64 + 16 * 64)
    # stage 1 (stride 2 on the 3x3: 8x8 -> 4x4, mid 32, out 128):
    # 1x1 64->32 at 8x8, 3x3 32->32 at 4x4, 1x1 32->128 at 4x4,
    # projection 1x1 64->128 at 4x4
    s1 = 2 * (64 * 64 * 32 + 16 * 9 * 32 * 32 + 16 * 32 * 128 + 16 * 64 * 128)
    head = 2 * 128 * 10
    assert flops.resnet_forward_flops(32, (1, 1), 16, 10) == stem + s0 + s1 + head


def test_resnet50_flops_are_the_published_count():
    # 4.1 G multiply-adds (He et al., 2016, Table 1: 3.8e9 without the
    # v1.5 stride move, about 4.1e9 with it)
    f = flops.resnet_forward_flops(224, (3, 4, 6, 3), 64, 1000)
    assert 8.0e9 < f < 8.4e9


def test_gate_bytes():
    assert flops.calib_gate_bytes(128, 1000) == 128 * 1000 * 4 + 128 * 5 + 12


def test_peaks_are_keyed_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        flops.peaks("cpu")


# --------------------------------------------------------------------------- #
# the files BENCHMARK.json names
# --------------------------------------------------------------------------- #


def test_every_file_benchmark_json_names_loads():
    bench = bench_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        conf = harness.load_json(c["file"])
        assert conf["name"] == c["name"]
        assert callable(importlib.import_module(f"bench.gen.{conf['tiers']}").build)
        assert set(conf["limits"]) == set(check.NAMES)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell, conf, traffic = harness.cell_files(bench, w["name"])
        for key in ("streams", "frames", "pool", "check_segments", "trace_segments",
                    "warmup_segments"):
            assert int(traffic[key]) >= 1
        assert int(traffic["frames"]) % int(conf["batch_size"]) == 0
    for m in bench["per_layer"]:
        assert callable(importlib.import_module(f"bench.metrics.{m['name']}").read)
        assert m["moves"] in e2e
    assert "setup_s" in e2e


# --------------------------------------------------------------------------- #
# the entry point
# --------------------------------------------------------------------------- #


def test_run_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "resnet50-paper.s16",
                        "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


TINY_RESNET = {
    "tiers": "resnet_tiers", "depths": [1, 1], "width": 8, "img_res": 32, "n_classes": 10,
    "fast_bits": 4, "matmul_precision": "highest", "resolutions": [8, 16, 24, 32],
    "acc_server": [0.6, 0.8, 0.9, 0.95], "deadline": 0.2, "frame_rate": 32.0,
    "batch_size": 8, "latency": 0.05, "server_time": 0.037, "fast_time": 0.02,
    "calib_time": 0.008, "stream_mbps": 5.0, "policy": "cbo", "max_backlog": 64,
    "use_fused": True, "branch_gain": 0.1, "logit_std": 8.0, "bn_frames": 16,
    "noise_floor": 0.15,
}
TINY_TRAFFIC = {"streams": 2, "frames": 16, "pool": 2, "check_segments": 2,
                "trace_segments": 1, "warmup_segments": 1}


def _limits(cell):
    return harness.load_json(f"bench/configs/{cell}.json")["limits"]


def test_last_line_has_the_contract_keys(monkeypatch, capsys):
    """A whole run, the device check faked: the tiny ResNet cell on the CPU."""
    from bench import run

    bench = bench_json()
    conf = dict(TINY_RESNET, limits=_limits("resnet50-paper"))
    monkeypatch.setattr(harness, "cell_files",
                        lambda b, w: (bench["workloads"][0], conf, TINY_TRAFFIC))
    monkeypatch.setattr(run, "configure_cache", lambda: None)
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                           memory_stats=lambda: {"peak_bytes_in_use": 123})
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    assert run.main(["--workload", "resnet50-paper.s16", "--seed", str(2**32 + 3),
                     "--seconds", "1.5", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"frames_per_s", "segment_p90_ms", "setup_s"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                              "memory_peak_bytes": 123}
    assert set(line["check"]) == set(check.NAMES)
    assert err.strip().splitlines()[-1].startswith(f"check {check.NAMES[-1]} ")


# --------------------------------------------------------------------------- #
# the check: the control and the faults it has to catch
# --------------------------------------------------------------------------- #


def _run(conf, traffic, *, control=False, fault=None, seed=11):
    return harness.run_cell(conf, traffic, seed=seed, seconds=0.05, trace=False,
                            t_process_start=time.perf_counter(),
                            limits=conf["limits"], control=control, fault=fault)


def test_control_at_a_lower_precision_is_not_correct():
    conf = dict(TINY_RESNET, limits=_limits("resnet50-paper"))
    good = _run(conf, TINY_TRAFFIC)
    assert good["correct"], good["check"]
    ctl = _run(conf, TINY_TRAFFIC, control=True)
    assert not ctl["correct"]
    failed = {k for k, v, lim in ctl["check"] if v > lim}
    assert {"fast_logit_err", "slow_logit_err", "conf_err"} <= failed


FLEET = {"streams": 4, "frames": 16, "pool": 1, "check_segments": 1,
         "trace_segments": 1, "warmup_segments": 1}


def _fleet_conf():
    return harness.load_json("bench/configs/fleet-cbo.json")


CELLS = {
    "resnet": lambda: (dict(TINY_RESNET, limits=_limits("resnet50-paper")), TINY_TRAFFIC),
    "fleet": lambda: (_fleet_conf(), FLEET),
}


# the synthetic slow tier answers alike at every rung, so a wrong rung is
# no fault the fleet configuration can have
CASES = [(cell, fault) for cell in sorted(CELLS)
         for fault in ("sound", "answer_altered", "half_left_out", "state_unchanged", "wrong_rung")
         if not (cell == "fleet" and fault == "wrong_rung")]


@pytest.mark.parametrize("cell,fault", CASES, ids=[f"{c}-{f}" for c, f in CASES])
def test_faults_in_the_timed_path_are_not_correct(cell, fault):
    conf, traffic = CELLS[cell]()
    plant = None if fault == "sound" else faults.FAULTS[fault]
    out = _run(conf, traffic, fault=plant, seed=5)
    assert out["correct"] is (plant is None), out["check"]


def test_control_plane_reference_matches_the_numpy_engine():
    """The plain control-plane reference replays a segment to the same
    counts as the program's own float64 numpy engine."""
    from bench.gen.synthetic import SyntheticTiers
    from bench.ref.control import replay

    conf = _fleet_conf()
    traffic = dict(FLEET, streams=16, frames=32)
    import jax

    from repro.core.netsim import Uplink, mbps
    from repro.net import EdgeFabric
    from repro.serving import MultiStreamServer, ServeConfig

    system = SyntheticTiers(conf, traffic, seed=3)
    frames, labels = system.segment(0)
    cfg = ServeConfig(resolutions=tuple(conf["resolutions"]), acc_server=tuple(conf["acc_server"]),
                      batch_size=8, frame_rate=32.0, deadline=0.2)
    fabric = EdgeFabric.degenerate(Uplink(bandwidth_bps=mbps(5.0 * 16), latency=0.05,
                                          server_time=0.037), n_streams=16)
    m = MultiStreamServer(cfg, system.fast, system.slow, lambda s: s, None, n_streams=16,
                          fabric=fabric, backend="numpy").process_streams(frames, labels)
    flat = frames.reshape(-1, *frames.shape[2:])
    lf = system.ref_fast(flat)
    # the engine's own float32 max-softmax, so that no confidence ties differ
    conf_ = np.asarray(jax.nn.softmax(lf, axis=-1).max(-1)).reshape(16, 32)
    fast_ok = lf.argmax(-1).reshape(16, 32) == labels
    slow_ok = np.repeat((system.ref_slow(flat, 224).argmax(-1).reshape(16, 32) == labels)[..., None],
                        len(conf["resolutions"]), -1)
    got = replay(harness.control_config(conf, traffic), conf_, fast_ok, slow_ok)
    assert got == {"frames": int(m.n_frames), "offloads": int(m.n_offloaded),
                   "misses": int(m.n_deadline_miss),
                   "correct": int(round(m.accuracy * m.n_frames))}
    assert got["offloads"] > 0
