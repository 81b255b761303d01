"""Micro-benchmark: tuple-chain reference vs vectorized frontier `cbo_plan`.

The serving loop re-plans every frame, so planner wall time is control-plane
latency.  Benchmarks the paper's Algorithm 1 at backlog k=64, m=5 in the
regime where such a backlog actually accumulates (frames arriving faster
than the deadline window drains, saturated uplink), plus lighter regimes,
and records old-vs-new wall time + speedup.  Run directly or via
``benchmarks/run.py``.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.policy import Env, Frame
from repro.policy.frontier import cbo_plan
from repro.policy.reference import cbo_plan_reference


def make_instance(k: int, m: int, *, fps: float, deadline: float,
                  bandwidth: float, seed: int = 0):
    rng = np.random.default_rng(seed)
    sizes_base = np.sort(rng.uniform(2e3, 6e4, size=m))
    frames = [Frame(arrival=i / fps, conf=float(rng.uniform(0.2, 0.99)),
                    sizes=tuple(sizes_base * rng.uniform(0.8, 1.2)))
              for i in range(k)]
    env = Env(bandwidth=bandwidth, latency=0.03, server_time=0.037,
              deadline=deadline, acc_server=tuple(np.sort(rng.uniform(0.6, 0.99, size=m))))
    return frames, env


def _time(fn, frames, env, repeats: int) -> float:
    fn(frames, env)  # warm-up
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(frames, env)
        best = min(best, time.perf_counter() - t0)
    return best


SCENARIOS = (
    # (name, k, m, fps, deadline) — "deep" is the acceptance regime
    ("deep_backlog_k64", 64, 5, 120.0, 1.0),
    ("mid_backlog_k32", 32, 5, 60.0, 0.5),
    ("shallow_k8", 8, 5, 30.0, 0.2),
)


def run(repeats: int = 15) -> dict:
    rows = []
    for name, k, m, fps, deadline in SCENARIOS:
        frames, env = make_instance(k, m, fps=fps, deadline=deadline, bandwidth=1.5e6)
        a = cbo_plan_reference(frames, env)
        b = cbo_plan(frames, env)
        assert a.offloads == b.offloads and a.total_gain == b.total_gain, name
        t_ref = _time(cbo_plan_reference, frames, env, repeats)
        t_vec = _time(cbo_plan, frames, env, repeats)
        row = {"scenario": name, "k": k, "m": m,
               "ref_us": round(t_ref * 1e6, 1), "vec_us": round(t_vec * 1e6, 1),
               "speedup": round(t_ref / t_vec, 2), "n_offloads": len(b.offloads)}
        rows.append(row)
        print(f"bench_policy_planner,{name},ref_us={row['ref_us']},"
              f"vec_us={row['vec_us']},speedup={row['speedup']}", flush=True)
    from benchmarks.common import emit_bench_json

    out = {"rows": rows}
    emit_bench_json("BENCH_planner.json", out, mirror="policy_planner.json")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
