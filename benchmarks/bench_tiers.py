"""Paper Table III: measured per-frame runtimes of the tiers + calibration
(on this CPU; the paper's NPU/GPU absolute numbers are quoted alongside)."""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from benchmarks.common import build_stack, out_path
from repro.models import api
from repro.models.transformer import ParallelPlan


def _time(fn, *args, n=20):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def run() -> dict:
    stack = build_stack()
    fh = api.build(C.FAST_CFG, ParallelPlan(remat=False))
    sh = api.build(C.SLOW_CFG, ParallelPlan(remat=False))
    imgs = jnp.asarray(stack.test["frames"][:32])

    fast_fn = jax.jit(lambda p, x: fh.forward(p, x))
    slow_fn = jax.jit(lambda p, x: sh.forward(p, x))
    t_fast = _time(fast_fn, stack.fast_params, imgs) / 32
    t_slow = _time(slow_fn, stack.slow_params, imgs) / 32

    logits = fast_fn(stack.fast_params, imgs)
    from repro.core.confidence import max_softmax

    calib_fn = jax.jit(lambda lg: stack.platt(max_softmax(lg)))
    t_calib = _time(calib_fn, logits) / 32

    out = {
        "measured_cpu_ms_per_frame": {
            "fast_tier": round(t_fast * 1e3, 3),
            "slow_tier": round(t_slow * 1e3, 3),
            "calibration": round(t_calib * 1e3, 4),
        },
        "paper_table3_ms": {"alexnet_npu": 20, "resnet152_server": 37, "calibration": 8},
        "ratio_slow_over_fast": round(t_slow / max(t_fast, 1e-9), 2),
    }
    with open(out_path("table3_tiers.json"), "w") as f:
        json.dump(out, f, indent=2)
    for k, v in out["measured_cpu_ms_per_frame"].items():
        print(f"bench_tiers/{k},ms_per_frame={v}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
