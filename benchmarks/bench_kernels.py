"""Kernel micro-benchmarks: correctness (interpret) + CPU-reference timings.

Wall-clock here times the jnp reference path (the Pallas kernels target TPU;
interpret mode is a correctness tool, not a perf path). The derived column
reports the ideal v5e kernel time from the roofline model for context.

``--batch-sweep`` additionally times a slow-tier-shaped forward pass
(flash-attention + int8 matmul at small serving shapes) across batch
sizes and fits the f(batch) latency curves from ``repro.slowtier.calibrate``
to the measurements — the calibration source for ``ContinuousBatching``
(docs/network.md has the recipe).  The winning fit lands in
``results/bench/BENCH_kernels.json`` under ``batch_fit``, ready for
``bench_slowtier.py --coeffs-from``.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit_bench_json, out_path
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.int8_matmul import ref as i8ref
from repro.kernels.fused_calib_gate.ref import calib_gate_ref
from repro.launch.roofline import HBM_BW, PEAK_FLOPS_BF16, PEAK_FLOPS_INT8


def _time(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


# batch-sweep shapes: one "request" is a small serving-sized forward slice
# (seq=256 attention + a 512x512 projection); batch stacks requests along
# the leading axis exactly the way a continuous-batching replica would
BATCH_SIZES = (1, 2, 4, 8, 16, 32)
SWEEP_S, SWEEP_H, SWEEP_D = 256, 4, 64
SWEEP_ROWS, SWEEP_K, SWEEP_N = 32, 512, 512


def batch_sweep(n_timing: int = 5) -> dict:
    """Time f(batch) on the reference tiers and fit the latency curves."""
    from repro.slowtier import fit_latency_model, model_coeffs

    attn = jax.jit(lambda q: attention_ref(q, q, q, causal=True))
    mm = jax.jit(i8ref.matmul_ref)
    rows = []
    for b in BATCH_SIZES:
        q = jax.random.normal(jax.random.PRNGKey(0),
                              (b, SWEEP_S, SWEEP_H, SWEEP_D), jnp.bfloat16)
        x = jax.random.normal(jax.random.PRNGKey(0),
                              (b * SWEEP_ROWS, SWEEP_K), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1),
                              (SWEEP_K, SWEEP_N), jnp.float32)
        t_attn = _time(attn, q, n=n_timing)
        t_mm = _time(mm, x, w, n=n_timing)
        rows.append({"batch": b, "attn_us": round(t_attn * 1e6, 1),
                     "matmul_us": round(t_mm * 1e6, 1),
                     "total_s": t_attn + t_mm})
    ns = np.array([r["batch"] for r in rows], dtype=np.float64)
    ys = np.array([r["total_s"] for r in rows])
    fits = {}
    for kind in ("flat", "linear", "step"):
        model, rmse = fit_latency_model(ns, ys, kind=kind)
        k, coeffs = model_coeffs(model)
        fits[kind] = {"kind": k, "coeffs": [float(c) for c in coeffs],
                      "rmse_us": round(rmse * 1e6, 2)}
    best_kind = min(fits, key=lambda k: fits[k]["rmse_us"])
    out = {"batch_sizes": list(BATCH_SIZES), "rows": rows,
           "fits": fits, "batch_fit": fits[best_kind]}
    for r in rows:
        print(f"bench_kernels/batch_sweep,batch={r['batch']},"
              f"attn_us={r['attn_us']},matmul_us={r['matmul_us']},"
              f"total_us={round(r['total_s'] * 1e6, 1)}")
    print(f"bench_kernels/batch_fit,kind={best_kind},"
          f"coeffs={fits[best_kind]['coeffs']},"
          f"rmse_us={fits[best_kind]['rmse_us']}")
    return out


def run(args=None) -> dict:
    rows = []

    M, K, N = 1024, 4096, 4096
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    t = _time(jax.jit(i8ref.matmul_ref), x, w)
    ideal = 2 * M * K * N / PEAK_FLOPS_INT8
    rows.append({"kernel": "int8_matmul_ref", "shape": f"{M}x{K}x{N}",
                 "us_per_call": round(t * 1e6, 1), "v5e_ideal_us": round(ideal * 1e6, 2)})

    B, S, H, D = 2, 2048, 8, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), jnp.bfloat16)
    t = _time(jax.jit(lambda q: attention_ref(q, q, q, causal=True)), q)
    flops = 4 * B * H * S * S * D / 2
    rows.append({"kernel": "flash_attention_ref", "shape": f"b{B}s{S}h{H}d{D}",
                 "us_per_call": round(t * 1e6, 1), "v5e_ideal_us": round(flops / PEAK_FLOPS_BF16 * 1e6, 2)})

    Bv, V = 256, 102_400
    lg = jax.random.normal(jax.random.PRNGKey(0), (Bv, V), jnp.float32)
    t = _time(jax.jit(lambda l: calib_gate_ref(l, -6.0, 2.0, 0.7)), lg)
    ideal = Bv * V * 4 / HBM_BW  # memory-bound single pass
    rows.append({"kernel": "fused_calib_gate_ref", "shape": f"{Bv}x{V}",
                 "us_per_call": round(t * 1e6, 1), "v5e_ideal_us": round(ideal * 1e6, 2)})

    with open(out_path("kernels_micro.json"), "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        print(f"bench_kernels/{r['kernel']},us_per_call={r['us_per_call']},derived=v5e_ideal_us:{r['v5e_ideal_us']}")

    out = {"rows": rows}
    if args is not None and args.batch_sweep:
        out.update(batch_sweep(n_timing=args.timing_reps))
    emit_bench_json("BENCH_kernels.json", out)
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch-sweep", action="store_true",
                    help="also sweep f(batch) and fit the slow-tier curves")
    ap.add_argument("--timing-reps", type=int, default=5)
    return ap.parse_args(argv)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(parse_args())
