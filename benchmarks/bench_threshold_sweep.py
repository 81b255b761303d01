"""Paper Fig. 4 (uncalibrated) + Fig. 7a (calibrated): accuracy and offload
fraction vs confidence threshold theta."""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np

from benchmarks.common import build_stack, out_path
from repro.models import api
from repro.models.transformer import ParallelPlan
from benchmarks import common as C


def run() -> dict:
    stack = build_stack()
    frames, labels = stack.test["frames"], stack.test["labels"]
    fh = api.build(C.FAST_CFG, ParallelPlan(remat=False))
    sh = api.build(C.SLOW_CFG, ParallelPlan(remat=False))

    # precompute both tiers' predictions + calibrated/uncalibrated conf
    from benchmarks.common import _accuracy

    _, fl = _accuracy(fh.forward, stack.fast_params, frames, labels)
    _, sl = _accuracy(sh.forward, stack.slow_params, frames, labels)
    fast_pred, slow_pred = np.argmax(fl, -1), np.argmax(sl, -1)
    from repro.core.confidence import max_softmax

    conf_raw = np.asarray(max_softmax(jnp.asarray(fl)))
    conf_cal = np.asarray(stack.platt(conf_raw))

    def sweep(conf):
        rows = []
        for theta in np.linspace(0, 1, 21):
            offload = conf < theta
            pred = np.where(offload, slow_pred, fast_pred)
            rows.append({"theta": round(float(theta), 3),
                         "accuracy": float((pred == labels).mean()),
                         "offload_frac": float(offload.mean())})
        return rows

    out = {"uncalibrated_fig4": sweep(conf_raw), "calibrated_fig7a": sweep(conf_cal)}
    with open(out_path("fig4_7_threshold_sweep.json"), "w") as f:
        json.dump(out, f, indent=2)

    # paper claim: to reach a mid accuracy target, calibrated needs far less
    # offload than uncalibrated at matched accuracy
    for name, rows in out.items():
        for r in rows[::4]:
            print(f"bench_threshold/{name},theta={r['theta']},acc={r['accuracy']:.3f},offload={r['offload_frac']:.3f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
