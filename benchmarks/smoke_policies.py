"""CI smoke: run EVERY registered policy through the unified replay engine.

Guards the registry against silently-broken entries: each policy must
construct via ``make_policy(name)``, replay a tiny synthetic trace without
raising, and return sane results.  Pure numpy + the policy plane — no JAX,
no model training — so it runs in seconds on a CI box.

  PYTHONPATH=src python benchmarks/smoke_policies.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.policy import Env, available_policies, make_policy, replay_trace


def tiny_trace(n: int = 90, m: int = 3, seed: int = 0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 5, size=n)
    local_pred = np.where(rng.uniform(size=n) < 0.6, labels, (labels + 1) % 5)
    slow_pred = np.stack([np.where(rng.uniform(size=n) < acc, labels, (labels + 2) % 5)
                          for acc in (0.7, 0.8, 0.9)])
    conf = rng.uniform(0.3, 0.99, size=n)
    sizes = [2e3, 8e3, 2e4]
    env = Env(bandwidth=5e5, latency=0.05, server_time=0.037, deadline=0.25,
              acc_server=(0.65, 0.78, 0.88))
    return labels, local_pred, slow_pred, conf, sizes, env


# registry entries that need constructor arguments in a live deployment get
# them here; everything else must work with defaults
POLICY_CFG = {"server": dict(frame_interval=1.0 / 30.0),
              "greedy-rate": dict(local_acc=0.6),
              "threshold": dict(theta=0.6)}


def main() -> int:
    labels, local_pred, slow_pred, conf, sizes, env = tiny_trace()
    failures = []
    for name in available_policies():
        try:
            policy = make_policy(name, **POLICY_CFG.get(name, {}))
            result = replay_trace(policy, conf=conf, slow_pred=slow_pred, sizes=sizes,
                                  env=env, frame_interval=1.0 / 30.0,
                                  local_pred=local_pred,
                                  window=30 if name == "optimal" else 0)
            acc = result.accuracy(labels)
            assert 0.0 <= acc <= 1.0
            assert len(result.results) == len(labels)
            print(f"smoke_policies,{name},acc={acc:.4f},"
                  f"offloaded={result.n_offloaded},late={result.n_late}", flush=True)
        except Exception as e:  # noqa: BLE001 — report every broken entry
            failures.append((name, repr(e)))
            print(f"smoke_policies,{name},FAILED: {e!r}", flush=True)
    if failures:
        print(f"{len(failures)} broken registry entries: {[n for n, _ in failures]}")
        return 1
    print(f"all {len(available_policies())} registered policies replay cleanly")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
