"""Calibration workflow: fit Platt / isotonic / temperature on a calibration
split, compare ECE/MCE (paper Table I), then plan offloads with Algorithm 1
under a live bandwidth estimate.

  PYTHONPATH=src:benchmarks python examples/calibrate_and_deploy.py
"""
import sys

import numpy as np


def main():
    import os

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import RESOLUTIONS, build_stack

    from repro.core.calibration import IsotonicCalibrator, PlattCalibrator, ece, mce
    from repro.core.netsim import mbps, png_size_model
    from repro.policy import Env, Frame, make_policy
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    stack = build_stack()
    conf, correct = stack.calib["conf"], stack.calib["correct"]
    n = len(conf) // 2
    print("=== calibration quality (holdout) ===")
    print(f"{'method':14s} {'ECE':>7s} {'MCE':>7s}")
    print(f"{'uncalibrated':14s} {ece(conf[n:], correct[n:]):7.3f} {mce(conf[n:], correct[n:]):7.3f}")
    for name, cal in [("platt", PlattCalibrator.fit(conf[:n], correct[:n])),
                      ("isotonic", IsotonicCalibrator.fit(conf[:n], correct[:n]))]:
        c = np.asarray(cal(conf[n:]))
        print(f"{name:14s} {ece(c, correct[n:]):7.3f} {mce(c, correct[n:]):7.3f}")

    # deploy: plan the next offloads from a backlog of 8 frames through the
    # policy plane (any registered policy works here — docs/policies.md)
    platt = PlattCalibrator.fit(conf, correct)
    cal = np.asarray(platt(conf[:8]))
    frames = [Frame(arrival=i / 30.0, conf=float(cal[i]),
                    sizes=tuple(png_size_model(r, base_res=32, base_bytes=60000.0) for r in RESOLUTIONS))
              for i in range(8)]
    env = Env(bandwidth=mbps(5.0), latency=0.1, server_time=0.037, deadline=0.2,
              acc_server=stack.acc_server_by_res)
    policy = make_policy("cbo")
    policy.observe(frames)
    plan = policy.plan(0.0, env)
    print("\n=== CBO plan @5 Mbps ===")
    print(f"theta={plan.theta:.3f}  resolution={RESOLUTIONS[plan.resolution]}px")
    print(f"planned offloads (frame, res): {[(i, RESOLUTIONS[r]) for i, r in plan.offloads]}")
    print(f"expected accuracy gain: +{plan.total_gain:.2f} over {len(frames)} frames")


if __name__ == "__main__":
    main()
