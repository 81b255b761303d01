"""Paper Table I: ECE/MCE for uncalibrated vs Platt vs Isotonic
(+temperature scaling as a beyond-paper extra)."""
from __future__ import annotations

import json

import numpy as np

from benchmarks.common import build_stack, out_path
from repro.core.calibration import IsotonicCalibrator, PlattCalibrator, TemperatureCalibrator, ece, mce


def run() -> dict:
    stack = build_stack()
    conf, correct = stack.calib["conf"], stack.calib["correct"]
    logits, labels = stack.calib["logits"], stack.calib["labels"]
    # fit on one half, evaluate on the other (holdout, as deployed)
    n = len(conf) // 2
    platt = PlattCalibrator.fit(conf[:n], correct[:n])
    iso = IsotonicCalibrator.fit(conf[:n], correct[:n])
    temp = TemperatureCalibrator.fit(logits[:n], labels[:n])

    rows = {}
    rows["uncalibrated"] = {"ece": ece(conf[n:], correct[n:]), "mce": mce(conf[n:], correct[n:])}
    rows["platt"] = {"ece": ece(np.asarray(platt(conf[n:])), correct[n:]),
                     "mce": mce(np.asarray(platt(conf[n:])), correct[n:])}
    rows["isotonic"] = {"ece": ece(np.asarray(iso(conf[n:])), correct[n:]),
                        "mce": mce(np.asarray(iso(conf[n:])), correct[n:])}
    rows["temperature"] = {"ece": ece(np.asarray(temp(logits[n:])), correct[n:]),
                           "mce": mce(np.asarray(temp(logits[n:])), correct[n:])}
    out = {"table": rows, "paper": {"uncalibrated": {"ece": 0.27, "mce": 0.48},
                                    "platt": {"ece": 0.07, "mce": 0.29},
                                    "isotonic": {"ece": 0.16, "mce": 0.41}}}
    with open(out_path("table1_calibration.json"), "w") as f:
        json.dump(out, f, indent=2)
    for k, v in rows.items():
        print(f"bench_calibration/{k},ece={v['ece']:.4f},mce={v['mce']:.4f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
