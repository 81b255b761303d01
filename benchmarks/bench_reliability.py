"""Paper Fig. 5 (uncalibrated) + Fig. 7b (calibrated): accuracy vs confidence
bins — the reliability diagram that motivates calibration."""
from __future__ import annotations

import json

import numpy as np

from benchmarks.common import build_stack, out_path
from repro.core.calibration import reliability_bins


def run() -> dict:
    stack = build_stack()
    conf, correct = stack.calib["conf"], stack.calib["correct"]
    cal = np.asarray(stack.platt(conf))

    def bins(c):
        count, acc, mean_conf = reliability_bins(c, correct, 10)
        return [{"bin": i, "count": int(count[i]), "accuracy": round(float(acc[i]), 4),
                 "mean_conf": round(float(mean_conf[i]), 4)} for i in range(10)]

    out = {"uncalibrated_fig5": bins(conf), "calibrated_fig7b": bins(cal)}

    # paper claim: calibrated accuracy spans a much wider range across bins
    def span(rows):
        a = [r["accuracy"] for r in rows if r["count"] > 5]
        return (max(a) - min(a)) if a else 0.0

    out["span_uncalibrated"] = round(span(out["uncalibrated_fig5"]), 4)
    out["span_calibrated"] = round(span(out["calibrated_fig7b"]), 4)
    with open(out_path("fig5_7b_reliability.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(f"bench_reliability/span,uncal={out['span_uncalibrated']},cal={out['span_calibrated']}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
