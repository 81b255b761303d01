"""Paper Fig. 10: slow-tier accuracy vs offload resolution ladder."""
from __future__ import annotations

import json

from benchmarks.common import RESOLUTIONS, build_stack, out_path


def run() -> dict:
    stack = build_stack()
    rows = [{"resolution": r, "accuracy": round(a, 4)}
            for r, a in zip(RESOLUTIONS, stack.acc_server_by_res)]
    out = {"ladder": rows, "fast_tier_acc": stack.acc_fast, "slow_tier_acc": stack.acc_slow}
    with open(out_path("fig10_resolution.json"), "w") as f:
        json.dump(out, f, indent=2)
    for r in rows:
        print(f"bench_resolution,res={r['resolution']},acc={r['accuracy']}")
    # monotone non-decreasing ladder is the paper's premise
    accs = [r["accuracy"] for r in rows]
    assert all(b >= a - 0.03 for a, b in zip(accs, accs[1:])), accs
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
