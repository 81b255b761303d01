"""Device time per segment of the fast and slow tier programs
(``jit_tier_fast`` and ``jit_tier_slow``), from the trace."""
from bench.metrics._common import module_seconds


def read(ctx):
    parts = [module_seconds(ctx["trace"], n) for n in ("jit_tier_fast", "jit_tier_slow")]
    if all(p is None for p in parts):
        return None
    return sum(p[0] for p in parts if p is not None) / ctx["out"]["segments"] * 1e3
