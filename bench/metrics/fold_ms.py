"""Host time per segment that the serving bridge spends folding the
compiled round's outputs back into its host objects (the program's
``PhaseProfiler`` "fold" phase)."""


def read(ctx):
    prof = ctx["out"]["profiler"]
    if prof is None or "fold" not in prof.totals:
        return None
    return prof.totals["fold"] / prof.counts["fold"] * 1e3
