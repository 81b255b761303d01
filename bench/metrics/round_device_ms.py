"""Device time per round of the compiled control plane (the scan program,
``jit_run``), from the trace."""
from bench.metrics._common import module_seconds


def read(ctx):
    got = module_seconds(ctx["trace"], "jit_run")
    if got is None:
        return None
    traffic, conf = ctx["traffic"], ctx["conf"]
    rounds = ctx["out"]["segments"] * (int(traffic["frames"]) // int(conf["batch_size"]))
    return got[0] / rounds * 1e3
