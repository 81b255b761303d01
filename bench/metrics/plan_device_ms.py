"""Device time per round of the compiled round's planning stage: the self
time of the scan program's (``jit_run``'s) ops whose instructions carry the
``round.plan`` name scope (``bench/program_trace.py``)."""
from bench.program_trace import program_trace, stage_seconds


def read(ctx):
    got = program_trace(ctx)
    if got is None:
        return None
    red, smap, segments = got
    secs = stage_seconds(red.module_op_s.get("jit_run", {}), smap).get("round.plan")
    if not secs:
        return None
    rounds = segments * (int(ctx["traffic"]["frames"]) // int(ctx["conf"]["batch_size"]))
    return secs / rounds * 1e3
