"""Device idle per traced segment while the serving bridge's own spans
(the program's ``repro.*`` spans) were open (``bench/program_trace.py``)."""
from bench.program_trace import program_trace


def read(ctx):
    got = program_trace(ctx)
    if got is None:
        return None
    red, _, segments = got
    if not any(name.startswith("repro.") for name in red.span_s):
        return None
    return red.idle_in_program_s / segments * 1e3
