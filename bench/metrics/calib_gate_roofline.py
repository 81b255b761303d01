"""The fused calibrate+gate kernel's share of its roofline: the least time
its bytes take at HBM bandwidth (it is bytes bound) over its device time
per call (its program, ``jit_calib_gate``), from the trace."""
from bench.flops import calib_gate_bytes, peaks
from bench.metrics._common import module_seconds


def read(ctx):
    conf, traffic = ctx["conf"], ctx["traffic"]
    got = module_seconds(ctx["trace"], "jit_calib_gate")
    if got is None:
        return None
    secs, runs = got
    batch = int(traffic["streams"]) * int(conf["batch_size"])
    least = calib_gate_bytes(batch, int(conf["n_classes"])) / peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return least / (secs / runs) * 100
