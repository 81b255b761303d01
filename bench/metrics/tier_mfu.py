"""The data plane's whole-step share of the chip's bf16 peak: the FLOPs the
algorithm needs (one fast forward per frame, one slow forward per escalated
frame, offloaded or missed) per second of the traced window.  The FLOPs of
one forward of each tier come from the configuration's own tiers module
(``bench/gen/<tiers>.py::tier_flops``); a module without that hook gets no
reading.  It counts what any implementation has to do, so work an
implementation wastes lowers it."""
import importlib

from bench.flops import peaks


def read(ctx):
    conf, out = ctx["conf"], ctx["out"]
    tier_flops = getattr(importlib.import_module(f"bench.gen.{conf['tiers']}"), "tier_flops",
                         None)
    if tier_flops is None or ctx["trace"] is None:
        return None
    fast, slow = tier_flops(conf)
    flops = fast * out["served"] + slow * out["escalated"]
    return flops / out["window_s"] / peaks(ctx["device_kind"])["bf16_flops"] * 100
