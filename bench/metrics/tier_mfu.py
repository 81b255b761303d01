"""The data plane's whole-step share of the chip's bf16 peak: the ResNet-50
FLOPs the algorithm needs (one fast forward per frame, one slow forward
per escalated frame, offloaded or missed) per second of the traced window.
It counts what any implementation has to do, so work an implementation
wastes lowers it."""
from bench.flops import peaks, resnet_forward_flops


def read(ctx):
    conf, out = ctx["conf"], ctx["out"]
    if conf["tiers"] != "resnet_tiers" or ctx["trace"] is None:
        return None
    per_frame = resnet_forward_flops(conf["img_res"], conf["depths"], conf["width"],
                                     conf["n_classes"])
    flops = per_frame * (out["served"] + out["escalated"])
    return flops / out["window_s"] / peaks(ctx["device_kind"])["bf16_flops"] * 100
