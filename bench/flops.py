"""Operations and bytes the served work needs, computed from shapes, and
the table of the chips' published peaks.

These are the yardstick for ``tier_mfu`` and ``calib_gate_roofline``: what
the algorithm has to do, whatever implements it.  ``tier_mfu`` takes a
configuration's FLOPs from its tiers module's ``tier_flops(conf)`` hook
(``bench/harness.py``): ``resnet_tiers`` returns ``resnet_forward_flops``;
another architecture's count lives beside its own tiers module, so that
adding it edits no file here.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def resnet_forward_flops(img_res: int, depths, width: int, n_classes: int) -> int:
    """Multiply-adds x 2 of one ResNet-v1.5 forward pass (bottleneck blocks,
    stride on the 3x3 convolution, SAME padding): convolutions and the head.
    Pooling, BatchNorm and additions are left out, as is usual."""
    def conv(h, cin, cout, k, stride):
        ho = -(-h // stride)
        return 2 * ho * ho * k * k * cin * cout, ho

    total, h = conv(img_res, 3, width, 7, 2)
    h = -(-h // 2)  # 3x3 max pool, stride 2
    cin = width
    for i, dep in enumerate(depths):
        mid = width * 2**i
        cout = mid * 4
        for b in range(dep):
            stride = 2 if (b == 0 and i > 0) else 1
            f1, _ = conv(h, cin, mid, 1, 1)
            f2, ho = conv(h, mid, mid, 3, stride)
            f3, _ = conv(ho, mid, cout, 1, 1)
            total += f1 + f2 + f3
            if b == 0:
                total += conv(h, cin, cout, 1, stride)[0]
            h, cin = ho, cout
    return total + 2 * cin * n_classes


def calib_gate_bytes(batch: int, classes: int) -> int:
    """The fused gate's least HBM traffic: float32 logits read once, the
    float32 confidence and int8 gate written, three float32 scalars read."""
    return batch * classes * 4 + batch * (4 + 1) + 3 * 4

