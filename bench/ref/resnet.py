"""Plain reference of the ResNet-50 tiers (arXiv:1512.03385, v1.5 bottleneck,
BatchNorm folded to a per-channel scale and bias), of their int4 copy, of
the calibrated confidence and of the server's reduced-resolution input.

The parameters are the benchmark's own (``bench/gen/resnet_tiers.py``),
laid out as the served model reads them: ``stem``, ``stage<i>/b<j>/{c1,
c2, c3[, proj]}`` with ``w`` (HWIO), ``scale`` and ``bias``, and ``head``
with ``w`` and ``b``.  Nothing here imports the program.

Precision.  The configuration states what the served tiers compute.
``mode="default"`` is float32 values whose convolutions and matrix products
take one bfloat16 pass (the TPU's default precision for float32): operands
rounded to bfloat16, products accumulated in float32 at ``HIGHEST``
precision so that nothing else is rounded.  ``mode="highest"`` is float32
throughout.  ``mode="bf16"`` is the control, one step below the default:
every value is held in bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
HI = jax.lax.Precision.HIGHEST


def _dot_dtype(mode):
    return BF16 if mode == "bf16" else F32


def _operand(x, mode):
    """A matmul operand as the mode's one pass sees it."""
    return x.astype(BF16).astype(F32) if mode == "default" else x


def conv(x, w, stride, mode):
    """NHWC x HWIO convolution, SAME padding, at the mode's precision."""
    dn = ("NHWC", "HWIO", "NHWC")
    if mode != "bf16":
        return jax.lax.conv_general_dilated(_operand(x, mode), _operand(w, mode),
                                            (stride, stride), "SAME",
                                            dimension_numbers=dn, precision=HI)
    return jax.lax.conv_general_dilated(x.astype(BF16), w.astype(BF16), (stride, stride),
                                        "SAME", dimension_numbers=dn)


def _unit(p, x, mode, stride=1, act=True):
    dt = _dot_dtype(mode)
    y = conv(x, p["w"], stride, mode).astype(dt) * p["scale"].astype(dt) + p["bias"].astype(dt)
    return jax.nn.relu(y) if act else y


def forward(params, images, depths, mode="default"):
    """(B, H, W, 3) images -> (B, classes) float32 logits."""
    dt = _dot_dtype(mode)
    x = images.astype(dt)
    x = _unit(params["stem"], x, mode, stride=2)
    x = jax.lax.reduce_window(x, jnp.asarray(-jnp.inf, dt), jax.lax.max,
                              (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for i, dep in enumerate(depths):
        for b in range(dep):
            p = params[f"stage{i}"][f"b{b}"]
            stride = 2 if (b == 0 and i > 0) else 1
            y = _unit(p["c1"], x, mode)
            y = _unit(p["c2"], y, mode, stride=stride)
            y = _unit(p["c3"], y, mode, act=False)
            idn = _unit(p["proj"], x, mode, stride=stride, act=False) if "proj" in p else x
            x = jnp.maximum(y + idn, 0)
    feat = jnp.mean(x.astype(F32), axis=(1, 2)).astype(dt)
    w = params["head"]["w"]
    if mode != "bf16":
        logits = jnp.dot(_operand(feat, mode), _operand(w, mode), precision=HI)
    else:
        logits = jnp.dot(feat, w.astype(BF16)).astype(F32)
    return logits + params["head"]["b"].astype(dt).astype(F32)


def int4_copy(params):
    """Symmetric per-tensor int4 quantize-dequantize of every weight matrix
    and kernel (``w`` leaves of two or more dimensions); scales and biases
    stay as they are."""
    def q(path, x):
        if path[-1].key != "w" or x.ndim < 2:
            return x
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 7.0
        return jnp.clip(jnp.round(x / s), -7, 7) * s
    return jax.tree_util.tree_map_with_path(q, params)


def degrade(images, res):
    """An upload at ``res`` pixels, scaled back to the model's input size."""
    B, H, W, C = images.shape
    if res >= H:
        return images
    small = jax.image.resize(images, (B, res, res, C), "bilinear")
    return jax.image.resize(small, (B, H, W, C), "bilinear")


def calibrated_confidence(logits, a, b):
    """Platt-calibrated max-softmax: sigmoid(-(a * max softmax + b))."""
    p = jax.nn.softmax(logits, axis=-1)
    return jax.nn.sigmoid(-(a * jnp.max(p, axis=-1) + b))
