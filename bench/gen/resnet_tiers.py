"""The ResNet-50 tiers of the paper's deployment, built from the seed.

The weights are the benchmark's own, made on the device in one jitted call:
He-normal convolution kernels, and BatchNorm folded to each unit's scale
and bias from the statistics of a seeded batch of frames, as a served
network's frozen BatchNorm is folded from its running statistics.  The
last unit of each residual branch starts at a small gain (``branch_gain``),
as a trained ResNet's last BatchNorm does, so that the logits depend on the
frame rather than on depth-amplified noise; the head is scaled to a
classifier's logit spread and centred on the seeded batch.

The slow tier serves these weights; the fast tier serves the program's own
int4 quantize-dequantize of them (the phone NPU's model), its head bias
centred on the same batch, since quantizing the head shifts every frame's
logits by one common offset.  That bias, Platt's calibration of the fast
tier's confidence against agreement with the slow tier, and the labels (the
slow tier's answers at full resolution) are computed by the plain reference
(``bench/ref/resnet.py``) at the configuration's stated precision, so that
nothing the check compares against is made by the program; that work runs
inside ``aside``, which set-up leaves out.  This follows the program's
``chip_smoke.py::build_workload``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import resnet_forward_flops
from bench.gen import video
from bench.ref import resnet as ref


@partial(jax.jit, static_argnames=("depths", "width", "n_classes", "branch_gain", "logit_std",
                                   "mode"))
def make_params(key, frames, *, depths, width, n_classes, branch_gain, logit_std, mode):
    """Seeded weights with BatchNorm folded from ``frames``' statistics."""
    keys = iter(jax.random.split(key, 4 * sum(depths) + 8))

    def unit(x, cin, cout, k, stride, act=True, gain=1.0):
        w = jax.random.normal(next(keys), (k, k, cin, cout), jnp.float32) / math.sqrt(k * k * cin)
        y = ref.conv(x, w, stride, mode)
        mean, std = jnp.mean(y, axis=(0, 1, 2)), jnp.std(y, axis=(0, 1, 2)) + 1e-5
        p = {"w": w, "scale": gain / std, "bias": -gain * mean / std}
        y = y * p["scale"] + p["bias"]
        return p, (jax.nn.relu(y) if act else y)

    params = {}
    params["stem"], x = unit(frames, 3, width, 7, 2)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    cin = width
    for i, dep in enumerate(depths):
        mid = width * 2**i
        cout = mid * 4
        stage = {}
        for b in range(dep):
            stride = 2 if (b == 0 and i > 0) else 1
            blk = {}
            blk["c1"], y = unit(x, cin, mid, 1, 1)
            blk["c2"], y = unit(y, mid, mid, 3, stride)
            blk["c3"], y = unit(y, mid, cout, 1, 1, act=False, gain=branch_gain)
            if b == 0:
                blk["proj"], idn = unit(x, cin, cout, 1, stride, act=False)
            else:
                idn = x
            x = jnp.maximum(y + idn, 0)
            stage[f"b{b}"] = blk
            cin = cout
        params[f"stage{i}"] = stage
    feat = jnp.mean(x, axis=(1, 2))
    w = jax.random.normal(next(keys), (cin, n_classes), jnp.float32)
    logits = jnp.dot(feat, w, precision=ref.HI)
    w = w * (logit_std / jnp.std(logits))
    params["head"] = {"w": w, "b": -jnp.mean(logits, axis=0) * (logit_std / jnp.std(logits))}
    return params


def platt_fit(scores, agree, n_iter=50):
    """Platt's sigmoid fit, float64 Newton steps with target smoothing:
    calibrated = sigmoid(-(a * score + b))."""
    s = np.asarray(scores, np.float64)
    pos = np.asarray(agree) > 0.5
    n_pos, n_neg = float(pos.sum()), float((~pos).sum())
    y = np.where(pos, (n_pos + 1) / (n_pos + 2), 1.0 / (n_neg + 2))
    a, b = -1.0, 0.0
    for _ in range(n_iter):
        p = 1.0 / (1.0 + np.exp(a * s + b))  # sigmoid(-(a s + b))
        # d nll / d(a, b) and its Hessian, for z = -(a s + b)
        r = p - y
        g = np.array([-np.mean(r * s), -np.mean(r)])
        w = p * (1 - p)
        h = np.array([[np.mean(w * s * s), np.mean(w * s)],
                      [np.mean(w * s), np.mean(w)]]) + 1e-6 * np.eye(2)
        a, b = np.array([a, b]) - np.linalg.solve(h, g)
    return float(a), float(b)


def build(conf: dict, traffic: dict, seed: int, aside):
    return ResNetTiers(conf, traffic, seed, aside)


def tier_flops(conf: dict) -> tuple[int, int]:
    """FLOPs of one fast and one slow forward at the configuration's input
    size: the same network, so the same count (the fast tier's int4 weights
    are dequantized and run as the slow tier's are)."""
    f = resnet_forward_flops(conf["img_res"], conf["depths"], conf["width"], conf["n_classes"])
    return f, f


class ResNetTiers:
    """Both tiers, their calibration and the traffic's frames and labels."""

    def __init__(self, conf: dict, traffic: dict, seed: int, aside):
        from repro.configs.base import ResNetConfig
        from repro.models import api
        from repro.quant.quantize import qdq_tree

        self.depths = tuple(conf["depths"])
        self.res = int(conf["img_res"])
        self.n_classes = int(conf["n_classes"])
        self.batch = int(traffic["streams"]) * int(conf["batch_size"])  # one round
        self.resolutions = tuple(conf["resolutions"])
        # every bit of the seed: the low 31 make the key, the rest are folded in
        key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
        k_w, k_cal, k_platt, k_seg = jax.random.split(key, 4)
        self.seg_key = k_seg
        self.traffic = traffic
        self.mode = conf["matmul_precision"]
        gen = self.gen = partial(video.segment, res=self.res, n_classes=self.n_classes,
                                 noise_floor=float(conf["noise_floor"]))
        # one frame from each of many videos, as running statistics see them
        cal = gen(k_cal, n_streams=int(conf["bn_frames"]), n_frames=1).reshape(
            -1, self.res, self.res, 3)
        self.params = make_params(k_w, cal, depths=self.depths, width=int(conf["width"]),
                                  n_classes=self.n_classes,
                                  branch_gain=float(conf["branch_gain"]),
                                  logit_std=float(conf["logit_std"]), mode=self.mode)
        self.ref_fwd = jax.jit(partial(ref.forward, depths=self.depths, mode=self.mode))
        self.aside = aside
        jax.block_until_ready((cal, self.params))
        with aside:
            q = ref.int4_copy(self.params)
            fast_bias = q["head"]["b"] - jnp.mean(self.ref_fwd(q, cal), axis=0)
            self.ref_fast_params = jax.block_until_ready({**q, "head": {**q["head"], "b": fast_bias}})

        model = ResNetConfig(name="resnet-50", img_res=self.res, depths=self.depths,
                             width=int(conf["width"]), n_classes=self.n_classes)
        forward = api.build(model).forward

        # one named program per tier, so that the trace tells them apart
        @jax.jit
        def tier_fast(params, x):
            return forward(params, x)

        @jax.jit
        def tier_slow(params, x):
            return forward(params, x)

        fast_params = qdq_tree(self.params, bits=int(conf["fast_bits"]), axis=None)
        fast_params = {**fast_params, "head": {**fast_params["head"], "b": fast_bias}}
        self.fast = lambda x: tier_fast(fast_params, x)
        self.slow = lambda x: tier_slow(self.params, x)
        self._programs = {"jit_tier_fast": (tier_fast, fast_params),
                          "jit_tier_slow": (tier_slow, self.params)}

        # calibration split: one round's worth of frames
        calib = jax.block_until_ready(gen(k_platt, n_streams=self.batch // 32 or 1, n_frames=32)
                                      .reshape(-1, self.res, self.res, 3)[: self.batch])
        with aside:
            fast_logits = self.ref_fwd(self.ref_fast_params, calib)
            slow_answer = jnp.argmax(self.ref_fwd(self.params, calib), -1)
            agree = np.asarray(jnp.argmax(fast_logits, -1) == slow_answer)
            msp = np.asarray(jnp.max(jax.nn.softmax(fast_logits, -1), -1))
            self.platt = platt_fit(msp, agree)
        self.agreement = float(agree.mean())
        if not 0.0 < self.agreement < 1.0:
            raise RuntimeError(f"fast/slow agreement {self.agreement}: nothing would escalate")

    def program_texts(self) -> dict:
        """Each tier program's compiled text at one round's input (S x B
        float32 frames at the configuration's size), as the bridge calls
        it: the program the trace ran, so a persistent-cache hit."""
        x = jax.ShapeDtypeStruct((self.batch, self.res, self.res, 3), jnp.float32)
        return {name: fn.lower(params, x).compile().as_text()
                for name, (fn, params) in self._programs.items()}

    def segment(self, i: int):
        """Segment ``i``'s (S, N, H, W, 3) host frames and (S, N) labels."""
        S, N = int(self.traffic["streams"]), int(self.traffic["frames"])
        frames = self.gen(jax.random.fold_in(self.seg_key, i), n_streams=S, n_frames=N)
        host = np.asarray(frames)
        flat = frames.reshape(-1, self.res, self.res, 3)
        with self.aside:
            labels = np.asarray(jnp.concatenate([
                jnp.argmax(self.ref_fwd(self.params, flat[j:j + self.batch]), -1)
                for j in range(0, S * N, self.batch)]))
        return host, labels.reshape(S, N)

    def ref_fast(self, x):
        return np.asarray(self.ref_fwd(self.ref_fast_params, jnp.asarray(x)))

    def ref_slow(self, x, res):
        return np.asarray(self.ref_fwd(self.params, ref.degrade(jnp.asarray(x), res)))

    def control_tiers(self):
        """The reference one precision step below the stated one, as
        (fast, slow) callables in the program's place."""
        fwd = jax.jit(partial(ref.forward, depths=self.depths, mode="bf16"))
        return (lambda x: fwd(self.ref_fast_params, x), lambda x: fwd(self.params, x))

    def control_conf(self, logits):
        """The calibrated confidence computed in bfloat16: the gate's control."""
        a, b = self.platt
        return ref.calibrated_confidence(logits.astype(jnp.bfloat16), a, b)
