"""Planted-signal stand-ins for the phones' NPU outputs, at fleet scale.

A copy of the program's ``serving/synthetic.py`` streams and tiers, so that
the benchmark's traffic cannot move with the program.  Each frame is a
2x2x4 array whose pixel (0, 0) carries its label's channel at 2.0 over
Gaussian noise; the fast tier reads that pixel plus pixel (1, 1) (signal
plus noise), the slow tier reads it alone (near oracle).  The data plane
is nearly free, so the control plane does the work.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

CLASSES = 4


def fast(images):
    return images[:, 0, 0, :CLASSES] + images[:, 1, 1, :CLASSES]


def slow(images):
    return images[:, 0, 0, :CLASSES] * 10.0


def build(conf: dict, traffic: dict, seed: int, aside=None):
    return SyntheticTiers(conf, traffic, seed)


class SyntheticTiers:
    """The two closed-form tiers and the traffic's frames and labels."""

    def __init__(self, conf: dict, traffic: dict, seed: int):
        self.res = int(conf["img_res"])
        self.traffic = traffic
        self.seed = seed
        self.fast, self.slow = fast, slow
        self.platt = None  # confidence is the plain max-softmax

    def segment(self, i: int):
        S, N = int(self.traffic["streams"]), int(self.traffic["frames"])
        rng = np.random.default_rng((self.seed, i))
        labels = rng.integers(0, CLASSES, size=(S, N))
        imgs = (rng.standard_normal((S, N, self.res, self.res, CLASSES), dtype=np.float32)
                * np.float32(0.8))
        s_idx, f_idx = np.meshgrid(np.arange(S), np.arange(N), indexing="ij")
        imgs[s_idx, f_idx, 0, 0, labels] = 2.0
        return imgs, labels

    def ref_fast(self, x):
        x = np.asarray(x, np.float32)
        return x[:, 0, 0, :CLASSES] + x[:, 1, 1, :CLASSES]

    def ref_slow(self, x, res):
        # every rung of the ladder is at least the frame's size: no resize
        return np.asarray(x, np.float32)[:, 0, 0, :CLASSES] * np.float32(10.0)

    def control_tiers(self):
        """The tiers in bfloat16, in the program's place."""
        bf = jnp.bfloat16
        return (lambda x: fast(x.astype(bf)).astype(jnp.float32),
                lambda x: (x.astype(bf)[:, 0, 0, :CLASSES] * bf(10.0)).astype(jnp.float32))

    def control_conf(self, logits):
        """The max-softmax confidence computed in bfloat16: the gate's control."""
        import jax

        return jnp.max(jax.nn.softmax(logits.astype(jnp.bfloat16), axis=-1), axis=-1)
