"""Seeded synthetic camera streams at full resolution, made on the device.

The construction is the program's ``data/video.py`` generator, copied here
and vectorized so that the benchmark's traffic cannot move with the
program: each stream is one video of a class drawn from the seed; its
frames are the class's template (an oriented grating plus a blob) drifting
frame by frame, mixed with a distracting class's template and noise in
proportion to the video's difficulty.  Easy classes have low difficulty,
hard ones high.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _template(c, yy, xx, n_classes):
    """Class ``c``'s RGB template at fractional coordinates ``(yy, xx)``."""
    c = c.astype(jnp.float32)
    ang = jnp.pi * c / n_classes
    freq = 3.0 + 2.0 * jnp.mod(c, 4.0)
    grating = jnp.sin(2 * jnp.pi * freq * (xx * jnp.cos(ang) + yy * jnp.sin(ang)))
    cx = 0.3 + 0.4 * jnp.mod(c * 37.0, 10.0) / 10.0
    cy = 0.3 + 0.4 * jnp.mod(c * 53.0, 10.0) / 10.0
    blob = jnp.exp(-(((xx - cx) ** 2 + (yy - cy) ** 2) / 0.02))
    base = 0.6 * grating + 1.2 * blob
    return jnp.stack([base * (0.5 + 0.5 * jnp.cos(c)), base * (0.5 + 0.5 * jnp.sin(1.0 + c)), base], -1)


@partial(jax.jit, static_argnames=("n_streams", "n_frames", "res", "n_classes"))
def segment(key, *, n_streams, n_frames, res, n_classes, noise_floor=0.15):
    """(n_streams, n_frames, res, res, 3) float32 frames: one video per stream."""
    k_lab, k_dif, k_drift, k_dis, k_noise = jax.random.split(key, 5)
    label = jax.random.randint(k_lab, (n_streams,), 0, n_classes)
    ramp = jnp.linspace(0.05, 0.9, n_classes)
    difficulty = jnp.clip(ramp[label] + 0.15 * jax.random.normal(k_dif, (n_streams,)), 0.0, 1.0)
    drift = jax.random.normal(k_drift, (n_streams, 2)) * 2
    distract = jax.random.randint(k_dis, (n_streams, n_frames), 0, n_classes)
    f = jnp.arange(n_frames, dtype=jnp.float32)
    shift = jnp.trunc(drift[:, None, :] * f[None, :, None]).astype(jnp.int32)  # (S, N, 2)
    grid = jnp.arange(res, dtype=jnp.int32)
    # np.roll of the template by ``shift`` reads it at (index - shift) mod res
    yi = jnp.mod(grid[None, None, :] - shift[..., 0:1], res).astype(jnp.float32) / res
    xi = jnp.mod(grid[None, None, :] - shift[..., 1:2], res).astype(jnp.float32) / res
    own = _template(label[:, None, None, None], yi[..., :, None], xi[..., None, :], n_classes)
    g = grid.astype(jnp.float32) / res
    other = _template(distract[..., None, None], g[:, None], g[None, :], n_classes)
    d = difficulty[:, None, None, None, None]
    img = (1 - 0.75 * d) * own + 0.75 * d * other
    noise = jax.random.normal(k_noise, img.shape, jnp.float32)
    return img + (noise_floor + 0.6 * d) * noise
