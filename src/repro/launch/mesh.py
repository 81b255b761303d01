"""Production mesh construction (assignment-mandated shapes).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.

Every mesh here has ``Auto`` axes.  ``jax.make_mesh`` defaults to
``Explicit`` axes since JAX 0.7, and ``sharding/axes.py::shard`` constrains
activations with ``with_sharding_constraint``, which refers only to
``Auto`` axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1, data_axis: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model_axis = min(model_axis, n)
    data_axis = min(data_axis, n // model_axis)
    return make_mesh((data_axis, model_axis), ("data", "model"))


def make_streams_mesh(n_devices: int | None = None):
    """Pure data-parallel mesh for fleet serving: the ``"streams"`` logical
    axis maps to ``"data"`` (sharding/axes.py), so an (n, 1) mesh splits
    the (S,) fleet arrays n ways while every per-cell/per-replica shared
    reduction stays replicated.  On CPU hosts, force n devices by setting
    ``XLA_FLAGS=--xla_force_host_platform_device_count=n`` *before* jax
    imports (see benchmarks/bench_fleet_control.py ``--devices``)."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    if n > len(jax.devices()):
        raise ValueError(f"asked for {n} devices, host has {len(jax.devices())} "
                         "(set --xla_force_host_platform_device_count before "
                         "jax imports)")
    return make_mesh((n, 1), ("data", "model"), devices=jax.devices()[:n])
