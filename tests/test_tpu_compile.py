"""Ahead-of-time compiles of the serving path for a described TPU v5e.

No chip is attached: the TPU compiler compiles each program for one chip of
a ``v5e:2x2`` topology described by ``jax.experimental.topologies``, which
refuses what the chip's compiler would (unaligned kernel blocks, too much
fast memory, a program that does not fit).  Nothing runs, so these say
nothing about results or times.  The topology is described inside a
fixture, never at import, and the tests skip where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs under the temp dir
        mp.setenv("TPU_LOG_DIR", "disabled")
        # an entry compiled for a described chip cannot be read back here
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            from jax.experimental import topologies

            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no libtpu, or it cannot describe v5e
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _struct(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def test_fused_calib_gate_at_the_served_fast_pass(one_chip):
    """The fast pass's kernel at S*b = 8 streams x 8 frames, 1000 classes —
    the block shapes ``cascade.fast_pass`` picks for that extent."""
    from repro.kernels.fused_calib_gate.kernel import calib_gate

    B, V = 64, 1000
    logits = jax.ShapeDtypeStruct((B, V), F32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
    compiled = calib_gate.lower(logits, scalar, scalar, scalar, bb=B, bv=V,
                                interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_int8_matmul_at_a_resnet50_conv(one_chip):
    """ResNet-50's last-stage first 1x1 conv (1024 -> 512 channels) over
    512 pixel rows, at the kernel's default 256x256x512 blocks."""
    from repro.kernels.int8_matmul.kernel import int8_matmul

    M, K, N = 512, 1024, 512
    args = (jax.ShapeDtypeStruct((M, K), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((M, 1), F32, sharding=one_chip),
            jax.ShapeDtypeStruct((K, N), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((1, N), F32, sharding=one_chip))
    compiled = int8_matmul.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_resnet50_forward_fits_one_chip(one_chip):
    """The slow tier at full published width (224², 1000 classes)."""
    from repro.configs.resnet_50 import FULL
    from repro.models import api

    h = api.build(FULL)
    params = jax.eval_shape(lambda k: h.init(k, dtype=F32), jax.random.PRNGKey(0))
    images = jax.ShapeDtypeStruct((8, 224, 224, 3), F32, sharding=one_chip)
    compiled = jax.jit(h.forward).lower(_struct(params, one_chip), images).compile()
    assert sum(x.size for x in jax.tree.leaves(params)) == 25_557_032
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_fleet_round_loop_compiles(one_chip):
    """``make_engine``'s scan of the fleet round: cbo policy, S=1024 streams,
    B=8, 2 rounds, as ``bench_fleet_control.bench_jax_one`` builds it."""
    from repro.core.netsim import payload_sizes, png_size_model
    from repro.policy.fleet_jax import spec_for_policy
    from repro.policy.registry import make_policy
    from repro.serving import engine_jax as ej

    S, R, B = 1024, 2, 8
    resolutions = (45, 90, 134, 179, 224)
    m = len(resolutions)
    sizes = payload_sizes(png_size_model, np.asarray(resolutions))
    planner = spec_for_policy(make_policy("cbo", max_backlog=8), sizes=sizes,
                              acc_server=(0.6, 0.75, 0.85, 0.92, 0.96),
                              deadline=0.2, latency=0.05, server_time=0.037)
    spec = ej.EngineSpec(n_streams=S, batch=B, n_cells=1, n_replicas=1,
                         planner=planner, collect="metrics")
    f = lambda *shape: jax.ShapeDtypeStruct(shape, F32)
    params = ej.EngineParams(
        sizes=f(m), cell_bw=f(1),
        cell_of=jax.ShapeDtypeStruct((S,), jnp.int32), replica_st=f(1),
        stream_bw=f(S), weights=f(S), bw_init=f(S))
    carry = jax.eval_shape(lambda p: ej.init_carry(spec, p), params)
    inputs = ej.RoundInputs(
        arr=f(R, S, B), valid=jax.ShapeDtypeStruct((R, S, B), bool),
        conf=f(R, S, B), fast_ok=jax.ShapeDtypeStruct((R, S, B), bool),
        slow_ok=jax.ShapeDtypeStruct((R, S, B, m), bool))
    compiled = ej.make_engine(spec).lower(
        *(_struct(t, one_chip) for t in (params, carry, inputs))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
