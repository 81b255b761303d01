"""Sharding rules, FSDP spec derivation, MoE dispatch correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import pad_heads
from repro.models.ptree import TensorSpec, tree_pspec, ts
from repro.sharding.axes import DEFAULT_RULES, shard, sharding_ctx
from repro.sharding.fsdp import fsdp_spec


class _FakeMesh:
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as _np

        self.devices = _np.empty(tuple(sizes.values()))


def test_tree_pspec_drops_non_divisible():
    rules = dict(DEFAULT_RULES)
    rules["_sizes"] = {"data": 16, "model": 16}
    spec = {
        "wq": ts((512, "embed"), (40, "q_heads"), (128, "head_dim")),  # 40 % 16 != 0
        "wg": ts((512, "embed"), (1408, "mlp")),
    }
    ps = tree_pspec(spec, rules)
    assert ps["wq"] == P(None, None, None)  # dropped, replicated
    assert ps["wg"] == P(None, "model")


def test_tree_pspec_no_axis_reuse():
    rules = dict(DEFAULT_RULES)
    rules["_sizes"] = {"model": 16}
    spec = ts((64, "q_heads"), (64, "mlp"))  # both map to model
    ps = tree_pspec(spec, rules)
    assert ps == P("model", None)  # first dim wins, no double use


def test_fsdp_spec_adds_data_axis():
    mesh = _FakeMesh({"data": 16, "model": 16})
    out = fsdp_spec(P(None, "model"), (4096, 1408), mesh)
    assert out == P("data", "model")
    # non-divisible first dim falls through to another dim
    out2 = fsdp_spec(P(None, None), (30, 4096), mesh)
    assert out2 == P(None, "data")


def test_pad_heads():
    assert pad_heads(40, 16) == 48
    assert pad_heads(56, 16) == 64
    assert pad_heads(32, 16) == 32
    assert pad_heads(7, 1) == 7


def test_shard_is_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = shard(x, "batch", None)
    assert y is x


def test_shard_applies_constraint_on_mesh():
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    with sharding_ctx(mesh):
        @jax.jit
        def f(x):
            return shard(x, "batch", "mlp_act") * 2
        out = f(jnp.ones((4, 8)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((4, 8)))


def test_padded_head_lm_matches_unpadded():
    """Dead padded heads (zero wo rows) must not change the logits."""
    from repro.configs.base import get_arch
    from repro.models import transformer as tr
    from repro.models.ptree import tree_init

    cfg = get_arch("qwen1.5-32b").smoke  # 4 heads
    plan_p = tr.ParallelPlan(model_axis=3, pad_attention_heads=True, remat=False)  # pads 4 -> 6
    plan_n = tr.ParallelPlan(model_axis=1, remat=False)
    h_p, _ = tr.effective_heads(cfg, plan_p)
    assert h_p == 6
    params_p = tree_init(tr.lm_param_spec(cfg, plan_p), jax.random.PRNGKey(0), dtype=jnp.float32)
    # build unpadded params from the padded ones (slice the first 4 heads)
    params_n = tree_init(tr.lm_param_spec(cfg, plan_n), jax.random.PRNGKey(0), dtype=jnp.float32)

    def crop(stacked_p):
        out = jax.tree.map(lambda x: x, stacked_p)
        a = stacked_p["attn"]
        for k in ("wq", "wk", "wv"):
            out["attn"][k] = a[k][:, :, :4, :]
        for k in ("bq", "bk", "bv"):
            out["attn"][k] = a[k][:, :4, :]
        out["attn"]["wo"] = a["wo"][:, :4, :, :]
        return out

    params_c = dict(params_p)
    params_c["layers"] = {"all": crop(params_p["layers"]["all"])}
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    # zero the dead wo rows in the padded model -> outputs must match crop
    wo = params_p["layers"]["all"]["attn"]["wo"]
    params_p["layers"]["all"]["attn"]["wo"] = wo.at[:, 4:].set(0.0)
    for k in ("bq", "bk", "bv"):
        b = params_p["layers"]["all"]["attn"][k]
        params_p["layers"]["all"]["attn"][k] = b.at[:, 4:].set(0.0)
    lg_p, _ = tr.lm_forward(params_p, toks, cfg, plan_p)
    lg_c, _ = tr.lm_forward(params_c, toks, cfg, plan_n)
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_c), rtol=2e-4, atol=2e-4)


def test_moe_matches_dense_oracle_when_capacity_unbounded():
    """Sort-based capacity dispatch == per-token dense top-k mix (no drops)."""
    from repro.configs.base import MoEConfig
    from repro.models.moe import apply_moe, moe_spec
    from repro.models.ptree import tree_init

    cfg = MoEConfig(n_routed=4, top_k=2, d_ff_expert=16, capacity_factor=8.0)
    d = 8
    spec = moe_spec(d, cfg, "swiglu")
    params = tree_init(spec, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, d), jnp.float32)
    out, aux = apply_moe(params, x, cfg, "swiglu")

    # dense oracle
    xf = x.reshape(-1, d)
    gates = jax.nn.softmax(xf @ params["router"], -1)
    top_v, top_i = jax.lax.top_k(gates, 2)
    top_v = top_v / top_v.sum(-1, keepdims=True)
    def expert(e, t):
        g = xf[t] @ params["wg"][e]
        u = xf[t] @ params["wu"][e]
        return (jax.nn.silu(g) * u) @ params["wd"][e]
    ref = np.zeros_like(np.asarray(xf))
    for t in range(xf.shape[0]):
        for j in range(2):
            ref[t] += float(top_v[t, j]) * np.asarray(expert(int(top_i[t, j]), t))
    np.testing.assert_allclose(np.asarray(out.reshape(-1, d)), ref, rtol=2e-4, atol=2e-4)
    assert float(aux) > 0


def test_moe_drops_when_capacity_tight():
    from repro.configs.base import MoEConfig
    from repro.models.moe import apply_moe, moe_spec
    from repro.models.ptree import tree_init

    cfg = MoEConfig(n_routed=2, top_k=1, d_ff_expert=8, capacity_factor=0.02)
    spec = moe_spec(4, cfg, "swiglu")
    params = tree_init(spec, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 4), jnp.float32)
    out, _ = apply_moe(params, x, cfg, "swiglu")
    # capacity 8 slots per expert << 256 tokens: most outputs are dropped zeros
    frac_zero = float((jnp.abs(out) < 1e-9).mean())
    assert frac_zero > 0.5
