"""Dropless MoE routing on the serving path, against a per-token top-k
loop; the router's published gate rules."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.models import moe
from repro.models.module import init_params
from repro.quant import QuantSpec, quantize_model

CFG = get_reduced("deepseek_v2_lite").replace(dtype="float32")


def _params(cfg=CFG, seed=0):
    p = init_params(jax.random.PRNGKey(seed), moe.moe_desc(cfg))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def _per_token(params, cfg, x, valid):
    """Each valid token's top-k experts run one at a time (numpy)."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    xs = np.asarray(x, np.float64).reshape(-1, cfg.d_model)

    def silu(a):
        return a / (1 + np.exp(-a))

    def ffn(h, g, u, d):
        return (silu(h @ g.T) * (h @ u.T)) @ d.T
    out = np.zeros_like(xs)
    for t, h in enumerate(xs):
        logits = p["router"] @ h
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:cfg.experts_per_token]
        gates = probs[top]
        if cfg.norm_topk_prob:
            gates = gates / gates.sum()
        gates = gates * cfg.routed_scaling_factor
        if valid.reshape(-1)[t]:
            for e, g in zip(top, gates):
                out[t] += g * ffn(h, p["gate"][e], p["up"][e], p["down"][e])
        out[t] += ffn(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return out.reshape(x.shape)


def _x(b, s, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, CFG.d_model))


def test_dropless_matches_per_token_loop_and_skips_padding():
    params = _params()
    x = _x(2, 9)
    valid = jnp.arange(9)[None] < jnp.asarray([[9], [5]])   # row 1 padded
    y, load = moe.moe_dropless(params, CFG, x, valid)
    want = _per_token(params, CFG, x, np.asarray(valid))
    # float32 against float64, sums of a few products of order 1
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5, rtol=1e-4)
    # 14 valid tokens x 2 experts; the 4 padding tokens are not routed
    assert int(load.sum()) == 14 * CFG.experts_per_token


def test_one_expert_takes_every_token_and_none_is_dropped():
    """A chunk routing every token to one expert: the capacity dispatch
    drops all but ``capacity`` of them, the dropless one none."""
    params = _params()
    params["router"] = jnp.zeros_like(params["router"]).at[3].set(1.0)
    x = jnp.abs(_x(1, 64)) + 0.5                 # router logit 3 dominates
    valid = jnp.ones((1, 64), bool)
    y, load = moe.moe_dropless(params, CFG, x, valid)
    want = _per_token(params, CFG, x, np.asarray(valid))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5, rtol=1e-4)
    assert int(load[3]) == 64 and int(load.max()) == 64
    dropped = moe.moe_apply(params, CFG.replace(capacity_factor=1.0), x)
    assert np.abs(np.asarray(dropped) - want).max() > 1e-2


def test_gates_follow_norm_topk_prob():
    params = _params()
    x = _x(1, 6).reshape(6, CFG.d_model)
    g_raw, e_raw = moe.route(params["router"], CFG, x)
    g_norm, e_norm = moe.route(params["router"],
                               CFG.replace(norm_topk_prob=True), x)
    np.testing.assert_array_equal(np.asarray(e_raw), np.asarray(e_norm))
    # published DeepSeek-V2: the top-k softmax probabilities as they are
    assert float(g_raw.sum(-1).max()) < 1.0
    np.testing.assert_allclose(np.asarray(g_norm.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g_norm), np.asarray(g_raw / g_raw.sum(-1, keepdims=True)),
        rtol=1e-6)
    g_scaled, _ = moe.route(params["router"],
                            CFG.replace(routed_scaling_factor=2.5), x)
    np.testing.assert_allclose(np.asarray(g_scaled), 2.5 * np.asarray(g_raw),
                               rtol=1e-6)
    # and the layer output moves with them
    valid = jnp.ones((1, 6), bool)
    y_raw, _ = moe.moe_dropless(params, CFG, x[None], valid)
    y_norm, _ = moe.moe_dropless(params, CFG.replace(norm_topk_prob=True),
                                 x[None], valid)
    assert np.abs(np.asarray(y_raw - y_norm)).max() > 1e-3


def test_grouped_kernel_path_matches_per_token_loop():
    """A 3-bit bank through the grouped BCQ kernel (interpret mode),
    against the per-token loop over the same planes rebuilt in float32;
    the ``ragged_dot`` path over the same planes agrees to the bfloat16
    rounding of the weights it rebuilds."""
    from repro.core.plane import dequantize
    from repro.kernels.grouped_matmul.ref import bank_dense
    params = _params()
    spec = QuantSpec(bits=3, group_size=16, iters=1, backend="bcq_xla")
    axes = jax.tree_util.tree_map(lambda d: d.axes, moe.moe_desc(CFG),
                                  is_leaf=lambda d: hasattr(d, "axes"))
    qp, _ = quantize_model(params, spec, axes)
    assert qp["gate"].packed.ndim == 4               # [E, q, out, in/8]
    dense = {k: (bank_dense(v) if v.packed.ndim == 4 else dequantize(v))
             if hasattr(v, "packed") else v for k, v in qp.items()}
    x = _x(2, 7)
    valid = jnp.arange(7)[None] < jnp.asarray([[7], [3]])
    want = _per_token(dense, CFG, x, np.asarray(valid))
    y_k, load_k = moe.moe_dropless(qp, CFG, x, valid, backend="mxu_pallas")
    # float32 throughout; the kernel sums per bit slab
    np.testing.assert_allclose(np.asarray(y_k), want, atol=1e-5, rtol=1e-4)
    y_x, load_x = moe.moe_dropless(qp, CFG, x, valid, backend="bcq_xla")
    np.testing.assert_array_equal(np.asarray(load_k), np.asarray(load_x))
    # bfloat16 weights (8 bits of mantissa) in sums that cancel to ~1e-3
    np.testing.assert_allclose(np.asarray(y_x), want, atol=1e-4)
