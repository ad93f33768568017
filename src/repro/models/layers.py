"""Shared building blocks: norms, rotary embeddings, MLPs, embeddings.

Every linear weight is declared as a ParamDesc [out, in] and executed via
``core.linear_apply`` — which transparently runs dense arrays or
BCQ-quantized ``BCQWeight`` leaves on the configured backend.  That single
dispatch point is how FIGLUT integrates as a first-class feature across
all ten architectures.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantized_linear import linear_apply
from repro.models.module import ParamDesc


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_desc(cfg, dim=None):
    d = dim or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": ParamDesc((d,), jnp.float32, ("embed",), "ones")}
    return {"scale": ParamDesc((d,), jnp.float32, ("embed",), "ones"),
            "bias": ParamDesc((d,), jnp.float32, ("embed",), "zeros")}


def norm_apply(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    if "bias" in params:                       # layernorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"] + params["bias"]
    else:                                      # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * params["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D] (D even), positions: [B, S] or [S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------


def mlp_desc(cfg, d_ff=None):
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    if cfg.mlp_act == "swiglu":
        return {
            "gate": ParamDesc((f, d), jnp.bfloat16, ("mlp", "embed")),
            "up": ParamDesc((f, d), jnp.bfloat16, ("mlp", "embed")),
            "down": ParamDesc((d, f), jnp.bfloat16, ("embed", "mlp")),
        }
    return {
        "up": ParamDesc((f, d), jnp.bfloat16, ("mlp", "embed")),
        "up_b": ParamDesc((f,), jnp.float32, ("mlp",), "zeros"),
        "down": ParamDesc((d, f), jnp.bfloat16, ("embed", "mlp")),
        "down_b": ParamDesc((d,), jnp.float32, ("embed",), "zeros"),
    }


def mlp_apply(params, x, backend=None):
    if "gate" in params:
        g = linear_apply(params["gate"], x, backend=backend)
        u = linear_apply(params["up"], x, backend=backend)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return linear_apply(params["down"], h, backend=backend,
                            row_parallel=True)
    h = linear_apply(params["up"], x, params.get("up_b"), backend=backend)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return linear_apply(params["down"], h, params.get("down_b"),
                        backend=backend, row_parallel=True)


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------


def embed_desc(cfg):
    d = {"tok": ParamDesc((cfg.padded_vocab, cfg.d_model), jnp.bfloat16,
                          ("vocab", "embed"), "embed")}
    if cfg.pos == "learned":
        d["pos"] = ParamDesc((cfg.max_seq_len, cfg.d_model), jnp.bfloat16,
                             (None, "embed"), "embed")
    if not cfg.tie_embeddings:
        d["unembed"] = ParamDesc((cfg.padded_vocab, cfg.d_model), jnp.bfloat16,
                                 ("vocab", "embed"))
    return d


def embed_apply(params, tokens, positions=None):
    x = jnp.take(params["tok"], tokens, axis=0)        # [B, S, d]
    if "pos" in params and positions is not None:
        x = x + jnp.take(params["pos"], positions, axis=0)
    return x


def unembed_apply(params, x, backend=None):
    from repro.parallel.sharding import shard_act
    w = params.get("unembed", params["tok"])           # tied if absent
    logits = linear_apply(w, x, backend=backend, out_dtype=jnp.float32)
    axes = ("batch",) + (None,) * (logits.ndim - 2) + ("vocab",)
    return shard_act(logits, axes)
