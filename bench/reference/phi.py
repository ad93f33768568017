"""Plain float32 reference of Phi-4-mini as this repository serves it.

Per layer (pre-RMSNorm, eps 1e-5):

    h = N1(x);  q = h Wq^T (24 heads of 128), k, v = h Wk^T, h Wv^T
    (8 heads of 128; query head i reads key/value head i // 3)
    q, k = rope(q), rope(k)          (theta 10000, halves rotated)
    x = x + softmax(q k^T / sqrt(128), causal) v  Wo^T
    h = N2(x);  x = x + (silu(h Wgate^T) * h Wup^T) Wdown^T

with the LM head tied to the token embedding after a final RMSNorm.

Departures from the published Phi-4-mini (arXiv:2503.01743), which the
program makes and the reference follows: rotary embedding on all 128
dimensions of a head where the model rotates 96 (partial_rotary_factor
0.75), and no LongRoPE rescaling.  Neither changes the work of a step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import causal_attention, linear, rms_norm, rope


def embed(w, tokens, cfg):
    return jnp.take(w["tok"], tokens, axis=0).astype(jnp.float32)


def block(lw, x, cfg, mode):
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, theta = cfg["head_dim"], cfg["rope_theta"]
    b, t, d = x.shape
    h = rms_norm(x, lw["ln1"]["scale"])
    mx = lw["mixer"]
    q = linear(h, mx["q"], mode).reshape(b, t, nh, hd)
    k = linear(h, mx["k"], mode).reshape(b, t, nkv, hd)
    v = linear(h, mx["v"], mode).reshape(b, t, nkv, hd)
    a = jax.lax.map(lambda s: causal_attention(
        rope(s[0], theta), rope(s[1], theta), s[2]), (q, k, v))
    x = x + linear(a, mx["o"], mode)
    h = rms_norm(x, lw["ln2"]["scale"])
    m = lw["mlp"]
    g = jax.nn.silu(linear(h, m["gate"], mode))
    return x + linear(g * linear(h, m["up"], mode), m["down"], mode)


def final(w, x, cfg):
    xn = rms_norm(x, w["final_norm"]["scale"].astype(jnp.float32))
    return xn, w["embed"]["tok"].astype(jnp.float32)
