"""Plain float32 reference of OPT as this repository serves it.

Per layer (pre-LayerNorm, eps 1e-5):

    h = LN1(x);  q, k, v = h Wq^T, h Wk^T, h Wv^T   (32 heads of 128)
    x = x + softmax(q k^T / sqrt(128), causal) v  Wo^T
    h = LN2(x);  x = x + gelu_tanh(h Wup^T + b_up) Wdown^T + b_down

with learned positions added to the token embedding (positions from 0)
and the LM head tied to the token embedding after a final LayerNorm.

Departures from the published OPT-6.7B (arXiv:2205.01068), which the
program makes and the reference follows: the MLP activation is GELU
(tanh form) where OPT uses ReLU; the attention projections carry no
bias; positions index the table from 0 (Hugging Face's OPT offsets them
by 2).  None changes the work of a step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import causal_attention, layer_norm, linear


def embed(w, tokens, cfg):
    t = tokens.shape[1]
    x = jnp.take(w["tok"], tokens, axis=0).astype(jnp.float32)
    return x + w["pos"][:t].astype(jnp.float32)[None]


def block(lw, x, cfg, mode):
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    b, t, d = x.shape
    h = layer_norm(x, lw["ln1"]["scale"], lw["ln1"]["bias"])
    mx = lw["mixer"]
    q = linear(h, mx["q"], mode).reshape(b, t, nh, hd)
    k = linear(h, mx["k"], mode).reshape(b, t, nh, hd)
    v = linear(h, mx["v"], mode).reshape(b, t, nh, hd)
    a = jax.lax.map(lambda s: causal_attention(*s), (q, k, v))
    x = x + linear(a, mx["o"], mode)
    h = layer_norm(x, lw["ln2"]["scale"], lw["ln2"]["bias"])
    m = lw["mlp"]
    u = jax.nn.gelu(linear(h, m["up"], mode, m["up_b"]), approximate=True)
    return x + linear(u, m["down"], mode, m["down_b"])


def final(w, x, cfg):
    fn = w["final_norm"]
    xn = layer_norm(x, fn["scale"].astype(jnp.float32),
                    fn["bias"].astype(jnp.float32))
    return xn, w["embed"]["tok"].astype(jnp.float32)
