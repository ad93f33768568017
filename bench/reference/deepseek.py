"""Plain float32 reference of DeepSeek-V2-Lite as this repository serves
it, written the paper's way (arXiv:2405.04434), with no absorption.

Per layer (pre-RMSNorm):

    h = N1(x)
    q = h Wq^T                       16 heads of nope 128 + rope 64
    c, kr = split(h Wkv_a^T)         latent 512, shared rotary key 64
    c = N_kv(c);  k_nope, v = split(c Wkv_b^T)   per head 128 + 128
    q_rope, kr = rope_yarn(q_rope), rope_yarn(kr)
    k = [k_nope, kr];  q = [q_nope, q_rope]
    x = x + softmax(q k^T * s, causal) v  Wo^T,  s = 192^-0.5 * mscale^2
    h = N2(x)
    layer 0:  x = x + (silu(h Wgate^T) * h Wup^T) Wdown^T
    MoE:      p = softmax(h Wrouter^T) over 64 experts; the top 6 run,
              x = x + sum_k p_k * expert_k(h) + shared(h)

with the gates not renormalised (``norm_topk_prob`` false) and times
``routed_scaling_factor``; ``shared`` is the SwiGLU of the 2 shared
experts (one 2,816-wide MLP).  Every token's top-6 experts run exactly:
no capacity, nothing dropped.  The head is untied: a final RMSNorm,
then the 102,400-row unembedding.

YaRN (``rope_scaling``): inverse frequencies blend ``base / factor`` and
``base`` over a linear ramp between the dimensions that turn
``beta_fast`` and ``beta_slow`` times over the original 4,096 positions;
cos and sin carry ``mscale(mscale) / mscale(mscale_all_dim)`` (1 here);
the softmax scale carries ``mscale(mscale_all_dim)^2``, with
``mscale(m) = 0.1 m ln(factor) + 1``.

Departures from the published model, which the program makes and this
reference follows (the configuration's ``assumed``): rotary in the
rotate-half layout where the checkpoint's rope columns are interleaved
(a fixed permutation of the drawn columns); the block RMSNorms at eps
1e-5 where the model states 1e-6 (the latent's norm at 1e-6).  Neither
changes the work of a step.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import dequantize, linear, Planes, rms_norm

HI = jax.lax.Precision.HIGHEST


def embed(w, tokens, cfg):
    return jnp.take(w["tok"], tokens, axis=0).astype(jnp.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg) -> jnp.ndarray:
    """The rotary inverse frequencies of the rope dimensions."""
    d, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    rs = cfg.get("rope_scaling")
    if not rs:
        return inv
    f, orig = rs["factor"], rs["original_max_position_embeddings"]

    def turns_dim(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    lo = max(math.floor(turns_dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(turns_dim(rs["beta_slow"])), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    return inv / f * ramp + inv * (1.0 - ramp)


def softmax_scale(cfg) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def rope(x, cfg):
    """Rotate-half rotary with YaRN over positions 0..T-1; x [T, H, d]."""
    t, _, d = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)
    rs = cfg.get("rope_scaling")
    f = (_mscale(rs["factor"], rs["mscale"])
         / _mscale(rs["factor"], rs["mscale_all_dim"])) if rs else 1.0
    cos, sin = jnp.cos(ang)[:, None] * f, jnp.sin(ang)[:, None] * f
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, scale):
    """One sequence: q, k [T, H, dq], v [T, H, dv] -> [T, H * dv]."""
    t, h, _ = q.shape
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, -1)


def swiglu(h, m, mode, prefix=""):
    g = jax.nn.silu(linear(h, m[prefix + "gate"], mode))
    return linear(g * linear(h, m[prefix + "up"], mode), m[prefix + "down"],
                  mode)


def routed(h, m, cfg, mode):
    """Each token's top-k experts, exactly: the experts run one at a time
    over every token, each token keeping the gate of an expert it chose
    (0 for the others)."""
    k = cfg["num_experts_per_tok"]
    p = jax.nn.softmax(linear(h, m["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(p, k)
    if cfg.get("norm_topk_prob"):
        top = top / top.sum(-1, keepdims=True)
    top = top * cfg.get("routed_scaling_factor", 1.0)
    e = m["router"].shape[0]
    gate = jnp.zeros(p.shape).at[
        tuple(jnp.indices(idx.shape)[:-1]) + (idx,)].set(top)   # [.., E]

    def one(acc, ew):
        g_e, wg, wu, wd = ew
        y = linear(jax.nn.silu(linear(h, wg, mode)) * linear(h, wu, mode),
                   wd, mode)
        return acc + g_e[..., None] * y, None
    out, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32),
                          (jnp.moveaxis(gate, -1, 0), m["gate"], m["up"],
                           m["down"]))
    assert m["gate"].shape[0] == e
    return out


def block(lw, x, cfg, mode):
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    b, t, _ = x.shape
    mx = lw["mixer"]
    h = rms_norm(x, lw["ln1"]["scale"])
    q = linear(h, mx["q"], mode).reshape(b, t, nh, dn + dr)
    kv_a = linear(h, mx["kv_a"], mode)
    c = rms_norm(kv_a[..., :r], mx["kv_a_norm"], eps=1e-6)
    kv = linear(c, mx["kv_b"], mode).reshape(b, t, nh, dn + dv)
    scale = softmax_scale(cfg)

    def one(s):
        q_s, kr_s, kv_s = s
        qr = rope(q_s[..., dn:], cfg)
        kr = rope(kr_s[:, None, :], cfg)                     # one head
        kq = jnp.concatenate([kv_s[..., :dn],
                              jnp.broadcast_to(kr, (t, nh, dr))], -1)
        return attention(jnp.concatenate([q_s[..., :dn], qr], -1), kq,
                         kv_s[..., dn:], scale)
    a = jax.lax.map(one, (q, kv_a[..., r:], kv))
    x = x + linear(a, mx["o"], mode)
    h = rms_norm(x, lw["ln2"]["scale"])
    m = lw["mlp"]
    if "router" in m:
        y = routed(h, m, cfg, mode) + swiglu(h, m, mode, "shared_")
    else:
        y = swiglu(h, m, mode)
    return x + y


def final(w, x, cfg):
    xn = rms_norm(x, w["final_norm"]["scale"].astype(jnp.float32))
    head = w["embed"]["unembed"]
    head = dequantize(head) if isinstance(head, Planes) \
        else head.astype(jnp.float32)
    return xn, head
