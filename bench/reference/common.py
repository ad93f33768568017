"""Shared arithmetic of the plain references: float32 ``jax.numpy`` only.

Nothing here imports the program.  A quantized weight arrives as
:class:`Planes`, the arrays the benchmark drew (``packed`` uint8 sign
planes, LSB-first along the input dim, bit 1 = +1; ``alpha`` per-plane
scale rows per input group; optional ``z`` offset row), and is rebuilt
here:

    W[o, i] = sum_p alpha[p, o, i // g] * (2 * bit_p[o, i] - 1) + z[o, i // g]

Two precisions:

  * ``"f32"``: the reference, every product in float32 at ``highest``;
  * ``"fp8"``: the control, the step below the served bfloat16
    activations -- every linear's input rows and weight rows are rounded
    to float8 e4m3 with a per-row scale (max |row| -> 448) before the
    product.  The benchmark never runs it; ``bench/tools/calibrate.py``
    reads it to set the limit of the check.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

MODES = ("f32", "fp8")
FP8_MAX = 448.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Planes:
    """A quantized weight as the benchmark drew it (the arrays may carry
    a leading stacked-layers axis)."""
    packed: jax.Array
    alpha: jax.Array
    z: Optional[jax.Array]
    group_size: int = dataclasses.field(metadata=dict(static=True))
    in_features: int = dataclasses.field(metadata=dict(static=True))


def _dense(packed, alpha, z, g: int, in_features: int) -> jax.Array:
    q, out, nb = packed.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (packed[..., None] >> shifts) & jnp.uint8(1)
    signs = bits.astype(jnp.float32).reshape(q, out, nb * 8) * 2.0 - 1.0
    scales = jnp.repeat(alpha.astype(jnp.float32), g, axis=-1)
    dense = (signs * scales[..., : nb * 8]).sum(0)
    if z is not None:
        dense = dense + jnp.repeat(z.astype(jnp.float32), g, axis=-1)[
            :, : nb * 8]
    return dense[:, :in_features]


def dequantize(w: Planes, layer=None) -> jax.Array:
    """Dense float32 ``[out, in]`` of one layer of a quantized weight
    (``layer`` indexes a leading stacked-layers axis); an expert bank,
    with a leading experts axis, comes out ``[E, out, in]``."""
    packed, alpha, z = w.packed, w.alpha, w.z
    if layer is not None:
        packed, alpha = packed[layer], alpha[layer]
        z = None if z is None else z[layer]

    def one(p, a, z):
        return _dense(p, a, z, w.group_size, w.in_features)
    if packed.ndim == 4:
        return jax.vmap(one)(packed, alpha, z)
    return one(packed, alpha, z)


def fp8_rows(x: jax.Array) -> jax.Array:
    """Round each row (last axis) to float8 e4m3 under a per-row scale."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def linear(x: jax.Array, w: jax.Array, mode: str, bias=None) -> jax.Array:
    """``x @ w.T (+ bias)``; ``w`` dense float32 ``[out, in]``."""
    if mode == "fp8":
        x, w = fp8_rows(x), fp8_rows(w)
    y = jnp.einsum("...i,oi->...o", x, w)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y


def layer_norm(x, scale, bias, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rms_norm(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def causal_attention(q, k, v):
    """One sequence: q [T, H, d], k/v [T, Hkv, d]; query head ``h`` reads
    key/value head ``h // (H / Hkv)``.  Returns [T, H * d]."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(t, h * d)


def rope(x, theta: float):
    """Rotary embedding on the whole head (halves rotated), positions
    ``0..T-1``; x [T, H, d]."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def stack_layers(stack) -> list:
    """Every layer of a plain stack tree in order, as ``(tree, index)``:
    ``{"layers": [layer, ...]}`` gives each layer with index ``None``;
    ``{"prefix": [layer, ...], "scan": [group, ...]}`` gives the prefix
    layers, then layer ``i`` of every group in turn, ``i`` indexing the
    groups' leading layers axis."""
    if "layers" in stack:
        return [(l, None) for l in stack["layers"]]
    out = [(l, None) for l in stack.get("prefix", [])]
    groups = stack.get("scan", [])
    if groups:
        n = jax.tree_util.tree_leaves(groups[0])[0].shape[0]
        out += [(g, i) for i in range(n) for g in groups]
    return out


def head_stats(x_final: jax.Array, head_w: jax.Array, vocab: int,
               targets: jax.Array, mode: str):
    """Per position of one sequence: the best logit, the logit of
    ``targets``, and the argmax.  x_final [T, d]; head_w [V_pad, d]."""
    logits = linear(x_final, head_w, mode)[:, :vocab]
    best = logits.max(-1)
    at = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best, at, jnp.argmax(logits, -1).astype(jnp.int32)


def _dense_layer(group, layer):
    """One layer's weights as dense float32 (bundles rebuilt)."""
    def conv(w):
        if isinstance(w, Planes):
            return dequantize(w, layer)
        return (w if layer is None else w[layer]).astype(jnp.float32)
    return jax.tree_util.tree_map(
        conv, group, is_leaf=lambda w: isinstance(w, Planes))


def logit_stats(arch, w: dict, cfg: dict, tokens, targets, mode: str = "f32"):
    """Run the whole model over ``tokens`` [B, T] (right-padded; causal,
    so padding never reaches an earlier position) one layer at a time,
    and return per position ``(best, at_target, argmax)``, each [B, T].

    ``arch`` is a reference module with ``embed(w_embed, tokens, cfg)``,
    ``block(layer, x, cfg, mode)`` and ``final(w, x, cfg)``; ``w`` is
    the plain weight tree.  Only one layer is ever dense at a time."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r}; known: {MODES}")
    tokens = jnp.asarray(tokens, jnp.int32)
    targets = jnp.asarray(targets, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda we, t: arch.embed(we, t, cfg))(w["embed"], tokens)
        one = jax.jit(lambda g, x: arch.block(
            _dense_layer(g, None), x, cfg, mode))
        stacked = jax.jit(lambda g, i, x: arch.block(
            _dense_layer(g, i), x, cfg, mode))
        for g, i in stack_layers(w["stack"]):
            x = one(g, x) if i is None else stacked(g, i, x)

        def stats(wf, x, tg):
            xn, head = arch.final(wf, x, cfg)
            return jax.lax.map(
                lambda a: head_stats(a[0], head, cfg["vocab_size"], a[1],
                                     mode), (xn, tg))
        wf = {"embed": w["embed"], "final_norm": w["final_norm"]}
        return jax.jit(stats)(wf, x, targets)
