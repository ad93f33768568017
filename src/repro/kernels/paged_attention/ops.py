"""Public jit-friendly wrappers for the fused paged-attention kernels.

Launch geometry (the kv-head tile ``block_h``) is resolved through
:func:`repro.tune.dispatch.kernel_config` unless pinned by the caller —
tuned JSON-cache entry if one exists for this (batch-bucket, Hkv,
kv-capacity, dtype, rep, block_size, device) point, deterministic
heuristic otherwise.  The oracles live in ``ref``:
``paged_decode_ref`` / ``paged_decode_int8_ref`` / ``paged_decode_mla_ref``
for the decode variants and ``paged_prefill_ref`` for chunked prefill.

The capability boundary (what falls back to the gathered-XLA path) lives
in :func:`repro.tune.dispatch.kernel_unsupported_reason` — float, int8
and MLA-latent pools are covered for decode; float and int8 pools for
chunked prefill; sliding-window masking and MLA prefill (which needs the
decompressing ``kv_map_fn``) still gather.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.tune import dispatch as _dispatch
from repro.tune.space import divisor_clamp
from . import paged_attention as _k


def live_pages(tables, q_pos, block_size: int, xp=jnp):
    """Pages each row can see: ``q_pos // block_size + 1`` (capped at the
    table width), 0 for a row with no live query (``q_pos < 0``) or one
    parked on the trash block (``tables[b, 0] < 0``).  No page past it
    can hold a slot the liveness rule admits.  ``xp=numpy`` gives the
    host's count of the same rule."""
    n = xp.minimum(q_pos // block_size + 1, tables.shape[1])
    return xp.where((tables[:, 0] >= 0) & (q_pos >= 0), n, 0).astype(
        xp.int32)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    pos_pool: jax.Array, tables: jax.Array,
                    positions: jax.Array, *, scale: Optional[float] = None,
                    block_h: Optional[int] = None, interpret: bool = False,
                    out_dtype=None) -> jax.Array:
    """Fused decode attention straight from the paged KV pool.

    q: [B, H, D]; k_pool/v_pool: [NB, BS, Hkv, D]; pos_pool: int32
    [NB, BS]; tables: int32 [B, pages] (-1 = unallocated); positions:
    int32 [B] (absolute position of each row's new token).
    Returns [B, H, D] in ``out_dtype`` (default q.dtype), FP32 accum.
    """
    b, h, d = q.shape
    nb, bs, hkv, dk = k_pool.shape
    if dk != d:
        raise ValueError(f"head_dim mismatch: q {d} vs pool {dk}")
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if v_pool.shape != k_pool.shape or pos_pool.shape != (nb, bs):
        raise ValueError("pool buffers disagree on [num_blocks, block_size]")
    rep = h // hkv
    pages = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5

    if block_h is None:
        cfg = _dispatch.kernel_config(
            "paged_attention", b=b, m=hkv, n=pages * bs,
            dtype=k_pool.dtype, mu=rep, group_size=bs, interpret=interpret)
        block_h = cfg.block_h
    block_h = divisor_clamp(block_h, hkv)

    # scale in f32 THEN round to the storage dtype — identical rounding
    # to decode_attend so fused and gathered paths stay interchangeable
    qg = (q.reshape(b, hkv, rep, d).astype(jnp.float32) * scale
          ).astype(k_pool.dtype)
    tables = jnp.asarray(tables, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    out = _k.paged_attention_tiled(
        qg, k_pool, v_pool, jnp.asarray(pos_pool, jnp.int32), tables,
        positions, live_pages(tables, positions, bs),
        block_size=bs, block_h=block_h, interpret=interpret)
    return out.reshape(b, h, d).astype(out_dtype or q.dtype)


def paged_attention_int8(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                         k_scale: jax.Array, v_scale: jax.Array,
                         pos_pool: jax.Array, tables: jax.Array,
                         positions: jax.Array, *,
                         scale: Optional[float] = None,
                         block_h: Optional[int] = None,
                         interpret: bool = False,
                         out_dtype=None) -> jax.Array:
    """Fused int8-KV decode attention: per-slot dequant scales ride the
    block-table DMA and fold in-kernel (``decode_attend`` int8 ordering).

    q: [B, H, D] float; k_pool/v_pool: int8 [NB, BS, Hkv, D];
    k_scale/v_scale: f32 [NB, BS, Hkv].  Returns [B, H, D].
    """
    b, h, d = q.shape
    nb, bs, hkv, dk = k_pool.shape
    if dk != d:
        raise ValueError(f"head_dim mismatch: q {d} vs pool {dk}")
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if k_scale.shape != (nb, bs, hkv) or v_scale.shape != (nb, bs, hkv):
        raise ValueError("scale pools disagree with KV pool geometry")
    rep = h // hkv
    pages = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5

    if block_h is None:
        cfg = _dispatch.kernel_config(
            "paged_attention", b=b, m=hkv, n=pages * bs,
            dtype=k_pool.dtype, mu=rep, group_size=bs, interpret=interpret)
        block_h = cfg.block_h
    block_h = divisor_clamp(block_h, hkv)

    # int8 pools compute in bf16 (decode_attend's compute dtype); the
    # q scaling still happens in f32 before the rounding
    qg = (q.reshape(b, hkv, rep, d).astype(jnp.float32) * scale
          ).astype(jnp.bfloat16)
    tables = jnp.asarray(tables, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    out = _k.paged_attention_tiled(
        qg, k_pool, v_pool, jnp.asarray(pos_pool, jnp.int32), tables,
        positions, live_pages(tables, positions, bs), k_scale, v_scale,
        block_size=bs, block_h=block_h, interpret=interpret)
    return out.reshape(b, h, d).astype(out_dtype or q.dtype)


def paged_attention_mla(q_eff: jax.Array, q_rope: jax.Array,
                        ckv_pool: jax.Array, krope_pool: jax.Array,
                        pos_pool: jax.Array, tables: jax.Array,
                        positions: jax.Array, *, scale: float,
                        block_h: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """Fused MLA absorbed decode over the latent pool.

    q_eff: f32 [B, H, lora] (``w_uk`` absorbed by the caller); q_rope:
    f32 [B, H, rope_dim]; latent pools [NB, BS, lora] / [NB, BS,
    rope_dim].  Returns the latent context f32 [B, H, lora] — the caller
    applies ``w_uv``.  ``block_h`` tiles H (no kv-head replication).
    """
    b, h, lora = q_eff.shape
    nb, bs = pos_pool.shape
    if ckv_pool.shape != (nb, bs, lora):
        raise ValueError("ckv pool disagrees with q_eff lora dim")
    if krope_pool.shape[:2] != (nb, bs):
        raise ValueError("krope pool disagrees on [num_blocks, block_size]")
    pages = tables.shape[1]

    if block_h is None:
        cfg = _dispatch.kernel_config(
            "paged_attention", b=b, m=h, n=pages * bs,
            dtype=ckv_pool.dtype, mu=1, group_size=bs, interpret=interpret)
        block_h = cfg.block_h
    block_h = divisor_clamp(block_h, h)

    return _k.paged_attention_mla_tiled(
        q_eff.astype(jnp.float32), q_rope.astype(jnp.float32),
        ckv_pool, krope_pool, jnp.asarray(pos_pool, jnp.int32),
        jnp.asarray(tables, jnp.int32), jnp.asarray(positions, jnp.int32),
        scale=float(scale), block_size=bs, block_h=block_h,
        interpret=interpret)


def paged_prefill(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                  pos_pool: jax.Array, tables: jax.Array,
                  positions: jax.Array, *, scale: Optional[float] = None,
                  k_scale: Optional[jax.Array] = None,
                  v_scale: Optional[jax.Array] = None,
                  block_h: Optional[int] = None, interpret: bool = False,
                  out_dtype=None) -> jax.Array:
    """Fused chunked-prefill attention straight from the paged KV pool.

    q: [B, C, H, D] (the current chunk, already inserted into the pool);
    positions: int32 [B, C], -1 for pad rows (those return zeros).
    Passing ``k_scale``/``v_scale`` (f32 [NB, BS, Hkv]) enables the int8
    dequant fold.  Returns [B, C, H, D].
    """
    b, c, h, d = q.shape
    nb, bs, hkv, dk = k_pool.shape
    if dk != d:
        raise ValueError(f"head_dim mismatch: q {d} vs pool {dk}")
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if positions.shape != (b, c):
        raise ValueError("positions must be [B, C] for chunked prefill")
    rep = h // hkv
    pages = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    int8 = k_scale is not None

    if block_h is None:
        cfg = _dispatch.kernel_config(
            "paged_prefill", b=b, m=hkv, n=pages * bs,
            dtype=k_pool.dtype, mu=rep, group_size=bs, interpret=interpret)
        block_h = cfg.block_h
    block_h = divisor_clamp(block_h, hkv)

    cdt = jnp.bfloat16 if int8 else k_pool.dtype
    qg = (q.reshape(b, c, hkv, rep, d).astype(jnp.float32) * scale
          ).astype(cdt)
    tables = jnp.asarray(tables, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    out = _k.paged_prefill_tiled(
        qg, k_pool, v_pool, jnp.asarray(pos_pool, jnp.int32), tables,
        positions, live_pages(tables, positions.max(axis=1), bs),
        k_scale, v_scale, block_size=bs, block_h=block_h,
        interpret=interpret)
    return out.reshape(b, c, h, d).astype(out_dtype or q.dtype)
