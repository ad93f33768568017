"""Attention: GQA / MLA / sliding-window, blockwise (flash-style) softmax,
unified KV caches (full + ring-buffer), cross-attention.

Memory design: prefill/train never materialize the full [Sq, Skv] score
matrix — queries and keys are processed in chunks with online softmax
(lax.scan over KV blocks inside a scan over Q blocks), so 32k-sequence
cells fit.  An optional ``kv_map_fn`` decompresses latent (MLA) KV blocks
inside the inner scan, keeping decompressed K/V transient.

KV cache layout (GQA):  {k, v: [B, L, Hkv, D], pos: [B, L] int32}
``pos`` holds the absolute position stored in each slot (-1 = empty); a
ring buffer (sliding window) is just L = window with slot = pos % L —
masking via ``pos`` makes full and ring caches the same code path.

MLA cache: {ckv: [B, L, lora], krope: [B, L, rope_dim], pos: [B, L]} —
the paper-exact compressed cache; decode uses the absorbed formulation
(scores in latent space) so per-step cost is O(L * lora), not O(L * H * d).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.quantized_linear import linear_apply
from repro.models.layers import apply_rope
from repro.models.module import ParamDesc
from repro.parallel.sharding import shard_act

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter descriptors
# ---------------------------------------------------------------------------


def attn_desc(cfg):
    d = cfg.d_model
    hd = cfg.head_dim_
    if cfg.attention == "mla":
        qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        p = {}
        if cfg.q_lora_rank:
            p["q_a"] = ParamDesc((cfg.q_lora_rank, d), jnp.bfloat16, ("lora", "embed"))
            p["q_a_norm"] = ParamDesc((cfg.q_lora_rank,), jnp.float32, ("lora",), "ones")
            p["q_b"] = ParamDesc((cfg.n_heads * qk_head, cfg.q_lora_rank),
                                 jnp.bfloat16, ("heads", "lora"))
        else:
            p["q"] = ParamDesc((cfg.n_heads * qk_head, d), jnp.bfloat16,
                               ("heads", "embed"))
        p["kv_a"] = ParamDesc((cfg.kv_lora_rank + cfg.qk_rope_head_dim, d),
                              jnp.bfloat16, ("lora", "embed"))
        p["kv_a_norm"] = ParamDesc((cfg.kv_lora_rank,), jnp.float32, ("lora",), "ones")
        p["kv_b"] = ParamDesc(
            (cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim), cfg.kv_lora_rank),
            jnp.bfloat16, ("heads", "lora"))
        p["o"] = ParamDesc((d, cfg.n_heads * cfg.v_head_dim), jnp.bfloat16,
                           ("embed", "heads"))
        return p
    p = {
        "q": ParamDesc((cfg.n_heads * hd, d), jnp.bfloat16, ("heads", "embed")),
        "k": ParamDesc((cfg.n_kv_heads * hd, d), jnp.bfloat16, ("kv_heads", "embed")),
        "v": ParamDesc((cfg.n_kv_heads * hd, d), jnp.bfloat16, ("kv_heads", "embed")),
        "o": ParamDesc((d, cfg.n_heads * hd), jnp.bfloat16, ("embed", "heads")),
    }
    if cfg.qkv_bias:
        p["q_b"] = ParamDesc((cfg.n_heads * hd,), jnp.float32, ("heads",), "zeros")
        p["k_b"] = ParamDesc((cfg.n_kv_heads * hd,), jnp.float32, ("kv_heads",), "zeros")
        p["v_b"] = ParamDesc((cfg.n_kv_heads * hd,), jnp.float32, ("kv_heads",), "zeros")
    return p


def cross_attn_desc(cfg):
    """Whisper decoder cross-attention (K/V from encoder output)."""
    return attn_desc(cfg)


# ---------------------------------------------------------------------------
# blockwise attention core
# ---------------------------------------------------------------------------


def _mask(qpos, kpos, causal: bool, window: int):
    """qpos [..., Sq, 1], kpos [..., 1, Sk] -> additive mask."""
    ok = kpos >= 0
    if causal:
        ok &= kpos <= qpos
    if window:
        ok &= (qpos - kpos) < window
    return jnp.where(ok, 0.0, NEG_INF)


def blockwise_attention(q, k, v, qpos, kpos, *, causal=True, window=0,
                        scale=None, q_chunk=512, kv_chunk=1024,
                        kv_map_fn: Optional[Callable] = None):
    """Online-softmax attention.

    q: [B, Sq, H, Dq]; k: [B, Sk, Hkv, Dq] (or latent [B, Sk, *] with
    kv_map_fn); v: [B, Sk, Hkv, Dv] (or None with kv_map_fn).
    qpos: [B, Sq] absolute positions; kpos: [B, Sk] (-1 = empty slot).
    kv_map_fn(k_blk, v_blk) -> (k [B,c,Hkv,Dq], v [B,c,Hkv,Dv]).
    Returns [B, Sq, H, Dv] in q.dtype (FP32 accumulation).
    """
    b, sq, h, dq = q.shape
    sk = k.shape[1]
    if kv_map_fn is None:
        kv_map_fn = lambda kb, vb: (kb, vb)
        hkv = k.shape[2]
        dv = v.shape[-1]
    else:
        kb0, vb0 = jax.eval_shape(kv_map_fn, k[:, :1], None if v is None else v[:, :1])
        hkv, dv = kb0.shape[2], vb0.shape[-1]
    rep = h // hkv
    scale = scale if scale is not None else dq ** -0.5

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    nq = -(-sq // q_chunk)
    nk = -(-sk // kv_chunk)
    sq_pad, sk_pad = nq * q_chunk, nk * kv_chunk
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, sq_pad - sq)))
    if sk_pad != sk:
        pad = [(0, 0), (0, sk_pad - sk)] + [(0, 0)] * (k.ndim - 2)
        k = jnp.pad(k, pad)
        if v is not None:
            v = jnp.pad(v, [(0, 0), (0, sk_pad - sk)] + [(0, 0)] * (v.ndim - 2))
        kpos = jnp.pad(kpos, ((0, 0), (0, sk_pad - sk)), constant_values=-1)

    qc = q.reshape(b, nq, q_chunk, h, dq).transpose(1, 0, 2, 3, 4)
    qposc = qpos.reshape(b, nq, q_chunk).transpose(1, 0, 2)
    kc = k.reshape(b, nk, kv_chunk, *k.shape[2:])
    kc = jnp.moveaxis(kc, 1, 0)
    vc = None
    if v is not None:
        vc = jnp.moveaxis(v.reshape(b, nk, kv_chunk, *v.shape[2:]), 1, 0)
    kposc = jnp.moveaxis(kpos.reshape(b, nk, kv_chunk), 1, 0)

    def q_block(qi, qpi):
        # qi: [B, qc, H, Dq] -> grouped [B, qc, Hkv, rep, Dq].  Operands
        # stay in their storage dtype (bf16 on TPU) with FP32 accumulation
        # — the MXU-native mode; upcasting K/V blocks to f32 would double
        # the cache-read bytes that dominate long-context cells.
        qg = (qi.reshape(b, q_chunk, hkv, rep, dq)
              .astype(jnp.float32) * scale).astype(qi.dtype)

        def kv_step(carry, inp):
            acc, m, l = carry
            kb, vb, kpb = (inp if vc is not None else (inp[0], None, inp[1]))
            kb, vb = kv_map_fn(kb, vb)
            s = jnp.einsum("bqhrd,bkhd->bqhrk", qg, kb.astype(qg.dtype),
                           preferred_element_type=jnp.float32)
            # keep score blocks (and thus the autodiff residual stack built
            # from them) sharded — GSPMD drops batch sharding on nested-scan
            # residuals without this (observed 16 GiB vs 1 GiB per device)
            s = shard_act(s, ("batch", None, "kv_heads", None, None))
            s = s + _mask(qpi[:, :, None, None, None],
                          kpb[:, None, None, None, :], causal, window)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhrk,bkhd->bqhrd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        init = (jnp.zeros((b, q_chunk, hkv, rep, dv), jnp.float32),
                jnp.full((b, q_chunk, hkv, rep), NEG_INF, jnp.float32),
                jnp.zeros((b, q_chunk, hkv, rep), jnp.float32))
        xs = (kc, vc, kposc) if vc is not None else (kc, kposc)
        (acc, m, l), _ = jax.lax.scan(kv_step, init, xs)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(b, q_chunk, h, dv)

    outs = jax.lax.map(lambda args: q_block(*args), (qc, qposc))

    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, sq_pad, h, dv)
    return out[:, :sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------


def cache_desc_gqa(cfg, batch: int, length: int):
    hd = cfg.head_dim_
    hkv = cfg.n_kv_heads * cfg.kv_replication
    dt = jnp.dtype(cfg.dtype)
    if cfg.sliding_window:
        length = min(length, cfg.sliding_window)
    c = {
        "k": ParamDesc((batch, length, hkv, hd),
                       jnp.int8 if cfg.kv_cache_bits == 8 else dt,
                       ("batch", None, "kv_heads", "head_dim"), "zeros"),
        "v": ParamDesc((batch, length, hkv, hd),
                       jnp.int8 if cfg.kv_cache_bits == 8 else dt,
                       ("batch", None, "kv_heads", "head_dim"), "zeros"),
        "pos": ParamDesc((batch, length), jnp.int32, ("batch", None), "zeros"),
    }
    if cfg.kv_cache_bits == 8:
        # symmetric per-(slot, head) scales
        c["k_scale"] = ParamDesc((batch, length, hkv), jnp.float32,
                                 ("batch", None, "kv_heads"), "zeros")
        c["v_scale"] = ParamDesc((batch, length, hkv), jnp.float32,
                                 ("batch", None, "kv_heads"), "zeros")
    return c


def _quantize_kv(t):
    """bf16 [B,S,H,D] -> (int8 values, f32 per-(slot,head) scales)."""
    tf = t.astype(jnp.float32)
    scale = jnp.max(jnp.abs(tf), axis=-1) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(tf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def cache_desc_mla(cfg, batch: int, length: int):
    # the MLA latent has no heads dim to shard over the model axis, so the
    # SEQUENCE dim is sharded instead ("kv_seq" -> model): decode attention
    # over a sequence-sharded cache is a partial softmax + small all-reduce,
    # vs 16x cache replication otherwise.
    dt = jnp.dtype(cfg.dtype)
    return {
        "ckv": ParamDesc((batch, length, cfg.kv_lora_rank), dt,
                         ("batch", "kv_seq", "lora"), "zeros"),
        "krope": ParamDesc((batch, length, cfg.qk_rope_head_dim), dt,
                           ("batch", "kv_seq", None), "zeros"),
        "pos": ParamDesc((batch, length), jnp.int32, ("batch", "kv_seq"),
                         "zeros"),
    }


def paged_cache_desc(cfg, batch: int, num_blocks: int, block_size: int,
                     max_blocks_per_seq: int):
    """Paged per-layer cache: the contiguous descriptors with batch ->
    num_blocks and length -> block_size (the pool), plus the block table.

    Sliding-window attention keeps its ring cache (paging a ring buys
    nothing: the window is already a fixed-size reservation), so paged
    caches are only built for full-attention configs.
    """
    if cfg.sliding_window:
        raise ValueError("paged KV cache requires sliding_window == 0 "
                         "(ring caches are already fixed-size)")
    base = (cache_desc_mla if cfg.attention == "mla" else cache_desc_gqa)(
        cfg, num_blocks, block_size)
    base["block_tables"] = ParamDesc((batch, max_blocks_per_seq), jnp.int32,
                                     ("batch", None), "zeros")
    return base


def empty_pos(pos_like):
    return jnp.full_like(pos_like, -1)


# --- paged layout -----------------------------------------------------------
#
# A paged per-layer cache stores every buffer as a shared pool
# [num_blocks, block_size, ...] plus a ``block_tables`` leaf
# [B, max_blocks_per_seq] int32 mapping each sequence's logical block i
# (positions [i*bs, (i+1)*bs)) to a physical pool block (-1 = unallocated).
# Physical block 0 is reserved as a trash block: any write whose target is
# out of range or unallocated lands there, and ``paged_view`` masks every
# slot reached through a -1 table entry, so trash contents are never read.
# The same ``pos``-based masking that drives the contiguous cache then
# makes a gathered view of the pool indistinguishable from a contiguous
# cache to the attention math.
#
# Decode (S == 1) does not need the gathered view at all: the fused
# Pallas kernels (``kernels/paged_attention``) apply the identical
# liveness mask inside the kernel while reading pool blocks directly
# through the block table — float, int8 (per-slot scales ride the same
# block DMA) and MLA-latent pools all run fused.  Chunked prefill
# (S > 1) has its own fused kernel reading prior context straight from
# the pool with per-query causal masking, so ``paged_view`` is only
# materialized on the remaining gathered fallbacks: sliding-window
# masking, mesh-indivisible head counts, and MLA *prefill* (which needs
# the decompressing ``kv_map_fn``) — see ``paged_decode_attend`` /
# ``paged_prefill_attend`` / ``mla_paged_decode_attend``.


def is_paged(cache: dict) -> bool:
    return "block_tables" in cache


def kv_entry_bytes(cfg) -> int:
    """KV-cache storage bytes per (token, layer) — the unit of the
    decode-bandwidth accounting in serve metrics and benchmarks."""
    if cfg.attention == "mla":
        return (cfg.kv_lora_rank + cfg.qk_rope_head_dim) \
            * jnp.dtype(cfg.dtype).itemsize
    hkv = cfg.n_kv_heads * cfg.kv_replication
    d = cfg.head_dim_
    if cfg.kv_cache_bits == 8:
        return 2 * hkv * d + 2 * hkv * 4        # int8 K/V + f32 scales
    return 2 * hkv * d * jnp.dtype(cfg.dtype).itemsize


def paged_view(cache: dict) -> dict:
    """Gather a per-sequence contiguous view of a paged cache.

    Returns a dict shaped like a contiguous cache ([B, max_blocks * bs,
    ...]) whose ``pos`` is -1 wherever the slot is not live — directly
    consumable by ``decode_attend`` / ``blockwise_attention``.
    """
    table = cache["block_tables"]                 # [B, nblk]
    b, nblk = table.shape
    bs = cache["pos"].shape[1]
    safe = jnp.maximum(table, 0).reshape(-1)
    view = {}
    for key, val in cache.items():
        if key == "block_tables":
            continue
        g = jnp.take(val, safe, axis=0)           # [B*nblk, bs, ...]
        view[key] = g.reshape(b, nblk * bs, *val.shape[2:])
    # A slot is live iff its table entry is allocated AND its stored
    # position equals its logical view index (position p always lands at
    # view index p).  The second check is what makes pool recycling
    # safe: a freed block re-allocated at a different logical index
    # still holds the previous owner's pos values, which would otherwise
    # pass the kpos <= qpos mask and leak dead K/V into attention.
    allocated = jnp.repeat(table >= 0, bs, axis=1)            # [B, nblk*bs]
    iota = jnp.arange(nblk * bs, dtype=jnp.int32)[None]
    view["pos"] = jnp.where(allocated & (view["pos"] == iota),
                            view["pos"], -1)
    return view


def _paged_insert(cache: dict, updates: dict, at) -> dict:
    """Scatter S new entries into the block pool via the block tables.

    Position p of row b lives at physical slot ``table[b, p // bs] * bs
    + p % bs``.  Writes with a negative position (masked left-pads), a
    logical block beyond the table, or an unallocated table entry are
    routed to the reserved trash block 0.
    """
    table = cache["block_tables"]                 # [B, nblk]
    nb, bs = cache["pos"].shape
    b, nblk = table.shape
    s = next(iter(updates.values())).shape[1]
    at = jnp.asarray(at, jnp.int32)
    if at.ndim == 0:
        at = jnp.broadcast_to(at, (b,))
    positions = at[:, None] + jnp.arange(s, dtype=jnp.int32)[None]   # [B, S]
    blk = positions // bs
    phys = jnp.take_along_axis(table, jnp.clip(blk, 0, nblk - 1), axis=1)
    valid = (positions >= 0) & (blk < nblk) & (phys >= 0)
    phys = jnp.where(valid, phys, 0)              # invalid -> trash block
    flat = phys * bs + positions % bs             # [B, S] into [nb*bs]

    new = dict(cache)
    for key, val in updates.items():
        buf = cache[key]
        fb = buf.reshape(nb * bs, *buf.shape[2:])
        new[key] = fb.at[flat].set(val.astype(buf.dtype)).reshape(buf.shape)
    posf = cache["pos"].reshape(nb * bs)
    new["pos"] = posf.at[flat].set(
        jnp.where(valid, positions, -1)).reshape(nb, bs)
    return new


def cache_insert(cache: dict, updates: dict, at):
    """Write S new entries starting at absolute position ``at``.

    ``at`` is a scalar or per-row [B] vector (ragged continuous batching).
    Slot convention: position p lives at slot p % L (ring semantics; a
    full-length cache is the special case L >= max position).  Paged
    caches (``block_tables`` present) scatter through the block table
    instead — see ``_paged_insert``.
    ``updates`` maps cache keys -> [B, S, ...] new values.
    """
    if is_paged(cache):
        return _paged_insert(cache, updates, at)
    b, length = cache["pos"].shape
    s = next(iter(updates.values())).shape[1]
    if s > length:
        # writing more than the ring holds (SWA prefill > window): only the
        # trailing `length` entries survive.
        updates = {k: v[:, -length:] for k, v in updates.items()}
        at = at + (s - length)
        s = length
    at = jnp.asarray(at, jnp.int32)
    if at.ndim == 0:
        at = jnp.broadcast_to(at, (b,))
    positions = at[:, None] + jnp.arange(s, dtype=jnp.int32)[None]   # [B, S]
    slots = positions % length
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]

    new = dict(cache)
    for key, val in updates.items():
        new[key] = cache[key].at[bidx, slots].set(val.astype(cache[key].dtype))
    new["pos"] = cache["pos"].at[bidx, slots].set(positions)
    return new


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def _split_heads(x, n, d):
    return x.reshape(*x.shape[:-1], n, d)


def gqa_apply(params, cfg, x, positions, *, cache=None, cache_at=None,
              causal=True, backend=None):
    """GQA/MHA/SWA attention.

    x: [B, S, d]; positions: [B, S].
    cache=None          -> train/eval full-sequence attention.
    cache + cache_at    -> write new KV at ``cache_at`` then attend to cache
                           (prefill: S>1; decode: S=1). Returns (out, cache).
    """
    b, s, d = x.shape
    hd = cfg.head_dim_
    h, hkv = cfg.n_heads, cfg.n_kv_heads

    q = _split_heads(linear_apply(params["q"], x, params.get("q_b"),
                                  backend=backend), h, hd)
    k = _split_heads(linear_apply(params["k"], x, params.get("k_b"),
                                  backend=backend), hkv, hd)
    v = _split_heads(linear_apply(params["v"], x, params.get("v_b"),
                                  backend=backend), hkv, hd)
    if cfg.kv_replication > 1:
        # replicate kv heads so the cache shards over TP > n_kv_heads:
        # q head i groups with effective kv head i // (H / (hkv*r))
        k = jnp.repeat(k, cfg.kv_replication, axis=2)
        v = jnp.repeat(v, cfg.kv_replication, axis=2)
        hkv = hkv * cfg.kv_replication
    q = shard_act(q, ("batch", None, "heads", None))
    k = shard_act(k, ("batch", None, "kv_heads", None))
    v = shard_act(v, ("batch", None, "kv_heads", None))

    if cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = blockwise_attention(q, k, v, positions, positions, causal=causal,
                                  window=cfg.sliding_window)
    elif cfg.kv_cache_bits == 8:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        cache = cache_insert(cache, {"k": kq, "v": vq,
                                     "k_scale": ks, "v_scale": vs}, cache_at)
        if s == 1:
            if is_paged(cache):
                # the router's fused int8 kernel folds the per-slot
                # scales in-kernel (decode_attend's ordering)
                out = paged_decode_attend(q, cache, positions,
                                          window=cfg.sliding_window,
                                          mode=cfg.paged_kernel)
            else:
                out = decode_attend(q, cache, positions,
                                    window=cfg.sliding_window)
        elif is_paged(cache):
            # chunked prefill: earlier chunks are only in the cache
            # (unlike the whole-prompt path below, the cache is NOT
            # empty here) — the router reads pool blocks directly and
            # dequantizes in-kernel, or gathers + dequantizes the view
            out = paged_prefill_attend(q, cache, positions,
                                       mode=cfg.paged_kernel)
        else:
            # prefill: attend over the fresh bf16 K/V (the cache was empty,
            # so causal/windowed attention over the prompt is equivalent) —
            # quantization error then only affects subsequent decode reads
            out = blockwise_attention(q, k, v, positions, positions,
                                      causal=True, window=cfg.sliding_window)
    elif s == 1:
        # decode fast path: contract in cache layout, bf16 reads; paged
        # caches route through the fused-vs-gathered kernel selector
        cache = cache_insert(cache, {"k": k, "v": v}, cache_at)
        if is_paged(cache):
            out = paged_decode_attend(q, cache, positions,
                                      window=cfg.sliding_window,
                                      mode=cfg.paged_kernel)
        else:
            out = decode_attend(q, cache, positions, window=cfg.sliding_window)
    else:
        cache = cache_insert(cache, {"k": k, "v": v}, cache_at)
        if is_paged(cache):
            out = paged_prefill_attend(q, cache, positions,
                                       mode=cfg.paged_kernel)
        else:
            out = blockwise_attention(q, cache["k"], cache["v"], positions,
                                      cache["pos"], causal=True,
                                      window=cfg.sliding_window)
    out = out.reshape(b, s, h * hd)
    out = linear_apply(params["o"], out, backend=backend, row_parallel=True)
    return (out, cache) if cache is not None else out


def decode_attend(q, cache, positions, *, window=0, scale=None):
    """Single-token attention against a cache, in storage layout.

    The generic blockwise path reshapes/transposes the whole cache into
    chunk-major order and upcasts chunks to f32 — ~4 extra cache-sized
    copies per layer that dominate the decode memory roofline.  Here the
    contractions run directly on the [B, L, Hkv, D] buffers in bf16
    (FP32 accumulation via preferred_element_type), no reshuffling.

    q: [B, 1, H, D]; positions: [B, 1] absolute position of the token.
    """
    k, v, kpos = cache["k"], cache["v"], cache["pos"]
    b, s, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    scale = scale if scale is not None else d ** -0.5
    if k.dtype == jnp.int8:
        # int8 KV: fold the per-slot scale into the score after an int8-read
        # contraction (the dequant multiply fuses into the dot epilogue)
        qg = (q.reshape(b, hkv, rep, d).astype(jnp.float32) * scale)
        sc = jnp.einsum("bhrd,blhd->bhrl", qg.astype(jnp.bfloat16),
                        k.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        sc = sc * cache["k_scale"].transpose(0, 2, 1)[:, :, None, :]
        v_eff = v.astype(jnp.bfloat16)
    else:
        # scale in f32 THEN round to the storage dtype — identical rounding
        # to the blockwise path so decode == forward to f32-accum noise
        qg = (q.reshape(b, hkv, rep, d).astype(jnp.float32) * scale
              ).astype(k.dtype)
        sc = jnp.einsum("bhrd,blhd->bhrl", qg, k,
                        preferred_element_type=jnp.float32)  # [B,Hkv,rep,L]
        v_eff = v
    # pin the (small) score sharding: when the cache shards head_dim over
    # the model axis, GSPMD otherwise prefers ALL-GATHERING the whole KV
    # cache per layer (~34 GB/step at 32k) over all-reducing these scores
    sc = shard_act(sc, ("batch", "kv_heads", None, "kv_seq"))
    ok = (kpos >= 0) & (kpos <= positions[:, :1])
    if window:
        ok &= (positions[:, :1] - kpos) < window
    sc = jnp.where(ok[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    if k.dtype == jnp.int8:
        p = p * cache["v_scale"].transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bhrl,blhd->bhrd", p.astype(v_eff.dtype), v_eff,
                     preferred_element_type=jnp.float32)
    out = shard_act(out, ("batch", "kv_heads", None, "head_dim"))
    return out.reshape(b, 1, h, d).astype(q.dtype)


PAGED_KERNEL_MODES = ("auto", "fused", "gather")


# --- sharded paged decode --------------------------------------------------
#
# When the paged serve engine runs over a TP/DP mesh, the KV pool leaves
# are sharded over "kv_heads" -> model and the fused kernel must launch
# per model-shard (a Pallas call has no GSPMD partitioning rule, so under
# plain jit XLA would all-gather the pool).  The engine declares the mesh
# through ``paged_shard_scope`` around its (trace-triggering) decode
# calls, and ``paged_decode_attend`` routes the kernel through
# ``shard_map`` over the mesh: each shard reads its own kv-head slice of
# every pool block, block tables/positions ride along replicated (or
# data-sharded with the batch rows), and no cross-device traffic happens
# inside the step at all — heads are embarrassingly parallel in decode
# attention.  The per-shard head counts feed ``tune.dispatch`` for the
# capability probe and ``block_h`` clamping, so head counts that do not
# divide the mesh fall back to the gathered ``paged_view`` path exactly
# like the other unsupported variants.

_PAGED_SHARD = {"mesh": None, "tp": 1, "shard_batch": False}


@contextlib.contextmanager
def paged_shard_scope(mesh, *, tp: int = 1, shard_batch: bool = False):
    """Declare the serving mesh for paged decode tracing.

    Active while the engine's jitted ``decode_step`` traces (tracing
    happens inside the first call, so the engine wraps every call);
    restores the previous scope on exit so engines with different
    meshes (or none) can coexist in one process."""
    prev = dict(_PAGED_SHARD)
    _PAGED_SHARD.update(mesh=mesh, tp=tp, shard_batch=shard_batch)
    try:
        yield
    finally:
        _PAGED_SHARD.update(prev)


def serving_mesh():
    """The mesh of the active :func:`paged_shard_scope` when it has a
    model axis wider than one device, else None — the serving engine's
    jitted steps trace inside that scope, so per-shard kernel launches
    (paged attention here, plane GEMMs in ``quant.backends``) see it."""
    return _PAGED_SHARD["mesh"] if _PAGED_SHARD["tp"] > 1 else None


def _fused_selected(mode: str, supported: bool) -> bool:
    """The single fused-vs-gather routing rule, shared by the device
    path (:func:`paged_decode_attend`) and the host mirror
    (:func:`paged_kernel_mode`) so the engine's labeling/metrics can
    never drift from the path the decode step actually takes: explicit
    "fused" runs wherever the kernel is supported (interpret mode
    off-TPU); "auto" additionally requires it to be hardware-native."""
    if mode not in PAGED_KERNEL_MODES:
        raise ValueError(f"paged_kernel must be one of {PAGED_KERNEL_MODES}, "
                         f"got {mode!r}")
    if mode == "gather" or not supported:
        return False
    return mode == "fused" or jax.default_backend() == "tpu"


def _paged_cache_caps(cache: dict, n_heads: int) -> dict:
    """The capability axes of a paged cache leaf, as the ``caps`` kwargs
    for ``tune.dispatch.kernel_unsupported_reason``.  MLA latent pools
    (``ckv`` leaf) probe with ``latent=True`` and kv heads == q heads
    (no replication in the absorbed formulation — heads are
    embarrassingly parallel over latent blocks)."""
    if "ckv" in cache:
        return dict(n_kv_heads=n_heads, kv_dtype=cache["ckv"].dtype,
                    latent=True)
    return dict(n_kv_heads=cache["k"].shape[2], kv_dtype=cache["k"].dtype,
                latent=False)


def fused_paged_supported(cache: dict, n_heads: int, *, window: int = 0,
                          tp: int = 1,
                          kernel: str = "paged_attention") -> bool:
    """Can a fused Pallas kernel serve this paged cache leaf?  Float,
    int8 (per-slot scale fold) and MLA-latent pools are covered for
    decode; float and int8 for chunked prefill
    (``kernel="paged_prefill"``).  Sliding-window masking, head counts
    that don't divide a ``tp``-way model mesh, and MLA prefill fall back
    to the gathered path — the capability boundary (and the per-cap
    fallback reason) lives in ``tune.dispatch.kernel_unsupported_reason``.
    """
    from repro.tune.dispatch import kernel_unsupported_reason
    if not is_paged(cache):
        return False
    bs = cache["pos"].shape[1]
    pages = cache["block_tables"].shape[-1]
    return kernel_unsupported_reason(
        kernel, m=n_heads, n=pages * bs, group_size=bs, window=window,
        tp=tp, **_paged_cache_caps(cache, n_heads)) is None


def _cfg_paged_caps(cfg) -> dict:
    """Config-level mirror of :func:`_paged_cache_caps` (for the host-
    side mode resolvers, which have no cache leaf to inspect)."""
    if cfg.attention == "mla":
        return dict(n_kv_heads=cfg.n_heads, kv_dtype=cfg.dtype, latent=True)
    return dict(n_kv_heads=cfg.n_kv_heads * cfg.kv_replication,
                kv_dtype="int8" if cfg.kv_cache_bits == 8 else cfg.dtype,
                latent=False)


def paged_kernel_mode(cfg, *, block_size: int, pages: int,
                      tp: int = 1) -> str:
    """Host-side mirror of the decode routing decision: resolve
    ``cfg.paged_kernel`` to the path ("fused" | "gather") a decode step
    on this config's paged cache will actually take — PER VARIANT, so
    an int8-KV or MLA config reports "fused" iff its own kernel variant
    really runs (no silent "fused" label on a gathered step).  Used by
    the serve engine for labeling and KV-bandwidth accounting — the
    device-side decisions in :func:`paged_decode_attend` /
    :func:`mla_paged_decode_attend` follow the same rule.  ``tp`` is the
    model-axis extent when serving over a mesh (the fused kernel then
    launches per-shard via ``shard_map``)."""
    from repro.tune.dispatch import kernel_supports
    ok = kernel_supports(
        "paged_attention", m=cfg.n_heads, n=pages * block_size,
        group_size=block_size, window=cfg.sliding_window, tp=tp,
        **_cfg_paged_caps(cfg))
    return "fused" if _fused_selected(cfg.paged_kernel, ok) else "gather"


def paged_prefill_mode(cfg, *, block_size: int, pages: int,
                       tp: int = 1) -> str:
    """Host-side mirror of the CHUNKED-PREFILL routing decision —
    :func:`paged_kernel_mode`'s counterpart for ``paged_prefill_attend``.
    MLA prefill always resolves to "gather" (the latent blocks must be
    decompressed through ``kv_map_fn``, which the prefill kernel does
    not fold)."""
    from repro.tune.dispatch import kernel_supports
    ok = kernel_supports(
        "paged_prefill", m=cfg.n_heads, n=pages * block_size,
        group_size=block_size, window=cfg.sliding_window, tp=tp,
        **_cfg_paged_caps(cfg))
    return "fused" if _fused_selected(cfg.paged_kernel, ok) else "gather"


def paged_decode_attend(q, cache, positions, *, window=0, scale=None,
                        mode="auto"):
    """Single-token attention on a PAGED cache.

    When the fused Pallas kernel is selected, the block-table gather
    happens *inside* the kernel (scalar-prefetched index_map) and the
    contiguous ``paged_view`` is never materialized — the decode path
    reads each live pool block exactly once instead of copying the whole
    table-addressable view per layer.  Otherwise: gather (``paged_view``)
    + :func:`decode_attend`, the reference path.

    mode: "auto" (fused only where it is the hardware-native path, i.e.
    on TPU), "fused" (force the kernel; interpret mode off-TPU), or
    "gather".  int8-KV pools route to the scale-folding kernel variant.
    Variants no kernel covers (sliding-window, mesh-indivisible head
    counts) fall back to the gathered path in every mode.

    Inside a :func:`paged_shard_scope` the kernel launches per
    model-shard through ``shard_map``: the pool's kv-head slice stays
    local to each shard and the capability probe / ``block_h`` clamp see
    the per-shard head counts.
    """
    mesh = _PAGED_SHARD["mesh"]
    tp = _PAGED_SHARD["tp"] if mesh is not None else 1
    use = _fused_selected(mode, fused_paged_supported(cache, q.shape[2],
                                                      window=window, tp=tp))
    if use:
        from repro.core.lut_gemm import interpret_mode
        interp = interpret_mode()
        from repro.kernels.paged_attention import (paged_attention,
                                                   paged_attention_int8)
        int8 = cache["k"].dtype == jnp.int8
        if int8:
            fn = functools.partial(paged_attention_int8, scale=scale,
                                   interpret=interp)
            args = (q[:, 0], cache["k"], cache["v"], cache["k_scale"],
                    cache["v_scale"], cache["pos"], cache["block_tables"],
                    positions[:, 0])
        else:
            fn = functools.partial(paged_attention, scale=scale,
                                   interpret=interp)
            args = (q[:, 0], cache["k"], cache["v"], cache["pos"],
                    cache["block_tables"], positions[:, 0])
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            dax = "data" if _PAGED_SHARD["shard_batch"] else None
            pool = P(None, None, "model", None)
            scl = P(None, None, "model")        # scale pools [NB, BS, Hkv]
            in_specs = (P(dax, "model", None),  # q [B, H, D]
                        pool, pool) \
                + ((scl, scl) if int8 else ()) \
                + (P(None, None),               # pos pool
                   P(dax, None),                # block tables
                   P(dax))                      # positions
            out3 = jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs,
                out_specs=P(dax, "model", None), check_vma=False)(*args)
            return out3[:, None]
        out = fn(*args)
        out = shard_act(out[:, None], ("batch", None, "heads", None))
        return out
    kv = paged_view(cache)
    return decode_attend(q, kv, positions, window=window, scale=scale)


def mla_paged_decode_attend(q_eff, q_rope, cache, positions, *, scale,
                            mode="auto"):
    """Absorbed MLA decode on a PAGED latent cache.

    q_eff: f32 [B, 1, H, lora] (``w_uk`` already absorbed); q_rope:
    [B, 1, H, rope_dim].  Returns the latent context [B, 1, H, lora] —
    the caller applies ``w_uv``.  When the fused kernel is selected the
    latent blocks are read straight from the pool (scores in latent
    space, the ``kv_map_fn`` decompression folded away by absorption)
    and ``paged_view`` is never materialized; otherwise: gather + the
    absorbed reference math.
    """
    mesh = _PAGED_SHARD["mesh"]
    tp = _PAGED_SHARD["tp"] if mesh is not None else 1
    h = q_eff.shape[2]
    use = _fused_selected(mode, fused_paged_supported(cache, h, tp=tp))
    if use:
        from repro.core.lut_gemm import interpret_mode
        interp = interpret_mode()
        from repro.kernels.paged_attention import paged_attention_mla
        fn = functools.partial(paged_attention_mla, scale=float(scale),
                               interpret=interp)
        args = (q_eff[:, 0], q_rope[:, 0].astype(jnp.float32),
                cache["ckv"], cache["krope"], cache["pos"],
                cache["block_tables"], positions[:, 0])
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            dax = "data" if _PAGED_SHARD["shard_batch"] else None
            # latent pools have no heads dim: they ride replicated (the
            # paged pool nulls the contiguous cache's "kv_seq" sharding —
            # see model.paged_cache_axes) and the QUERY heads shard
            ctx3 = jax.shard_map(
                fn, mesh=mesh, check_vma=False,
                in_specs=(P(dax, "model", None),   # q_eff [B, H, lora]
                          P(dax, "model", None),   # q_rope [B, H, dr]
                          P(None, None, None),     # ckv pool
                          P(None, None, None),     # krope pool
                          P(None, None),           # pos pool
                          P(dax, None),            # block tables
                          P(dax)),                 # positions
                out_specs=P(dax, "model", None))(*args)
            return ctx3[:, None]
        return fn(*args)[:, None]
    kv = paged_view(cache)
    return _mla_absorbed_ctx(q_eff, q_rope, kv["ckv"], kv["krope"],
                             kv["pos"], positions, scale)


def paged_prefill_attend(q, cache, positions, *, scale=None, mode="auto"):
    """Chunked-prefill attention on a PAGED cache (current chunk already
    inserted into the pool).

    q: [B, C, H, D]; positions: int32 [B, C] (-1 on pad rows).  When the
    fused kernel is selected, the chunk's queries attend over prior
    context straight from the block pool (scalar-prefetched block-table
    indexing, per-query causal masking across the chunk boundary, int8
    scales folded in-kernel) and ``paged_view`` is never materialized.
    Otherwise: gather + ``blockwise_attention``, the reference path.
    Pad query rows differ harmlessly between the two (kernel: zeros;
    blockwise: unnormalized garbage) — both are discarded downstream.
    """
    mesh = _PAGED_SHARD["mesh"]
    tp = _PAGED_SHARD["tp"] if mesh is not None else 1
    use = _fused_selected(mode, fused_paged_supported(
        cache, q.shape[2], tp=tp, kernel="paged_prefill"))
    if use:
        from repro.core.lut_gemm import interpret_mode
        interp = interpret_mode()
        from repro.kernels.paged_attention import paged_prefill
        int8 = cache["k"].dtype == jnp.int8
        fn = functools.partial(paged_prefill, scale=scale,
                               interpret=interp)
        args = (q, cache["k"], cache["v"], cache["pos"],
                cache["block_tables"], positions) \
            + ((cache["k_scale"], cache["v_scale"]) if int8 else ())
        if int8:
            fn = functools.partial(
                lambda q_, k_, v_, p_, t_, pos_, ks_, vs_, **kw:
                paged_prefill(q_, k_, v_, p_, t_, pos_,
                              k_scale=ks_, v_scale=vs_, **kw),
                scale=scale, interpret=interp)
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            # prefill runs one sequence's chunk at a time (B=1), which a
            # data axis > 1 cannot split — replicate unless B divides
            dax = "data" if (_PAGED_SHARD["shard_batch"]
                             and q.shape[0] % dict(mesh.shape).get(
                                 "data", 1) == 0) else None
            pool = P(None, None, "model", None)
            scl = P(None, None, "model")
            in_specs = (P(dax, None, "model", None),  # q [B, C, H, D]
                        pool, pool,
                        P(None, None),                # pos pool
                        P(dax, None),                 # block tables
                        P(dax, None)) \
                + ((scl, scl) if int8 else ())        # scale pools
            out = jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs,
                out_specs=P(dax, None, "model", None),
                check_vma=False)(*args)
            return out
        return shard_act(fn(*args), ("batch", None, "heads", None))
    kv = paged_view(cache)
    if cache["k"].dtype == jnp.int8:
        kd = (kv["k"].astype(jnp.float32)
              * kv["k_scale"][..., None]).astype(q.dtype)
        vd = (kv["v"].astype(jnp.float32)
              * kv["v_scale"][..., None]).astype(q.dtype)
        return blockwise_attention(q, kd, vd, positions, kv["pos"],
                                   causal=True, scale=scale)
    return blockwise_attention(q, kv["k"], kv["v"], positions, kv["pos"],
                               causal=True, scale=scale)


def cross_kv(params, cfg, enc_out, backend=None):
    """Project encoder output to cross-attention K/V (cached at prefill)."""
    hd = cfg.head_dim_
    hkv = cfg.n_kv_heads
    k = _split_heads(linear_apply(params["k"], enc_out, params.get("k_b"),
                                  backend=backend), hkv, hd)
    v = _split_heads(linear_apply(params["v"], enc_out, params.get("v_b"),
                                  backend=backend), hkv, hd)
    return k, v


def cross_attend(params, cfg, x, k, v, backend=None):
    """Decoder cross-attention against (possibly cached) encoder K/V."""
    b, s, _ = x.shape
    hd = cfg.head_dim_
    h = cfg.n_heads
    q = _split_heads(linear_apply(params["q"], x, params.get("q_b"),
                                  backend=backend), h, hd)
    qpos = jnp.zeros((b, s), jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(k.shape[1], dtype=jnp.int32)[None],
                            (b, k.shape[1]))
    out = blockwise_attention(q, k, v, qpos, kpos, causal=False)
    return linear_apply(params["o"], out.reshape(b, s, h * hd),
                        backend=backend, row_parallel=True)


# ---------------------------------------------------------------------------
# MLA attention block (deepseek-v2 / minicpm3)
# ---------------------------------------------------------------------------


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps) * scale
    return y.astype(x.dtype)


def _mla_absorbed_ctx(q_eff, q_rope, ckv_all, krope_all, kpos, positions,
                      scale):
    """Gathered/contiguous absorbed-decode math: latent-space scores +
    softmax + latent context.  q_eff: f32 [B, 1, H, lora]; returns
    [B, 1, H, lora] f32 (the caller applies ``w_uv``).  The mask relies
    on ``kpos`` being -1 on every non-live slot (``paged_view`` sets
    this for paged caches; contiguous caches store -1 on empty slots).
    """
    sc = jnp.einsum("bshl,bkl->bshk", q_eff, ckv_all.astype(jnp.float32))
    sc = sc + jnp.einsum("bshr,bkr->bshk", q_rope.astype(jnp.float32),
                         krope_all.astype(jnp.float32))
    sc = sc * scale
    # mask: slot occupied and slot position <= current decode position
    m = (kpos >= 0)[:, None, None, :] & \
        (kpos[:, None, None, :] <= positions[:, 0][:, None, None, None])
    sc = jnp.where(m, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bshk,bkl->bshl", p, ckv_all.astype(jnp.float32))


def mla_apply(params, cfg, x, positions, *, cache=None, cache_at=None,
              backend=None):
    """Multi-head latent attention with compressed KV cache.

    Prefill/train: decompress latent KV inside the blockwise scan.
    Decode (S==1): absorbed formulation — scores/values in latent space.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    scale = (dn + dr) ** -0.5

    # --- queries -----------------------------------------------------
    if cfg.q_lora_rank:
        qa = linear_apply(params["q_a"], x, backend=backend)
        qa = _rms(qa, params["q_a_norm"])
        q = linear_apply(params["q_b"], qa, backend=backend)
    else:
        q = linear_apply(params["q"], x, backend=backend)
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    # --- compressed KV ------------------------------------------------
    kv_a = linear_apply(params["kv_a"], x, backend=backend)   # [B,S,lora+dr]
    ckv = _rms(kv_a[..., :lora], params["kv_a_norm"])
    krope = apply_rope(kv_a[..., lora:][:, :, None, :], positions,
                       cfg.rope_theta)[:, :, 0, :]            # shared head

    w_kvb = params["kv_b"]
    # split decompression weight into W_uk [H, dn, lora], W_uv [H, dv, lora]
    from repro.core.bcq import BCQWeight, dequantize
    w_dense = dequantize(w_kvb, jnp.float32) if isinstance(w_kvb, BCQWeight) \
        else w_kvb.astype(jnp.float32)
    w_kvb3 = w_dense.reshape(h, dn + dv, lora)
    w_uk, w_uv = w_kvb3[:, :dn, :], w_kvb3[:, dn:, :]

    if cache is not None:
        cache = cache_insert(cache, {"ckv": ckv, "krope": krope}, cache_at)

    if s == 1 and cache is not None:
        # ---- absorbed decode: O(L * lora) per step -------------------
        q_eff = jnp.einsum("bshn,hnl->bshl", q_nope.astype(jnp.float32), w_uk)
        if is_paged(cache):
            # the router reads latent blocks straight from the pool when
            # the fused MLA kernel is selected (no gathered view)
            ctx = mla_paged_decode_attend(q_eff, q_rope, cache, positions,
                                          scale=scale,
                                          mode=cfg.paged_kernel)
        else:
            ctx = _mla_absorbed_ctx(q_eff, q_rope, cache["ckv"],
                                    cache["krope"], cache["pos"],
                                    positions, scale)
        out = jnp.einsum("bshl,hvl->bshv", ctx, w_uv)          # [B,1,H,dv]
    else:
        if cache is not None:
            # MLA prefill stays on the gathered view: the latent blocks
            # must be decompressed through kv_map_fn (W_uk/W_uv per
            # block), which the fused prefill kernel does not fold
            kv = paged_view(cache) if is_paged(cache) else cache
            ckv_all, krope_all, kpos = kv["ckv"], kv["krope"], kv["pos"]
        else:
            ckv_all, krope_all, kpos = ckv, krope, positions
        # ---- prefill/train: decompress per KV block ------------------
        def kv_map(latent_blk, _):
            c, kr = latent_blk[..., :lora], latent_blk[..., lora:]
            k_nope = jnp.einsum("bkl,hnl->bkhn", c.astype(jnp.float32), w_uk)
            v_b = jnp.einsum("bkl,hvl->bkhv", c.astype(jnp.float32), w_uv)
            k_full = jnp.concatenate(
                [k_nope, jnp.broadcast_to(kr[:, :, None, :].astype(jnp.float32),
                                          (*k_nope.shape[:2], h, dr))], axis=-1)
            # keep f32: transient inside the KV-block scan; matches the
            # absorbed decode path's precision
            return k_full, v_b

        latent = jnp.concatenate([ckv_all, krope_all], axis=-1)
        # MLA stays f32-operand: the absorbed decode path reassociates the
        # score computation, so both paths run f32 to stay numerically
        # interchangeable (the latent cache is ~8x smaller than a GQA
        # cache, so the bf16-operand byte saving matters much less here).
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1).astype(jnp.float32)
        out = blockwise_attention(q_full, latent, None, positions, kpos,
                                  causal=True, scale=scale, kv_map_fn=kv_map)

    out = out.reshape(b, s, h * dv).astype(x.dtype)
    out = linear_apply(params["o"], out, backend=backend, row_parallel=True)
    return (out, cache) if cache is not None else out
