"""Pallas TPU kernel: fused paged-KV decode attention.

The paged serving engine stores every layer's KV as a shared pool
``[num_blocks, block_size, Hkv, D]`` plus per-sequence block tables
``[B, max_blocks_per_seq]`` (see ``models/attention.py``).  The XLA
fallback materializes a gathered per-sequence view of the pool before
every decode step — a full-cache copy per layer, exactly the bandwidth
waste FIGLUT's LUT dataflow exists to avoid.  This kernel moves the
block-table lookup *into* the attention kernel, the same "indirection
stays on-chip" principle as the LUT kernel's keyed reads: each grid step
DMAs one physical pool block straight into VMEM via a block-table-driven
``index_map`` (scalar-prefetched, so the address is known before the
step runs) and folds it into a flash-style online softmax.  The gathered
view is never built.

Masking is identical to ``paged_view``'s liveness rule and happens on
the scores in-kernel: a slot contributes iff

  * its table entry is allocated (``table[b, j] >= 0``),
  * its stored position equals its logical view index ``j * bs + i``
    (recycled pool blocks still hold a dead sequence's positions — this
    is what makes pool recycling safe), and
  * its position is causally visible (``pos <= q_pos``).

``pos == -1`` pads and trash-block contents fail the second check, so
they are read but never attended — matching the gathered oracle.

Grid: ``(B, Hkv / block_h, num_logical_blocks)`` with the page dim
innermost; the output block (revisited across pages) doubles as the
FP32 accumulator, with running max / sum in VMEM scratch.  Rows with no
live slot at all (idle batch rows parked on the trash block) produce
zeros — the engine discards their outputs.

Every kernel body is two-dimensional, which is what the TPU compiler
lowers.  The caller lays the queries of one head tile out as rows
``[R, D]`` ordered head-major (``R = block_h * rep`` for decode,
``block_h * rep * C`` for a prefill chunk), so a row's kv head is a
contiguous row range.  Per pool slot ``i`` the scores of all rows
against all ``block_h`` keys of that slot are one MXU product
``[R, D] x [block_h, D]^T``; the row's own head is picked by a
head-ownership mask and a lane sum, and the values are folded back the
same way.  The slot positions ride as a ``[NB, 1, BS]`` view so their
block is whole in its last two dims.

Variant coverage (the FLUTE offline-restructure-then-fuse pattern: all
layout work happens at quantize/admission time so the in-loop index
math stays trivial):

* ``_paged_attn_int8_kernel`` — int8-KV pools.  The per-slot
  ``k_scale``/``v_scale`` rows (``[NB, BS, Hkv]`` f32, written at
  admission by ``_quantize_kv``) ride the *same* block-table-driven DMA
  as the KV block, and the dequant fold happens on the score / value
  epilogues in-kernel: raw int8 scores are multiplied by ``k_scale``
  before the running max, and ``v_scale`` folds into the PV contraction
  only — the running sum ``l`` accumulates *unscaled* probabilities so
  the final normalization matches the gathered ``decode_attend``
  ordering (softmax first, then ``p * v_scale``).
* ``_paged_attn_mla_kernel`` — MLA latent pools.  The caller absorbs
  ``w_uk`` into the query (``q_eff = q_nope @ w_uk``) so scores live in
  latent space; the kernel reads ``ckv``/``k_rope`` blocks straight
  from the pool and returns the *latent* context (``w_uv`` is applied
  by the caller).  ``kv_map_fn`` never runs: the per-block compute IS
  the absorbed form.
* ``_paged_prefill_kernel`` — chunked-prefill flash attention.  The
  current chunk's queries attend over prior context (and the chunk
  itself, already inserted into the pool) via the same scalar-prefetch
  block-table indexing, with per-query causal masking across the chunk
  boundary and an online softmax over pool blocks.  Pad query rows
  (``pos < 0``) see no live slot and produce zeros.  An int8 flavour
  folds the per-slot scales exactly like the decode variant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: scoped VMEM the paged kernels may claim (prefill tiles hold a whole
#: chunk of query rows)
VMEM_LIMIT = 64 * 1024 * 1024


def _dot_nt(a, b):
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _attend_block(q, k_ref, v_ref, ks_ref, vs_ref, ok, o_ref, m_ref, l_ref,
                  *, rows_per_head: int, row0=0):
    """Fold one pool block into the online softmax of the row tile.

    q: [R, D] pre-scaled rows, head-major (rows ``h*rows_per_head ..``
    of the head tile belong to its kv head h; this row tile starts at
    row ``row0``); k_ref/v_ref blocks [1, BS, bh, D];
    ks_ref/vs_ref int8 scale blocks [1, BS, bh] or None; ok: bool
    [1 or R, BS] liveness.  The int8 fold follows ``decode_attend``:
    k_scale before the softmax, v_scale on the values only, so ``l``
    sums UNSCALED probabilities."""
    r = q.shape[0]
    bs, bh = k_ref.shape[1], k_ref.shape[2]
    row = lax.broadcasted_iota(jnp.int32, (r, bh), 0) + row0
    col = lax.broadcasted_iota(jnp.int32, (r, bh), 1) * rows_per_head
    own = (row >= col) & (row < col + rows_per_head)       # row's kv head
    lane = lax.broadcasted_iota(jnp.int32, (r, bs), 1)

    def kv(ref, i):
        t = ref[0, i]                                       # [bh, D]
        if t.dtype != q.dtype:                              # int8 pools
            t = t.astype(jnp.float32).astype(q.dtype)
        return t

    s = jnp.zeros((r, bs), jnp.float32)
    for i in range(bs):
        s_i = _dot_nt(q, kv(k_ref, i))                     # [R, bh]
        if ks_ref is not None:
            s_i = s_i * ks_ref[0, pl.ds(i, 1), :]           # dequant fold
        s_i = jnp.sum(jnp.where(own, s_i, 0.0), axis=1, keepdims=True)
        s = jnp.where(lane == i, s_i, s)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                     # [R, 1]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # fully-masked-so-far rows have m == NEG_INF: exp(NEG_INF - NEG_INF)
    # would be 1, so masked probabilities are forced to 0 explicitly
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)

    pv = None
    for i in range(bs):
        p_i = jnp.sum(jnp.where(lane == i, p, 0.0), axis=1, keepdims=True)
        w = jnp.where(own, p_i, 0.0)                        # [R, bh]
        if vs_ref is not None:
            w = w * vs_ref[0, pl.ds(i, 1), :]
        v_i = kv(v_ref, i)
        part = lax.dot_general(w.astype(v_i.dtype), v_i,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        pv = part if pv is None else pv + part
    o_ref[0, 0] = o_ref[0, 0] * corr + pv


def _init_acc(o_ref, m_ref, l_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)


def _finish(o_ref, l_ref):
    # rows with zero live slots keep l == 0 -> output 0 (discarded)
    o_ref[0, 0] = o_ref[0, 0] / jnp.maximum(l_ref[...], 1e-30)


def _slot_live(entry, pos_ref, j, block_size):
    """[1, BS]: allocated entry and stored position == logical index."""
    logical = j * block_size + lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    slot_pos = pos_ref[0]                                   # [1, BS]
    return (entry >= 0) & (slot_pos == logical), slot_pos


def _paged_decode_kernel(tables_ref, qpos_ref, q_ref, k_ref, v_ref, *rest,
                         block_size: int, pages: int, rep: int, int8: bool):
    if int8:
        ks_ref, vs_ref, pos_ref, o_ref, m_ref, l_ref = rest
    else:
        (pos_ref, o_ref, m_ref, l_ref), ks_ref, vs_ref = rest, None, None
    b = pl.program_id(0)
    j = pl.program_id(2)                       # logical page (innermost)

    @pl.when(j == 0)
    def _():
        _init_acc(o_ref, m_ref, l_ref)

    live, slot_pos = _slot_live(tables_ref[b, j], pos_ref, j, block_size)
    ok = live & (slot_pos <= qpos_ref[b])      # the paged_view rule
    _attend_block(q_ref[0, 0], k_ref, v_ref, ks_ref, vs_ref, ok, o_ref,
                  m_ref, l_ref, rows_per_head=rep)

    @pl.when(j == pages - 1)
    def _():
        _finish(o_ref, l_ref)


def _pool_specs(block_size, block_h, d, int8, prefetch):
    """BlockSpecs of the pool operands (k, v[, k_scale, v_scale], pos),
    all addressed through the scalar-prefetched block table; unallocated
    (-1) entries fetch the trash block 0, whose contents are read but
    masked by the liveness rule."""
    def blk(bi, ji, tables):
        return jnp.maximum(tables[bi, ji], 0)

    if prefetch == 2:                          # decode: (b, h, page)
        pool = lambda bi, hi, ji, t, qp: (blk(bi, ji, t), 0, hi, 0)
        scl = lambda bi, hi, ji, t, qp: (blk(bi, ji, t), 0, hi)
        pos = lambda bi, hi, ji, t, qp: (blk(bi, ji, t), 0, 0)
    else:                                      # prefill: (b, h, rows, page)
        pool = lambda bi, hi, ri, ji, t: (blk(bi, ji, t), 0, hi, 0)
        scl = lambda bi, hi, ri, ji, t: (blk(bi, ji, t), 0, hi)
        pos = lambda bi, hi, ri, ji, t: (blk(bi, ji, t), 0, 0)
    specs = [pl.BlockSpec((1, block_size, block_h, d), pool),
             pl.BlockSpec((1, block_size, block_h, d), pool)]
    if int8:
        specs += [pl.BlockSpec((1, block_size, block_h), scl),
                  pl.BlockSpec((1, block_size, block_h), scl)]
    specs.append(pl.BlockSpec((1, 1, block_size), pos))
    return specs


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "block_h", "interpret"),
)
def paged_attention_tiled(q, k_pool, v_pool, pos_pool, tables, positions,
                          k_scale=None, v_scale=None, *, block_size: int,
                          block_h: int, interpret: bool = False):
    """Raw tiled decode call (shapes already grouped / validated).

    q: [B, Hkv, rep, D] *pre-scaled* by the caller — in the KV storage
    dtype for float pools (identical rounding to ``decode_attend``), in
    bf16 for int8 pools.  k_pool / v_pool: [NB, BS, Hkv, D]; pos_pool:
    int32 [NB, BS]; tables: int32 [B, pages]; positions: int32 [B].
    ``k_scale``/``v_scale`` (f32 [NB, BS, Hkv]) switch on the int8
    dequant fold.  Returns f32 [B, Hkv, rep, D].  ``block_h`` must
    divide Hkv.
    """
    b, hkv, rep, d = q.shape
    nb, bs = pos_pool.shape
    pages = tables.shape[1]
    int8 = k_scale is not None
    assert hkv % block_h == 0, (hkv, block_h)
    assert bs == block_size and k_pool.shape[:2] == (nb, bs)
    if int8:
        assert k_scale.shape == (nb, bs, hkv), (k_scale.shape, (nb, bs, hkv))
    nhb, r = hkv // block_h, block_h * rep
    rows = q.reshape(b, nhb, r, d)             # head-major rows per tile

    kernel = functools.partial(_paged_decode_kernel, block_size=block_size,
                               pages=pages, rep=rep, int8=int8)
    tile = lambda bi, hi, ji, t, qp: (bi, hi, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, positions
        grid=(b, nhb, pages),
        in_specs=[pl.BlockSpec((1, 1, r, d), tile)]
        + _pool_specs(block_size, block_h, d, int8, prefetch=2),
        out_specs=pl.BlockSpec((1, 1, r, d), tile),
        scratch_shapes=[
            pltpu.VMEM((r, 1), jnp.float32),   # running max
            pltpu.VMEM((r, 1), jnp.float32),   # running sum
        ],
    )
    args = [tables, positions, rows, k_pool, v_pool]
    if int8:
        args += [k_scale, v_scale]
    args.append(pos_pool.reshape(nb, 1, bs))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nhb, r, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*args)
    return out.reshape(b, hkv, rep, d)


def _paged_attn_mla_kernel(tables_ref, qpos_ref, qe_ref, qr_ref, ckv_ref,
                           kr_ref, pos_ref, o_ref, m_ref, l_ref, *,
                           block_size: int, pages: int, scale: float):
    """MLA absorbed decode over latent pool blocks.  Scores are computed
    in latent space (``q_eff = q_nope @ w_uk`` absorbed by the caller)
    plus the decoupled rope term; the accumulated output is the LATENT
    context (caller applies ``w_uv``)."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    qe = qe_ref[0]                             # [bh, lora] f32
    qr = qr_ref[0]                             # [bh, dr] f32
    ckv = ckv_ref[0].astype(jnp.float32)       # [bs, lora]
    kr = kr_ref[0].astype(jnp.float32)         # [bs, dr]
    s = (jnp.einsum("hl,kl->hk", qe, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("hr,kr->hk", qr, kr,
                      preferred_element_type=jnp.float32)) * scale

    entry = tables_ref[b, j]
    qpos = qpos_ref[b]
    logical = j * block_size + lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    slot_pos = pos_ref[0]                      # [1, bs]
    ok = (entry >= 0) & (slot_pos == logical) & (slot_pos <= qpos)
    s = jnp.where(ok, s, NEG_INF)              # [1, bs] broadcasts over h

    m_prev = m_ref[...]                        # [bh, 1]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    pv = jnp.einsum("hk,kl->hl", p, ckv,
                    preferred_element_type=jnp.float32)
    o_ref[0] = o_ref[0] * corr + pv

    @pl.when(j == pages - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = o_ref[0] / jnp.maximum(l, 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "block_h", "scale", "interpret"),
)
def paged_attention_mla_tiled(q_eff, q_rope, ckv_pool, krope_pool,
                              pos_pool, tables, positions, *, scale: float,
                              block_size: int, block_h: int,
                              interpret: bool = False):
    """MLA tiled kernel call (absorbed decode).

    q_eff: f32 [B, H, lora] (w_uk already absorbed); q_rope: f32
    [B, H, rope_dim]; ckv_pool: [NB, BS, lora]; krope_pool:
    [NB, BS, rope_dim]; pos_pool: int32 [NB, BS].
    Returns the latent context, f32 [B, H, lora].  ``block_h`` tiles the
    QUERY head dim (MLA has no kv-head replication).
    """
    b, h, lora = q_eff.shape
    dr = q_rope.shape[-1]
    nb, bs = pos_pool.shape
    pages = tables.shape[1]
    assert h % block_h == 0, (h, block_h)
    assert bs == block_size and ckv_pool.shape == (nb, bs, lora)
    assert krope_pool.shape == (nb, bs, dr)

    kernel = functools.partial(_paged_attn_mla_kernel,
                               block_size=block_size, pages=pages,
                               scale=float(scale))

    def _pool_idx(bi, hi, ji, tables, qpos):
        return (jnp.maximum(tables[bi, ji], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, positions
        grid=(b, h // block_h, pages),
        in_specs=[
            pl.BlockSpec((1, block_h, lora),
                         lambda bi, hi, ji, tables, qpos: (bi, hi, 0)),
            pl.BlockSpec((1, block_h, dr),
                         lambda bi, hi, ji, tables, qpos: (bi, hi, 0)),
            pl.BlockSpec((1, block_size, lora), _pool_idx),
            pl.BlockSpec((1, block_size, dr), _pool_idx),
            pl.BlockSpec((1, 1, block_size),
                         lambda bi, hi, ji, tables, qpos:
                         (jnp.maximum(tables[bi, ji], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_h, lora),
                               lambda bi, hi, ji, tables, qpos:
                               (bi, hi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_h, 1), jnp.float32),
            pltpu.VMEM((block_h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lora), jnp.float32),
        interpret=interpret,
    )(tables, positions, q_eff, q_rope, ckv_pool, krope_pool,
      pos_pool.reshape(nb, 1, bs))


#: most query rows one prefill grid step holds (its score tiles must fit
#: the scoped VMEM; 4096 rows of 32 heads x 128-token chunks still do)
MAX_ROWS = 2048


def _row_tile(r: int) -> int:
    """Rows per prefill grid step: all of them, or the largest divisor of
    ``r`` that is a multiple of 8 and at most :data:`MAX_ROWS`."""
    if r <= MAX_ROWS:
        return r
    return max((t for t in range(8, MAX_ROWS + 1, 8) if r % t == 0),
               default=r)


def _paged_prefill_kernel(tables_ref, q_ref, qpos_ref, k_ref, v_ref, *rest,
                          block_size: int, pages: int, rows_per_head: int,
                          int8: bool):
    """Chunked-prefill flash attention over pool blocks: the chunk's
    query rows (each with its own absolute position) attend over every
    live slot causally visible to them — prior context AND the already-
    inserted chunk itself — with per-query masking across the chunk
    boundary.  Pad query rows (``pos < 0``) see no live slot and yield
    zeros (``l == 0`` guard)."""
    if int8:
        ks_ref, vs_ref, pos_ref, o_ref, m_ref, l_ref = rest
    else:
        (pos_ref, o_ref, m_ref, l_ref), ks_ref, vs_ref = rest, None, None
    b = pl.program_id(0)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        _init_acc(o_ref, m_ref, l_ref)

    live, slot_pos = _slot_live(tables_ref[b, j], pos_ref, j, block_size)
    ok = live & (slot_pos <= qpos_ref[0])      # [T, BS] per-query causal
    _attend_block(q_ref[0, 0], k_ref, v_ref, ks_ref, vs_ref, ok, o_ref,
                  m_ref, l_ref, rows_per_head=rows_per_head,
                  row0=pl.program_id(2) * q_ref.shape[2])

    @pl.when(j == pages - 1)
    def _():
        _finish(o_ref, l_ref)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "block_h", "interpret"),
)
def paged_prefill_tiled(q, k_pool, v_pool, pos_pool, tables, positions,
                        k_scale=None, v_scale=None, *, block_size: int,
                        block_h: int, interpret: bool = False):
    """Raw tiled chunked-prefill call.

    q: [B, C, Hkv, rep, D] in compute dtype, pre-scaled by the caller;
    positions: int32 [B, C] (absolute position per chunk token, -1 for
    pads — pad rows return zeros).  k_scale/v_scale (f32 [NB, BS, Hkv])
    switch on the int8 dequant fold.  Returns f32 [B, C, Hkv, rep, D].
    """
    b, c, hkv, rep, d = q.shape
    nb, bs = pos_pool.shape
    pages = tables.shape[1]
    int8 = k_scale is not None
    assert hkv % block_h == 0, (hkv, block_h)
    assert bs == block_size and k_pool.shape[:2] == (nb, bs)
    assert positions.shape == (b, c)
    nhb = hkv // block_h
    r = block_h * rep * c
    # rows of one head tile, head-major: (head, rep, chunk token)
    rows = q.reshape(b, c, nhb, block_h * rep, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b, nhb, r, d)
    qpos = jnp.broadcast_to(positions[:, None, :], (b, block_h * rep, c)) \
        .reshape(b, r, 1)

    # a long chunk's rows are tiled so one tile's scores fit VMEM; every
    # row tile streams the sequence's pool blocks again
    tr = _row_tile(r)
    kernel = functools.partial(_paged_prefill_kernel, block_size=block_size,
                               pages=pages, rows_per_head=rep * c, int8=int8)
    tile = lambda bi, hi, ri, ji, t: (bi, hi, ri, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # tables only
        grid=(b, nhb, r // tr, pages),
        in_specs=[pl.BlockSpec((1, 1, tr, d), tile),
                  pl.BlockSpec((1, tr, 1),
                               lambda bi, hi, ri, ji, t: (bi, ri, 0))]
        + _pool_specs(block_size, block_h, d, int8, prefetch=1),
        out_specs=pl.BlockSpec((1, 1, tr, d), tile),
        scratch_shapes=[
            pltpu.VMEM((tr, 1), jnp.float32),
            pltpu.VMEM((tr, 1), jnp.float32),
        ],
    )
    args = [jnp.asarray(tables, jnp.int32), rows, qpos, k_pool, v_pool]
    if int8:
        args += [k_scale, v_scale]
    args.append(pos_pool.reshape(nb, 1, bs))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nhb, r, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*args)
    return out.reshape(b, nhb, block_h * rep, c, d).transpose(0, 3, 1, 2, 4) \
        .reshape(b, c, hkv, rep, d)
