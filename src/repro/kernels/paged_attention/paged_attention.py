"""Pallas TPU kernel: fused paged-KV decode attention.

The paged serving engine stores every layer's KV as a shared pool
``[num_blocks, block_size, Hkv, D]`` plus per-sequence block tables
``[B, max_blocks_per_seq]`` (see ``models/attention.py``).  The XLA
fallback materializes a gathered per-sequence view of the pool before
every decode step — a full-cache copy per layer, exactly the bandwidth
waste FIGLUT's LUT dataflow exists to avoid.  These kernels move the
block-table lookup *into* the attention kernel, the same "indirection
stays on-chip" principle as the LUT kernel's keyed reads: pool blocks
are copied straight into VMEM through the scalar-prefetched block table
and folded into a flash-style online softmax.  The gathered view is
never built.

Masking is identical to ``paged_view``'s liveness rule and happens on
the scores in-kernel: a slot contributes iff

  * its table entry is allocated (``table[b, j] >= 0``),
  * its stored position equals its logical view index ``j * bs + i``
    (recycled pool blocks still hold a dead sequence's positions — this
    is what makes pool recycling safe), and
  * its position is causally visible (``pos <= q_pos``).

``pos == -1`` pads and trash-block contents fail the second check.

**Live bound.**  The rule implies that no page ``j > q_pos // bs`` can
contribute, so the op wrappers hand each kernel ``n_live[b]`` (scalar-
prefetched beside the tables): ``q_pos // bs + 1`` for decode, the
chunk's largest position's page + 1 for prefill, 0 for a row with no
live query or one parked on the trash block (``table[b, 0] < 0``).
Work follows the live KV, not ``max_batch x max_seq_len``.

Grids.  Decode: ``(B, Hkv / block_h)``.  One grid step is one row's head
tile, and it loops over the row's live pages in groups of
:func:`decode_pages` consecutive logical pages (``DECODE_TOKENS`` = 128
tokens): the K/V blocks of a group are copied by hand from the
``ANY``-space pools, double-buffered, so group g+1 is in flight while g
is attended; a zero-live row runs no iteration.  Prefill: ``(B,
Hkv / block_h, row tiles, pages)`` with the page dim innermost; a step
at or past ``n_live`` does no compute, and its ``index_map`` returns the
block of the row's last live page (the trash block when it has none),
the block the step before it holds, so the pipeline copies nothing for
it.  Both keep the FP32 accumulator in the output block with running
max / sum in VMEM scratch, and rows with no live slot produce zeros —
the engine discards their outputs.

Every kernel body is two-dimensional, which is what the TPU compiler
lowers.  Decode: the K/V blocks are copied whole, ``[BS, block_h, D]``
a page, and read back a *head pack* at a time — the ``pack`` heads one
32-bit word holds per slot (2 for 16-bit pools, 4 for int8): one
strided 32-bit load per eight slots gives the pack's keys as
``[pack * P * BS, D]`` rows interleaved slot by slot.  A kv head's
query rows sit in their own ``[rpad, D]`` tile (``rep`` rows padded to
whole sublanes); the pack's scores are one ``[pack * rpad, D] x
[D, pack * P * BS]`` product, each head's rows masked to its own
interleaved lanes, and its values fold back in one product; one online
softmax update covers all heads' rows at once.  Slot positions (and
int8 scales) are read from whole-pool VMEM views, each element repeated
per head of a pack, and laid along the group's lanes by a row load and
a lane rotation per page: the TPU copies no window narrower than 128
lanes.

Prefill: the caller lays the queries of one head tile out as rows
``[R, D]`` ordered head-major (``R = block_h * rep * C``), so a row's kv
head is a contiguous row range; per pool slot ``i`` the scores of all
rows against all ``block_h`` keys of that slot are one MXU product
``[R, D] x [block_h, D]^T``, the row's own head is picked by a
head-ownership mask and a lane sum, and the values are folded back the
same way.  Its slot positions ride as a ``[NB, 1, BS]`` view so their
block is whole in its last two dims.

Variant coverage (the FLUTE offline-restructure-then-fuse pattern: all
layout work happens at quantize/admission time so the in-loop index
math stays trivial):

* int8-KV pools (``k_scale``/``v_scale`` given): the per-slot scales
  (``[NB, BS, Hkv]`` f32, written at admission by ``_quantize_kv``)
  are folded in-kernel — decode reads them from whole-pool views laid
  out ``[NB, Hkv, BS]`` (repeated per head of a pack) so a head's
  scales for a block are one run;
  prefill's ride the same block-table-driven copy as the KV block.  Raw
  int8 scores are multiplied by ``k_scale`` before the running max, and
  ``v_scale`` folds into the PV contraction only — the running sum
  ``l`` accumulates *unscaled* probabilities so the final normalization
  matches the gathered ``decode_attend`` ordering (softmax first, then
  ``p * v_scale``).
* ``_paged_attn_mla_kernel`` — MLA latent pools.  The caller absorbs
  ``w_uk`` into the query (``q_eff = q_nope @ w_uk``) so scores live in
  latent space; the kernel reads ``ckv``/``k_rope`` blocks straight
  from the pool and returns the *latent* context (``w_uv`` is applied
  by the caller).  ``kv_map_fn`` never runs: the per-block compute IS
  the absorbed form.  It keeps a ``(B, H / block_h, pages)`` grid of
  one pool block per step over the whole table, without the live bound.
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


#: KV tokens one decode grid step folds: ``DECODE_TOKENS // block_size``
#: consecutive logical pages per step (capped by the table width)
DECODE_TOKENS = 128


def decode_pages(block_size: int, pages: int) -> int:
    """Pages one decode grid step copies in and attends over."""
    return max(1, min(pages, DECODE_TOKENS // block_size))


def _flat_rows(x, run: int):
    """``x`` flattened into rows of ``run * (128 // run)`` lanes (padded),
    so each aligned run of ``run`` elements lies inside one row."""
    w = run * max(1, 128 // run)
    flat = x.reshape(-1)
    flat = jnp.pad(flat, (0, -flat.size % w))
    return flat.reshape(-1, w)


def _gather_row(ref, runs, run: int, fill):
    """[1, len(runs) * run]: lanes ``p*run ..`` hold the ``run`` elements
    of the flat view ``ref`` (:func:`_flat_rows`) starting at
    ``runs[p] = (live, start)``, ``fill`` where ``live`` is false.  A row
    load and a lane rotation per run: the TPU neither copies nor reshapes
    windows narrower than its 128 lanes."""
    w = ref.shape[1]
    per = w // run                              # runs per row of the view
    lane = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    rows = []
    for c in range(0, len(runs), per):
        out = jnp.full((1, w), fill, ref.dtype)
        for p, (live, start) in enumerate(runs[c:c + per]):
            row = ref[pl.ds(start // w, 1), :]
            row = pltpu.roll(row, (p * run - start % w) % w, 1)
            out = jnp.where((lane // run == p) & live, row, out)
        rows.append(out)
    return jnp.concatenate(rows, axis=1)[:, :len(runs) * run]


def _head_pack(dtype, block_h: int) -> int:
    """Kv heads read together: the heads one 32-bit word holds per slot
    (2 for 16-bit pools, 4 for int8), when they divide the head tile."""
    pack = 4 // jnp.dtype(dtype).itemsize
    return pack if block_h % pack == 0 else 1


def _paged_decode_kernel(tables_ref, nlive_ref, qpos_ref, q_ref, pos_ref,
                         *rest, block_size: int, pages: int, group: int,
                         hkv: int, pack: int, int8: bool):
    """One (row, head tile): a loop over the row's live page groups.

    Each group is ``group`` consecutive logical pages whose K/V blocks
    are copied by hand from the ``ANY``-space pools, double-buffered
    (group g+1 is in flight while g is attended).  A page at or past
    ``n_live``, or unallocated, is never copied: its value slots are
    zeroed so no stale or uninitialised slot reaches the PV product, and
    its positions read -1, which the liveness rule masks.  Slot
    positions (and int8 scales) come from whole-pool VMEM views, each
    element repeated ``pack`` times to match the interleaved rows of
    :func:`_attend_group`."""
    if int8:
        ks_ref, vs_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, m_ref, l_ref, \
            sem = rest
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, m_ref, l_ref, sem = rest
    b, hb = pl.program_id(0), pl.program_id(1)
    n, qpos = nlive_ref[b], qpos_ref[b]
    bs, block_h = block_size, q_ref.shape[2]

    def page(g, p):
        """(copied?, physical block) of the group's p-th page."""
        j = g * group + p
        entry = tables_ref[b, jnp.minimum(j, pages - 1)]
        return (j < n) & (entry >= 0), jnp.maximum(entry, 0)

    def copies(g, slot, action):
        for p in range(group):
            live, blk = page(g, p)

            @pl.when(live)
            def _():
                heads = pl.ds(hb * block_h, block_h)
                for i, (hbm, buf) in enumerate(((k_hbm, kbuf),
                                                (v_hbm, vbuf))):
                    cp = pltpu.make_async_copy(hbm.at[blk, :, heads],
                                               buf.at[slot, p], sem.at[i, slot])
                    getattr(cp, action)()

    _init_acc(o_ref, m_ref, l_ref)
    n_groups = (n + group - 1) // group

    @pl.when(n_groups > 0)
    def _():
        copies(0, 0, "start")

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _():
            copies(g + 1, 1 - slot, "start")

        copies(g, slot, "wait")
        pg = [page(g, p) for p in range(group)]
        for p, (live, _) in enumerate(pg):
            @pl.when(jnp.logical_not(live))
            def _():
                vbuf[slot, p] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

        run = bs * pack
        slot_pos = _gather_row(pos_ref, [(live, blk * run) for live, blk in pg],
                               run, -1)
        logical = g * group * bs + lax.broadcasted_iota(
            jnp.int32, slot_pos.shape, 1) // pack
        ok = (slot_pos == logical) & (slot_pos <= qpos)
        scales = None
        if int8:                     # scale views are [NB, Hkv, BS] runs
            scales = [[_gather_row(ref, [(live, (blk * hkv + hb * block_h
                                                 + h) * run)
                                         for live, blk in pg], run, 0.0)
                       for h in range(block_h)] for ref in (ks_ref, vs_ref)]
        _attend_group(q_ref, kbuf, vbuf, slot, ok, o_ref, m_ref, l_ref,
                      pack, scales)
        return carry

    lax.fori_loop(0, n_groups, body, 0)
    _finish(o_ref, l_ref)


def _attend_group(q_ref, kbuf, vbuf, slot, ok, o_ref, m_ref, l_ref, pack,
                  scales=None):
    """Fold one page group into the online softmax of every kv head.

    Heads are read ``pack`` at a time: one strided 32-bit load per eight
    slots picks the word that holds those heads' values of a slot, so
    the pack's keys arrive as ``[pack * T, D]`` rows interleaved slot by
    slot (row ``pack * t + j`` is slot t of the pack's head j).  The
    pack's query rows ``[pack * rpad, D]`` meet them in one product per
    pack, each head's rows masked to its own interleaved lanes; one
    softmax update covers all heads' rows ``[block_h * rpad, pack * T]``;
    the values fold back in one product per pack, the masked lanes'
    probabilities being 0.  ``scales`` (per head ``[1, pack * T]`` k- and
    v-scale rows) switch on the int8 fold in ``decode_attend``'s order:
    k_scale on the raw scores, v_scale on the probabilities of the PV
    product only, so ``l`` sums UNSCALED probabilities."""
    block_h, rpad, d = q_ref.shape[2:]
    tp = ok.shape[1]                                        # pack * T
    t = tp // pack
    cdt = q_ref.dtype

    def heads(buf, k):
        """[pack * T, D] rows of the k-th head pack (interleaved)."""
        if pack > 1 or buf.dtype.itemsize == 4:
            words = buf.bitcast(jnp.uint32).reshape(-1, d)
            per_slot = block_h // pack
            x = words[pl.ds(slot * t * per_slot + k, t, stride=per_slot)]
            x = pltpu.bitcast(x, buf.dtype)
        else:                        # a lone 8/16-bit head: sublane gather
            x = buf[slot, :, :, k].reshape(t, d)
        if x.dtype != cdt:                                  # int8 pools
            x = x.astype(jnp.float32).astype(cdt)
        return x

    own = lax.broadcasted_iota(jnp.int32, (block_h * rpad, tp), 1) % pack \
        == lax.broadcasted_iota(jnp.int32, (block_h * rpad, tp), 0) \
        // rpad % pack
    ok = own & ok
    npk = block_h // pack
    s = []
    for k in range(npk):
        q = jnp.concatenate([q_ref[0, 0, k * pack + j] for j in range(pack)],
                            axis=0)                         # [pack*rpad, D]
        s_k = _dot_nt(q, heads(kbuf, k))                    # [pack*rpad, tp]
        if scales is not None:                              # dequant fold
            s_k = s_k * jnp.concatenate(
                [jnp.broadcast_to(scales[0][k * pack + j], (rpad, tp))
                 for j in range(pack)], axis=0)
        s.append(s_k)
    s = jnp.where(ok, jnp.concatenate(s, axis=0), NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]                 # [bh*rpad, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
    pv = []
    rows = pack * rpad
    for k in range(npk):
        w = p[k * rows:(k + 1) * rows]
        if scales is not None:
            w = w * jnp.concatenate(
                [jnp.broadcast_to(scales[1][k * pack + j], (rpad, tp))
                 for j in range(pack)], axis=0)
        v = heads(vbuf, k)
        pv.append(lax.dot_general(w.astype(v.dtype), v,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32))
    o_ref[0, 0] = o_ref[0, 0] * corr + jnp.concatenate(pv, axis=0)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "block_h", "interpret"),
)
def paged_attention_tiled(q, k_pool, v_pool, pos_pool, tables, positions,
                          n_live, k_scale=None, v_scale=None, *,
                          block_size: int, block_h: int,
                          interpret: bool = False):
    """Raw tiled decode call (shapes already grouped / validated).

    q: [B, Hkv, rep, D] *pre-scaled* by the caller — in the KV storage
    dtype for float pools (identical rounding to ``decode_attend``), in
    bf16 for int8 pools.  k_pool / v_pool: [NB, BS, Hkv, D]; pos_pool:
    int32 [NB, BS]; tables: int32 [B, pages]; positions: int32 [B];
    n_live: int32 [B], the pages each row can see (0 for an idle row).
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
    nhb, group = hkv // block_h, decode_pages(bs, pages)
    pack = _head_pack(k_pool.dtype, block_h)
    rpad = -(-rep // 8) * 8                    # a head's rows: whole sublanes
    rows = jnp.pad(q, ((0, 0), (0, 0), (0, rpad - rep), (0, 0))) \
        .reshape(b, nhb, block_h, rpad, d)
    # each slot's entry repeated per head of a pack; a head's scales for a
    # block are one run
    views = [_flat_rows(jnp.repeat(pos_pool, pack, axis=1), bs * pack)]
    if int8:
        views += [_flat_rows(jnp.repeat(x.transpose(0, 2, 1), pack, axis=2),
                             bs * pack) for x in (k_scale, v_scale)]

    kernel = functools.partial(_paged_decode_kernel, block_size=bs,
                               pages=pages, group=group, hkv=hkv, pack=pack,
                               int8=int8)
    tile = lambda bi, hi, *_: (bi, hi, 0, 0, 0)
    out_tile = lambda bi, hi, *_: (bi, hi, 0, 0)
    whole = lambda x: pl.BlockSpec(x.shape, lambda *_: (0, 0))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    buf = lambda dt: pltpu.VMEM((2, group, bs, block_h, d), dt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                 # tables, n_live, positions
        grid=(b, nhb),
        in_specs=[pl.BlockSpec((1, 1, block_h, rpad, d), tile)]
        + [whole(x) for x in views] + [any_space, any_space],
        out_specs=pl.BlockSpec((1, 1, block_h * rpad, d), out_tile),
        scratch_shapes=[
            buf(k_pool.dtype), buf(v_pool.dtype),
            pltpu.VMEM((block_h * rpad, 1), jnp.float32),  # running max
            pltpu.VMEM((block_h * rpad, 1), jnp.float32),  # running sum
            pltpu.SemaphoreType.DMA((2, 2)),               # (k|v, slot)
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nhb, block_h * rpad, d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tables, n_live, positions, rows, *views, k_pool, v_pool)
    return out.reshape(b, hkv, rpad, d)[:, :, :rep]


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


def _pool_specs(block_size, block_h, d, int8):
    """BlockSpecs of the prefill pool operands (k, v[, k_scale, v_scale],
    pos), all addressed through the scalar-prefetched block table.  A
    step at or past the row's live bound maps to the block of its last
    live page (the trash block 0 when it has none), the block the step
    before it holds, so the pipeline issues no copy for it."""
    def blk(bi, ji, tables, n_live):
        last = jnp.maximum(n_live[bi] - 1, 0)
        return jnp.maximum(tables[bi, jnp.minimum(ji, last)], 0)

    pool = lambda bi, hi, ri, ji, t, n: (blk(bi, ji, t, n), 0, hi, 0)
    scl = lambda bi, hi, ri, ji, t, n: (blk(bi, ji, t, n), 0, hi)
    pos = lambda bi, hi, ri, ji, t, n: (blk(bi, ji, t, n), 0, 0)
    specs = [pl.BlockSpec((1, block_size, block_h, d), pool),
             pl.BlockSpec((1, block_size, block_h, d), pool)]
    if int8:
        specs += [pl.BlockSpec((1, block_size, block_h), scl),
                  pl.BlockSpec((1, block_size, block_h), scl)]
    specs.append(pl.BlockSpec((1, 1, block_size), pos))
    return specs


def _paged_prefill_kernel(tables_ref, nlive_ref, q_ref, qpos_ref, k_ref,
                          v_ref, *rest, block_size: int, pages: int,
                          rows_per_head: int, int8: bool):
    """Chunked-prefill flash attention over pool blocks: the chunk's
    query rows (each with its own absolute position) attend over every
    live slot causally visible to them — prior context AND the already-
    inserted chunk itself — with per-query masking across the chunk
    boundary.  Steps at or past the row's live bound do no work.  Pad
    query rows (``pos < 0``) see no live slot and yield zeros
    (``l == 0`` guard)."""
    if int8:
        ks_ref, vs_ref, pos_ref, o_ref, m_ref, l_ref = rest
    else:
        (pos_ref, o_ref, m_ref, l_ref), ks_ref, vs_ref = rest, None, None
    b = pl.program_id(0)
    row0 = pl.program_id(2) * q_ref.shape[2]
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        _init_acc(o_ref, m_ref, l_ref)

    @pl.when(j < nlive_ref[b])
    def _():
        live, slot_pos = _slot_live(tables_ref[b, j], pos_ref, j, block_size)
        ok = live & (slot_pos <= qpos_ref[0])  # [T, BS] per-query causal
        _attend_block(q_ref[0, 0], k_ref, v_ref, ks_ref, vs_ref, ok, o_ref,
                      m_ref, l_ref, rows_per_head=rows_per_head, row0=row0)

    @pl.when(j == pages - 1)
    def _():
        _finish(o_ref, l_ref)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "block_h", "interpret"),
)
def paged_prefill_tiled(q, k_pool, v_pool, pos_pool, tables, positions,
                        n_live, k_scale=None, v_scale=None, *,
                        block_size: int, block_h: int,
                        interpret: bool = False):
    """Raw tiled chunked-prefill call.

    q: [B, C, Hkv, rep, D] in compute dtype, pre-scaled by the caller;
    positions: int32 [B, C] (absolute position per chunk token, -1 for
    pads — pad rows return zeros); n_live: int32 [B], the pages the
    row's queries can see.  k_scale/v_scale (f32 [NB, BS, Hkv])
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
    tile = lambda bi, hi, ri, *_: (bi, hi, ri, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, n_live
        grid=(b, nhb, r // tr, pages),
        in_specs=[pl.BlockSpec((1, 1, tr, d), tile),
                  pl.BlockSpec((1, tr, 1), lambda bi, hi, ri, *_: (bi, ri, 0))]
        + _pool_specs(block_size, block_h, d, int8),
        out_specs=pl.BlockSpec((1, 1, tr, d), tile),
        scratch_shapes=[
            pltpu.VMEM((tr, 1), jnp.float32),
            pltpu.VMEM((tr, 1), jnp.float32),
        ],
    )
    args = [tables, n_live, rows, qpos, k_pool, v_pool]
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
