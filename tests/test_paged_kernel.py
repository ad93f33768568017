"""Fused paged-attention kernels: equivalence matrix + dispatch.

Kernel level (interpret mode): each fused Pallas kernel must match its
gathered ``paged_view``-style oracle — the float decode kernel on
GQA/MHA/MQA head layouts, f32 and bf16 pools; the int8 decode kernel
with per-slot dequant scales; the MLA latent decode kernel; and the
chunked-prefill kernel against ``blockwise_attention`` — all on
scrambled and *recycled* block tables (stale positions from a dead
owner), ``pos == -1`` pads, -1 table entries and fully-idle rows,
across the block_h launch-geometry space; and at page and page-group
edges, where the kernels' live bound must never read a block past it.

Model level: ``decode_step``/``prefill_chunk`` with
``paged_kernel="fused"`` must be token/logit-equivalent to ``"gather"``
on every variant — running the right kernel where one is supported (GQA
float, int8-KV, MLA decode) and falling back cleanly through
``tune.dispatch.kernel_unsupported_reason`` where none is (sliding-
window masking, MLA prefill).  The acceptance invariant — neither the
fused decode path nor the fused prefill path materializes the gathered
view — is pinned by monkeypatching ``paged_view`` to raise.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.kernels.paged_attention import (divisor_clamp, paged_attention,
                                           paged_attention_int8,
                                           paged_attention_mla,
                                           paged_decode_int8_ref,
                                           paged_decode_mla_ref,
                                           paged_decode_ref, paged_prefill,
                                           paged_prefill_ref)
from repro.models import Model
from repro.models import attention as attn
from repro.serve import set_block_tables
from repro.tune import cache as tcache
from repro.tune import dispatch as tdispatch
from repro.tune.space import KernelConfig, candidate_configs, clamp_config

RNG = jax.random.PRNGKey(0)


def _f32(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params)


def _model(arch="opt_6_7b", **over):
    cfg = get_reduced(arch).replace(remat=False, dtype="float32",
                                    capacity_factor=8.0, **over)
    m = Model(cfg)
    return m, _f32(m.init(RNG))


#: the ragged-liveness case of the parametrised oracle tests: block 16,
#: 12 pages (two page groups of the decode kernel, the second partial)
EDGES = "edges"
EDGE_BS, EDGE_PAGES = 16, 12
#: each row's newest position: page edges (0, 15, 16), a mid page, the
#: last slot of the first page group and the first of the second, the
#: last slot of the table; None is an idle row between live ones
EDGE_QPOS = (0, 15, None, 16, 77, 127, 128, EDGE_BS * EDGE_PAGES - 1)


def _edge_case(*, h=8, hkv=4, d=16, dtype=jnp.float32, **_):
    """Ragged liveness at page and page-group edges.  Past each row's
    newest position: the rest of its last page holds stale positions
    (each equal to its logical index, as a longer dead sequence left
    them), and the row's next table entry is a recycled block whose
    stale positions are likewise all logical — only causality (and the
    kernels' live bound) keeps them out."""
    bs, pages, b = EDGE_BS, EDGE_PAGES, len(EDGE_QPOS)
    nb = 1 + sum(-(-(qp + 1) // bs) + 1 for qp in EDGE_QPOS
                 if qp is not None)
    rng = np.random.default_rng(11)
    k = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), dtype)
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    tables = np.full((b, pages), -1, np.int32)
    pos = np.full((nb, bs), -1, np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    positions = np.zeros(b, np.int32)
    for row, qp in enumerate(EDGE_QPOS):
        if qp is None:
            continue
        positions[row] = qp
        n = qp // bs + 1
        for j in range(min(n + 1, pages)):   # live pages + one stale
            blk = free.pop()
            tables[row, j] = blk
            pos[blk] = j * bs + np.arange(bs)
    return (q, k, v, jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray(positions))


def _pool_case(seed, *, b=3, h=8, hkv=4, d=16, nb=24, bs=4, pages=6,
               dtype=jnp.float32, recycle=True, idle_row=True):
    """Scrambled paged-decode problem: ragged live lengths, -1 table
    pads, stale positions in recycled blocks, optionally an idle row.
    ``seed == EDGES`` gives :func:`_edge_case` instead."""
    if seed == EDGES:
        return _edge_case(h=h, hkv=hkv, d=d, dtype=dtype)
    assert nb > b * pages, "pool too small for worst-case live blocks"
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), dtype)
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    tables = np.full((b, pages), -1, np.int32)
    pos = np.full((nb, bs), -1, np.int32)
    free = list(rng.permutation(np.arange(1, nb)))
    positions = np.zeros(b, np.int32)
    start = 1 if idle_row else 0         # row 0 idle: all table entries -1
    for row in range(start, b):
        live = int(rng.integers(1, pages * bs))
        positions[row] = live - 1
        for j in range(-(-live // bs)):
            blk = free.pop()
            tables[row, j] = blk
            pos[blk] = j * bs + np.arange(bs)
    if recycle and free:
        # a "freed" block still holding a dead owner's positions gets
        # handed to the last row at a DIFFERENT logical index: its stale
        # pos values fail the pos == logical check and must be masked
        stale = free.pop()
        pos[stale] = np.arange(bs)               # claims positions 0..bs-1
        j = int(np.argmax(tables[b - 1] < 0))
        if j > 0:                                 # logical index != 0
            tables[b - 1, j] = stale
    return (q, k, v, jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray(positions))


def _int8_pool_case(seed, **kw):
    """_pool_case with the K/V pools re-drawn as int8 + per-slot scales
    (same scrambled/recycled table layout)."""
    q, k, v, pos, tables, positions = _pool_case(seed, **kw)
    rng = np.random.default_rng(101 if seed == EDGES else seed + 100)
    nb, bs, hkv, d = k.shape
    k8 = jnp.asarray(np.clip(np.round(rng.normal(size=k.shape) * 40),
                             -127, 127), jnp.int8)
    v8 = jnp.asarray(np.clip(np.round(rng.normal(size=v.shape) * 40),
                             -127, 127), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (nb, bs, hkv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (nb, bs, hkv)), jnp.float32)
    return q, k8, v8, ks, vs, pos, tables, positions


def _mla_pool_case(seed, *, b=3, h=8, lora=12, dr=8, nb=24, bs=4, pages=6):
    """Latent-pool analogue of _pool_case (absorbed-decode inputs)."""
    _, _, _, pos, tables, positions = _pool_case(seed, b=b, nb=nb, bs=bs,
                                                 pages=pages)
    rng = np.random.default_rng(seed + 200)
    ckv = jnp.asarray(rng.normal(size=(nb, bs, lora)), jnp.float32)
    krope = jnp.asarray(rng.normal(size=(nb, bs, dr)), jnp.float32)
    q_eff = jnp.asarray(rng.normal(size=(b, h, lora)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, h, dr)), jnp.float32)
    return q_eff, q_rope, ckv, krope, pos, tables, positions


# ---------------------------------------------------------------------------
# kernel vs gathered oracle (interpret mode)
# ---------------------------------------------------------------------------


class TestFusedKernel:
    @pytest.mark.parametrize("h,hkv", [(8, 4), (4, 4), (6, 1)])  # GQA/MHA/MQA
    @pytest.mark.parametrize("seed", [0, 1, EDGES])
    def test_matches_gathered_oracle(self, h, hkv, seed):
        q, k, v, pos, tables, positions = _pool_case(seed, h=h, hkv=hkv)
        want = paged_decode_ref(q, k, v, pos, tables, positions)
        got = paged_attention(q, k, v, pos, tables, positions,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_bf16_pool(self):
        q, k, v, pos, tables, positions = _pool_case(2, dtype=jnp.bfloat16)
        want = paged_decode_ref(q, k, v, pos, tables, positions)
        got = paged_attention(q, k, v, pos, tables, positions,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=2e-2)

    def test_idle_row_outputs_zero_not_nan(self):
        """A row whose table is all -1 (parked on the trash block) has no
        live slot: the kernel's l == 0 guard must yield zeros, the oracle
        likewise — never NaN from a fully-masked softmax."""
        q, k, v, pos, tables, positions = _pool_case(3, idle_row=True)
        got = paged_attention(q, k, v, pos, tables, positions,
                              interpret=True)
        want = paged_decode_ref(q, k, v, pos, tables, positions)
        assert np.isfinite(np.asarray(got)).all()
        assert np.abs(np.asarray(got)[0]).max() == 0.0
        assert np.abs(np.asarray(want)[0]).max() == 0.0

    def test_block_h_space_agrees(self):
        """Every clamped block_h launch produces the same numbers."""
        q, k, v, pos, tables, positions = _pool_case(4, h=8, hkv=4)
        want = paged_attention(q, k, v, pos, tables, positions,
                               interpret=True, block_h=4)
        for cfg in candidate_configs("paged_attention", b=3, m=4,
                                     n=6 * 4, group_size=4):
            got = paged_attention(q, k, v, pos, tables, positions,
                                  interpret=True, block_h=cfg.block_h)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6)

    def test_recycled_block_stale_pos_masked(self):
        """Zeroing the recycled block's K/V must not change the output:
        its stale positions are masked, so its contents are dead."""
        q, k, v, pos, tables, positions = _pool_case(5, recycle=True)
        stale_blocks = sorted(set(range(k.shape[0]))
                              - set(np.asarray(tables).ravel().tolist()))
        base = paged_attention(q, k, v, pos, tables, positions,
                               interpret=True)
        k2, v2 = np.asarray(k).copy(), np.asarray(v).copy()
        # scribble over every block NOT in any table AND over the trash
        # block 0 — none of them may be observable
        for blk in (*stale_blocks, 0):
            k2[blk] = 7.7
            v2[blk] = -7.7
        got = paged_attention(q, jnp.asarray(k2, k.dtype),
                              jnp.asarray(v2, v.dtype), pos, tables,
                              positions, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   atol=1e-6)


class TestFusedInt8Kernel:
    @pytest.mark.parametrize("h,hkv", [(8, 4), (4, 4), (6, 1)])
    @pytest.mark.parametrize("seed", [0, 1, EDGES])
    def test_matches_gathered_int8_oracle(self, h, hkv, seed):
        """Per-slot scales fold in-kernel to the decode_attend ordering
        (bf16 compute -> atol at bf16-epsilon scale)."""
        q, k8, v8, ks, vs, pos, tables, positions = _int8_pool_case(
            seed, h=h, hkv=hkv)
        want = paged_decode_int8_ref(q, k8, v8, ks, vs, pos, tables,
                                     positions)
        got = paged_attention_int8(q, k8, v8, ks, vs, pos, tables,
                                   positions, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-2)

    def test_recycled_block_and_scales_masked(self):
        """Scribbling dead blocks' VALUES AND SCALES must be invisible —
        the scale rows ride the same table-driven DMA, so a stale block's
        scales must never touch a live score."""
        q, k8, v8, ks, vs, pos, tables, positions = _int8_pool_case(5)
        stale = sorted(set(range(k8.shape[0]))
                       - set(np.asarray(tables).ravel().tolist()))
        base = paged_attention_int8(q, k8, v8, ks, vs, pos, tables,
                                    positions, interpret=True)
        k2, v2 = np.asarray(k8).copy(), np.asarray(v8).copy()
        ks2, vs2 = np.asarray(ks).copy(), np.asarray(vs).copy()
        for blk in (*stale, 0):
            k2[blk], v2[blk] = 127, -127
            ks2[blk], vs2[blk] = 99.0, -99.0
        got = paged_attention_int8(
            q, jnp.asarray(k2, jnp.int8), jnp.asarray(v2, jnp.int8),
            jnp.asarray(ks2), jnp.asarray(vs2), pos, tables, positions,
            interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   atol=1e-6)

    def test_idle_row_outputs_zero_not_nan(self):
        q, k8, v8, ks, vs, pos, tables, positions = _int8_pool_case(3)
        got = paged_attention_int8(q, k8, v8, ks, vs, pos, tables,
                                   positions, interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        assert np.abs(np.asarray(got)[0]).max() == 0.0


class TestFusedMlaKernel:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_matches_gathered_mla_oracle(self, seed):
        q_eff, q_rope, ckv, krope, pos, tables, positions = _mla_pool_case(
            seed)
        sc = (12 + 8) ** -0.5
        want = paged_decode_mla_ref(q_eff, q_rope, ckv, krope, pos, tables,
                                    positions, scale=sc)
        got = paged_attention_mla(q_eff, q_rope, ckv, krope, pos, tables,
                                  positions, scale=sc, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_block_h_space_agrees(self):
        q_eff, q_rope, ckv, krope, pos, tables, positions = _mla_pool_case(4)
        sc = (12 + 8) ** -0.5
        want = paged_attention_mla(q_eff, q_rope, ckv, krope, pos, tables,
                                  positions, scale=sc, interpret=True,
                                  block_h=8)
        for cfg in candidate_configs("paged_attention", b=3, m=8, n=24,
                                     group_size=4):
            got = paged_attention_mla(q_eff, q_rope, ckv, krope, pos,
                                      tables, positions, scale=sc,
                                      interpret=True, block_h=cfg.block_h)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6)

    def test_recycled_block_stale_pos_masked(self):
        q_eff, q_rope, ckv, krope, pos, tables, positions = _mla_pool_case(5)
        sc = (12 + 8) ** -0.5
        stale = sorted(set(range(ckv.shape[0]))
                       - set(np.asarray(tables).ravel().tolist()))
        base = paged_attention_mla(q_eff, q_rope, ckv, krope, pos, tables,
                                   positions, scale=sc, interpret=True)
        c2, r2 = np.asarray(ckv).copy(), np.asarray(krope).copy()
        for blk in (*stale, 0):
            c2[blk], r2[blk] = 7.7, -7.7
        got = paged_attention_mla(q_eff, q_rope, jnp.asarray(c2),
                                  jnp.asarray(r2), pos, tables, positions,
                                  scale=sc, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   atol=1e-6)


class TestFusedPrefillKernel:
    @staticmethod
    def _chunk(seed, positions, *, b, c, h, d):
        """A chunk of queries per row: positions[row]-c+1 .. positions[row]
        (clamped at -1 pads below position 0)."""
        b = len(positions)
        rng = np.random.default_rng(303 if seed == EDGES else seed + 300)
        q = jnp.asarray(rng.normal(size=(b, c, h, d)), jnp.float32)
        cpos = (np.asarray(positions)[:, None]
                - np.arange(c - 1, -1, -1)[None]).astype(np.int32)
        cpos = np.where(cpos < 0, -1, cpos)
        return q, jnp.asarray(cpos)

    @pytest.mark.parametrize("h,hkv", [(8, 4), (4, 4), (6, 1)])
    @pytest.mark.parametrize("seed", [0, 1, EDGES])
    def test_matches_gathered_oracle(self, h, hkv, seed):
        _, k, v, pos, tables, positions = _pool_case(seed, h=h, hkv=hkv)
        q, cpos = self._chunk(seed, positions, b=3, c=5, h=h, d=16)
        want = paged_prefill_ref(q, k, v, pos, tables, cpos)
        got = paged_prefill(q, k, v, pos, tables, cpos, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("seed", [0, 1, EDGES])
    def test_int8_matches_gathered_oracle(self, seed):
        _, k8, v8, ks, vs, pos, tables, positions = _int8_pool_case(seed)
        q, cpos = self._chunk(seed, positions, b=3, c=5, h=8, d=16)
        want = paged_prefill_ref(q, k8, v8, pos, tables, cpos,
                                 k_scale=ks, v_scale=vs)
        got = paged_prefill(q, k8, v8, pos, tables, cpos, k_scale=ks,
                            v_scale=vs, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2)

    def test_matches_blockwise_attention_on_live_rows(self):
        """End-to-end cross-check against the generic flash path the
        gathered prefill used: identical on non-pad query rows."""
        _, k, v, pos, tables, positions = _pool_case(7, idle_row=False)
        q, cpos = self._chunk(7, positions, b=3, c=5, h=8, d=16)
        cache = {"k": k, "v": v, "pos": pos, "block_tables": tables}
        kv = attn.paged_view(cache)
        want = attn.blockwise_attention(q, kv["k"], kv["v"], cpos,
                                        kv["pos"], causal=True)
        got = paged_prefill(q, k, v, pos, tables, cpos, interpret=True)
        live = np.asarray(cpos) >= 0
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], atol=1e-5)

    def test_row_tiles_agree(self, monkeypatch):
        """A long chunk's query rows split over several grid steps (each
        re-streams the pool blocks) give the untiled answer."""
        import sys
        kmod = sys.modules["repro.kernels.paged_attention.paged_attention"]
        _, k, v, pos, tables, positions = _pool_case(9)
        q, cpos = self._chunk(9, positions, b=3, c=7, h=8, d=16)
        want = paged_prefill_ref(q, k, v, pos, tables, cpos)
        monkeypatch.setattr(kmod, "MAX_ROWS", 8)   # 8 heads x 7 -> 7 tiles
        assert kmod._row_tile(8 * 7) == 8
        got = paged_prefill(q, k, v, pos, tables, cpos, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_pad_rows_zero_causality_and_block_h(self):
        _, k, v, pos, tables, positions = _pool_case(8)
        q, cpos = self._chunk(8, positions, b=3, c=6, h=8, d=16)
        got = paged_prefill(q, k, v, pos, tables, cpos, interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        pads = np.asarray(cpos) < 0
        if pads.any():
            assert np.abs(np.asarray(got)[pads]).max() == 0.0
        for bh in (1, 2, 4):
            same = paged_prefill(q, k, v, pos, tables, cpos, block_h=bh,
                                 interpret=True)
            np.testing.assert_allclose(np.asarray(same), np.asarray(got),
                                       atol=1e-6)
        # causality across the chunk boundary: scribbling a key slot
        # AFTER a query's position must not change that query's output
        row = 2
        qp = int(np.asarray(positions)[row])
        k2 = np.asarray(k).copy()
        blk = int(np.asarray(tables)[row, qp // 4])
        k2[blk, qp % 4] = 50.0                   # the row's LAST position
        got2 = paged_prefill(q, jnp.asarray(k2, k.dtype), v, pos, tables,
                             cpos, interpret=True)
        early = np.asarray(cpos)[row] < qp
        np.testing.assert_allclose(np.asarray(got2)[row][early[:, None]
                                                         .repeat(8, 1)],
                                   np.asarray(got)[row][early[:, None]
                                                        .repeat(8, 1)],
                                   atol=1e-6)
        changed = np.asarray(cpos)[row] == qp
        assert np.abs(np.asarray(got2)[row][changed]
                      - np.asarray(got)[row][changed]).max() > 1e-4


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_blocks_past_the_live_bound_are_never_read(kernel, int8):
    """Every pool block wholly past a row's live bound — the stale block
    after each row's last page, the unreferenced ones and the trash
    block 0 — is filled with NaN (its K/V, or its int8 scales).  A
    kernel that read one would poison the row through ``0 * NaN`` in
    the PV product; both kernels stop at the live bound, so the output
    is finite and equals the oracle on the pool before poisoning."""
    if int8:
        q, k, v, ks, vs, pos, tables, positions = _int8_pool_case(EDGES)
    else:
        q, k, v, pos, tables, positions = _pool_case(EDGES)
        ks = vs = None
    t, qp = np.asarray(tables), np.asarray(positions)
    live = {int(t[r, j]) for r in range(len(t)) if t[r, 0] >= 0
            for j in range(qp[r] // EDGE_BS + 1)}
    dead = [blk for blk in range(k.shape[0]) if blk not in live]
    assert 0 in dead and set(t[t >= 0].tolist()) - live  # stale blocks

    def poison(x):
        x = np.asarray(x).copy()
        x[dead] = np.nan
        return jnp.asarray(x)

    if kernel == "decode":
        if int8:
            want = paged_decode_int8_ref(q, k, v, ks, vs, pos, tables,
                                         positions)
            got = paged_attention_int8(q, k, v, poison(ks), poison(vs), pos,
                                       tables, positions, interpret=True)
        else:
            want = paged_decode_ref(q, k, v, pos, tables, positions)
            got = paged_attention(q, poison(k), poison(v), pos, tables,
                                  positions, interpret=True)
    else:
        qc, cpos = TestFusedPrefillKernel._chunk(EDGES, positions, b=None,
                                                 c=5, h=8, d=16)
        want = paged_prefill_ref(qc, k, v, pos, tables, cpos, k_scale=ks,
                                 v_scale=vs)
        if int8:
            got = paged_prefill(qc, k, v, pos, tables, cpos,
                                k_scale=poison(ks), v_scale=poison(vs),
                                interpret=True)
        else:
            got = paged_prefill(qc, poison(k), poison(v), pos, tables, cpos,
                                interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2 if int8 else 1e-5)


# ---------------------------------------------------------------------------
# dispatch: config space, capability probe, divisor clamp
# ---------------------------------------------------------------------------


class TestDispatch:
    def test_kernel_config_resolves(self):
        cfg = tdispatch.kernel_config("paged_attention", b=4, m=4, n=128,
                                      dtype=jnp.float32, mu=2, group_size=8)
        assert isinstance(cfg, KernelConfig)
        assert cfg.block_h == 4                  # all heads of m=4

    def test_divisor_clamp(self):
        assert divisor_clamp(0, 6) == 6
        assert divisor_clamp(4, 6) == 3
        assert divisor_clamp(5, 8) == 4
        assert divisor_clamp(1, 7) == 1
        # dispatch-side clamp: only tiles the chip compiles (all heads
        # unless a 128-multiple divisor), whatever a cache entry says
        assert clamp_config(KernelConfig(block_h=5), "paged_attention",
                            b=1, m=8, n=64, group_size=8).block_h == 8

    def test_candidates_deduped_and_lead_with_heuristic(self):
        cands = candidate_configs("paged_attention", b=2, m=4, n=64,
                                  group_size=8)
        assert len(cands) == len(set(cands))
        assert cands[0].block_h == 4             # heuristic: all heads

    def test_supports_matrix(self):
        ok = dict(m=8, n=64, group_size=8, n_kv_heads=4)
        assert tdispatch.kernel_supports("paged_attention", **ok)
        # this PR's coverage lifts: int8-KV and MLA decode are fused now
        assert tdispatch.kernel_supports(
            "paged_attention", **{**ok, "kv_dtype": "int8"})
        assert tdispatch.kernel_supports(
            "paged_attention", **{**ok, "latent": True})
        assert tdispatch.kernel_supports("paged_prefill", **ok)
        assert tdispatch.kernel_supports(
            "paged_prefill", **{**ok, "kv_dtype": "int8"})
        # the reasons name WHICH cap failed, not just that one did
        rsn = tdispatch.kernel_unsupported_reason
        assert rsn("paged_attention", **{**ok, "window": 16}) == "window"
        assert rsn("paged_attention", m=7, n=64, group_size=8,
                   n_kv_heads=4) == "heads"
        assert rsn("paged_attention", **{**ok, "tp": 3}) == "tp"
        assert rsn("paged_attention",
                   **{**ok, "kv_dtype": "int4"}) == "kv_dtype"
        assert rsn("paged_prefill", **{**ok, "latent": True}) == "latent"
        assert rsn("nope", **ok) == "unknown_kernel"
        assert rsn("paged_attention", **ok) is None
        # GEMM-kernel path unchanged by the new caps
        assert tdispatch.kernel_supports("lut_gemm", m=64, n=128,
                                         group_size=64)
        assert rsn("lut_gemm", m=64, n=128, group_size=12) == "group_size"

    def test_unsupported_reason_lands_on_trace(self):
        from repro.obs.trace import Tracer, activate
        t = Tracer()
        with activate(t):
            tdispatch.kernel_unsupported_reason(
                "paged_prefill", m=8, n=64, group_size=8, n_kv_heads=4,
                latent=True)
        ev = [e for e in t.events
              if e.get("name") == "kernel_unsupported:paged_prefill"]
        assert ev and ev[0]["args"]["reason"] == "latent"

    def test_stale_cache_cannot_resurrect_bad_config(self):
        """Old tune-cache entries must not force an invalid launch on the
        new prefill kernel: 'paged_prefill' is a NEW cache-key kernel name
        (pre-PR caches keyed every paged entry 'paged_attention', so they
        can never collide), and even a poisoned entry is divisor-clamped
        before launch."""
        key = tcache.cache_key("paged_prefill", b=2, m=8, n=24,
                               dtype=jnp.float32, mu=2, group_size=4)
        assert "paged_prefill" in key            # disjoint from old keys
        poisoned = clamp_config(KernelConfig(block_h=5), "paged_prefill",
                                b=2, m=8, n=24, group_size=4)
        assert poisoned.block_h == 8             # clamped to a legal tile

    def test_paged_kernel_mode_host_mirror(self):
        cfg = get_reduced("opt_6_7b").replace(paged_kernel="fused")
        assert attn.paged_kernel_mode(cfg, block_size=4, pages=8) == "fused"
        assert attn.paged_kernel_mode(cfg.replace(paged_kernel="gather"),
                                      block_size=4, pages=8) == "gather"
        # auto off-TPU: gather (the kernel is not hardware-native here)
        assert attn.paged_kernel_mode(cfg.replace(paged_kernel="auto"),
                                      block_size=4, pages=8) == "gather"
        # int8-KV and MLA decode are fused variants now
        for lifted in ({"kv_cache_bits": 8},
                       {"attention": "mla", "kv_lora_rank": 8,
                        "qk_rope_head_dim": 4}):
            assert attn.paged_kernel_mode(cfg.replace(**lifted),
                                          block_size=4, pages=8) == "fused"
        with pytest.raises(ValueError):
            attn.paged_kernel_mode(cfg.replace(paged_kernel="bogus"),
                                   block_size=4, pages=8)

    def test_paged_prefill_mode_host_mirror(self):
        cfg = get_reduced("opt_6_7b").replace(paged_kernel="fused")
        assert attn.paged_prefill_mode(cfg, block_size=4, pages=8) == "fused"
        assert attn.paged_prefill_mode(cfg.replace(kv_cache_bits=8),
                                       block_size=4, pages=8) == "fused"
        # MLA prefill needs the decompressing kv_map_fn -> stays gathered
        mla = cfg.replace(attention="mla", kv_lora_rank=8,
                          qk_rope_head_dim=4)
        assert attn.paged_prefill_mode(mla, block_size=4,
                                       pages=8) == "gather"
        assert attn.paged_prefill_mode(cfg.replace(paged_kernel="gather"),
                                       block_size=4, pages=8) == "gather"


# ---------------------------------------------------------------------------
# sliding-window: a fallback variant at the op-router level
# ---------------------------------------------------------------------------


def test_window_falls_back_and_masks():
    """window != 0 is not fused; the router must gather and apply the
    window mask (only reachable through direct op calls — SWA configs
    keep their ring caches and never page)."""
    q, k, v, pos, tables, positions = _pool_case(6, idle_row=False)
    cache = {"k": k, "v": v, "pos": pos, "block_tables": tables}
    assert not attn.fused_paged_supported(cache, q.shape[1], window=8)
    got = attn.paged_decode_attend(q[:, None], cache,
                                   positions[:, None], window=8,
                                   mode="fused")
    kv = attn.paged_view(cache)
    want = attn.decode_attend(q[:, None], kv, positions[:, None], window=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # and the windowed result must differ from the unwindowed one for a
    # row with more than `window` live tokens (the mask actually bites)
    row = int(np.argmax(np.asarray(positions) >= 8))
    unwindowed = attn.paged_decode_attend(q[:, None], cache,
                                          positions[:, None], mode="gather")
    assert np.abs(np.asarray(got)[row] - np.asarray(unwindowed)[row]).max() \
        > 1e-6


# ---------------------------------------------------------------------------
# model level: fused vs gathered decode across variants
# ---------------------------------------------------------------------------


def _serve_tokens(m, params, mode, seed=7, steps=4):
    """Chunked-prefill a scrambled table then greedy-decode ``steps``
    tokens with the given paged_kernel mode; returns (tokens, logits)."""
    cfg = m.cfg.replace(paged_kernel=mode)
    mm = Model(cfg)
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 11)), jnp.int32)
    cache = mm.init_paged_cache(1, num_blocks=12, block_size=4,
                                max_blocks_per_seq=8)
    table = np.full((1, 8), -1, np.int32)
    table[0, :5] = [7, 2, 9, 4, 1]               # scrambled physical order
    cache = set_block_tables(cache, table)
    logits, cache = mm.prefill_chunk(params, {"tokens": toks}, cache,
                                     jnp.int32(0), jnp.int32(10))
    out, last = [], logits
    pos = 11
    for _ in range(steps):
        tok = int(np.argmax(np.asarray(last)[0]))
        out.append(tok)
        last, cache = mm.decode_step(
            params, jnp.asarray([[tok]], jnp.int32), cache, pos)
        pos += 1
    return out, np.asarray(last)


@pytest.mark.parametrize("arch,over,atol", [
    ("opt_6_7b", {}, 2e-4),                      # GQA -> fused kernels
    ("phi4_mini_3_8b", {}, 2e-4),                # RoPE GQA -> fused kernels
    # int8-KV -> fused kernels; the wider logit atol is the bf16
    # running-vs-global softmax rounding accumulated over the stack
    # (token equality is the serve-level contract)
    ("opt_6_7b", {"kv_cache_bits": 8}, 2e-3),
])
def test_decode_fused_matches_gather(arch, over, atol):
    m, params = _model(arch, **over)
    toks_f, logits_f = _serve_tokens(m, params, "fused")
    toks_g, logits_g = _serve_tokens(m, params, "gather")
    assert toks_f == toks_g
    np.testing.assert_allclose(logits_f, logits_g, atol=atol)
    assert np.isfinite(logits_f).all()


@pytest.mark.slow
@pytest.mark.parametrize("arch,over", [
    ("minicpm3_4b", {}),                  # MLA -> fused decode, gathered
                                          # prefill (kv_map_fn decompress)
    ("opt_6_7b", {"scan_layers": True}),  # stacked leaves, in-scan
])
def test_decode_fused_matches_gather_slow(arch, over):
    m, params = _model(arch, **over)
    toks_f, logits_f = _serve_tokens(m, params, "fused")
    toks_g, logits_g = _serve_tokens(m, params, "gather")
    assert toks_f == toks_g
    np.testing.assert_allclose(logits_f, logits_g, atol=2e-4)


@pytest.mark.parametrize("over", [{}, {"kv_cache_bits": 8}])
def test_fused_paths_never_materialize_view(monkeypatch, over):
    """The acceptance invariant: with the fused kernels selected, neither
    the chunked-prefill step nor the decode step may call ``paged_view``
    at all — for float AND int8-KV pools."""
    m, params = _model(**over)

    def boom(cache):
        raise AssertionError("paged_view materialized on a fused path")
    mm = Model(m.cfg.replace(paged_kernel="fused"))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, m.cfg.vocab_size, (1, 7)), jnp.int32)
    cache = mm.init_paged_cache(1, num_blocks=8, block_size=4,
                                max_blocks_per_seq=4)
    cache = set_block_tables(cache, np.array([[3, 1, 5, -1]], np.int32))
    # patched BEFORE prefill: the chunked-prefill flash kernel reads the
    # pool through the block table, never through a gathered view
    monkeypatch.setattr(attn, "paged_view", boom)
    _, cache = mm.prefill_chunk(params, {"tokens": toks}, cache,
                                jnp.int32(0), jnp.int32(6))
    logits, _ = mm.decode_step(params, toks[:, :1], cache, 7)
    assert np.isfinite(np.asarray(logits)).all()
    # sanity: the gathered path DOES go through paged_view
    mg = Model(m.cfg.replace(paged_kernel="gather"))
    with pytest.raises(Exception):
        mg.decode_step(params, toks[:, :1], cache, 7)


@pytest.mark.slow
def test_fused_mla_decode_never_materializes_view(monkeypatch):
    """MLA: absorbed decode is fused (prefill legitimately gathers for
    the decompressing kv_map_fn, so patch only after the prefill)."""
    m, params = _model("minicpm3_4b")
    mm = Model(m.cfg.replace(paged_kernel="fused"))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, m.cfg.vocab_size, (1, 7)), jnp.int32)
    cache = mm.init_paged_cache(1, num_blocks=8, block_size=4,
                                max_blocks_per_seq=4)
    cache = set_block_tables(cache, np.array([[3, 1, 5, -1]], np.int32))
    _, cache = mm.prefill_chunk(params, {"tokens": toks}, cache,
                                jnp.int32(0), jnp.int32(6))

    def boom(cache):
        raise AssertionError("paged_view materialized on the fused MLA "
                             "decode path")
    monkeypatch.setattr(attn, "paged_view", boom)
    logits, _ = mm.decode_step(params, toks[:, :1], cache, 7)
    assert np.isfinite(np.asarray(logits)).all()
