"""Main-path kernels compile for a TPU v5e at OPT-6.7B widths.

No chip is needed: the TPU compiler compiles for a *described* ``v5e``
chip, which refuses what the Pallas interpreter accepts (blocks the
(8, 128) tiling rejects, gathers, batched 3-D contractions, scoped-VMEM
overruns).  Each kernel is lowered through its public op wrapper with
``interpret=False`` and the launch the serving path resolves (the
heuristic: no tune cache), and the compiled program must hold the
kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the workers of a
parallel test run each import every test file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.plane import PlaneBundle

B_DECODE, B_PREFILL = 8, 64
GROUP, BITS = 128, 3
# (out_features, in_features): q/k/v/o, fc1, fc2
GEMM_SHAPES = [(4096, 4096), (16384, 4096), (4096, 16384)]
HEADS, HEAD_DIM = 32, 128
NUM_BLOCKS, BLOCK_SIZE, PAGES, CHUNK = 192, 16, 32, 64


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_persistent_cache, monkeypatch):
    """Compile ``fn`` on shapes placed on the described chip (heuristic
    launches only) and return the optimized HLO text."""
    monkeypatch.setenv("REPRO_TUNE", "off")

    def run(fn, *shapes):
        args = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


def _bundle(m, n, kind="bcq"):
    sds = jax.ShapeDtypeStruct
    q = 2 if kind == "ternary" else BITS
    return PlaneBundle(
        packed=sds((q, m, n // 8), jnp.uint8),
        alpha=sds((1 if kind == "ternary" else q, m, n // GROUP),
                  jnp.float32),
        z=None if kind == "ternary" else sds((m, n // GROUP), jnp.float32),
        group_size=GROUP, in_features=n, out_features=m, kind=kind)


def _gemm(kernel):
    from repro.kernels.bcq_matmul import bcq_matmul
    from repro.kernels.lut_gemm import lut_gemm
    from repro.kernels.ternary_matmul import ternary_matmul
    fn = {"lut_gemm": lut_gemm, "bcq_matmul": bcq_matmul,
          "ternary_matmul": ternary_matmul}[kernel]
    return lambda x, w, **kw: fn(x, w, interpret=False, **kw)


@pytest.mark.parametrize("m,n", GEMM_SHAPES)
@pytest.mark.parametrize("kernel",
                         ["lut_gemm", "bcq_matmul", "ternary_matmul"])
def test_gemm_kernel_compiles(compile_for, kernel, m, n):
    kind = "ternary" if kernel == "ternary_matmul" else "bcq"
    hlo = compile_for(_gemm(kernel),
                      jax.ShapeDtypeStruct((B_DECODE, n), jnp.bfloat16),
                      _bundle(m, n, kind))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["lut_gemm", "bcq_matmul"])
def test_gemm_kernel_compiles_at_prefill_batch(compile_for, kernel):
    """A prefill chunk's rows take the larger batch tile."""
    hlo = compile_for(_gemm(kernel),
                      jax.ShapeDtypeStruct((B_PREFILL, 4096), jnp.bfloat16),
                      _bundle(4096, 4096))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel",
                         ["lut_gemm", "bcq_matmul", "ternary_matmul"])
def test_largest_tune_tiles_compile(compile_for, kernel):
    """The biggest tiles the tune space can emit fit VMEM (full-table
    one-hot LUT reads at the widest reduction dim)."""
    from repro.tune.space import KernelConfig, clamp_config
    m, n = 4096, 16384
    cfg = clamp_config(KernelConfig(block_b=10 ** 6, block_m=10 ** 6,
                                    block_n=10 ** 6, read_mode="onehot",
                                    half_lut=False), kernel,
                       b=B_PREFILL, m=m, n=n, group_size=GROUP)
    kind = "ternary" if kernel == "ternary_matmul" else "bcq"
    run = _gemm(kernel)
    hlo = compile_for(lambda x, w: run(x, w, **cfg.to_kwargs(kernel)),
                      jax.ShapeDtypeStruct((B_PREFILL, n), jnp.bfloat16),
                      _bundle(m, n, kind))
    assert "tpu_custom_call" in hlo


def _pools(int8):
    sds = jax.ShapeDtypeStruct
    dt = jnp.int8 if int8 else jnp.bfloat16
    pools = (sds((NUM_BLOCKS, BLOCK_SIZE, HEADS, HEAD_DIM), dt),) * 2
    scales = (sds((NUM_BLOCKS, BLOCK_SIZE, HEADS), jnp.float32),) * 2
    return pools, (scales if int8 else ()), sds((NUM_BLOCKS, BLOCK_SIZE),
                                                jnp.int32)


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_paged_decode_compiles(compile_for, int8):
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_int8)
    sds = jax.ShapeDtypeStruct
    (k, v), scales, pos = _pools(int8)
    q = sds((B_DECODE, HEADS, HEAD_DIM), jnp.bfloat16)
    tables = sds((B_DECODE, PAGES), jnp.int32)
    qpos = sds((B_DECODE,), jnp.int32)
    if int8:
        fn = lambda q, k, v, ks, vs, p, t, qp: paged_attention_int8(
            q, k, v, ks, vs, p, t, qp, interpret=False)
    else:
        fn = lambda q, k, v, p, t, qp: paged_attention(
            q, k, v, p, t, qp, interpret=False)
    hlo = compile_for(fn, q, k, v, *scales, pos, tables, qpos)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_paged_prefill_compiles(compile_for, int8):
    from repro.kernels.paged_attention import paged_prefill
    sds = jax.ShapeDtypeStruct
    (k, v), scales, pos = _pools(int8)
    q = sds((1, CHUNK, HEADS, HEAD_DIM), jnp.bfloat16)
    tables = sds((1, PAGES), jnp.int32)
    qpos = sds((1, CHUNK), jnp.int32)

    def fn(q, k, v, p, t, qp, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return paged_prefill(q, k, v, p, t, qp, interpret=False, **kw)
    hlo = compile_for(fn, q, k, v, pos, tables, qpos, *scales)
    assert "tpu_custom_call" in hlo


def test_paged_prefill_compiles_long_chunk(compile_for):
    """The engine's largest default prefill bucket (512 tokens): its
    query rows tile over grid steps so each tile fits VMEM."""
    from repro.kernels.paged_attention import paged_prefill
    sds = jax.ShapeDtypeStruct
    (k, v), _, pos = _pools(False)
    hlo = compile_for(
        lambda q, k, v, p, t, qp: paged_prefill(q, k, v, p, t, qp,
                                                interpret=False),
        sds((1, 512, HEADS, HEAD_DIM), jnp.bfloat16), k, v, pos,
        sds((1, PAGES), jnp.int32), sds((1, 512), jnp.int32))
    assert "tpu_custom_call" in hlo


# the chat cells' attention at their real shapes: max_batch 32, a
# 2,048-token table of 16-token blocks, each config's KV pool
CELL_ROWS, CELL_PAGES = 32, 128
CELLS = {"opt-6.7b": (32, 32, 449), "phi4-mini": (24, 8, 1793)}


def _cell_pools(cell, int8):
    sds = jax.ShapeDtypeStruct
    heads, kv_heads, blocks = CELLS[cell]
    dt = jnp.int8 if int8 else jnp.bfloat16
    kv = (sds((blocks, BLOCK_SIZE, kv_heads, HEAD_DIM), dt),) * 2
    scales = (sds((blocks, BLOCK_SIZE, kv_heads), jnp.float32),) * 2
    return heads, kv, (scales if int8 else ()), \
        sds((blocks, BLOCK_SIZE), jnp.int32)


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_paged_decode_compiles_at_cell_shape(compile_for, cell, int8):
    """The page-group decode kernel (manual double-buffered page copies,
    a loop bounded by each row's live pages) at both cells' shapes: MHA
    32 x 128 and GQA 8 kv heads with rep 3."""
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_int8)
    sds = jax.ShapeDtypeStruct
    heads, (k, v), scales, pos = _cell_pools(cell, int8)
    q = sds((CELL_ROWS, heads, HEAD_DIM), jnp.bfloat16)
    tables = sds((CELL_ROWS, CELL_PAGES), jnp.int32)
    qpos = sds((CELL_ROWS,), jnp.int32)
    if int8:
        fn = lambda q, k, v, ks, vs, p, t, qp: paged_attention_int8(
            q, k, v, ks, vs, p, t, qp, interpret=False)
    else:
        fn = lambda q, k, v, p, t, qp: paged_attention(
            q, k, v, p, t, qp, interpret=False)
    hlo = compile_for(fn, q, k, v, *scales, pos, tables, qpos)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_paged_prefill_compiles_at_cell_shape(compile_for, cell):
    """A 512-bucket chunk over the cells' 128-page tables, its page steps
    bounded by the chunk's live pages."""
    from repro.kernels.paged_attention import paged_prefill
    sds = jax.ShapeDtypeStruct
    heads, (k, v), _, pos = _cell_pools(cell, False)
    hlo = compile_for(
        lambda q, k, v, p, t, qp: paged_prefill(q, k, v, p, t, qp,
                                                interpret=False),
        sds((1, 512, heads, HEAD_DIM), jnp.bfloat16), k, v, pos,
        sds((1, CELL_PAGES), jnp.int32), sds((1, 512), jnp.int32))
    assert "tpu_custom_call" in hlo
