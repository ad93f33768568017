"""The grouped BCQ expert kernel (interpret mode) against its oracle, and
the expert-sorted row layout it reads."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.plane import PlaneBundle
from repro.kernels.grouped_matmul import (bcq_grouped_matmul, expert_layout,
                                          gather_rows, ref, row_tile,
                                          scatter_rows)


def _bank(e, n, k, g, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return PlaneBundle(
        packed=jax.random.bits(k1, (e, 3, n, k // 8), jnp.uint8),
        alpha=jax.random.uniform(k2, (e, 3, n, k // g), jnp.float32,
                                 0.01, 0.02),
        z=0.002 * jax.random.normal(k3, (e, n, k // g)),
        group_size=g, in_features=k, out_features=n)


def _run(x, experts, valid, bank, block_t):
    lay = expert_layout(experts, valid, bank.packed.shape[0], block_t)
    y = bcq_grouped_matmul(scatter_rows(x, lay), bank, lay, interpret=True,
                           out_dtype=jnp.float32)
    return gather_rows(y, lay), lay


# (experts, out, in, group, experts given rows): a chunk of 128-byte
# reduction steps; a 176-byte reduction (an expert's down projection
# shape) in one step; one expert holding every row
CASES = [(8, 48, 256, 128, [0, 1, 2, 5]),
         (8, 256, 176, 16, [0, 3, 7]),
         (4, 128, 128, 32, [2])]


@pytest.mark.parametrize("e,n,k,g,used", CASES)
@pytest.mark.parametrize("block_t", [8, 16])
def test_kernel_matches_oracle(e, n, k, g, used, block_t):
    """Experts with no rows are skipped, groups of one row and groups
    spanning several tiles are exact to float32 summation order."""
    bank = _bank(e, n, k, g)
    rng = np.random.default_rng(e + n)
    a = 37
    experts = jnp.asarray(rng.choice(used, a), jnp.int32)
    experts = experts.at[0].set(used[-1]).at[1].set(used[0])
    x = jax.random.normal(jax.random.PRNGKey(1), (a, k))
    valid = jnp.ones((a,), bool)
    y, lay = _run(x, experts, valid, bank, block_t)
    want = ref.grouped_matmul_ref(x, experts, bank)
    # float32 in both, the kernel summing per 1,024-weight chunk and bit
    # slab: agreement to float32 rounding of values of order 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5,
                               rtol=1e-5)
    load = np.bincount(np.asarray(experts), minlength=e)
    np.testing.assert_array_equal(np.asarray(lay.load), load)
    assert int(lay.n_live[0]) == sum(-(-c // block_t) for c in load)


def test_one_row_groups_and_invalid_rows():
    """Decode's shape: one or two rows per expert; padding rows get no
    tile, come back zero and are not counted."""
    bank = _bank(8, 64, 128, 32, seed=3)
    experts = jnp.asarray([5, 1, 5, 6, 0, 0], jnp.int32)
    valid = jnp.asarray([True, True, True, True, False, False])
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 128))
    y, lay = _run(x, experts, valid, bank, 8)
    want = ref.grouped_matmul_ref(x, experts, bank) * valid[:, None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5,
                               rtol=1e-5)
    assert np.asarray(lay.load).tolist() == [0, 1, 0, 0, 0, 2, 1, 0]
    assert int(lay.n_live[0]) == 3          # experts 1, 5 and 6
    assert np.asarray(lay.dest)[4:].tolist() == [lay.rows] * 2


def test_no_valid_rows_reads_nothing():
    bank = _bank(4, 32, 128, 32)
    experts = jnp.zeros((5,), jnp.int32)
    y, lay = _run(jnp.ones((5, 128)), experts, jnp.zeros((5,), bool), bank,
                  8)
    assert int(lay.n_live[0]) == 0
    assert not np.asarray(y).any()


def test_layout_packs_each_expert_into_whole_tiles():
    experts = jnp.asarray([3, 0, 3, 3, 1, 3, 0, 3, 3, 3], jnp.int32)
    lay = expert_layout(experts, jnp.ones((10,), bool), 4, 4)
    dest = np.asarray(lay.dest)
    # expert 0 -> tile 0, expert 1 -> tile 1, expert 3 (7 rows) -> 2, 3
    assert sorted(dest[np.asarray(experts) == 0]) == [0, 1]
    assert dest[4] == 4
    assert sorted(dest[np.asarray(experts) == 3]) == list(range(8, 15))
    assert int(lay.n_live[0]) == 4
    assert np.asarray(lay.tile_expert)[:4].tolist() == [0, 1, 3, 3]
    # tiles past the live ones repeat the last live tile's blocks
    assert set(np.asarray(lay.tile_src)[4:].tolist()) <= {3}
    assert set(np.asarray(lay.tile_expert)[4:].tolist()) <= {3}


def test_row_tile():
    assert row_tile(32 * 6, 64) == 8           # a decode step
    assert row_tile(512 * 6, 64) == 96         # a 512-token prefill chunk
    assert row_tile(10 ** 6, 8) == 128
