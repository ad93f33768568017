"""Oracle for the grouped BCQ matmul: every row against its expert's
dense dequantized matrix, in float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.plane import PlaneBundle, dequantize


def bank_dense(bank: PlaneBundle) -> jax.Array:
    """[E, N, K] float32 of a [E, q, N, K/8] bundle."""
    def one(p, a, z):
        return dequantize(PlaneBundle(
            packed=p, alpha=a, z=z, group_size=bank.group_size,
            in_features=bank.in_features, out_features=bank.out_features,
            kind=bank.kind))
    return jax.vmap(one)(bank.packed, bank.alpha, bank.z)


def grouped_matmul_ref(x: jax.Array, experts: jax.Array,
                       bank: PlaneBundle) -> jax.Array:
    """x [A, K], experts int32 [A] -> f32 [A, N]: row a times the dense
    matrix of expert ``experts[a]``."""
    w = bank_dense(bank)[experts]                       # [A, N, K]
    return jnp.einsum("ak,ank->an", x.astype(jnp.float32), w,
                      precision=jax.lax.Precision.HIGHEST)
