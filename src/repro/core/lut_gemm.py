"""Backend dispatch for executing BCQ-quantized linears.

Backends (all numerically equivalent up to FP reassociation; tested):

  * ``dense``      — dequantize to dense f32 and matmul (FPE baseline; the
                     "GPU engine" of Table IV).
  * ``bcq_xla``    — pure-XLA packed execution: unpack uint8 planes on the
                     fly, per-plane +-1 contraction scaled by alpha + offset
                     term.  This is the backend used by the *distributed*
                     model (pjit-traceable on any backend, incl. the CPU
                     dry-run): HLO sees q/16 of the dense weight bytes.
  * ``lut_pallas`` — the paper-faithful Pallas kernel (kernels/lut_gemm).
  * ``mxu_pallas`` — the beyond-paper dequant-in-VMEM kernel
                     (kernels/bcq_matmul).
  * ``ternary_pallas`` — the dedicated 1.58-bit kernel
                     (kernels/ternary_matmul); only consumes
                     ``kind="ternary"`` bundles.

The ``dense``/``bcq_xla`` paths are *kind-aware* through
``plane.dequantize``, so a ternary bundle executes correctly on every
XLA fallback; only the per-plane ``bcq_xla_planes`` contraction is
BCQ-specific.

The Pallas backends target TPU; off-TPU they run under
``interpret=True``.  :func:`interpret_mode` decides that at each launch,
never at import (importing ``repro`` initializes no JAX backend).

Launch geometry for the Pallas backends (block sizes, LUT read mode,
hFFLUT) is resolved per call through :mod:`repro.tune` — tuned JSON-cache
entries when present (``python -m repro.tune`` pre-tunes a model's layer
shapes), deterministic heuristics otherwise.  Nothing in this module or
its callers hard-codes a block constant.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.bcq import BCQWeight, dequantize, unpack_planes

Backend = Literal["dense", "bcq_xla", "lut_pallas", "mxu_pallas",
                  "ternary_pallas"]

def interpret_mode() -> bool:
    """True off-TPU: Pallas kernels then run in the interpreter (tests,
    examples).  Resolved where a kernel launches."""
    return jax.default_backend() != "tpu"


def bcq_xla_matmul(x: jax.Array, w: BCQWeight, out_dtype=None) -> jax.Array:
    """Pure-XLA packed BCQ GEMM.

    Per plane i:  y_i[b, m] = sum_G alpha[i,m,G] * sum_{n in G} pm1[m,n] x[b,n]
    computed as a grouped contraction so alpha stays per-(row, group); offset
    folds into per-group activation sums.  XLA fuses unpack+scale into the
    matmul prologue; HBM-side weight bytes remain the packed uint8 planes.
    """
    out_dtype = out_dtype or x.dtype
    if w.kind != "bcq":
        raise ValueError(
            f"bcq_xla_matmul reads independent ±1 planes (kind='bcq'); "
            f"got kind={w.kind!r} — use the fused path or ternary_pallas")
    q, m, nb = w.packed.shape
    n_pad = nb * 8
    g = w.group_size
    n_groups = w.alpha.shape[-1]

    xf = x.astype(jnp.float32)
    if xf.shape[-1] != n_pad:
        xf = jnp.pad(xf, [(0, 0)] * (xf.ndim - 1) + [(0, n_pad - xf.shape[-1])])
    lead = xf.shape[:-1]
    xg = xf.reshape(-1, n_groups, g)                       # [B, G, g]

    pm1 = unpack_planes(w.packed, dtype=jnp.float32)       # [q, M, n_pad]
    pm1 = pm1.reshape(q, m, n_groups, g)
    # per-plane grouped partial sums: [q, B, M, G]
    part = jnp.einsum("bGn,qmGn->qbmG", xg, pm1,
                      preferred_element_type=jnp.float32)
    y = jnp.einsum("qbmG,qmG->bm", part, w.alpha,
                   preferred_element_type=jnp.float32)
    if w.z is not None:
        y = y + jnp.einsum("bG,mG->bm", xg.sum(-1), w.z,
                           preferred_element_type=jnp.float32)
    return y.reshape(*lead, m).astype(out_dtype)


def bcq_xla_matmul_fused(x: jax.Array, w: BCQWeight, out_dtype=None,
                         compute_dtype=jnp.bfloat16) -> jax.Array:
    """XLA packed BCQ GEMM, dequant-then-single-matmul formulation.

    The dense weight is reconstructed inside the jit scope in
    ``compute_dtype`` (bf16: 2 B/weight of traffic on a fusing backend —
    the per-plane form materializes 16 B/weight) and contracted with FP32
    accumulation.  The 0.56 B/weight packed traffic of the paper's engine
    needs the Pallas kernel (kernels/bcq_matmul), which streams packed
    planes HBM->VMEM and never writes the dense form to HBM.
    """
    out_dtype = out_dtype or x.dtype
    dense = dequantize(w, dtype=compute_dtype)             # fused by XLA
    y = jnp.einsum("...n,mn->...m", x.astype(compute_dtype), dense,
                   preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def bcq_apply(x: jax.Array, w: BCQWeight, backend: Backend = "bcq_xla",
              out_dtype=None) -> jax.Array:
    """Execute y = x @ dequant(w).T on the selected backend."""
    if backend == "dense":
        return bcq_xla_matmul_fused(x, w, out_dtype,
                                    compute_dtype=jnp.float32)
    if backend == "bcq_xla":
        return bcq_xla_matmul_fused(x, w, out_dtype)
    if backend == "bcq_xla_planes":
        return bcq_xla_matmul(x, w, out_dtype)
    if backend == "lut_pallas":
        from repro.kernels.lut_gemm import lut_gemm
        # block sizes / read mode resolved via repro.tune dispatch
        return lut_gemm(x, w, interpret=interpret_mode(), out_dtype=out_dtype)
    if backend == "mxu_pallas":
        from repro.kernels.bcq_matmul import bcq_matmul
        return bcq_matmul(x, w, interpret=interpret_mode(), out_dtype=out_dtype)
    if backend == "ternary_pallas":
        from repro.kernels.ternary_matmul import ternary_matmul
        return ternary_matmul(x, w, interpret=interpret_mode(), out_dtype=out_dtype)
    raise ValueError(f"unknown backend {backend!r}")
