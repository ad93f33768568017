"""Whole-model post-training quantization into plane bundles.

Walks a params tree, finds linear weights by leaf name, and replaces each
with a :class:`~repro.core.plane.PlaneBundle` — after which every
``linear_apply`` call site executes the LUT/BCQ/ternary path of the
configured backend.  This is the internal PTQ engine behind the
declarative entry point ``repro.quant.quantize_model(params, QuantSpec,
axes)``.  Supports:

  * per-layer bit maps (mixed precision, Fig. 17) — fractional widths
    below 2 (the :data:`~repro.core.plane.TERNARY_BITS` sentinel) route
    that layer onto the ternary format (MxGLUT-style format mixing),
  * "bcq" (alternating non-uniform), "rtn" (uniform-as-BCQ) and
    "ternary" (sign+mask bundle) methods,
  * scan-stacked params ([L, out, in] -> packed [L, q, out, in/8] so
    lax.scan still slices layer-by-layer),
  * expert banks ([E, f, d] folded to [E*f, d]; rows are independent so
    this is exact per-expert quantization),
  * abstract mode for the dry-run (ShapeDtypeStructs, no allocation).

Weight leaves quantized (QUANT_KEYS): attention/MLA projections, MLP and
expert matrices, SSM in/out projections.  Routers, norms, biases, convs
and embeddings stay FP (standard weight-only practice; embeddings are
lookups, not GEMMs).
"""
from __future__ import annotations

import functools
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bcq as bcq_mod
from repro.core.bcq import BCQWeight

QUANT_KEYS = {
    "q", "k", "v", "o", "q_a", "q_b", "kv_a", "kv_b",
    "gate", "up", "down", "shared_gate", "shared_up", "shared_down",
    "in_proj", "out_proj", "unembed",
}

# leaves that match QUANT_KEYS but must stay FP
_SKIP_KEYS = {"router", "conv_w", "conv_b", "tok", "pos"}


def _walk(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk(v, path + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk(v, path + (i,))
    else:
        yield path, tree


def _set_path(tree, path, value):
    if not path:
        return value
    head, rest = path[0], path[1:]
    if isinstance(tree, dict):
        out = dict(tree)
        out[head] = _set_path(tree[head], rest, value)
        return out
    out = list(tree)
    out[head] = _set_path(tree[head], rest, value)
    return type(tree)(out) if isinstance(tree, tuple) else out


_INPUT_AXES = {"embed", "lora", "mlp", "heads", "kv_heads", "vocab"}


def _is_quant_leaf(path, leaf, axes=None) -> bool:
    """True for genuine [out, in] GEMM weights.

    Name collision guard: qwen's QKV *bias* is also called "q_b" (like
    MLA's q_b projection) and, scan-stacked, is 2-D — so when logical
    axes are available we additionally require the last (input) axis to
    be a contraction axis, which biases ('heads',) fail.
    """
    name = path[-1] if path else ""
    if name in _SKIP_KEYS or name not in QUANT_KEYS:
        return False
    if not (hasattr(leaf, "ndim") and leaf.ndim >= 2):
        return False
    if axes:
        rank = len(axes) - (1 if axes[0] == "layers" else 0)
        return axes[-1] in _INPUT_AXES and rank >= 2
    return True


def collect_linears(params, axes_tree=None) -> dict:
    """{'/'.join(path): array} for every quantizable weight.

    Pass ``axes_tree`` (``Model.axes()``) to apply the same logical-axes
    name-collision guard the quantizer uses (qwen's scan-stacked q_b
    bias), so bit plans and quantization agree on the layer set.
    """
    out = {}
    for p, l in _walk(params):
        axes = _axes_of(axes_tree, p) if axes_tree is not None else None
        if _is_quant_leaf(p, l, axes):
            out["/".join(map(str, p))] = l
    return out


def _axes_of(axes_tree, path):
    node = axes_tree
    try:
        for p in path:
            node = node[p]
        return node
    except (KeyError, IndexError, TypeError):
        return None


_BATCH_AXES = ("layers", "experts")


def _lead_batch(axes, ndim):
    """# of leading dims kept as quantization batch dims.

    'layers' (lax.scan slices it) and 'experts' (EP-sharded; folding E into
    the row dim would merge a sharded dim and force an all-gather on every
    dequantize — measured ~65 GB/step on mixtral decode).
    """
    n = 0
    axes = axes or ()
    while n < len(axes) and axes[n] in _BATCH_AXES and ndim - n > 2:
        n += 1
    return n


def _quantize_leaf(w, axes, bits, method, group_size, iters):
    """Quantize one weight leaf, handling stacked leading batch dims.

    ``bits`` may be fractional: widths below 2 select the ternary format
    regardless of ``method`` (the mixed-precision planner's sentinel).
    """
    # format registry lookup (lazy import: repro.quant.api imports this
    # module); every registered format lowers into PlaneBundle planes
    from repro.quant.formats import format_for_bits
    fmt = format_for_bits(method, bits)
    nb = _lead_batch(axes, w.ndim)

    def quant2d(w2):
        return fmt.quantize(w2, bits=fmt.plane_bits(bits),
                            group_size=group_size, iters=iters)

    if nb:
        lead = w.shape[:nb]
        rows = int(np.prod(w.shape[nb:-1]))
        cols = w.shape[-1]
        # cast per layer inside the map: a float32 copy of the whole
        # stacked leaf would be 2x its bf16 bytes (8.6 GB for OPT-6.7B's
        # fc1 stack)
        w3 = w.reshape(int(np.prod(lead)), rows, cols)
        stacked = jax.lax.map(lambda wi: quant2d(wi.astype(jnp.float32)), w3)
        unflat = lambda a: a.reshape(*lead, *a.shape[1:])
        return BCQWeight(packed=unflat(stacked.packed),
                         alpha=unflat(stacked.alpha),
                         z=None if stacked.z is None else unflat(stacked.z),
                         group_size=int(group_size),
                         in_features=cols, out_features=rows,
                         kind=stacked.kind)
    rows = int(np.prod(w.shape[:-1]))
    return quant2d(w.reshape(rows, w.shape[-1]).astype(jnp.float32))


def quantize_model(params, axes_tree=None, *, bits=4, method: str = "bcq",
                   group_size: int = 128, iters: int = 5,
                   bit_map: Optional[Mapping[str, float]] = None):
    """Replace every quantizable linear with a PlaneBundle.

    bit_map: optional {'path/like/this': bits} per-layer override (mixed
    precision; fractional widths below 2 select ternary).  axes_tree:
    logical-axes tree (Model.axes()) used to detect scan-stacked
    weights; optional for unrolled models.

    This is the PTQ *engine*; the public surface is the declarative
    entry point, which also plans mixed precision and returns a
    manifest::

        from repro.quant import QuantSpec, quantize_model
        qparams, manifest = quantize_model(params, QuantSpec(...), axes)
    """
    out = params
    for path, leaf in list(_walk(params)):
        axes = _axes_of(axes_tree, path) if axes_tree is not None else None
        if not _is_quant_leaf(path, leaf, axes):
            continue
        key = "/".join(map(str, path))
        b = bit_map.get(key, bits) if bit_map else bits
        wq = _quantize_leaf(leaf, axes, b, method, group_size, iters)
        out = _set_path(out, path, wq)
    return out


def abstract_quantized_params(abstract_tree, axes_tree, *, bits=4,
                              group_size: int = 128):
    """ShapeDtypeStruct version of quantize_model for the dry-run.

    Maps each quantizable linear's SDS to the BCQWeight SDS bundle with the
    same stacking rules — no weight is ever allocated.
    """
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    out = abstract_tree
    for path, leaf in list(_walk(abstract_tree)):
        axes = _axes_of(axes_tree, path)
        if not _is_quant_leaf(path, leaf, axes):
            continue
        nb = _lead_batch(axes, len(leaf.shape))
        lead_dims = tuple(leaf.shape[:nb])
        rows = int(np.prod(leaf.shape[nb:-1]))
        cols = leaf.shape[-1]
        npad = -(-cols // group_size) * group_size
        ngr = npad // group_size
        wq = BCQWeight(
            packed=sds((*lead_dims, bits, rows, npad // 8), jnp.uint8),
            alpha=sds((*lead_dims, bits, rows, ngr), jnp.float32),
            z=sds((*lead_dims, rows, ngr), jnp.float32),
            group_size=group_size, in_features=cols, out_features=rows,
        )
        out = _set_path(out, path, wq)
    return out
