"""QuantizedLinear — the drop-in linear layer executing BCQ weights.

A linear's weight leaf is either a dense ``jax.Array`` (training /
unquantized) or a :class:`~repro.core.bcq.BCQWeight` (post-PTQ serving).
``linear_apply`` hands every call to the backend *registry*
(:mod:`repro.quant.backends`): the ``backend`` argument is a preference
(``None``/"auto" lets the registry pick the best native path), and
capability negotiation walks the preference's fallback chain
(``mxu_pallas``/``lut_pallas`` -> ``bcq_xla`` -> ``dense``) per weight —
model code never branches on quantization state or pins an executor.
For the Pallas backends the launch geometry is resolved per layer shape
through :mod:`repro.tune` (tuned cache or heuristic).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.bcq import BCQWeight


_CAPTURE = None


def set_capture(fn):
    """Install a capture hook fn(w, x) called on every linear_apply —
    used to collect per-layer calibration activations for OPTQ (eager
    forward passes only; hooks see tracers under jit)."""
    global _CAPTURE
    _CAPTURE = fn


def linear_apply(w, x: jax.Array, bias: Optional[jax.Array] = None,
                 backend: Optional[str] = None, out_dtype=None,
                 row_parallel: bool = False) -> jax.Array:
    """y = x @ W^T (+ bias).  W is dense [out, in] or BCQWeight.

    ``backend``: preference name from the registry ("auto"/None, "dense",
    "bcq_xla", "lut_pallas", "mxu_pallas", ...) — resolution and fallback
    happen in :func:`repro.quant.backends.execute_linear`.
    ``row_parallel`` marks a linear whose INPUT dim is model-sharded
    (attention out, MLP down): under a serving mesh its kernel runs per
    input shard and the partial products are summed.
    """
    if _CAPTURE is not None:
        _CAPTURE(w, x)
    # function-level import: quant.backends imports core submodules, so a
    # module-level import would be order-sensitive during package init
    from repro.quant.backends import execute_linear
    y = execute_linear(x, w, backend=backend, out_dtype=out_dtype or x.dtype,
                       row_parallel=row_parallel)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def quantize_linear(w: jax.Array, bits: int, method: str = "bcq",
                    group_size: int = 128, iters: int = 5) -> BCQWeight:
    """Quantize one dense [out, in] weight through the format registry.

    ``method`` is a format name ("bcq", "rtn"/"uniform", "ternary", or any
    :func:`repro.quant.register_format` addition).  Kept as a thin shim
    over :mod:`repro.quant.formats` for callers quantizing single
    matrices; whole trees should use ``repro.quant.quantize_model``.
    """
    from repro.quant.formats import get_format
    fmt = get_format(method)
    return fmt.quantize(w, bits=fmt.plane_bits(bits), group_size=group_size,
                        iters=iters)
