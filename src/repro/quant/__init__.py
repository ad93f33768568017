"""repro.quant — the unified quantization API.

    from repro.quant import QuantSpec, quantize_model

    spec = QuantSpec(format="bcq", bits=2.4, group_size=64)
    qparams, manifest = quantize_model(params, spec, model.axes())

See :mod:`repro.quant.spec` (declarative config), :mod:`repro.quant.
formats` (bcq / rtn / ternary -> BCQ planes), :mod:`repro.quant.backends`
(capability-negotiated execution with fallback chains), :mod:`repro.
quant.api` (quantize + manifest) and :mod:`repro.quant.checkpoint`
(pre-quantized checkpoints).

Only :mod:`repro.quant.spec` (stdlib-only) loads eagerly — the heavier
submodules resolve lazily via PEP 562 so ``import repro.configs`` (which
embeds QuantSpec in ModelConfig) stays light and cycle-free.
"""
from repro.quant.spec import TERNARY_BITS, QuantSpec, canonical_format

_LAZY = {
    # formats
    "FormatInfo": "formats", "available_formats": "formats",
    "format_for_bits": "formats", "get_format": "formats",
    "register_format": "formats", "quantize_ternary": "formats",
    # backends
    "BackendInfo": "backends", "available_backends": "backends",
    "execute_linear": "backends", "fallback_chain": "backends",
    "get_backend": "backends", "kernel_for": "backends",
    "register_backend": "backends", "resolve_backend": "backends",
    # api
    "QuantManifest": "api", "build_manifest": "api", "plan_bits": "api",
    "quantize_model": "api", "init_quantized": "api",
    # checkpoint
    "load_quantized": "checkpoint", "save_quantized": "checkpoint",
}

__all__ = ["QuantSpec", "TERNARY_BITS", "canonical_format", *sorted(_LAZY)]


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f"repro.quant.{_LAZY[name]}")
        value = getattr(mod, name)
        globals()[name] = value          # cache for subsequent lookups
        return value
    raise AttributeError(f"module 'repro.quant' has no attribute {name!r}")


def __dir__():
    return sorted(__all__)
