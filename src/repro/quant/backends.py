"""Execution-backend registry: capability negotiation + fallback chain.

``linear_apply`` used to pick its execution path with an ``isinstance``
check plus a raw string; now every linear resolves here:

    name = resolve_backend(preference, w)     # capability negotiation
    y    = execute_linear(x, w, backend=preference)

Each registered backend declares

  * ``available()`` — can it run *at all* on this host (Pallas kernels run
    anywhere via interpret mode, so this is almost always True);
  * ``native()``    — is it the hardware-native path here (Pallas on TPU);
    ``auto`` resolution only considers native backends, so a CPU host
    auto-selects ``bcq_xla`` instead of interpret-mode Pallas, while an
    *explicit* preference still runs interpreted (tests, kernel bring-up);
  * ``supports(w)`` — per-weight capability: plane count, group-size
    granularity, problem geometry (consults
    :func:`repro.tune.dispatch.kernel_supports` for the Pallas kernels).

Resolution walks the preference's fallback chain —
``ternary_pallas``/``mxu_pallas``/``lut_pallas`` -> ``bcq_xla`` ->
``dense`` — and returns the first backend that is usable and supports the
weight, so a new format or an odd group size degrades gracefully instead
of crashing a serve tick.  ``supports`` is *kind-aware*: the dedicated
ternary kernel only claims ``kind="ternary"`` bundles and the generic
plane kernels only ``kind="bcq"``, while the XLA fallbacks execute any
kind through the kind-aware ``plane.dequantize``.

Dense (unquantized) array leaves resolve to the plain einsum path, making
this the single dispatch point for *every* linear in the model stack.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.bcq import BCQWeight
from repro.core import lut_gemm as _lg


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class BackendInfo:
    name: str
    execute: Callable[..., jax.Array]          # (x, w, out_dtype) -> y
    supports: Callable[[BCQWeight], bool]
    available: Callable[[], bool]
    native: Callable[[], bool]
    kernel: Optional[str] = None               # repro.tune kernel id
    description: str = ""


_REGISTRY: Dict[str, BackendInfo] = {}

#: resolution order for ``backend="auto"`` (best native first).
#: ``ternary_pallas`` heads the chain but only claims ``kind="ternary"``
#: bundles, so generic BCQ weights resolve exactly as before.
AUTO_CHAIN: Tuple[str, ...] = ("ternary_pallas", "mxu_pallas", "lut_pallas",
                               "bcq_xla", "dense")

#: explicit-preference fallback chains (first entry = the preference)
FALLBACK_CHAINS: Dict[str, Tuple[str, ...]] = {
    "ternary_pallas": ("ternary_pallas", "bcq_xla", "dense"),
    "mxu_pallas": ("mxu_pallas", "bcq_xla", "dense"),
    "lut_pallas": ("lut_pallas", "bcq_xla", "dense"),
    "bcq_xla": ("bcq_xla", "dense"),
    "bcq_xla_planes": ("bcq_xla_planes", "bcq_xla", "dense"),
    "dense": ("dense",),
    "auto": AUTO_CHAIN,
}


def register_backend(info: BackendInfo,
                     chain: Optional[Tuple[str, ...]] = None) -> BackendInfo:
    _REGISTRY[info.name] = info
    if chain is not None:
        FALLBACK_CHAINS[info.name] = chain
    elif info.name not in FALLBACK_CHAINS:
        FALLBACK_CHAINS[info.name] = (info.name, "bcq_xla", "dense")
    return info


def get_backend(name: str) -> BackendInfo:
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_backends() -> Tuple[str, ...]:
    return tuple(n for n in _REGISTRY if _REGISTRY[n].available())


def fallback_chain(preference: Optional[str]) -> Tuple[str, ...]:
    pref = preference or "auto"
    if pref not in FALLBACK_CHAINS:
        raise KeyError(f"unknown backend preference {pref!r}; known: "
                       f"{sorted(FALLBACK_CHAINS)}")
    return FALLBACK_CHAINS[pref]


# ---------------------------------------------------------------------------
# resolution + execution
# ---------------------------------------------------------------------------


#: preferences whose chains hold Pallas kernels: resolving one of them
#: to an XLA path is a fall-down, recorded as such on the active trace
_KERNEL_PREFERENCES = ("auto", "ternary_pallas", "mxu_pallas", "lut_pallas")


def resolve_backend(preference: Optional[str], w: BCQWeight) -> str:
    """Pick the backend that will execute this weight.

    The head of an *explicit* chain only needs ``available()`` (interpret
    mode is a legitimate explicit request); fallback entries and ``auto``
    require ``native()`` so we never silently degrade onto an emulated
    kernel.  ``dense`` always supports everything, so resolution total.
    A resolution that leaves a kernel preference for ``bcq_xla``/``dense``
    is recorded on the active trace as a ``backend_fallback``
    (``obs.trace.record_backend_fallback``).
    """
    pref = preference or "auto"
    chain = fallback_chain(pref)
    name = "dense"
    for i, cand in enumerate(chain):
        info = get_backend(cand)
        explicit = i == 0 and pref != "auto"
        usable = info.available() if explicit else info.native()
        if usable and info.supports(w):
            name = cand
            break
    if pref in _KERNEL_PREFERENCES and get_backend(name).kernel is None:
        from repro.obs.trace import record_backend_fallback
        record_backend_fallback(pref, name, m=w.out_features,
                                n=w.in_features, kind=w.kind)
    return name


def execute_linear(x: jax.Array, w, *, backend: Optional[str] = None,
                   out_dtype=None, row_parallel: bool = False) -> jax.Array:
    """y = x @ W^T for a dense array or BCQWeight leaf.

    This is the single execution-dispatch point of the model stack:
    ``backend`` is a *preference*, and capability negotiation picks the
    first link of its fallback chain that can run this weight.  Under a
    serving mesh (``models.attention.paged_shard_scope`` with a model
    axis) a Pallas kernel launches per shard (:func:`_sharded_kernel`):
    the TPU compiler cannot partition a Mosaic kernel by itself.
    """
    out_dtype = out_dtype or x.dtype
    if not isinstance(w, BCQWeight):
        return jnp.einsum("...n,mn->...m", x, w.astype(x.dtype),
                          preferred_element_type=jnp.float32).astype(out_dtype)
    info = get_backend(resolve_backend(backend, w))
    if info.kernel is not None:
        from repro.models.attention import serving_mesh
        mesh = serving_mesh()
        if mesh is not None:
            return _sharded_kernel(info.execute, x, w, out_dtype, mesh,
                                   row_parallel)
    return info.execute(x, w, out_dtype)


def _sharded_kernel(run, x, w: BCQWeight, out_dtype, mesh,
                    row_parallel: bool) -> jax.Array:
    """Launch a plane kernel per model shard (Megatron layout).

    Column-parallel (default): each shard holds whole input rows of its
    slice of output features and writes that slice.  Row-parallel (the
    input dim is model-sharded: attention out, MLP down): each shard
    reduces over its slice of the input dim and the partial products are
    summed over the model axis.  A weight whose dims do not split into
    whole rows / whole scale groups runs replicated on every shard.
    """
    from jax.sharding import PartitionSpec as P

    tp = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    lead = (None,) * (x.ndim - 1)
    q, m, nb = w.packed.shape
    ng = w.alpha.shape[-1]
    if row_parallel and w.in_features == nb * 8 and ng % tp == 0 \
            and (w.in_features // tp) % w.group_size == 0:
        cut = dict(in_features=w.in_features // tp,
                   out_features=w.out_features)
        specs = (P(*lead, "model"), P(None, None, "model"),
                 P(None, None, "model"), P(None, "model"))
        out_spec, reduce = P(*lead, None), True
    elif not row_parallel and m == w.out_features and m % tp == 0:
        cut = dict(in_features=w.in_features, out_features=m // tp)
        specs = (P(*lead, None), P(None, "model", None),
                 P(None, "model", None), P("model", None))
        out_spec, reduce = P(*lead, "model"), False
    else:
        cut = dict(in_features=w.in_features, out_features=w.out_features)
        specs = (P(*lead, None), P(), P(), P())
        out_spec, reduce = P(*lead, None), False
    args = (x, w.packed, w.alpha) + (() if w.z is None else (w.z,))

    def shard(xs, packed, alpha, *z):
        ws = BCQWeight(packed=packed, alpha=alpha, z=z[0] if z else None,
                       group_size=w.group_size, kind=w.kind, **cut)
        y = run(xs, ws, jnp.float32)
        return jax.lax.psum(y, "model") if reduce else y

    y = jax.shard_map(shard, mesh=mesh, in_specs=specs[:len(args)],
                      out_specs=out_spec, check_vma=False)(*args)
    return y.astype(out_dtype)


def kernel_for(preference: Optional[str]) -> Optional[str]:
    """The repro.tune kernel id the preference would launch (for pretune):
    None when resolution lands on an XLA/dense path."""
    pref = preference or "auto"
    for i, name in enumerate(fallback_chain(pref)):
        info = get_backend(name)
        explicit = i == 0 and pref != "auto"
        if info.available() if explicit else info.native():
            return info.kernel
    return None


# ---------------------------------------------------------------------------
# built-in backends (executors live in repro.core.lut_gemm / repro.kernels)
# ---------------------------------------------------------------------------


def _supports_any(w: BCQWeight) -> bool:
    return True


def _supports_bcq_planes(w: BCQWeight) -> bool:
    # the per-plane grouped contraction reads independent ±1 planes;
    # ternary (sign+mask) bundles take the kind-aware fused paths instead
    return w.kind == "bcq"


def _supports_pallas(kernel: str):
    def check(w: BCQWeight) -> bool:
        from repro.tune.dispatch import kernel_supports
        if w.packed.ndim != 3:          # stacked leaves only run inside scan
            return False
        return kernel_supports(kernel, m=w.out_features, n=w.in_features,
                               group_size=w.group_size, bits=w.bits,
                               kind=w.kind)
    return check


def _exec(backend_name: str):
    def run(x, w, out_dtype):
        return _lg.bcq_apply(x, w, backend=backend_name, out_dtype=out_dtype)
    return run


register_backend(BackendInfo(
    name="dense", execute=_exec("dense"), supports=_supports_any,
    available=lambda: True, native=lambda: True,
    description="dequantize to f32 and matmul (FPE baseline, Table IV)"))

register_backend(BackendInfo(
    name="bcq_xla", execute=_exec("bcq_xla"), supports=_supports_any,
    available=lambda: True, native=lambda: True,
    description="pure-XLA packed execution (pjit-traceable everywhere)"))

register_backend(BackendInfo(
    name="bcq_xla_planes", execute=_exec("bcq_xla_planes"),
    supports=_supports_bcq_planes, available=lambda: True,
    native=lambda: False,
    description="per-plane grouped-contraction XLA variant"))

register_backend(BackendInfo(
    name="lut_pallas", execute=_exec("lut_pallas"),
    supports=_supports_pallas("lut_gemm"),
    available=lambda: True, native=_on_tpu, kernel="lut_gemm",
    description="paper-faithful FIGLUT Pallas kernel (interpret off-TPU)"))

register_backend(BackendInfo(
    name="mxu_pallas", execute=_exec("mxu_pallas"),
    supports=_supports_pallas("bcq_matmul"),
    available=lambda: True, native=_on_tpu, kernel="bcq_matmul",
    description="dequant-in-VMEM MXU Pallas kernel (interpret off-TPU)"))

register_backend(BackendInfo(
    name="ternary_pallas", execute=_exec("ternary_pallas"),
    supports=_supports_pallas("ternary_matmul"),
    available=lambda: True, native=_on_tpu, kernel="ternary_matmul",
    description="dedicated 1.58-bit kernel: in-kernel sign decode onto "
                "the half-LUT, single alpha row (interpret off-TPU)"))
