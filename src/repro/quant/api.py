"""The public quantization entry point: ``quantize_model(params, spec)``.

Ties the pieces together:

  1. resolve the per-layer bit plan from the spec — a uniform integer,
     or (fractional ``bits`` like ``2.4``) a sensitivity-driven mixed-
     precision allocation via :func:`repro.core.mixed_precision.
     allocate_bits` over every quantizable linear (paper Fig. 17), plus
     explicit per-layer ``spec.overrides`` pins applied last;
  2. quantize the tree through the format registry
     (:mod:`repro.quant.formats`) with the scan/expert stacking rules of
     :mod:`repro.quant.ptq`;
  3. return the quantized tree *and* a :class:`QuantManifest` — per-layer
     format/plane-bits/bytes plus achieved parameter-weighted average
     bits — which the launcher prints, CI uploads, and the quantized
     checkpoint embeds.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Any, Mapping, Optional, Tuple

import numpy as np

from repro.core import mixed_precision as mp
from repro.core.bcq import BCQWeight
from repro.quant import formats as formats_mod
from repro.quant.spec import QuantSpec


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QuantManifest:
    """What actually got quantized, layer by layer."""

    spec: dict
    layers: list                      # [{path, format, plane_bits, ...}]
    n_layers: int = 0
    n_weights: int = 0                # scalar weights quantized
    dense_bytes: int = 0              # bf16 baseline footprint
    quant_bytes: int = 0              # packed planes + scales
    avg_plane_bits: float = 0.0       # parameter-weighted stored planes
    avg_effective_bits: float = 0.0   # quant_bytes * 8 / n_weights

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def from_dict(cls, d: Mapping) -> "QuantManifest":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def summary(self) -> str:
        comp = (self.dense_bytes / self.quant_bytes
                if self.quant_bytes else float("inf"))
        return (f"{self.n_layers} layers / {self.n_weights:,} weights "
                f"quantized: avg {self.avg_plane_bits:.2f} plane-bits "
                f"({self.avg_effective_bits:.2f} stored bits/weight incl. "
                f"scales), {self.quant_bytes/2**20:.1f} MiB vs "
                f"{self.dense_bytes/2**20:.1f} MiB bf16 ({comp:.1f}x)")


# ---------------------------------------------------------------------------
# bit planning
# ---------------------------------------------------------------------------


def plan_bits(linears: Mapping[str, Any], spec: QuantSpec,
              x_cal: Optional[Mapping[str, Any]] = None) -> dict:
    """Per-layer bit plan for a spec: uniform, or mixed for fractional bits.

    Stacked leaves ([L, out, in] / [E, f, d]) are handled by the
    sensitivity probe directly (it flattens and row-subsamples); sizes
    stay parameter-weighted over the full leaves.
    """
    fmt = formats_mod.get_format(spec.format)
    unknown = [k for k in spec.overrides_map if k not in linears]
    if unknown:
        raise ValueError(
            f"spec.overrides name layers that are not quantizable linears: "
            f"{unknown}; known layers: {sorted(linears)}")
    if fmt.fixed_plane_bits is not None:
        if spec.overrides:
            raise ValueError(
                f"format {spec.format!r} stores a fixed "
                f"{fmt.fixed_plane_bits} planes per layer; per-layer bit "
                "overrides are not supported")
        return {k: fmt.fixed_plane_bits for k in linears}
    if spec.bits < 1:
        raise ValueError(
            f"spec.bits={spec.bits:g}: need >= 1 bit to quantize "
            "(an unquantized model shouldn't call quantize_model)")

    if spec.is_fractional:
        # probe with the format that will actually be applied — BCQ's
        # reconstruction error misranks layers for rtn/other formats,
        # and sub-2-bit candidates (the ternary sentinel) must be
        # measured with the ternary quantizer
        def _probe_quantize(w2, *, bits, group_size, iters):
            f = formats_mod.format_for_bits(spec.format, bits)
            return f.quantize(w2, bits=f.plane_bits(max(bits, 1)),
                              group_size=group_size, iters=iters)
        sens = functools.partial(mp.layer_sensitivity, iters=2, max_rows=192,
                                 quantizer=_probe_quantize)
        plan = mp.allocate_bits(linears, target_avg_bits=spec.bits,
                                candidates=spec.candidate_bits,
                                group_size=spec.group_size, x_cal=x_cal,
                                sensitivity_fn=sens)
    else:
        plan = {k: spec.int_bits for k in linears}

    for key, b in spec.overrides_map.items():
        if key in plan:
            plan[key] = float(b) if float(b) < 2 else int(b)
    return plan


# ---------------------------------------------------------------------------
# quantize_model
# ---------------------------------------------------------------------------


def quantize_model(params, spec: QuantSpec, axes_tree=None, *,
                   x_cal: Optional[Mapping[str, Any]] = None,
                   ) -> Tuple[Any, QuantManifest]:
    """Quantize every eligible linear of ``params`` per ``spec``.

    Returns ``(quantized_params, manifest)``.  ``axes_tree``
    (``Model.axes()``) enables scan-stack detection; ``x_cal`` optionally
    supplies per-layer calibration activations for the mixed-precision
    sensitivity probe.
    """
    from repro.quant import ptq  # lazy: ptq uses the format registry

    fmt = formats_mod.get_format(spec.format)
    linears = ptq.collect_linears(params, axes_tree)
    plan = plan_bits(linears, spec, x_cal=x_cal)

    qparams = ptq.quantize_model(
        params, axes_tree, bits=fmt.plane_bits(max(spec.bits, 1)),
        method=spec.format, group_size=spec.group_size, iters=spec.iters,
        bit_map=plan)

    manifest = build_manifest(qparams, spec, plan, linears,
                              axes_tree=axes_tree)
    return qparams, manifest


def init_quantized(desc_tree, spec: QuantSpec, axes_tree, rng,
                   ) -> Tuple[Any, QuantManifest]:
    """Draw random weights from ``rng`` and quantize them leaf by leaf.

    Returns what ``quantize_model(init_params(rng, desc_tree), spec,
    axes_tree)`` returns — the same per-leaf keys, so the same values —
    but each quantizable leaf is drawn, quantized and dropped before the
    next one is drawn: the dense model never coexists with its bundles,
    and a stacked leaf is cast to float32 one layer at a time.
    Fractional (mixed-precision) specs rank every dense linear at once,
    so they take the two-step path.
    """
    import jax

    from repro.models.module import init_leaf, init_params, is_desc
    from repro.quant import ptq  # lazy: ptq uses the format registry

    if spec.is_fractional:
        return quantize_model(init_params(rng, desc_tree), spec, axes_tree)
    flat, treedef = jax.tree_util.tree_flatten_with_path(desc_tree,
                                                         is_leaf=is_desc)
    keys = jax.random.split(rng, len(flat))        # = init_params' keys
    slots = []                                     # (path, desc, quantize?)
    linears = {}
    for kp, d in flat:
        path = tuple(getattr(k, "key", getattr(k, "idx", None)) for k in kp)
        quant = is_desc(d) and ptq._is_quant_leaf(
            path, jax.ShapeDtypeStruct(d.shape, d.dtype),
            ptq._axes_of(axes_tree, path))
        if quant:
            linears["/".join(map(str, path))] = \
                jax.ShapeDtypeStruct(d.shape, d.dtype)
        slots.append((path, d, quant))
    plan = plan_bits(linears, spec)

    out = []
    for (path, d, quant), key in zip(slots, keys):
        if not is_desc(d):
            out.append(d)
        elif quant:
            out.append(ptq._quantize_leaf(
                init_leaf(key, d), ptq._axes_of(axes_tree, path),
                plan["/".join(map(str, path))], spec.format,
                spec.group_size, spec.iters))
        else:
            out.append(init_leaf(key, d))
    qparams = jax.tree_util.tree_unflatten(treedef, out)
    return qparams, build_manifest(qparams, spec, plan, linears,
                                   axes_tree=axes_tree)


def build_manifest(qparams, spec: QuantSpec, plan: Mapping[str, int],
                   linears: Mapping[str, Any], axes_tree=None) -> QuantManifest:
    from repro.quant import ptq  # lazy: ptq uses the format registry

    quantized = {"/".join(map(str, p)): leaf
                 for p, leaf in ptq._walk(qparams)
                 if isinstance(leaf, BCQWeight)}
    layers, n_weights, dense_bytes, quant_bytes, plane_acc = [], 0, 0, 0, 0.0
    for key in sorted(quantized):
        wq = quantized[key]
        # packed is [*lead, q, rows, in/8]; the plane axis is always -3
        planes = int(wq.packed.shape[-3])
        shape = tuple(int(s) for s in np.shape(linears[key])) \
            if key in linears else None
        n = int(np.prod(shape)) if shape else \
            int(np.prod(wq.packed.shape[:-3])) * wq.out_features * wq.in_features
        # nbytes() reads the bundle that was actually stored — for
        # ternary that is sign+mask planes, ONE alpha row and no offset,
        # so the manifest no longer overstates ternary model size
        qb = int(wq.nbytes())
        layers.append({
            "path": key,
            "format": "ternary" if wq.kind == "ternary" else spec.format,
            "plane_bits": planes,
            # information-theoretic width (ternary stores 2 planes but
            # carries log2(3) bits); == plane_bits for dense-coded formats
            "effective_bits": float(wq.effective_bits),
            "group_size": int(wq.group_size),
            "shape": list(shape) if shape else None,
            "dense_bytes": 2 * n, "quant_bytes": qb,
        })
        n_weights += n
        dense_bytes += 2 * n
        quant_bytes += qb
        plane_acc += planes * n
    avg_plane = plane_acc / n_weights if n_weights else 0.0
    return QuantManifest(
        spec=spec.to_dict(), layers=layers, n_layers=len(layers),
        n_weights=n_weights, dense_bytes=dense_bytes,
        quant_bytes=quant_bytes, avg_plane_bits=avg_plane,
        avg_effective_bits=(quant_bytes * 8 / n_weights) if n_weights else 0.0)
