"""Production mesh construction.

FUNCTIONS (never module-level constants) so importing this module never
touches jax device state — required because the dry-run pins the device
count via XLA_FLAGS before any jax initialization.

``make_mesh`` is the single construction point; every axis is Auto.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Mesh with an Auto axis type on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (single pod, 256 chips) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_parallel: int = 0):
    """Elastic helper: best (data, model) mesh for whatever devices exist.

    Used after a failure/re-scale event: the checkpoint is topology-
    agnostic, so training resumes on the largest divisor mesh.
    """
    if model_parallel <= 0:
        model_parallel = min(16, n_devices)
    while n_devices % model_parallel:
        model_parallel //= 2
    data = n_devices // model_parallel
    return make_mesh((data, model_parallel), ("data", "model"))


def parse_mesh(spec: str, tp: int = 0):
    """Build a serving mesh from a CLI flag.

    ``spec`` is either ``"auto"`` (largest ``(data, model)`` divisor mesh
    over whatever devices exist, with ``tp`` pinning the model axis) or
    an explicit ``"DxM"`` shape like ``"2x4"`` (data x model; must
    multiply to the visible device count).
    """
    if spec == "auto":
        n = len(jax.devices())
        if tp and n % tp:
            # make_mesh_for would silently halve tp down to a divisor —
            # an explicit request for a model-parallel extent must not
            # degrade to less (or no) TP without the operator noticing
            raise ValueError(f"--tp {tp} does not divide the {n} visible "
                             f"devices")
        return make_mesh_for(n, tp)
    try:
        data, model = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"--mesh expects 'auto' or 'DxM' (e.g. 2x4), "
                         f"got {spec!r}")
    if tp and tp != model:
        raise ValueError(f"--tp {tp} contradicts --mesh {spec} "
                         f"(model axis {model})")
    n = len(jax.devices())
    if data * model != n:
        raise ValueError(f"--mesh {spec} needs {data * model} devices, "
                         f"found {n} (hint: "
                         f"--xla_force_host_platform_device_count)")
    return make_mesh((data, model), ("data", "model"))
