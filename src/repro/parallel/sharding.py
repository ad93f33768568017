"""Logical-axis sharding: rules -> NamedShardings with divisibility fallback.

Every parameter/cache descriptor carries logical axis names (models/module
docstring).  ``build_shardings`` maps them onto mesh axes via a rules
table, with two production-grade guards:

  * **divisibility fallback** — if a dim is not divisible by its mesh-axis
    extent (mixtral's 8 experts on model=16, GQA kv=8 heads, ...), the
    mapping is dropped for that dim and the next candidate dim may claim
    the axis instead.  This is why one rules table serves all ten
    architectures: EP when experts divide, expert-internal TP otherwise;
    kv-head sharding when it divides, head_dim sharding otherwise.
  * **axis-conflict resolution** — a PartitionSpec may not repeat a mesh
    axis; dims are processed left-to-right and later dims skip axes
    already claimed.

Rules values may be a single mesh axis, a tuple (sharded over several,
e.g. FSDP over ("pod", "data")), or None.

BCQWeight leaves (quantized params) derive field shardings from the
logical axes of the original [*, out, in] weight: packed/alpha/z inherit
the row axis; the packed input dim inherits the input axis when the
*packed* byte count still divides.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.bcq import BCQWeight

# logical axis -> mesh axis (or tuple of mesh axes, or None)
DEFAULT_RULES = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": "model",       # claimed only if heads axes fell through
    "mlp": "model",
    "experts": "model",        # EP when divisible, else falls to mlp-TP
    "embed": None,
    "lora": None,
    "batch": "data",
    "layers": None,
    "state": None,
    "kv_seq": "model",          # sequence-sharded KV when heads can't shard
}


def make_rules(*, fsdp: bool = False, multi_pod: bool = False,
               act_shard: bool = False, extra: Optional[dict] = None) -> dict:
    rules = dict(DEFAULT_RULES)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    rules["batch"] = data_axes
    if fsdp:
        rules["embed"] = data_axes      # 2-D weight sharding: TP x FSDP
    if act_shard:
        # shard the remat stash's embed dim over the model axis (training):
        # 60.5 -> 8.5 GiB/device on mamba2 train_4k
        rules["act_embed"] = "model"
    if extra:
        rules.update(extra)
    return rules


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(shape, axes, mesh: Mesh, rules: dict) -> P:
    """PartitionSpec for one array given its logical axes."""
    sizes = _axis_sizes(mesh)
    used = set()
    parts = []
    axes = axes or (None,) * len(shape)
    for dim, ax in zip(shape, axes):
        target = rules.get(ax) if ax is not None else None
        if target is None:
            parts.append(None)
            continue
        tup = (target,) if isinstance(target, str) else tuple(target)
        tup = tuple(a for a in tup if a in sizes and a not in used)
        total = int(np.prod([sizes[a] for a in tup])) if tup else 1
        if not tup or dim % total != 0:
            parts.append(None)          # divisibility fallback: replicate
            continue
        used.update(tup)
        parts.append(tup if len(tup) > 1 else tup[0])
    while parts and parts[-1] is None:
        parts.pop()                      # trailing Nones are implicit
    return P(*parts)


def _bcq_shardings(leaf: BCQWeight, axes, mesh: Mesh, rules: dict):
    """Shardings for a quantized weight's packed/alpha/z fields.

    General form: the original weight's logical axes are
    (*lead_batch, row_ax, in_ax) where lead_batch covers any stacked
    layers/experts dims kept as quantization batch dims; the packed
    planes insert a bits dim after the batch dims.
    """
    axes = tuple(axes) if axes else ()
    nb = leaf.packed.ndim - 3           # leading batch dims on the fields
    lead = axes[:nb] if len(axes) >= nb + 2 else (None,) * nb
    row_ax = axes[-2] if len(axes) >= 2 else None
    in_ax = axes[-1] if len(axes) >= 1 else None
    packed_axes = (*lead, None, row_ax, in_ax)
    alpha_axes = (*lead, None, row_ax, None)
    z_axes = (*lead, row_ax, None)
    return BCQWeight(
        packed=NamedSharding(mesh, spec_for(leaf.packed.shape, packed_axes,
                                            mesh, rules)),
        alpha=NamedSharding(mesh, spec_for(leaf.alpha.shape, alpha_axes,
                                           mesh, rules)),
        z=(NamedSharding(mesh, spec_for(leaf.z.shape, z_axes, mesh, rules))
           if leaf.z is not None else None),
        group_size=leaf.group_size, in_features=leaf.in_features,
        out_features=leaf.out_features, kind=leaf.kind,
    )


def _walk(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk(v, path + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk(v, path + (i,))
    else:
        yield path, tree


def _get(tree, path, default=None):
    node = tree
    try:
        for p in path:
            node = node[p]
        return node
    except (KeyError, IndexError, TypeError):
        return default


def _set(tree, path, value):
    if not path:
        return value
    head, rest = path[0], path[1:]
    if isinstance(tree, dict):
        out = dict(tree)
        out[head] = _set(tree[head], rest, value)
        return out
    out = list(tree)
    out[head] = _set(tree[head], rest, value)
    return type(tree)(out) if isinstance(tree, tuple) else out


def build_shardings(mesh: Mesh, tree, axes_tree, rules: dict):
    """NamedSharding pytree matching ``tree`` (params, opt state or cache).

    ``tree`` leaves: arrays / ShapeDtypeStructs / BCQWeight bundles.
    ``axes_tree`` leaves: logical-axes tuples at the same paths (BCQWeight
    paths resolve to the original dense weight's axes).
    """
    out = tree
    for path, leaf in list(_walk(tree)):
        if leaf is None:
            continue
        axes = _get(axes_tree, path)
        if isinstance(leaf, BCQWeight):
            out = _set(out, path, _bcq_shardings(leaf, axes, mesh, rules))
        elif hasattr(leaf, "shape"):
            spec = spec_for(leaf.shape, axes, mesh, rules)
            out = _set(out, path, NamedSharding(mesh, spec))
    return out


def batch_shardings(mesh: Mesh, specs: dict, rules: dict) -> dict:
    """Shardings for an input batch: leading dim = batch, rest replicated."""
    out = {}
    for k, v in specs.items():
        axes = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = NamedSharding(mesh, spec_for(v.shape, axes, mesh, rules))
    return out


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# activation sharding constraints
# ---------------------------------------------------------------------------
# GSPMD propagation alone picks catastrophically bad layouts at a few key
# points (e.g. replicating [B, S, V] logits instead of sharding the vocab —
# a 26 GiB/device difference at train_4k scale).  Model code calls
# ``shard_act(x, logical_axes)``; launchers opt in via
# ``set_activation_rules(mesh, rules)``.  Without a registered mesh it is a
# no-op, so single-device tests/examples are unaffected.

_ACT: dict = {"mesh": None, "rules": None}


def set_activation_rules(mesh: Optional[Mesh], rules: Optional[dict]):
    _ACT["mesh"] = mesh
    _ACT["rules"] = rules


def shard_act(x, axes):
    mesh, rules = _ACT["mesh"], _ACT["rules"]
    if mesh is None or rules is None:
        return x
    spec = spec_for(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
