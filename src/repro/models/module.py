"""Minimal functional module system: parameter descriptors -> params.

No flax/haiku on this box, and the dry-run must build parameter *shapes*
without allocating 236B-scale weights — so model definitions construct
trees of :class:`ParamDesc` (shape, dtype, logical axes, initializer), and
three interpreters consume them:

  * ``init_params``     — materialize real arrays (tests/examples/training)
  * ``abstract_params`` — ShapeDtypeStructs only (the dry-run path)
  * ``logical_axes``    — same-structure tree of logical-axis tuples, fed to
                          ``parallel.sharding.to_named_sharding``

Logical axis names used across the zoo:
  "embed"    — d_model            -> usually replicated (or fsdp)
  "vocab"    — vocabulary         -> model
  "heads"    — attention heads    -> model
  "kv_heads" — kv heads           -> model (with replication fallback)
  "head_dim" — per-head dim       -> None
  "mlp"      — ffn hidden         -> model
  "experts"  — MoE expert count   -> model (EP) / None
  "layers"   — stacked-scan layer -> None
  "lora"     — MLA latent dim     -> None
  "state"    — SSM state dim      -> None
  "fsdp"     — weight-sharded dp  -> data (when fsdp enabled)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamDesc:
    """Declarative parameter: everything needed to init/shard/abstract it."""
    shape: tuple
    dtype: Any = jnp.float32
    axes: tuple = ()                 # logical axes, len == len(shape)
    init: str = "normal"             # normal | zeros | ones | embed | scan_normal
    scale: float = 0.02

    def __post_init__(self):
        if self.axes and len(self.axes) != len(self.shape):
            raise ValueError(f"axes {self.axes} rank != shape {self.shape}")


def is_desc(x) -> bool:
    return isinstance(x, ParamDesc)


def _draw(key, shape, d: ParamDesc) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * d.scale
            ).astype(d.dtype)


@functools.partial(jax.jit, static_argnums=1)
def _draw_stacked(key, d: ParamDesc) -> jax.Array:
    keys = jax.random.split(key, d.shape[0])
    return jax.lax.map(lambda k: _draw(k, d.shape[1:], d), keys)


def init_leaf(key, d: ParamDesc) -> jax.Array:
    """Materialize one descriptor.  Scan-stacked leaves (leading
    ``"layers"`` axis) are drawn one layer at a time from per-layer keys,
    so no full-stack float32 buffer ever exists."""
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init not in ("normal", "embed", "scan_normal"):
        raise ValueError(f"unknown init {d.init}")
    if d.axes and d.axes[0] == "layers" and len(d.shape) > 2:
        return _draw_stacked(key, d)
    return _draw(key, d.shape, d)


def init_params(rng: jax.Array, tree) -> Any:
    """Materialize a descriptor tree into real arrays (one key per leaf,
    in flatten order — ``quant.init_quantized`` draws the same)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_desc)
    keys = jax.random.split(rng, len(leaves))
    out = [init_leaf(k, d) if is_desc(d) else d for k, d in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(tree) -> Any:
    """ShapeDtypeStruct tree — zero allocation (dry-run path)."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype) if is_desc(d) else d,
        tree, is_leaf=is_desc)


def logical_axes(tree) -> Any:
    """Tree of logical-axis tuples matching the descriptor tree."""
    return jax.tree_util.tree_map(
        lambda d: d.axes if is_desc(d) else None, tree, is_leaf=is_desc)


def param_count(tree) -> int:
    total = 0
    for d in jax.tree_util.tree_leaves(tree, is_leaf=is_desc):
        if is_desc(d):
            total += int(np.prod(d.shape))
    return total


def param_bytes(tree) -> int:
    total = 0
    for d in jax.tree_util.tree_leaves(tree, is_leaf=is_desc):
        if is_desc(d):
            total += int(np.prod(d.shape)) * jnp.dtype(d.dtype).itemsize
    return total


def tree_map_with_path(fn, tree, path=()):
    """tree_map over dict/list/tuple trees, calling ``fn(path, leaf)``
    with the tuple of keys/indices leading to each leaf."""
    if isinstance(tree, dict):
        return {k: tree_map_with_path(fn, v, path + (k,))
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(tree_map_with_path(fn, v, path + (i,))
                          for i, v in enumerate(tree))
    return fn(path, tree)
