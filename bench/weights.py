"""Weights drawn on the device from the seed, in the served format.

The shapes and dtypes come from the program's own format: the quantized
tree is ``jax.eval_shape`` of ``repro.quant.quantize_model`` over the
model's abstract parameters, so no solver runs and no dense model is
drawn.  One jitted call then fills every leaf on the device:

  * ``packed`` sign planes: uniform random bits (each weight's q signs
    are independent fair coins);
  * ``alpha`` per-plane scale rows: positive and halving plane by plane
    (``0.016 * 2^-i``, times a uniform factor in [0.75, 1.25]), the
    profile a BCQ solve of N(0, 0.02^2) weights gives;
  * ``z`` offsets: small, N(0, 0.002^2);
  * norm gains (every leaf the model declares with init ``"ones"``,
    whatever its name) near 1: N(1, 0.1^2);
  * every other leaf -- embeddings, biases, a router -- N(0, 0.02^2) in
    its stored dtype, random so that the check sees it.

The solver's output values change no work on the timed path; the
bundles' shapes, dtypes and bytes do, and those are the program's.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from bench.counts import Head, Linear


def seed_key(seed: int, salt: int = 0) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits:
    ``jax.random.PRNGKey`` would drop the high bits)."""
    a, b = np.random.SeedSequence([seed, salt]).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(a)), int(b))


def abstract_quantized(model, spec):
    """ShapeDtypeStruct tree of the served (quantized) parameters."""
    from repro.quant import quantize_model
    return jax.eval_shape(
        lambda p: quantize_model(p, spec, model.axes())[0], model.abstract())


def _name(path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "name", getattr(
        k, "idx", k)))) for k in path)


def norm_gains(model) -> frozenset:
    """Names of the leaves the model declares with init ``"ones"``."""
    from repro.models.module import is_desc
    flat = jax.tree_util.tree_leaves_with_path(model.desc(), is_leaf=is_desc)
    return frozenset(_name(p) for p, d in flat
                     if is_desc(d) and d.init == "ones")


def _draw_leaf(key, names, sds, gain: bool):
    leaf = names[-1]
    shape, dtype = sds.shape, sds.dtype
    if leaf == "packed":
        return jax.random.bits(key, shape, jnp.uint8)
    if leaf == "alpha":
        q = shape[-3]
        half = (0.5 ** jnp.arange(q, dtype=jnp.float32)).reshape(
            (q,) + (1,) * 2)
        u = jax.random.uniform(key, shape, jnp.float32, 0.75, 1.25)
        return (0.016 * half * u).astype(dtype)
    if leaf == "z":
        return (0.002 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if gain:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def draw(model, spec, seed: int):
    """Every leaf of the model's served tree drawn on the device in one
    jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        abstract_quantized(model, spec))
    gains = norm_gains(model)
    names = [_name(p) for p, _ in flat]
    sdss = [s for _, s in flat]

    def fill(key):
        keys = jax.random.split(key, len(sdss))
        return [_draw_leaf(k, n, s, n in gains)
                for k, n, s in zip(keys, names, sdss)]

    leaves = jax.jit(fill)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def is_bundle(x) -> bool:
    return hasattr(x, "packed") and hasattr(x, "alpha")


def _nbytes(a) -> int:
    return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize


def bundle_bytes(b) -> int:
    """Stored bytes of one bundle leaf (all layers)."""
    n = _nbytes(b.packed) + _nbytes(b.alpha)
    if b.z is not None:
        n += _nbytes(b.z)
    return n


def linears(params) -> List[Linear]:
    """Every quantized linear role of the stack, read from the live tree:
    a leaf under ``scan`` carries a leading layers axis, and any axis
    before a bundle's planes besides it is its experts.  A bundle under
    ``embed`` is the LM head (:func:`head`), not one of them."""
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            params, is_leaf=is_bundle):
        names = _name(path)
        if not is_bundle(leaf) or names[0] == "embed":
            continue
        lead = leaf.packed.shape[:-3]
        layers = int(lead[0]) if "scan" in names and lead else 1
        experts = int(np.prod(lead)) // layers
        out.append(Linear(name="/".join(names),
                          n=int(leaf.out_features), k=int(leaf.in_features),
                          stored_bytes=bundle_bytes(leaf) // (layers * experts),
                          layers=layers, experts=experts))
    return out


def plain(params):
    """The tree with every bundle turned into the reference's own
    :class:`~bench.reference.common.Planes` over the same arrays."""
    from bench.reference.common import Planes

    def conv(x):
        if is_bundle(x):
            if x.kind != "bcq":
                raise ValueError(f"no reference for {x.kind!r} bundles")
            return Planes(x.packed, x.alpha, x.z, int(x.group_size),
                          int(x.in_features))
        return x
    return jax.tree_util.tree_map(conv, params, is_leaf=is_bundle)


def total_bytes(params) -> int:
    return sum(_nbytes(a) for a in jax.tree_util.tree_leaves(params))


def head(params) -> Head:
    """The LM head of the live tree: the untied ``embed/unembed`` where
    there is one, else the tied token embedding."""
    emb = params["embed"]
    h = emb.get("unembed", emb["tok"])
    if is_bundle(h):
        return Head(int(h.out_features), bundle_bytes(h), True)
    return Head(int(h.shape[0]), _nbytes(h), False)
