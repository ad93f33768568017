"""Each plain reference against the program, at tiny sizes on the CPU.

The program runs its own full-sequence forward (``Model.forward``, its
backends and kernels as they resolve off-TPU) on weights the benchmark
drew; the reference rebuilds the same bundles itself in float32.  The
program keeps bfloat16 activations between layers, so the two agree to
bf16 rounding, not exactly.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import harness, spec, weights
from bench.reference.common import Planes, dequantize, logit_stats

from conftest import tiny_config


def _program_and_reference(name, seed=3, b=2, t=24):
    from repro.models import Model
    c = tiny_config(name)
    cfg = harness.model_config(c)
    model = Model(cfg)
    params = weights.draw(model, cfg.quant, seed)
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, c["vocab_size"], (b, t)).astype(np.int32)
    tgts = rng.integers(0, c["vocab_size"], (b, t)).astype(np.int32)
    logits = np.asarray(model.forward(params, {"tokens": jnp.asarray(toks)}),
                        np.float32)
    best, at, top = logit_stats(spec.reference(c["reference"]),
                                weights.plain(params), c, toks, tgts)
    return logits, tgts, np.asarray(best), np.asarray(at), np.asarray(top)


@pytest.mark.parametrize("name", ["opt-6.7b-bcq3", "phi4-mini-bcq3"])
def test_reference_matches_program(name):
    logits, tgts, best, at, top = _program_and_reference(name)
    scale = np.abs(logits).max()
    want_at = np.take_along_axis(logits, tgts[..., None], -1)[..., 0]
    assert np.abs(best - logits.max(-1)).max() <= 0.03 * scale
    assert np.abs(at - want_at).max() <= 0.03 * scale
    assert (top == logits.argmax(-1)).mean() >= 0.9


def test_dequantize_rebuilds_the_planes():
    # one weight row of 16 inputs, 2 planes, group 8: hand-built bits
    bits = np.zeros((2, 1, 16), np.uint8)
    bits[0, 0, ::2] = 1                       # plane 0: + - + - ...
    bits[1, 0, :8] = 1                        # plane 1: first group +
    packed = (bits.reshape(2, 1, 2, 8) << np.arange(8, dtype=np.uint8)
              ).sum(-1).astype(np.uint8)
    alpha = np.array([[[1.0, 2.0]], [[0.5, 0.25]]], np.float32)
    z = np.array([[0.1, -0.1]], np.float32)
    w = dequantize(Planes(jnp.asarray(packed), jnp.asarray(alpha),
                          jnp.asarray(z), 8, 16))
    sign0 = np.where(bits[0, 0] == 1, 1.0, -1.0)
    sign1 = np.where(bits[1, 0] == 1, 1.0, -1.0)
    a0 = np.repeat([1.0, 2.0], 8)
    a1 = np.repeat([0.5, 0.25], 8)
    want = sign0 * a0 + sign1 * a1 + np.repeat([0.1, -0.1], 8)
    np.testing.assert_allclose(np.asarray(w)[0], want, rtol=1e-6)


def _planes(rng, lead, q, out, inp, g):
    """Random planes with leading axes ``lead``, and their dense weights
    rebuilt here by numpy alone."""
    nb = inp // 8
    packed = rng.integers(0, 256, lead + (q, out, nb)).astype(np.uint8)
    alpha = rng.uniform(0.1, 0.5, lead + (q, out, inp // g)).astype(
        np.float32)
    z = rng.normal(0, 0.05, lead + (out, inp // g)).astype(np.float32)
    bits = np.unpackbits(packed, axis=-1, bitorder="little")
    dense = ((2.0 * bits - 1.0) * np.repeat(alpha, g, -1)).sum(-3) \
        + np.repeat(z, g, -1)
    return Planes(jnp.asarray(packed), jnp.asarray(alpha), jnp.asarray(z),
                  g, inp), dense


class _ToyArch:
    """A reference module of a toy decoder: a dense layer is ``x + x W^T``,
    an MoE layer adds every expert's ``x W_e^T`` weighted by a softmax
    over its router."""

    @staticmethod
    def embed(w, tokens, cfg):
        return jnp.take(w["tok"], tokens, axis=0)

    @staticmethod
    def block(lw, x, cfg, mode):
        from bench.reference.common import linear
        if "router" in lw:
            gates = jax.nn.softmax(jnp.einsum("bti,ei->bte", x, lw["router"]))
            ys = jnp.einsum("bti,eoi->bteo", x, lw["experts"])
            return x + jnp.einsum("bte,bteo->bto", gates, ys)
        return x + linear(x, lw["w"], mode)

    @staticmethod
    def final(w, x, cfg):
        return x * w["final_norm"]["scale"], w["embed"]["tok"]


def test_reference_runs_a_dense_prefix_then_a_scanned_expert_group():
    rng = np.random.default_rng(11)
    d, v, e, layers, g = 16, 11, 3, 2, 8
    dense_w, dense_np = _planes(rng, (), 2, d, d, g)
    bank, bank_np = _planes(rng, (layers, e), 2, d, d, g)
    router = rng.normal(0, 0.3, (layers, e, d)).astype(np.float32)
    tok = rng.normal(0, 0.5, (v, d)).astype(np.float32)
    scale = rng.uniform(0.5, 1.5, d).astype(np.float32)
    w = {"embed": {"tok": jnp.asarray(tok)},
         "stack": {"prefix": [{"w": dense_w}],
                   "scan": [{"experts": bank,
                             "router": jnp.asarray(router)}]},
         "final_norm": {"scale": jnp.asarray(scale)}}
    toks = rng.integers(0, v, (2, 5)).astype(np.int32)
    tgts = rng.integers(0, v, (2, 5)).astype(np.int32)
    best, at, top = logit_stats(_ToyArch, w, {"vocab_size": v}, toks, tgts)

    # the plain loop: every layer in order, numpy float64
    x = tok[toks].astype(np.float64)
    x = x + x @ dense_np.T
    for i in range(layers):
        s = np.einsum("bti,ei->bte", x, router[i])
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        x = x + np.einsum("bte,bteo->bto", p,
                          np.einsum("bti,eoi->bteo", x, bank_np[i]))
    logits = (x * scale) @ tok.T
    np.testing.assert_allclose(np.asarray(best), logits.max(-1), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(at), np.take_along_axis(logits, tgts[..., None], -1)[..., 0],
        rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(top), logits.argmax(-1))


def test_reference_imports_nothing_of_the_program():
    import ast
    import os
    ref_dir = os.path.join(spec.BENCH_DIR, "reference")
    for f in os.listdir(ref_dir):
        if not f.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(ref_dir, f)).read())
        mods = [n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)]
        mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        assert not [m for m in mods if m.startswith("repro")], f
