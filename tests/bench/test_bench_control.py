"""The control: the reference with float8 linears in the program's place
must fail the check that the program passes.

At the cells' widths this is read on the chip (``bench/tools/
calibrate.py``; the readings are in PERF.md).  Here the same comparison
runs at a size a test run holds: 2 layers of width 512 and a vocabulary
of 8192, where the program's widest gap reads under 0.01 and the
control's over 0.08 (CPU), so 0.03 separates them.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from bench import harness, spec, weights
from bench.reference.common import logit_stats
from conftest import tiny_config

LIMIT = 0.03


def _mid(name):
    c = tiny_config(name)
    c.update(hidden_size=512, word_embed_proj_dim=512, head_dim=128,
             num_attention_heads=4, vocab_size=8192)
    if "ffn_dim" in c:
        c["ffn_dim"] = 2048
    else:
        c.update(intermediate_size=1536, num_key_value_heads=2)
    return c


@pytest.mark.parametrize("name", ["opt-6.7b-bcq3", "phi4-mini-bcq3"])
def test_control_fails_where_program_passes(name):
    from repro.models import Model
    c = _mid(name)
    cfg = harness.model_config(c)
    model = Model(cfg)
    ref = spec.reference(c["reference"])
    params = weights.draw(model, cfg.quant, 5)
    toks = np.random.default_rng(5).integers(0, 8192, (2, 64)).astype(np.int32)
    logits = model.forward(params, {"tokens": jnp.asarray(toks)})
    served = np.asarray(logits).argmax(-1).astype(np.int32)
    w = weights.plain(params)
    best, at, _ = logit_stats(ref, w, c, toks, served)
    program_gap = float(np.max(np.asarray(best) - np.asarray(at)))
    _, _, top8 = logit_stats(ref, w, c, toks, served, "fp8")
    best, at8, _ = logit_stats(ref, w, c, toks, top8)
    control_gap = float(np.max(np.asarray(best) - np.asarray(at8)))
    assert program_gap <= LIMIT < control_gap, (program_gap, control_gap)
