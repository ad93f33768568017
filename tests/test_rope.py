"""YaRN rotary (DeepSeek-V2's ``rope_scaling``) against hand-computed
values; configurations without it are unchanged."""
import math

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.layers import (apply_rope, rope_attention_factor,
                                 rope_freqs, yarn_correction_range,
                                 yarn_mscale)

LITE = get_config("deepseek_v2_lite")


def test_yarn_frequencies_by_hand():
    # DeepSeek-V2-Lite: rope 64, theta 10000, factor 40 over 4096
    # positions, beta 32 / 1.  The ramp runs between the dimensions that
    # turn 32 and 1 times over 4096 positions:
    #   64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10
    #   64 ln(4096 / (1 * 2 pi)) / (2 ln 10000)  = 22.51 -> 23
    assert yarn_correction_range(32, 1, 64, 10000.0, 4096) == (10, 23)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    got = np.asarray(rope_freqs(64, 10000.0, LITE.yarn))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the first 11 frequencies kept, from the 24th on divided by 40
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)


def test_yarn_softmax_scale_by_hand():
    # mscale = 0.1 * 0.707 * ln 40 + 1 = 1.2608;  mscale^2 = 1.5896
    m = yarn_mscale(40, 0.707)
    assert abs(m - (0.1 * 0.707 * math.log(40) + 1)) < 1e-12
    assert round(m, 4) == 1.2608 and round(m * m, 4) == 1.5896
    # cos and sin carry mscale / mscale_all_dim: 1 when the two are equal
    assert rope_attention_factor(LITE.yarn) == 1.0
    assert yarn_mscale(1.0, 0.707) == 1.0


def test_without_yarn_rope_is_unchanged():
    """The plain rotary, written out: no config without rope scaling
    sees any change."""
    assert get_config("phi4_mini_3_8b").yarn is None
    assert get_config("deepseek_v2_236b").yarn is None
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16), jnp.float32)
    pos = jnp.arange(5)[None] + jnp.asarray([[0], [7]])
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, 16, 2, jnp.float32) / 16))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :8], x[..., 8:]
    want = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_array_equal(np.asarray(apply_rope(x, pos, 10000.0)),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(rope_freqs(16, 10000.0)),
                                  np.asarray(freqs))


def test_yarn_rotation_keeps_norms_and_differs_past_the_ramp():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 2, 64), jnp.float32)
    pos = jnp.asarray([[0, 100, 3000]])
    plain = apply_rope(x, pos, 10000.0)
    yarn = apply_rope(x, pos, 10000.0, LITE.yarn)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(yarn), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(yarn[:, 0]),
                                  np.asarray(x[:, 0]))   # position 0
    assert np.abs(np.asarray(yarn - plain)[:, 1:]).max() > 0.1
