"""Hypothesis property tests for the plane-bundle layout."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency (pip install -e .[dev]) — the suite "
           "must collect without it")
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.core import plane
from repro.quant import formats

_SET = dict(max_examples=25, deadline=None,
            suppress_health_check=[HealthCheck.too_slow])


@st.composite
def weight_matrices(draw):
    m = draw(st.integers(8, 48))
    n = draw(st.integers(16, 160))
    seed = draw(st.integers(0, 2**16))
    scale = draw(st.floats(0.01, 10.0))
    rng = np.random.default_rng(seed)
    return jnp.array((rng.normal(size=(m, n)) * scale).astype(np.float32))


def _bundle(fmt_name, W, group_size):
    fmt = formats.get_format(fmt_name)
    bits = fmt.fixed_plane_bits or 3
    return fmt.quantize(W, bits=bits, group_size=group_size, iters=1)


@given(weight_matrices(), st.sampled_from(["bcq", "rtn", "ternary"]),
       st.sampled_from([16, 32, 64]))
@settings(**_SET)
def test_repack_unpack_identity(W, fmt_name, group_size):
    """pack(unpack(planes)) is the identity for every format/group size
    — the bit-plane layout survives a round trip untouched."""
    wq = _bundle(fmt_name, W, group_size)
    planes = plane.unpack_planes(wq.packed)
    repacked = plane.pack_planes(planes)
    np.testing.assert_array_equal(np.asarray(repacked),
                                  np.asarray(wq.packed))
    # unpacked planes are ±1 by contract (bit 1 encodes +1)
    assert set(np.unique(np.asarray(planes))) <= {-1, 1}


@given(weight_matrices(), st.sampled_from([16, 32, 64]))
@settings(**_SET)
def test_ternary_dequant_is_three_valued(W, group_size):
    """Ternary bundles decode to exactly {-a, 0, +a} per group row."""
    wq = _bundle("ternary", W, group_size)
    assert wq.kind == "ternary" and wq.z is None
    assert wq.alpha.shape[0] == 1
    dense = np.asarray(plane.dequantize(wq))
    a = np.repeat(np.asarray(wq.alpha[0]), group_size,
                  axis=-1)[:, :W.shape[1]]
    ratio = np.where(a > 0, dense / np.maximum(a, 1e-30), 0.0)
    assert np.all(np.isin(np.round(ratio).astype(int), [-1, 0, 1]))
    np.testing.assert_allclose(ratio, np.round(ratio), atol=1e-6)


@given(weight_matrices(), st.sampled_from(["bcq", "rtn", "ternary"]))
@settings(**_SET)
def test_bundle_survives_flatten_unflatten(W, fmt_name):
    """PlaneBundle is a pytree: jit/scan/sharding all flatten it, and
    the static metadata (kind included) must ride the treedef."""
    import jax

    wq = _bundle(fmt_name, W, 32)
    leaves, treedef = jax.tree_util.tree_flatten(wq)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.kind == wq.kind
    assert back.group_size == wq.group_size
    assert (back.z is None) == (wq.z is None)
    np.testing.assert_array_equal(np.asarray(back.packed),
                                  np.asarray(wq.packed))
