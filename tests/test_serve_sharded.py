"""Sharded paged serving: token-for-token equivalence of the mesh
engine against the single-device engine, KV-pool sharding placement, and
the capability negotiation that routes mesh-indivisible head counts to
the gathered path.

Subprocess harness per ``tests/test_sharding.py``: each case runs in a
clean interpreter with 8 fake CPU devices (the device count must be
pinned before jax initializes) and reports JSON on stdout.  The fused
kernel runs under the Pallas interpreter inside ``shard_map`` — slow but
bit-exact, which is the point: greedy decode over a (2, 4) TP/DP mesh
must match the unsharded engine token for token.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, prelude: str = "") -> dict:
    """Run code under 8 fake devices in a clean interpreter; returns JSON.

    ``code`` is dedented BEFORE the (unindented) prelude is prepended —
    mixing the two indentation levels would defeat textwrap.dedent."""
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + prelude + textwrap.dedent(code))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=570,
                       env={**os.environ, "PYTHONPATH": SRC,
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


_COMMON = """
import jax, json
import jax.numpy as jnp
import numpy as np
from repro.configs import get_reduced
from repro.models import Model
from repro.serve import PagedServeEngine, Request
from repro.launch.mesh import make_mesh

def f32(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params)

def requests(cfg, lens=(5, 11, 3, 17), max_new=5):
    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, (int(l),)),
                    max_new_tokens=max_new)
            for i, l in enumerate(lens)]

def tokens_of(done):
    return {str(r.uid): r.out_tokens for r in done}

def first_attn_leaf(cache, key):
    stack = cache.get("layers") or cache.get("prefix") or cache["scan"]
    return stack[0]["self"][key]

KW = dict(num_blocks=24, block_size=4, max_batch=4, max_seq_len=64,
          prefill_buckets=(8, 16))
"""


def test_sharded_matches_single_device_gqa_bcq():
    """Acceptance: on an 8-fake-device (2, 4) mesh, greedy paged decode
    of a GQA + BCQ-quantized model matches the single-device engine
    token-for-token in BOTH fused and gather kernel modes, and the KV
    pool leaves are actually sharded over the model axis."""
    out = run_sub("""
    from repro.quant import QuantSpec, quantize_model
    cfg = get_reduced("opt_6_7b").replace(
        remat=False, dtype="float32", n_heads=8, n_kv_heads=4, head_dim=16)
    model = Model(cfg)
    params = f32(model.init(jax.random.PRNGKey(0)))
    spec = QuantSpec(bits=3, group_size=32, iters=2, backend="bcq_xla")
    qparams, _ = quantize_model(params, spec, model.axes())
    qmodel = Model(cfg.replace(quant=spec))

    base = PagedServeEngine(qmodel, qparams, **KW)
    ref = tokens_of(base.run(requests(cfg)))
    base.pool.check()

    mesh = make_mesh((2, 4), ("data", "model"))
    res = {"ref_lens": sorted(len(v) for v in ref.values())}
    for mode in ("fused", "gather"):
        eng = PagedServeEngine(qmodel, qparams, mesh=mesh,
                               paged_kernel=mode, **KW)
        got = tokens_of(eng.run(requests(cfg)))
        eng.pool.check()
        k = first_attn_leaf(eng.cache, "k")
        res[mode] = {
            "equal": got == ref,
            "decode_path": eng.decode_path,
            "k_spec": str(k.sharding.spec),
            "k_shape": list(k.shape),
            "tokens_out": eng.metrics.counters["tokens_out"],
        }
    print(json.dumps(res))
    """, prelude=_COMMON)
    for mode in ("fused", "gather"):
        r = out[mode]
        assert r["equal"], f"{mode}: sharded tokens diverged from single"
        # kv_heads dim (index 2 of [NB, BS, Hkv, D]) carries the model axis
        assert r["k_spec"] == "PartitionSpec(None, None, 'model')", r
        assert r["tokens_out"] > 0
    assert out["fused"]["decode_path"] == "fused"
    assert out["gather"]["decode_path"] == "gather"


def test_sharded_pallas_linears_launch_per_shard():
    """A Pallas kernel cannot be partitioned by the compiler, so under a
    serving mesh each quantized linear launches per model shard
    (column-parallel, row-parallel + psum for attention-out / MLP-down).
    Greedy tokens match the single-device engine, and the per-shard path
    is the one traced."""
    out = run_sub("""
    from repro.quant import QuantSpec, quantize_model
    import repro.quant.backends as qb
    calls = []
    real = qb._sharded_kernel
    def spy(run, x, w, od, mesh, row_parallel):
        calls.append(row_parallel)
        return real(run, x, w, od, mesh, row_parallel)
    qb._sharded_kernel = spy
    cfg = get_reduced("opt_6_7b").replace(
        remat=False, dtype="float32", n_heads=8, n_kv_heads=4, head_dim=16)
    model = Model(cfg)
    params = f32(model.init(jax.random.PRNGKey(0)))
    spec = QuantSpec(bits=3, group_size=32, iters=2, backend="mxu_pallas")
    qparams, _ = quantize_model(params, spec, model.axes())
    qmodel = Model(cfg.replace(quant=spec))
    ref = tokens_of(PagedServeEngine(qmodel, qparams, **KW)
                    .run(requests(cfg, lens=(5, 11), max_new=3)))
    eng = PagedServeEngine(qmodel, qparams, paged_kernel="fused",
                           mesh=make_mesh((2, 4), ("data", "model")), **KW)
    got = tokens_of(eng.run(requests(cfg, lens=(5, 11), max_new=3)))
    print(json.dumps({"equal": got == ref, "launches": len(calls),
                      "row_parallel": sum(calls)}))
    """, prelude=_COMMON)
    assert out["equal"], "per-shard kernels diverged from single device"
    # decode + prefill traces x (q, k, v, up column; o, down row) x 2 layers
    assert out["launches"] >= 12 and out["row_parallel"] * 3 == \
        out["launches"]


def test_dense_sharded_with_preemption_pressure():
    """Dense params, pool small enough to preempt: the sharded engine's
    preempt-by-recompute must replay to the same tokens as the
    single-device engine (same scheduler, sharded decode)."""
    out = run_sub("""
    cfg = get_reduced("opt_6_7b").replace(
        remat=False, dtype="float32", n_heads=8, n_kv_heads=4, head_dim=16)
    model = Model(cfg)
    params = f32(model.init(jax.random.PRNGKey(0)))
    kw = dict(KW, num_blocks=10)          # 9 usable blocks: forces preempts
    lens = (9, 13, 6, 11)
    base = PagedServeEngine(model, params, **kw)
    ref = tokens_of(base.run(requests(cfg, lens=lens)))
    base.pool.check()
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = PagedServeEngine(model, params, mesh=mesh, paged_kernel="fused",
                           **kw)
    got = tokens_of(eng.run(requests(cfg, lens=lens)))
    eng.pool.check()
    print(json.dumps({"equal": got == ref,
                      "preempted": eng.metrics.counters["preempted"],
                      "path": eng.decode_path}))
    """, prelude=_COMMON)
    assert out["equal"]
    assert out["path"] == "fused"


@pytest.mark.slow
def test_narrow_gqa_falls_back_to_head_dim_and_gather():
    """kv_heads=2 cannot divide tp=4: the pool must shard head_dim over
    the model axis instead (divisibility fallback), the fused kernel
    must NOT be selected even when forced (capability negotiation), and
    tokens still match the single-device engine."""
    out = run_sub("""
    cfg = get_reduced("opt_6_7b").replace(
        remat=False, dtype="float32", n_heads=8, n_kv_heads=2, head_dim=16)
    model = Model(cfg)
    params = f32(model.init(jax.random.PRNGKey(0)))
    base = PagedServeEngine(model, params, **KW)
    ref = tokens_of(base.run(requests(cfg)))
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = PagedServeEngine(model, params, mesh=mesh, paged_kernel="fused",
                           **KW)
    got = tokens_of(eng.run(requests(cfg)))
    eng.pool.check()
    k = first_attn_leaf(eng.cache, "k")
    print(json.dumps({"equal": got == ref, "path": eng.decode_path,
                      "k_spec": str(k.sharding.spec)}))
    """, prelude=_COMMON)
    assert out["equal"]
    assert out["path"] == "gather"        # forced fused still negotiates down
    assert out["k_spec"] == "PartitionSpec(None, None, None, 'model')"


@pytest.mark.slow
def test_sharded_scan_stacked_layers():
    """Scan-stacked layer caches carry a leading layers axis on every
    pool leaf; sharding must land on kv_heads one position later."""
    out = run_sub("""
    cfg = get_reduced("opt_6_7b").replace(
        remat=False, dtype="float32", n_heads=8, n_kv_heads=4, head_dim=16,
        scan_layers=True)
    model = Model(cfg)
    params = f32(model.init(jax.random.PRNGKey(0)))
    base = PagedServeEngine(model, params, **KW)
    ref = tokens_of(base.run(requests(cfg)))
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = PagedServeEngine(model, params, mesh=mesh, paged_kernel="fused",
                           **KW)
    got = tokens_of(eng.run(requests(cfg)))
    k = first_attn_leaf(eng.cache, "k")
    print(json.dumps({"equal": got == ref, "path": eng.decode_path,
                      "k_spec": str(k.sharding.spec)}))
    """, prelude=_COMMON)
    assert out["equal"]
    assert out["path"] == "fused"
    assert out["k_spec"] == "PartitionSpec(None, None, None, 'model')"


def test_sharded_async_engine_matches_sync():
    """The double-buffered async tick composes with the mesh engine: the
    on-device sampler (per-row PRNG keys threaded through the sharded
    ``decode_and_sample`` jit) must reproduce the sync engine's host
    sampling token-for-token over the (2, 4) mesh, for greedy AND
    seeded temperature/top-k rows, and overlap more device time."""
    out = run_sub("""
    cfg = get_reduced("opt_6_7b").replace(
        remat=False, dtype="float32", n_heads=8, n_kv_heads=4, head_dim=16)
    model = Model(cfg)
    params = f32(model.init(jax.random.PRNGKey(0)))

    def sampled():
        rs = requests(cfg)
        for r in rs[1::2]:
            r.temperature, r.top_k, r.seed = 0.7, 8, 99 + r.uid
        return rs

    mesh = make_mesh((2, 4), ("data", "model"))
    ref_eng = PagedServeEngine(model, params, mesh=mesh,
                               paged_kernel="fused", **KW)
    ref = tokens_of(ref_eng.run(sampled()))
    ref_eng.pool.check()
    eng = PagedServeEngine(model, params, mesh=mesh, paged_kernel="fused",
                           **KW)
    got = tokens_of(eng.run_async(sampled()))
    eng.pool.check()
    print(json.dumps({
        "equal": got == ref,
        "path": eng.decode_path,
        "busy_async": eng.metrics.decode_inflight_fraction(),
        "busy_sync": ref_eng.metrics.decode_inflight_fraction(),
        "pool_free": eng.pool.free_blocks == eng.pool.capacity,
    }))
    """, prelude=_COMMON)
    assert out["equal"], "sharded async tokens diverged from sync"
    assert out["path"] == "fused"
    assert out["pool_free"]
    assert out["busy_async"] > out["busy_sync"], out


def test_sharded_prefix_cache_matches_single_device_off():
    """Prefix sharing is mesh-transparent: block tables (and the prefix
    index) are replicated host state, so the sharded engine with the
    cache ON must match the single-device engine with the cache OFF
    token-for-token on a shared-prefix stream — while actually hitting
    (adopted blocks are read by every model shard through the same
    replicated table)."""
    out = run_sub("""
    cfg = get_reduced("opt_6_7b").replace(
        remat=False, dtype="float32", n_heads=8, n_kv_heads=4, head_dim=16)
    model = Model(cfg)
    params = f32(model.init(jax.random.PRNGKey(0)))

    def shared(cfg, base_uid=0, max_new=4):
        rng = np.random.default_rng(21)
        prefix = rng.integers(0, cfg.vocab_size, (12,))
        tails = [3, 6, 2, 5]
        return [Request(uid=base_uid + i,
                        prompt=np.concatenate(
                            [prefix, rng.integers(0, cfg.vocab_size,
                                                  (int(t),))]),
                        max_new_tokens=max_new)
                for i, t in enumerate(tails)]

    base = PagedServeEngine(model, params, **KW)
    ref = tokens_of(base.run(shared(cfg)))
    base.pool.check()

    # wave 1 (all admit cold, registering the prefix) then wave 2 (same
    # prompts, fresh uids) through the SAME sharded engine: wave 2 must
    # hit the warm index and still match the cold single-device run
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = PagedServeEngine(model, params, mesh=mesh, paged_kernel="fused",
                           prefix_cache=True, **KW)
    eng.run(shared(cfg))
    got = tokens_of(eng.run(shared(cfg, base_uid=10)))
    eng.pool.check()
    s = eng.metrics.summary()
    eng.prefix.clear()
    want = {}
    for uid, toks in ref.items():
        want[uid] = toks
        want[str(int(uid) + 10)] = toks
    print(json.dumps({
        "equal": got == want,
        "path": eng.decode_path,
        "hit_blocks": s["counters"]["prefix_hit_blocks"],
        "hit_rate": s["prefix_cache"]["hit_rate"],
        "pool_free_after_clear":
            eng.pool.free_blocks == eng.pool.capacity,
    }))
    """, prelude=_COMMON)
    assert out["equal"], "sharded prefix-cache run diverged from baseline"
    assert out["path"] == "fused"
    assert out["hit_blocks"] > 0 and out["hit_rate"] > 0
    assert out["pool_free_after_clear"]
