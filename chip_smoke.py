"""Chip smoke: serve OPT-6.7B in 3-bit BCQ on one TPU through the normal
entry points, and check what comes out.

    python chip_smoke.py                # one chip (what CI on the chip runs)
    python chip_smoke.py --four-chips   # only the TP=4 sharded-serving check
    python chip_smoke.py --arch deepseek_v2_lite   # another registered arch

One process; the model is built by the launcher's own
``repro.launch.serve.build_model`` at OPT-6.7B's published width (32
layers, d_model 4096, 32 heads, d_ff 16384, vocab 50272) with random
weights from ``--seed``, quantized leaf by leaf to 3-bit BCQ at group
size 128.  Phases, in order (any failure exits non-zero, and the result
line is printed only when every phase passed):

  device     the first device is a TPU;
  kernels    ``lut_gemm`` and ``bcq_matmul`` (compiled, not interpreted)
             on layer 0's bundles of OPT's four linears at decode batch
             8, and ``ternary_matmul`` on a ternary bundle of the
             attention shape, each against its ``ref.py`` oracle;
  serve      the paged engine (prefix cache on, ``paged_kernel=auto``)
             answers 6 greedy requests (prompts of 16..256 tokens, 16 new
             tokens each) once with the default backend preference and
             once with ``lut_pallas``: every request completes, decode
             and prefill run the fused paged kernels, and every quantized
             linear resolved to a Pallas backend (the trace holds no
             ``backend_fallback``);
  reference  the logits of one prefill and a few decode steps against
             the same bundles run through the XLA dequant path (``dense``
             backend, f32 weights, ``highest`` matmul precision, gathered
             paged attention).

``--arch`` serves another registered configuration at its published
width the same way.  For a mixture of experts (DeepSeek-V2-Lite) the
kernel phase also runs the grouped expert kernel on the first MoE
layer's gate bank against its oracle; a latent-attention (MLA) model
prefills on the gathered view (the fused prefill kernel does not
decompress latents), so only its decode must run fused.

``--four-chips`` runs only the sharded check: the same model on a 1x4
``("data", "model")`` mesh against ``devices[0]``, compared on logits,
with the KV pool sharded over ``model``.

Every kernel runs its heuristic launch (``REPRO_TUNE=off``: the tune
cache lives outside the checkout).  Times printed on the way are
informational (compile included), not metrics.  The last line of
standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# decode batch of the kernel phase, and the serve/reference geometry
DECODE_BATCH = 8
N_REQUESTS, MAX_NEW = 6, 16
PROMPT_MIN, PROMPT_MAX, SHARED_PREFIX = 16, 256, 64
BLOCK_SIZE, NUM_BLOCKS, MAX_BATCH, MAX_SEQ = 16, 192, 8, 512
PREFILL_BUCKETS = (64,)
PROBE_PROMPT, PROBE_STEPS = 64, 4

# Tolerances, as max |got - want| / max |want|.
#  * kernels: both sides compute in f32 from the same bf16 activations
#    and the same bundles; only summation order differs.
#  * logits: activations are bf16 between layers on both paths, so a
#    one-ulp rounding difference (2^-9 relative) anywhere in 32 layers
#    propagates; random weights keep LayerNorm'd activations O(1).
KERNEL_TOL = 1e-3
LOGITS_TOL = 5e-2

GEMM_KERNELS = ("lut_gemm", "bcq_matmul", "ternary_matmul")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[smoke] FAIL: {msg}")


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(np.isfinite(got).all(), "non-finite values in the result")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(jax):
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {dev.platform!r}")
    log(f"device: {dev.device_kind} x{len(jax.devices())}")
    return dev


def build(seed: int, backend: str = "auto", arch: str = "opt_6_7b"):
    import jax
    from repro.configs import get_config
    from repro.launch.serve import build_model
    from repro.quant import QuantSpec

    cfg = get_config(arch)
    # one alternating-refinement round: the solver's round count changes
    # the weights' quality, not the serving path under test, and each
    # round costs about a minute of chip time at this size
    spec = QuantSpec(format="bcq", bits=3, group_size=128, iters=1,
                     backend=backend)
    t0 = time.time()
    model, params, manifest = build_model(cfg, spec, seed=seed)
    jax.block_until_ready(params)
    log(f"built {cfg.name} ({cfg.n_layers}L d={cfg.d_model} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}): {manifest.summary()} "
        f"[{time.time() - t0:.1f}s, informational]")
    return model, params


def layer0_bundles(params) -> dict:
    """Layer 0's bundle of each of the four dense linear roles (attention
    q and out, MLP up and down; an expert bank is not one)."""
    import jax
    from repro.core.plane import PlaneBundle
    from repro.quant.ptq import _walk
    roles = {("mixer", "q"): "attn_qkv", ("mixer", "o"): "attn_out",
             ("mlp", "up"): "fc1", ("mlp", "down"): "fc2"}
    out = {}
    for path, leaf in _walk(params):
        role = roles.get(tuple(path[-2:]))
        if role and role not in out and isinstance(leaf, PlaneBundle):
            if "scan" in path:                  # a leading layers axis
                leaf = jax.tree_util.tree_map(lambda a: a[0], leaf)
            if leaf.packed.ndim == 3:
                out[role] = leaf
    check(len(out) == 4, f"found linears {sorted(out)}")
    return out


def first_expert_bank(params):
    """The first MoE layer's routed gate bank ([E, q, out, in/8]), or
    None for a model without routed experts."""
    import jax
    from repro.core.plane import PlaneBundle
    from repro.quant.ptq import _walk
    for path, leaf in _walk(params):
        if tuple(path[-2:]) == ("mlp", "gate") \
                and isinstance(leaf, PlaneBundle):
            if "scan" in path:
                leaf = jax.tree_util.tree_map(lambda a: a[0], leaf)
            if leaf.packed.ndim == 4:
                return leaf
    return None


def phase_grouped_kernel(params, seed: int):
    """The grouped expert kernel on a decode step's assignments (32 rows x
    top-k over the bank's experts, a few rows per expert)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.grouped_matmul import (bcq_grouped_matmul,
                                              expert_layout, gather_rows,
                                              ref, row_tile, scatter_rows)
    bank = first_expert_bank(params)
    if bank is None:
        return
    e, a = bank.packed.shape[0], 32 * 6
    key = jax.random.PRNGKey(seed + 3)
    x = jax.random.normal(key, (a, bank.in_features), jnp.bfloat16)
    experts = jax.random.randint(jax.random.fold_in(key, 1), (a,), 0, e)

    def run(x, experts, w):
        lay = expert_layout(experts, experts >= 0, e, row_tile(a, e))
        return gather_rows(bcq_grouped_matmul(
            scatter_rows(x, lay), w, lay, interpret=False,
            out_dtype=jnp.float32), lay)
    t0 = time.time()
    got = jax.block_until_ready(jax.jit(run)(x, experts, bank))
    dt = time.time() - t0
    want = ref.grouped_matmul_ref(x, experts, bank)
    err = rel_err(got, want)
    log(f"kernel bcq_grouped_matmul gate bank [{e}x{bank.out_features}x"
        f"{bank.in_features}] rows={a}: max rel err {err:.3e} "
        f"(tol {KERNEL_TOL:g}) [first call {dt:.1f}s, informational]")
    check(err <= KERNEL_TOL, f"grouped kernel: rel err {err:.3e}")


def phase_kernels(params, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.kernels.bcq_matmul import bcq_matmul
    from repro.kernels.bcq_matmul import ref as bcq_ref
    from repro.kernels.lut_gemm import lut_gemm
    from repro.kernels.lut_gemm import ref as lut_ref
    from repro.kernels.ternary_matmul import ternary_matmul
    from repro.kernels.ternary_matmul import ref as ter_ref
    from repro.quant.formats import quantize_ternary

    key = jax.random.PRNGKey(seed + 1)
    bundles = layer0_bundles(params)
    runs = {
        "lut_gemm": (lambda x, w: lut_gemm(x, w, interpret=False,
                                           out_dtype=jnp.float32),
                     lut_ref.dense_ref),
        "bcq_matmul": (lambda x, w: bcq_matmul(x, w, interpret=False,
                                               out_dtype=jnp.float32),
                       bcq_ref.bcq_matmul_ref),
    }
    ternary_w = quantize_ternary(
        jax.random.normal(key, (4096, 4096), jnp.float32) * 0.02,
        group_size=128)
    cases = [(name, role, w, *runs[name]) for role, w in bundles.items()
             for name in runs]
    cases.append(("ternary_matmul", "attn_qkv(ternary)", ternary_w,
                  lambda x, w: ternary_matmul(x, w, interpret=False,
                                              out_dtype=jnp.float32),
                  ter_ref.dense_ref))
    for name, role, w, run, oracle in cases:
        x = jax.random.normal(jax.random.fold_in(key, w.in_features),
                              (DECODE_BATCH, w.in_features), jnp.bfloat16)
        t0 = time.time()
        got = jax.block_until_ready(jax.jit(run)(x, w))
        dt = time.time() - t0
        with jax.default_matmul_precision("highest"):
            want = oracle(x, w, out_dtype=jnp.float32)
        err = rel_err(got, want)
        log(f"kernel {name} {role} [{w.out_features}x{w.in_features}] "
            f"B={DECODE_BATCH}: max rel err {err:.3e} (tol {KERNEL_TOL:g}) "
            f"[first call {dt:.1f}s, informational]")
        check(got.shape == (DECODE_BATCH, w.out_features),
              f"{name} {role}: shape {got.shape}")
        check(err <= KERNEL_TOL, f"{name} {role}: rel err {err:.3e}")


def requests(vocab: int, seed: int):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, (SHARED_PREFIX,))
    reqs = []
    for i in range(N_REQUESTS):
        n = int(rng.integers(PROMPT_MIN, PROMPT_MAX + 1))
        prompt = rng.integers(0, vocab, (n,))
        if i < 2:          # two requests open with the same 64 tokens
            prompt = np.concatenate([shared, prompt])[:PROMPT_MAX]
        reqs.append(Request(uid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=MAX_NEW))
    return reqs


def engine(model, params, *, backend=None, paged_kernel="auto",
           prefix_cache=True, mesh=None, tracer=None):
    from repro.models import Model
    from repro.serve import PagedServeEngine
    if backend is not None:
        model = Model(model.cfg.replace(
            quant=model.cfg.quant.replace(backend=backend)))
    return PagedServeEngine(
        model, params, num_blocks=NUM_BLOCKS, block_size=BLOCK_SIZE,
        max_batch=MAX_BATCH, max_seq_len=MAX_SEQ,
        prefill_buckets=PREFILL_BUCKETS, paged_kernel=paged_kernel,
        prefix_cache=prefix_cache, mesh=mesh, tracer=tracer)


def phase_serve(model, params, seed: int, backend):
    from repro import obs
    tracer = obs.Tracer()
    label = backend or f"{model.cfg.quant.backend} (default)"
    t0 = time.time()
    eng = engine(model, params, backend=backend, tracer=tracer)
    # MLA prefill decompresses latents on the gathered view
    want_prefill = "gather" if model.cfg.attention == "mla" else "fused"
    check(eng.decode_path == "fused" and eng.prefill_path == want_prefill,
          f"{label}: decode path {eng.decode_path}, prefill path "
          f"{eng.prefill_path} (want fused, {want_prefill})")
    reqs = requests(model.cfg.vocab_size, seed)
    done = eng.run(reqs, max_ticks=2000)
    wall = time.time() - t0
    check(len(done) == N_REQUESTS, f"{label}: {len(done)} of {N_REQUESTS} "
          "requests finished")
    for r in done:
        check(r.error is None, f"{label}: request {r.uid} failed: {r.error}")
        check(len(r.out_tokens) == MAX_NEW,
              f"{label}: request {r.uid} got {len(r.out_tokens)} tokens")
    # the paths every step and chunk of the run took, not just the plan
    m = eng.metrics
    check(m.decode_path == "fused" and m.prefill_path == want_prefill,
          f"{label}: ran decode on {dict(m.decode_path_steps)}, prefill on "
          f"{dict(m.prefill_path_chunks)}")
    ev = tracer.events
    # no linear left its kernel preference, and the GEMM kernels were
    # configured while the programs were traced
    falls = [e["args"] for e in ev if e["name"] == "backend_fallback"]
    used = sorted({e["args"]["kernel"] for e in ev
                   if e["name"].startswith("kernel_config:")
                   and e["args"]["kernel"] in GEMM_KERNELS})
    check(not falls, f"{label}: linears fell back: {falls[:3]}")
    check(bool(used), f"{label}: no GEMM kernel was configured")
    s = eng.metrics.summary()
    log(f"serve [{label}]: {len(done)} requests x {MAX_NEW} tokens ok; "
        f"decode={eng.decode_path} prefill={eng.prefill_path}; GEMM "
        f"kernels {used}; prefix hit blocks "
        f"{s['counters'].get('prefix_hit_blocks', 0)} "
        f"[wall {wall:.1f}s incl. compile, informational]")
    obs.trace.set_active(None)
    return eng


def probe(eng, vocab: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed + 7)
    tokens = rng.integers(0, vocab, (PROBE_PROMPT + PROBE_STEPS,))
    return eng.probe_logits(tokens[:PROBE_PROMPT], tokens[PROBE_PROMPT:])


def phase_reference(model, params, served, seed: int):
    import jax
    got = probe(served, model.cfg.vocab_size, seed)
    with jax.default_matmul_precision("highest"):
        ref_eng = engine(model, params, backend="dense",
                         paged_kernel="gather", prefix_cache=False)
        want = probe(ref_eng, model.cfg.vocab_size, seed)
    err = rel_err(got, want)
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    log(f"reference: logits of 1 prefill + {PROBE_STEPS} decode steps vs "
        f"XLA dequant (dense f32, highest): max rel err {err:.3e} "
        f"(tol {LOGITS_TOL:g}); argmax agreement {agree:.2f} "
        f"(informational)")
    check(got.shape == want.shape == (1 + PROBE_STEPS, model.cfg.vocab_size),
          f"logits shape {got.shape} vs {want.shape}")
    check(err <= LOGITS_TOL, f"reference logits rel err {err:.3e}")


def phase_four_chips(model, params, seed: int):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as shd
    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    single = engine(model, params, prefix_cache=False)
    want = probe(single, model.cfg.vocab_size, seed)
    mesh = make_mesh((1, 4), ("data", "model"))
    shd.set_activation_rules(mesh, shd.make_rules())
    sharded = engine(model, params, prefix_cache=False, mesh=mesh)
    got = probe(sharded, model.cfg.vocab_size, seed)
    # OPT's layers are scan-stacked: k is [layers, blocks, slots, kv heads,
    # head dim]
    k = sharded.cache["scan"][0]["self"]["k"]
    spec = tuple(k.sharding.spec) + (None,) * (k.ndim - len(k.sharding.spec))
    err = rel_err(got, want)
    log(f"four chips: TP=4 vs devices[0] logits max rel err {err:.3e} "
        f"(tol {LOGITS_TOL:g}); decode={sharded.decode_path} "
        f"prefill={sharded.prefill_path}; KV pool spec {spec}")
    check(spec[3] == "model", f"KV pool heads not sharded over model: {spec}")
    check(sharded.decode_path == "fused" and sharded.prefill_path == "fused",
          f"sharded decode {sharded.decode_path}, prefill "
          f"{sharded.prefill_path} (want fused)")
    check(err <= LOGITS_TOL, f"sharded logits rel err {err:.3e}")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TP=4 sharded-serving check")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arch", default="opt_6_7b",
                    help="registered configuration to serve (one chip)")
    args = ap.parse_args()

    # heuristic launches only: the tune cache lives outside the checkout
    os.environ["REPRO_TUNE"] = "off"
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        raise SystemExit(f"[smoke] FAIL: the repro package is not next to "
                         f"this script ({e})")
    log(f"compile cache: {setup_compile_cache()}")
    import jax

    t_start = time.time()
    dev = phase_device(jax)
    model, params = build(args.seed, arch=args.arch)
    if args.four_chips:
        phase_four_chips(model, params, args.seed)
    else:
        phase_kernels(params, args.seed)
        phase_grouped_kernel(params, args.seed)
        served = phase_serve(model, params, args.seed, None)
        phase_serve(model, params, args.seed, "lut_pallas")
        phase_reference(model, params, served, args.seed)
    stats = dev.memory_stats() or {}
    log(f"peak device bytes in use: {stats.get('peak_bytes_in_use')} "
        f"of {stats.get('bytes_limit')}; total wall "
        f"{time.time() - t_start:.1f}s (informational)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
