import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"       # fake devices live on the host

"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production mesh, print memory/cost analysis, and extract roofline terms.

MUST be run as a module: ``PYTHONPATH=src python -m repro.launch.dryrun
--arch mixtral_8x7b --shape decode_32k [--multi-pod] [--quant 4] ...``
The XLA_FLAGS line above runs before ANY jax import (jax locks the device
count at first init); nothing in this module imports jax at module scope
before it executes.

Per shape cell:
  train_4k    -> train_step  (loss+grad+AdamW update, dense bf16, FSDP)
  prefill_32k -> prefill     (quantized BCQ weights, fills the cache)
  decode_32k  -> decode_step (quantized, 1 token vs 32k cache)
  long_500k   -> decode_step (sub-quadratic archs only)

Roofline extraction lowers two UNROLLED reduced-depth variants and
extrapolates per-period costs (exact for homogeneous stacks) because
cost_analysis counts while-loop bodies once; the full scanned model is
compiled for the memory-fit proof (see roofline/analysis.py).
"""
import argparse
import dataclasses
import json
import sys
import time


def _parse():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", required=True,
                   help="train_4k|prefill_32k|decode_32k|long_500k")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--quant", type=int, default=4,
                   help="BCQ bits for serving shapes (0 = dense)")
    p.add_argument("--backend", default="bcq_xla",
                   help="gemm backend for quantized serving")
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--no-roofline", action="store_true",
                   help="memory-fit compile only")
    p.add_argument("--json-out", default="")
    p.add_argument("--remat", type=int, default=1)
    p.add_argument("--seq-shard", type=int, default=0,
                   help="shard train sequence dim over the model axis (SP)")
    p.add_argument("--kv-bits", type=int, default=16,
                   help="8 -> int8 KV cache (serve shapes)")
    return p.parse_args()


def active_params(cfg, model) -> tuple:
    """(n_active, n_total) excluding token/pos embeddings; inactive routed
    experts removed (MODEL_FLOPS convention: 6*N_active*D)."""
    total = model.n_params()
    embed = cfg.vocab_size * cfg.d_model
    if cfg.pos == "learned":
        embed += cfg.max_seq_len * cfg.d_model
    if not cfg.tie_embeddings:
        embed += 0  # unembed participates in compute; keep it
    n_eff = total - embed
    if cfg.tie_embeddings:
        n_eff += cfg.vocab_size * cfg.d_model      # head matmul still runs
    inactive = 0
    if cfg.n_experts:
        f = cfg.moe_d_ff or cfg.d_ff
        per_expert = 3 * f * cfg.d_model
        n_moe_layers = sum(1 for i in range(cfg.n_layers)
                           if cfg.mlp_kind(i) == "moe")
        inactive = n_moe_layers * per_expert * \
            (cfg.n_experts - cfg.experts_per_token)
    return n_eff - inactive, total


def build_cell(arch: str, shape_name: str, *, quant=4, backend="bcq_xla",
               fsdp=True, multi_pod=False, remat=True, scan=True,
               n_layers=None, seq_shard=False, kv_bits=16):
    """Everything needed to lower one cell: (fn, example_args, shardings,
    mesh, cfg).  n_layers overrides depth (roofline extrapolation)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, SHAPES
    from repro.models import Model
    from repro.models.module import abstract_params
    from repro.optim import adamw
    from repro.parallel import sharding as shd
    from repro.quant.ptq import abstract_quantized_params
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.supports_long_context():
        raise SystemExit(f"SKIP: {arch} has no sub-quadratic path for "
                         f"long_500k (full attention) — see DESIGN.md")
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = shd.make_rules(fsdp=fsdp and shape.kind == "train",
                           multi_pod=multi_pod,
                           act_shard=shape.kind == "train")
    if seq_shard:
        rules["seq"] = "model"

    overrides = dict(remat=remat, scan_layers=scan)
    if n_layers is not None:
        # keep prefix pattern intact; n_layers counts total layers
        overrides["n_layers"] = n_layers
        overrides["scan_layers"] = False
    if shape.kind != "train" and quant:
        from repro.quant.spec import QuantSpec
        overrides["quant"] = QuantSpec(bits=quant, backend=backend)
    if shape.kind != "train" and kv_bits != 16:
        overrides["kv_cache_bits"] = kv_bits
    model_par = 16
    if shape.kind != "train" and cfg.attention == "gqa" and cfg.n_kv_heads \
            and cfg.n_kv_heads < model_par and model_par % cfg.n_kv_heads == 0:
        # kv-head replication: 2x cache memory beats per-layer cache
        # all-gathers when TP > n_kv_heads (serve shapes only).  Requires
        # the q-head grouping to stay integral (phi4's 24 heads fall back
        # to head_dim sharding).
        r = model_par // cfg.n_kv_heads
        if cfg.n_heads % (cfg.n_kv_heads * r) == 0:
            overrides["kv_replication"] = r
    cfg = cfg.replace(**overrides)
    model = Model(cfg)
    shd.set_activation_rules(mesh, rules)

    aparams = model.abstract()
    axes = model.axes()
    if shape.kind != "train" and quant:
        aparams = abstract_quantized_params(aparams, axes, bits=quant)
    p_sh = shd.build_shardings(mesh, aparams, axes, rules)

    specs = cfg.input_specs(shape)
    b_sh = shd.batch_shardings(mesh, specs, rules)

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()

        def train_step(params, opt, batch):
            loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
            # pin gradient shardings to the param shardings — the grad
            # accumulators inside the layer-scan backward otherwise come out
            # replicated (same GSPMD loop-carry failure as the attention
            # residuals; ~58 GiB/device on mamba2 train without this)
            grads = jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s),
                grads, p_sh)
            new_p, new_o, metrics = adamw.apply_updates(params, grads, opt,
                                                        opt_cfg)
            return new_p, new_o, metrics

        a_opt = jax.eval_shape(adamw.init_state, aparams)
        o_sh = adamw.AdamWState(
            count=shd.replicated(mesh),
            m=shd.build_shardings(mesh, a_opt.m, axes, rules),
            v=shd.build_shardings(mesh, a_opt.v, axes, rules))
        fn = train_step
        args = (aparams, a_opt, specs)
        in_sh = (p_sh, o_sh, b_sh)
        out_sh = (p_sh, o_sh, None)
        donate = (0, 1)          # params + opt state update in place
    else:
        cache_len = shape.seq_len
        batch = shape.global_batch
        acache = model.abstract_cache(batch, cache_len)
        c_sh = shd.build_shardings(mesh, acache, model.axes() and
                                   _cache_axes(model, batch, cache_len), rules)
        if shape.kind == "prefill":
            def prefill(params, batch_in, cache):
                return model.prefill(params, batch_in, cache)
            fn = prefill
            args = (aparams, specs, acache)
            in_sh = (p_sh, b_sh, c_sh)
            out_sh = (None, c_sh)
            donate = (2,)        # cache filled in place
        else:
            tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
            specs = {"tokens": tok}

            def decode(params, tokens, cache, positions):
                return model.decode_step(params, tokens, cache, positions)
            fn = decode
            tok_sh = shd.batch_shardings(mesh, {"tokens": tok}, rules)["tokens"]
            pos_sh = shd.batch_shardings(mesh, {"p": pos}, rules)["p"]
            args = (aparams, tok, acache, pos)
            in_sh = (p_sh, tok_sh, c_sh, pos_sh)
            out_sh = (None, c_sh)
            donate = (2,)        # cache updated in place
    return fn, args, in_sh, out_sh, donate, mesh, cfg, shape, model


def _cache_axes(model, batch, length):
    from repro.models.module import logical_axes
    return logical_axes(model.cache_desc(batch, length))


def lower_and_compile(fn, args, in_sh, out_sh, mesh, label="", donate=()):
    import jax
    t0 = time.time()
    with mesh:
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=donate)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    print(f"[dryrun] {label}: lower {t_lower:.1f}s compile {t_compile:.1f}s")
    return compiled


def run_cell(arch, shape_name, *, quant=4, backend="bcq_xla", fsdp=True,
             multi_pod=False, remat=True, roofline=True, seq_shard=False,
             kv_bits=16):
    """Compile the full scanned model (memory proof) and, if requested,
    two unrolled reduced-depth variants for extrapolated roofline terms.
    Returns a result dict."""
    from repro.configs import get_config
    from repro.roofline import analysis as ra

    cfg0 = get_config(arch)
    kw = dict(quant=quant, backend=backend, fsdp=fsdp, multi_pod=multi_pod,
              remat=remat, seq_shard=seq_shard, kv_bits=kv_bits)

    # ---- full model, scanned: the memory-fit / shardability proof -------
    fn, args, in_sh, out_sh, donate, mesh, cfg, shape, model = build_cell(
        arch, shape_name, scan=True, **kw)
    compiled = lower_and_compile(fn, args, in_sh, out_sh, mesh,
                                 f"{arch}/{shape_name}/full", donate)
    full = ra.from_compiled(compiled)
    ma = compiled.memory_analysis()
    print(f"[dryrun] memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}GiB "
          f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB "
          f"out={ma.output_size_in_bytes/2**30:.2f}GiB per device")
    print(f"[dryrun] cost_analysis (scanned, loop bodies counted once): "
          f"flops={full.flops:.3e} bytes={full.bytes_accessed:.3e}")

    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "quant": quant if shape.kind != "train" else 0,
        "mesh": list(mesh.devices.shape),
        "device_mem_gb": full.device_memory_gb(),
        "compile_ok": True,
    }

    if roofline:
        # ---- layer-extrapolated costs (unrolled L1 / L2 periods) --------
        from repro.models.transformer import scan_grouping
        pre, period, reps = scan_grouping(cfg0)
        l1 = pre + period
        l2 = pre + 2 * period
        rls = []
        for ln in (l1, l2):
            fn, args, in_sh, out_sh, dn, mesh2, _, _, _ = build_cell(
                arch, shape_name, scan=True, n_layers=ln, **kw)
            c = lower_and_compile(fn, args, in_sh, out_sh, mesh2,
                                  f"{arch}/{shape_name}/L{ln}", dn)
            rls.append(ra.from_compiled(c))
        roof = ra.extrapolate(rls[0], rls[1], 1, 2, (cfg0.n_layers - pre) / period,
                              mem=full)
        n_act, n_tot = active_params(cfg0, model)
        mf = ra.model_flops(cfg0, shape, n_act, n_tot)
        n_chips = int(mesh.devices.size)
        useful = mf / max(roof.flops * n_chips, 1e-9)
        row = roof.row()
        row.update({"model_flops_global": mf,
                    "useful_flops_ratio": useful,
                    "n_active_params": n_act, "n_total_params": n_tot})
        if shape.kind == "decode":
            row["analytic"] = ra.serve_analytic_bytes(
                cfg0, shape, n_act, quant or 4)
        result["roofline"] = row
        print(f"[dryrun] roofline (extrapolated to {cfg0.n_layers}L): "
              f"t_comp={roof.t_compute*1e3:.2f}ms t_mem={roof.t_memory*1e3:.2f}ms "
              f"t_coll={roof.t_collective*1e3:.2f}ms -> {roof.bottleneck}-bound, "
              f"roofline_frac={roof.fraction_of_roofline():.3f}, "
              f"useful_flops={useful:.3f}")
    return result


def main():
    args = _parse()
    res = run_cell(args.arch, args.shape, quant=args.quant,
                   backend=args.backend, fsdp=bool(args.fsdp),
                   multi_pod=args.multi_pod, remat=bool(args.remat),
                   roofline=not args.no_roofline,
                   seq_shard=bool(args.seq_shard), kv_bits=args.kv_bits)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(res, f, indent=2, default=str)
    print(json.dumps(res, indent=2, default=str))


if __name__ == "__main__":
    main()
