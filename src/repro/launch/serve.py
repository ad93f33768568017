"""Production serving launcher: quantize (or load pre-quantized) and serve.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral_8x7b \
        --bits 3 --requests 16

Quantization is driven by a :class:`repro.quant.QuantSpec` — built from
the CLI flags, or loaded whole from ``--spec spec.json`` (flags override
file fields).  Highlights:

  * ``--bits 2.4`` (fractional) runs sensitivity-driven mixed precision
    via ``core.mixed_precision.allocate_bits`` (paper Fig. 17); the
    printed manifest reports the achieved average.
  * ``--method ternary`` serves {-a, 0, +a} weights as a plane-native
    sign+mask bundle (one alpha row, no offset) routed to the dedicated
    ``ternary_matmul`` kernel where native; ``--bits 1.58`` instead
    mixes ternary/2/3-bit layers under a log2(3) average-bit budget.
  * ``--bits 0`` explicitly serves the dense FP model (no silent skip).
  * ``--save-quantized DIR`` / ``--load-quantized DIR`` persist / reuse
    the quantized tree, so relaunches skip minutes of PTQ solver time;
    a loaded checkpoint serves token-for-token identically to
    quantize-at-launch.
  * ``--manifest-json PATH`` dumps the per-layer manifest (CI artifact).

Default engine is the paged-KV engine (block pool + chunked-prefill
scheduler + streaming + metrics); ``--engine slots`` falls back to the
contiguous fixed-slot engine (required for SSM/hybrid, enc-dec and
sliding-window models, which the paged cache does not cover).
``--paged-kernel`` picks the paged attention paths: ``auto`` (fused
Pallas kernels where hardware-native), ``fused`` (force the kernels,
interpret mode off-TPU) or ``gather`` (the paged_view fallback).  The
fused coverage spans float, int8-KV (per-slot scales folded in-kernel)
and MLA-latent decode plus float/int8-KV chunked prefill; the paths
resolve per variant (MLA prefill still gathers for its decompressing
``kv_map_fn``) and are printed as ``decode path`` / ``prefill path``.

``--prefix-cache on|off`` (default: on for the paged engine) shares KV
blocks across requests with a common block-aligned prompt prefix —
refcounted adoption at admission, copy-on-write by recompute on the
first divergent or partially-filled block (see ``docs/serving.md``).

``--trace-out trace.json`` records an event-level serving trace (spans
for admission, prefix lookups, prefill chunks, decode dispatch, device
sync, sampling, preemptions and evictions, plus one track per request)
and writes Chrome trace-event JSON — open it in Perfetto or
``chrome://tracing``; ``--trace-timeline N`` also prints the host-side
per-request timeline table.  See ``docs/observability.md``.

``--async`` serves through the asyncio frontend
(``repro.serve.frontend``) and the double-buffered engine tick: every
request is submitted from its own coroutine, token streams are consumed
concurrently, sampling runs on-device, and the device sync for step N
hides behind the planning and dispatch of step N+1.  ``--deadline-ms``
gives every third request a deadline so the demo exercises expiry and
block release under load; outputs remain token-for-token identical to
the synchronous engine.

``--mesh auto`` (or an explicit ``DxM`` shape like ``2x4``) serves the
paged engine sharded over a ``("data", "model")`` mesh: KV pool leaves
shard over kv_heads (head_dim fallback for narrow-GQA), params ride
``parallel.sharding.build_shardings`` (BCQ bundles included), and the
fused kernel launches per model-shard via ``shard_map``.  ``--tp N``
pins the model axis under ``--mesh auto``.  Smoke it on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import argparse
import time


def build_spec(args):
    """Resolve the QuantSpec from --spec JSON + CLI overrides.

    Returns None for an explicitly dense serve (--bits 0 with no spec
    file, or a spec whose bits resolve to 0).
    """
    from repro.quant import QuantSpec, canonical_format

    if args.bits is not None and args.bits == 0:
        # explicit dense request wins before any spec normalization
        # (ternary would otherwise coerce bits back to its 2 planes)
        return None
    base = QuantSpec.load(args.spec) if args.spec else QuantSpec()
    kw = {}
    if args.bits is not None:
        kw["bits"] = args.bits
    elif args.format is not None and \
            canonical_format(args.format) != base.format:
        # switching format without --bits: reset to the new format's
        # default instead of carrying the old format's bit-width over
        # (ternary rejects any bits != 2)
        kw["bits"] = None
    if args.format is not None:
        kw["format"] = args.format
    if args.backend is not None:
        kw["backend"] = args.backend
    if args.group_size is not None:
        kw["group_size"] = args.group_size
    if args.iters is not None:
        kw["iters"] = args.iters
    try:
        spec = base.replace(**kw) if kw else base
    except ValueError as e:                  # e.g. --method ternary --bits 4
        raise SystemExit(f"invalid quant flags: {e}")
    return None if spec.bits == 0 else spec


def build_model(cfg, spec, *, seed: int = 0):
    """The served model: random weights drawn from ``seed`` and, when
    ``spec`` is given, quantized leaf by leaf
    (:func:`repro.quant.init_quantized` — the dense model never coexists
    with its bundles, so a full-width model fits one chip).

    Returns ``(model, params, manifest)``; ``model.cfg.quant`` is
    ``spec``.  Shared by this launcher and ``chip_smoke.py``.
    """
    import jax
    from repro import quant as quant_api
    from repro.models import Model

    rng = jax.random.PRNGKey(seed)
    if spec is None:
        model = Model(cfg)
        return model, model.init(rng), None
    model = Model(cfg.replace(quant=spec))
    params, manifest = quant_api.init_quantized(model.desc(), spec,
                                                model.axes(), rng)
    return model, params, manifest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt_6_7b")
    ap.add_argument("--reduced", type=int, default=1)
    # --- quantization spec (repro.quant) -------------------------------
    ap.add_argument("--bits", type=float, default=None,
                    help="weight bits; fractional (e.g. 2.4) -> mixed "
                         "precision; sub-2 budgets (e.g. 1.58) mix "
                         "ternary/2/3-bit layers; 0 -> serve dense FP "
                         "(default: 4)")
    ap.add_argument("--method", "--format", dest="format", default=None,
                    choices=["bcq", "rtn", "uniform", "ternary"],
                    help="quant format (registry: repro.quant.formats)")
    ap.add_argument("--backend", default=None,
                    help="execution preference (auto | dense | bcq_xla | "
                         "lut_pallas | mxu_pallas | ternary_pallas); "
                         "capability negotiation falls back down the "
                         "chain per weight")
    ap.add_argument("--group-size", type=int, default=None,
                    help="scale group size along the input dim (default 128)")
    ap.add_argument("--iters", type=int, default=None,
                    help="BCQ alternating-refinement rounds (default 5)")
    ap.add_argument("--spec", default="",
                    help="QuantSpec JSON file; explicit flags override")
    ap.add_argument("--save-quantized", default="",
                    help="write the quantized params + spec/manifest to "
                         "this checkpoint dir after PTQ")
    ap.add_argument("--load-quantized", default="",
                    help="serve pre-quantized params from this checkpoint "
                         "dir (skips PTQ; spec comes from the checkpoint)")
    ap.add_argument("--manifest-json", default="",
                    help="write the quantization manifest to this path")
    # --- engine --------------------------------------------------------
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "paged", "slots"],
                    help="auto picks paged where the model supports it "
                         "(attention-only, no SWA/enc-dec), else slots")
    ap.add_argument("--slots", type=int, default=4,
                    help="[slots engine] fixed cache rows")
    ap.add_argument("--cache-len", type=int, default=256,
                    help="[slots engine] per-row KV reservation (also the "
                         "paged engine's default --max-seq-len)")
    ap.add_argument("--max-seq-len", type=int, default=0,
                    help="[paged engine] per-sequence context cap "
                         "(default: --cache-len)")
    ap.add_argument("--num-blocks", type=int, default=64,
                    help="[paged engine] shared KV pool size")
    ap.add_argument("--block-size", type=int, default=16,
                    help="[paged engine] tokens per block")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="[paged engine] concurrent sequences")
    ap.add_argument("--paged-kernel", default="auto",
                    choices=["auto", "fused", "gather"],
                    help="[paged engine] paged attention path: fused "
                         "Pallas kernels (auto: only where hardware-"
                         "native; fused: force, interpret mode off-TPU) "
                         "vs the gathered paged_view fallback.  Fused "
                         "covers float/int8-KV/MLA decode and float/"
                         "int8-KV chunked prefill; the remaining gaps "
                         "(MLA prefill) negotiate down per variant")
    ap.add_argument("--prefix-cache", default=None,
                    choices=["on", "off"],
                    help="[paged engine] share KV blocks across requests "
                         "with a common block-aligned prompt prefix "
                         "(refcounted, copy-on-write by recompute; see "
                         "docs/serving.md).  Default: on for the paged "
                         "engine")
    ap.add_argument("--mesh", default="",
                    help="[paged engine] serve sharded over a (data, "
                         "model) mesh: 'auto' (largest divisor mesh over "
                         "the visible devices; --tp pins the model axis) "
                         "or an explicit DxM shape like 2x4")
    ap.add_argument("--tp", type=int, default=0,
                    help="model-parallel extent for --mesh auto")
    ap.add_argument("--async", dest="async_engine", action="store_true",
                    help="[paged engine] serve through the asyncio "
                         "frontend with the double-buffered engine tick: "
                         "concurrent per-request coroutines, on-device "
                         "sampling, and step N's device sync hidden "
                         "behind step N+1's dispatch (token-identical "
                         "to the synchronous engine)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="with --async: give every third request this "
                         "deadline so the demo exercises expiry and "
                         "block release under load (0: no deadlines)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated")
    ap.add_argument("--metrics-json", default="",
                    help="write the metrics summary to this path")
    ap.add_argument("--trace-out", default="",
                    help="[paged engine] record an event-level serving "
                         "trace and write it as Chrome trace-event JSON "
                         "(open in https://ui.perfetto.dev or "
                         "chrome://tracing; see docs/observability.md)")
    ap.add_argument("--trace-timeline", type=int, default=0, metavar="N",
                    help="with --trace-out: also print the first N rows "
                         "of the host-side per-request timeline table")
    ap.add_argument("--trace-profiler-bridge", action="store_true",
                    help="with --trace-out: wrap host spans in "
                         "jax.profiler annotations so device profiles "
                         "line up with the serving trace")
    ap.add_argument("--pretune", action="store_true",
                    help="autotune kernel configs for this model's layer "
                         "shapes before serving (persists to the JSON "
                         "cache; see python -m repro.tune)")
    args = ap.parse_args()

    import numpy as np
    from repro import quant as quant_api
    from repro.configs import get_config, get_reduced
    from repro.models import Model
    from repro.serve import PagedServeEngine, Request, ServeEngine
    from repro.serve.engine import supports_paging
    from repro.launch.compile_cache import setup_compile_cache

    print(f"[launch.serve] compile cache: {setup_compile_cache()}")
    if args.backend is not None:
        try:    # fail fast on both paths: before PTQ and before ckpt load
            quant_api.fallback_chain(args.backend)
        except KeyError as e:
            raise SystemExit(f"--backend: {e.args[0]}")
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    cfg = cfg.replace(max_seq_len=max(cfg.max_seq_len, args.cache_len))

    manifest = None
    if args.load_quantized:
        # weight-shape flags describe the *stored* weights and cannot be
        # changed after the fact; --backend is a runtime execution
        # preference, so it still applies to a loaded checkpoint
        fixed = {"--bits": args.bits, "--method": args.format,
                 "--group-size": args.group_size, "--iters": args.iters,
                 "--spec": args.spec or None,
                 "--save-quantized": args.save_quantized or None}
        bad = [k for k, v in fixed.items() if v is not None]
        if bad:
            raise SystemExit(f"{', '.join(bad)} cannot be combined with "
                             "--load-quantized: the checkpoint's weights "
                             "are already quantized (re-quantize without "
                             "--load-quantized instead)")
        params, spec, manifest, extra = quant_api.load_quantized(
            args.load_quantized)
        if extra.get("arch") and extra["arch"] != cfg.name:
            raise SystemExit(f"checkpoint arch {extra['arch']!r} does not "
                             f"match --arch {cfg.name!r}")
        # cfg.name is identical for reduced and full configs — compare
        # dimensions too, or a reduced checkpoint dies in the first
        # forward with an opaque shape error
        dims = {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                "vocab_size": cfg.vocab_size}
        stored = {k: extra[k] for k in dims if k in extra}
        bad = {k: (v, dims[k]) for k, v in stored.items() if v != dims[k]}
        if bad:
            raise SystemExit(
                f"checkpoint model dims do not match --arch/--reduced: "
                + ", ".join(f"{k}: ckpt {a} vs cfg {b}"
                            for k, (a, b) in bad.items()))
        if args.backend is not None:
            spec = spec.replace(backend=args.backend)
        print(f"[launch.serve] loaded quantized checkpoint "
              f"{args.load_quantized} ({spec.describe()})")
    else:
        spec = build_spec(args)
        if spec is None:
            if args.save_quantized:
                raise SystemExit("--save-quantized requires quantization "
                                 "(remove --bits 0)")
            print("[launch.serve] serving dense FP (no quantization)")
            model, params, _ = build_model(cfg, None)
        else:
            t0 = time.time()
            try:
                model, params, manifest = build_model(cfg, spec)
            except ValueError as e:   # spec errors surfaced at plan time
                raise SystemExit(f"invalid quant spec: {e}")
            print(f"[launch.serve] {spec.describe()} in "
                  f"{time.time()-t0:.1f}s")
            print(f"[launch.serve] {manifest.summary()}")
            if args.save_quantized:
                path = quant_api.save_quantized(
                    args.save_quantized, params, spec, manifest,
                    arch=cfg.name,
                    extra_meta={"d_model": cfg.d_model,
                                "n_layers": cfg.n_layers,
                                "vocab_size": cfg.vocab_size})
                print(f"[launch.serve] quantized checkpoint -> {path}")
    if args.manifest_json:
        if manifest is not None:
            manifest.save(args.manifest_json)
            print(f"[launch.serve] manifest -> {args.manifest_json}")
        else:
            print(f"[launch.serve] warning: --manifest-json ignored "
                  f"(no manifest: dense serve, or checkpoint saved "
                  f"without one)")

    if spec is not None:
        cfg = cfg.replace(quant=spec)
        model = Model(cfg)
    print(f"[launch.serve] {cfg.name}: {model.n_params():,} params, "
          f"backend preference {cfg.backend_preference}")

    on_token = None
    if args.stream:
        on_token = lambda tok, req: print(f"  [stream] req {req.uid} "
                                          f"+tok {tok}")
    engine = args.engine
    if engine == "auto":
        engine = "paged" if supports_paging(cfg) else "slots"
        print(f"[launch.serve] engine=auto -> {engine}")
    mesh = None
    if args.mesh:
        if engine != "paged":
            raise SystemExit("--mesh requires the paged engine "
                             "(SSM/hybrid, enc-dec and sliding-window "
                             "models serve single-device for now)")
        from repro.launch.mesh import parse_mesh
        from repro.parallel import sharding as shd
        try:
            mesh = parse_mesh(args.mesh, tp=args.tp)
        except ValueError as e:
            raise SystemExit(str(e))
        shd.set_activation_rules(mesh, shd.make_rules())
        print(f"[launch.serve] mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"over {mesh.devices.size} devices")
    elif args.tp:
        raise SystemExit("--tp only applies with --mesh auto")
    if args.prefix_cache is not None and engine != "paged":
        raise SystemExit("--prefix-cache requires the paged engine "
                         "(the slots engine has no shared KV pool)")
    if args.async_engine and engine != "paged":
        raise SystemExit("--async requires the paged engine (the slots "
                         "engine has no double-buffered tick)")
    if args.deadline_ms and not args.async_engine:
        raise SystemExit("--deadline-ms requires --async")
    tracer = None
    if args.trace_out:
        if engine != "paged":
            raise SystemExit("--trace-out requires the paged engine "
                             "(the slots engine has no trace hooks)")
        from repro import obs
        tracer = obs.Tracer(profiler_bridge=args.trace_profiler_bridge)
    elif args.trace_timeline or args.trace_profiler_bridge:
        raise SystemExit("--trace-timeline/--trace-profiler-bridge "
                         "require --trace-out")
    if engine == "paged":
        eng = PagedServeEngine(model, params, num_blocks=args.num_blocks,
                               block_size=args.block_size,
                               max_batch=args.max_batch,
                               max_seq_len=args.max_seq_len or args.cache_len,
                               prefill_buckets=(16, 32, 64),
                               pretune=args.pretune,
                               paged_kernel=args.paged_kernel,
                               prefix_cache=args.prefix_cache != "off",
                               mesh=mesh, tracer=tracer)
        print(f"[launch.serve] paged-kernel={args.paged_kernel} -> "
              f"decode path: {eng.decode_path}  "
              f"prefill path: {eng.prefill_path}")
    else:
        eng = ServeEngine(model, params, slots=args.slots,
                          cache_len=args.cache_len,
                          prefill_buckets=(16, 32, 64),
                          pretune=args.pretune)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (int(rng.integers(4, 24)),))
               for _ in range(args.requests)]
    t0 = time.time()
    if args.async_engine:
        done = _run_async_demo(eng, prompts, args)
    else:
        reqs = [Request(uid=i, prompt=p, max_new_tokens=args.max_new,
                        on_token=on_token)
                for i, p in enumerate(prompts)]
        done = eng.run(reqs)
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"[launch.serve] {len(done)} requests, {toks} tokens, "
          f"{toks/dt:.1f} tok/s")
    if engine == "paged":
        s = eng.metrics.summary()
        print(f"[launch.serve] ttft p50={s['ttft_s']['p50']*1e3:.1f}ms "
              f"p95={s['ttft_s']['p95']*1e3:.1f}ms  "
              f"per-token p50={s['per_token_s']['p50']*1e3:.1f}ms  "
              f"occupancy mean={s['occupancy']['mean']:.2f} "
              f"peak={s['occupancy']['peak']:.2f}  "
              f"preempted={s['counters']['preempted']}")
        print(f"[launch.serve] queue delay "
              f"p50={s['queue_delay_s']['p50']*1e3:.1f}ms  "
              f"decode in-flight fraction="
              f"{s['decode_inflight_fraction']:.2f}  "
              f"cancelled={s['counters']['cancelled']} "
              f"deadline-expired={s['counters']['deadline_expired']}")
        pk = s["paged_kernel"]
        print(f"[launch.serve] decode path={pk['path']}  KV bytes/token: "
              f"fused={pk['kv_bytes_per_token_fused']:.0f} "
              f"gathered={pk['kv_bytes_per_token_gathered']:.0f}")
        print(f"[launch.serve] prefill path={pk['prefill_path']}  "
              f"KV bytes/prefill token: "
              f"fused={pk['kv_bytes_per_prefill_token_fused']:.0f} "
              f"gathered={pk['kv_bytes_per_prefill_token_gathered']:.0f}")
        if eng.prefix is not None:
            pc = s["prefix_cache"]
            print(f"[launch.serve] prefix cache: hit-rate "
                  f"{pc['hit_rate']:.2f}  blocks saved "
                  f"{pc['blocks_saved']}  tokens saved "
                  f"{pc['tokens_saved']}  effective capacity "
                  f"peak {s['effective_capacity']['peak']:.2f}x")
        if args.metrics_json:
            eng.metrics.to_json(args.metrics_json)
            print(f"[launch.serve] metrics -> {args.metrics_json}")
        if tracer is not None:
            from repro import obs
            obs.save_chrome(tracer, args.trace_out)
            print(f"[launch.serve] trace -> {args.trace_out} "
                  f"({len(tracer.events)} events, {tracer.dropped} "
                  f"dropped; open in https://ui.perfetto.dev)")
            if args.trace_timeline:
                print(obs.format_timeline(tracer,
                                          max_rows=args.trace_timeline))


def _run_async_demo(eng, prompts, args):
    """Serve ``prompts`` through :class:`AsyncServeFrontend`: one
    submitting coroutine per request next to the engine loop, every
    token consumed from its handle's async stream, and (with
    ``--deadline-ms``) a deadline on every third request so expiry and
    block release are exercised under real concurrency."""
    import asyncio

    from repro.serve import AsyncServeFrontend

    fe = AsyncServeFrontend(eng, max_queue=max(8, 2 * len(prompts)))

    async def client(i, prompt):
        dl = args.deadline_ms if args.deadline_ms and i % 3 == 2 else None
        h = await fe.submit(prompt, max_new_tokens=args.max_new,
                            deadline_ms=dl)
        async for tok in h:
            if args.stream:
                print(f"  [stream] req {h.uid} +tok {tok}")
        return await h.wait()

    async def run():
        loop = asyncio.ensure_future(fe.serve_forever())
        try:
            done = await asyncio.gather(
                *(client(i, p) for i, p in enumerate(prompts)))
        finally:
            fe.close()
            await loop
        return done

    done = asyncio.run(run())
    expired = [r.uid for r in done if r.error == "deadline"]
    if expired:
        print(f"[launch.serve] deadline expired: "
              f"{len(expired)} requests {expired}")
    return done


if __name__ == "__main__":
    main()
