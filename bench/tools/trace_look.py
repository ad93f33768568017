"""Record one cell's profiler trace and print what it holds, for reading
by hand (which device lines exist, how programs and kernels are named).

    python3 bench/tools/trace_look.py --workload opt-6.7b-bcq3.chat \\
        --seconds 12 --out out/trace_opt_chat.json.gz

``--out`` keeps the whole reduced trace (``bench.devtrace.save``); the
test of the reduction reads a slice of one (``tests/bench/data``).
"""
import argparse
import collections
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.dirname(
                   os.path.abspath(__file__))]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=12)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered rate (default: the traffic file's)")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import devtrace, harness, layers, loadgen, spec
    from repro import obs

    bench = spec.load_benchmark(ROOT)
    wl = spec.workload(bench, args.workload)
    c = spec.config(bench, wl["config"])
    tr = spec.traffic(wl["traffic"])
    s = harness.build(c, args.seed)
    harness.warm_up(s, c)
    tracer = obs.Tracer(capacity=1 << 22, profiler_bridge=True)
    s.engine.attach_tracer(tracer)
    print(f"setup {time.perf_counter() - T_START:.1f}s; pool "
          f"{s.num_blocks} blocks", flush=True)
    items = loadgen.schedule(tr, args.seed, args.seconds, c["vocab_size"],
                             rate=args.rate)
    pre = loadgen.schedule(tr, args.seed, tr["preroll_s"], c["vocab_size"],
                           rate=args.rate, part=1)
    w = harness.run_window(s, items, args.seconds, tr["drain_s"],
                           tr["trace_s"], preroll=pre,
                           preroll_s=tr["preroll_s"])
    t_a, t_b, tdir = w.trace
    t0 = time.perf_counter()
    trace = devtrace.load(tdir)
    print(f"trace loaded in {time.perf_counter() - t0:.1f}s; e2e "
          f"{harness.end_to_end(w)}", flush=True)
    for p, lines in trace.devices.items():
        for l, evs in lines.items():
            print(f"DEVICE {p} / {l}: {len(evs)} events")
    for l, evs in trace.host.items():
        names = collections.Counter(e.name for e in evs)
        print(f"HOST {l}: {len(evs)} events; top {names.most_common(8)}")
    ops = trace.ops()
    tot = collections.Counter()
    for e in ops:
        tot[e.name] += e.dur
    print("TOP OPS")
    for n, d in tot.most_common(40):
        ex = next(e for e in ops if e.name == n)
        print(f"  {d * 1e3:9.3f} ms  {n}  {json.dumps(ex.stats)[:400]}")
    mods = collections.Counter(e.name for e in trace.device_lines(
        devtrace.MODULES)[0]) if trace.device_lines(devtrace.MODULES) else {}
    print(f"MODULES {dict(mods)}")
    ctx = layers.Context(engine=c["engine"], linears=s.linears,
                         shape=s.shape, window=(w.t0, w.t1),
                         trace_window=(t_a, t_b), device=trace,
                         tracer=tracer, recs=w.recs,
                         kind=jax.devices()[0].device_kind,
                         drain_end=w.drain_end)
    print(f"anchor {trace.anchor()} prof window {ctx.prof_window()} busy "
          f"{ctx.busy_s():.4f}s of {ctx.trace_seconds:.4f}s")
    for m in spec.per_layer(bench, args.workload):
        try:
            print(f"METRIC {m['name']}: {spec.reader(m).read(ctx)}")
        except Exception as ex:                  # noqa: BLE001
            print(f"METRIC {m['name']}: error {ex!r}")
    print(f"BREAKDOWN {json.dumps(ctx.breakdown())}")
    print(f"memory {jax.devices()[0].memory_stats()}")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    devtrace.save(trace, args.out)


if __name__ == "__main__":
    main()
