"""Find a cell's knee: serve its mix at several offered rates in one
process and print, per rate, what was offered and what was served.

    python3 bench/tools/sweep.py --workload opt-6.7b-bcq3.chat \\
        --rates 1,2,3,4 --seconds 20

The knee is the highest rate whose requests are all served with a queue
that does not grow: none failed, at most one in twenty (and at least
one) still without a first token when the window closes, and output
tokens/s at least 0.85 of the offered tokens/s.  A cell's traffic file
then states 0.8 of it as a number (``--set-rate`` writes it there); the
benchmark's runs never sweep.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.dirname(
                   os.path.abspath(__file__))]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--set-rate", action="store_true",
                    help="write 0.8 x the knee into the cell's traffic file")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness, loadgen, spec, stats
    from repro.serve import AsyncServeFrontend

    bench = spec.load_benchmark(ROOT)
    wl = spec.workload(bench, args.workload)
    c = spec.config(bench, wl["config"])
    tr = spec.traffic(wl["traffic"])
    s = harness.build(c, args.seed)
    harness.warm_up(s, c)
    rows = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        items = loadgen.schedule(tr, args.seed, args.seconds,
                                 c["vocab_size"], rate=rate)
        pre = loadgen.schedule(tr, args.seed, tr["preroll_s"],
                               c["vocab_size"], rate=rate, part=1)
        s.frontend = AsyncServeFrontend(s.engine,
                                        max_queue=c["engine"]["max_queue"])
        w = harness.run_window(s, items, args.seconds, tr["drain_s"],
                               uid_base=10**6 * k, preroll=pre,
                               preroll_s=tr["preroll_s"])
        # requests that had not been admitted when the window closed
        late = sum(1 for r in w.recs if r.req is not None and
                   (not r.times or r.times[0] > w.t1))
        ttft = [r.times[0] - r.due for r in w.recs if r.ok and r.times]
        e2e = harness.end_to_end(w)
        rows.append({
            "rate": rate, "requests": len(items),
            "offered_tok_per_s": sum(it.max_new for it in items)
            / args.seconds,
            **e2e,
            "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
            "no_first_token_at_close": late,
            "failed": sum(1 for r in w.recs if not r.ok),
            "compiles": w.compiles,
            "ttft_ms_by_prompt_len": sorted(
                [len(r.item.prompt), round((r.times[0] - r.due) * 1e3, 1)]
                for r in w.recs if r.ok and r.times),
            "drain_s": w.drain_end - w.t1})
        print(json.dumps(rows[-1]), flush=True)
    knee = None                  # the highest rate below which all held
    for r in sorted(rows, key=lambda r: r["rate"]):
        if not knee_ok(r):
            break
        knee = r["rate"]
    print(json.dumps({"knee": knee}), flush=True)
    if args.set_rate and knee:
        path = os.path.join(ROOT, "bench", "traffic", wl["traffic"] + ".json")
        tr["arrivals"]["rate_per_s"] = round(0.8 * knee, 3)
        tr["rate_from"] = (
            f"0.8 x the knee {knee}/s of a sweep on one TPU v5e over "
            f"{args.rates} requests/s ({args.seconds:g} s windows after "
            f"{tr['preroll_s']:g} s of pre-roll, seed {args.seed})")
        with open(path, "w") as f:
            json.dump(tr, f, indent=2)
        print(f"wrote rate {tr['arrivals']['rate_per_s']} to {path}",
              flush=True)


def knee_ok(r: dict) -> bool:
    return (r["failed"] == 0
            and r["no_first_token_at_close"] <= max(1, r["requests"] // 20)
            and r["output_tok_per_s"] >= 0.85 * r["offered_tok_per_s"])


if __name__ == "__main__":
    main()
