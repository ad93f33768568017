"""Readings that set a cell's limit, in one process: for each seed, the
program's widest served-token gap and the control's on the same sample.

    python3 bench/tools/calibrate.py --workload opt-6.7b-bcq3.chat \\
        --seeds 101-112 --seconds 20 [--fault token]

Each seed draws its own weights and traffic, serves the cell's mix at
the cell's rate for ``--seconds`` through the timed path, and compares
as ``bench/run.py`` does (the same sample of finished requests), judged
by the same rule against the cell's limit (``harness.judge``).  The
control is the reference with float8 linears put in the program's
place (see ``bench/reference/common.py``), judged the same way: its
``control_correct`` has to come out false.  ``--fault token`` instead
plants a fault in the timed path -- every token the decode step samples
is shifted by one where it is produced -- and reads the program's gap.
The benchmark's own runs never run any of this.  One JSON line per seed.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.dirname(
                   os.path.abspath(__file__))]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def seeds(arg: str):
    if "-" in arg:
        a, b = arg.split("-")
        return list(range(int(a), int(b) + 1))
    return [int(x) for x in arg.split(",")]


def plant_token_fault():
    """Shift every token the decode step samples by one, on the device."""
    from repro.models.model import Model
    orig = Model.decode_and_sample

    def shifted(self, *a, **kw):
        toks, cache = orig(self, *a, **kw)
        return (toks + 1) % self.cfg.vocab_size, cache
    Model.decode_and_sample = shifted


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--fault", choices=("", "token"), default="")
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness, loadgen, spec

    if args.fault == "token":
        plant_token_fault()
    bench = spec.load_benchmark(ROOT)
    wl = spec.workload(bench, args.workload)
    c = spec.config(bench, wl["config"])
    tr = spec.traffic(wl["traffic"])
    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        s = harness.build(c, seed)
        harness.warm_up(s, c)
        items = loadgen.schedule(tr, seed, args.seconds, c["vocab_size"])
        pre = loadgen.schedule(tr, seed, tr["preroll_s"], c["vocab_size"],
                               part=1)
        w = harness.run_window(s, items, args.seconds, tr["drain_s"],
                               preroll=pre, preroll_s=tr["preroll_s"])
        e2e = harness.end_to_end(w)
        sample = harness.sample_finished(w, tr["reference_sample"], seed)
        failed = sum(1 for r in w.recs if not r.ok)
        short = harness.short_outputs(w.recs)
        s.engine.cache = None
        s.engine = s.frontend = None
        gc.collect()
        t1 = time.perf_counter()
        got = harness.check(c, tr, s, sample)
        t2 = time.perf_counter()
        ctl = None if args.no_control or args.fault else \
            harness.check(c, tr, s, sample, control=True)
        correct, compared = harness.judge(got, short, c, tr)
        print(json.dumps({
            "seed": seed, "fault": args.fault or None,
            "program_gap": got["max_logit_gap"], "correct": correct,
            "control_gap": ctl and ctl["max_logit_gap"],
            "control_correct": ctl and harness.judge(ctl, short, c, tr)[0],
            "compared": compared,
            "tokens": got["tokens_compared"], "failed": failed,
            "attempted": len(items), "e2e": e2e, "compiles": w.compiles,
            "serve_s": t1 - t0, "reference_s": t2 - t1,
            "peak": (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use")}), flush=True)
        del s, w, sample
        gc.collect()


if __name__ == "__main__":
    main()
