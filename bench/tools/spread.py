"""Two sets of runs of one cell, each run its own process, and the spread
of every end-to-end metric in each set.

    python3 bench/tools/spread.py --workload opt-6.7b-bcq3.chat \\
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 40 \\
        --out out/spread_opt_chat.jsonl [--trace-seeds 21,22,23] \
        [--first-seed 10]

Runs ``bench/run.py`` exactly as a check does: first one run recorded
apart (``--first-seed``; in a fresh checkout it compiles, and the first
process on a machine loads everything cold), then set after set with
the same seeds, then the traced runs.  This process never touches JAX, so
each child has the chip to itself.  A spread is the distance between
the first and third quartile (``statistics.quantiles(values, n=4)``) over
the median; a bound is set at about five times the widest spread.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from bench import stats  # noqa: E402  (no JAX: the children own the chip)


def run(args, seed, trace):
    cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"),
           "--workload", args.workload, "--seed", str(seed),
           "--seconds", str(args.seconds), "--trace", str(trace)]
    t0 = time.time()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    out = None
    if p.returncode == 0 and lines:
        out = json.loads(lines[-1])
    err = [l for l in p.stderr.splitlines() if l.startswith("[bench]")]
    return {"seed": seed, "trace": trace, "rc": p.returncode,
            "wall_s": time.time() - t0, "result": out, "log": err,
            "stderr_tail": p.stderr[-1500:] if p.returncode else ""}


def spreads(rows):
    vals = {}
    for r in rows:
        if r["result"] is None:
            continue
        for k, m in r["result"]["metrics"].items():
            vals.setdefault(k, []).append(m["value"])
    return {k: {"median": statistics.median(v),
                "spread": stats.spread(v) if len(v) >= 2 else None}
            for k, v in vals.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--first-seed", type=int, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    tseeds = [int(s) for s in args.trace_seeds.split(",") if s]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sets = []
    with open(args.out, "a") as f:
        if args.first_seed is not None:
            r = run(args, args.first_seed, 0)
            r["set"] = "first"
            f.write(json.dumps(r) + "\n")
            f.flush()
            res = r["result"] or {}
            print(json.dumps({"first_seed": args.first_seed, "rc": r["rc"],
                              "wall_s": round(r["wall_s"], 1),
                              "correct": res.get("correct"),
                              "metrics": {m: v["value"] for m, v in
                                          res.get("metrics", {}).items()},
                              "check": res.get("check")}), flush=True)
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                r = run(args, seed, 0)
                r["set"] = k
                rows.append(r)
                f.write(json.dumps(r) + "\n")
                f.flush()
                res = r["result"] or {}
                print(json.dumps({"set": k, "seed": seed, "rc": r["rc"],
                                  "wall_s": round(r["wall_s"], 1),
                                  "correct": res.get("correct"),
                                  "failed": res.get("failed"),
                                  "metrics": {m: v["value"] for m, v in
                                              res.get("metrics", {}).items()},
                                  "check": res.get("check")}), flush=True)
            sets.append(spreads(rows))
        for seed in tseeds:
            r = run(args, seed, 1)
            r["set"] = "trace"
            f.write(json.dumps(r) + "\n")
            f.flush()
            res = r["result"] or {}
            print(json.dumps({"trace_seed": seed, "rc": r["rc"],
                              "wall_s": round(r["wall_s"], 1),
                              "correct": res.get("correct"),
                              "metrics": {m: v["value"] for m, v in
                                          res.get("metrics", {}).items()},
                              "device": res.get("device"),
                              "check": res.get("check")}), flush=True)
    for k, sp in enumerate(sets):
        print(json.dumps({"set": k, "spreads": {
            m: {"median": v["median"], "spread": v["spread"]}
            for m, v in sp.items()}}), flush=True)


if __name__ == "__main__":
    main()
