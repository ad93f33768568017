"""Run the full dry-run matrix: every (arch x shape) on single-pod
(+roofline) and multi-pod (compile proof).  Each cell runs in a fresh
subprocess (jax locks the fake-device count at first init) with a
timeout; results land in ``results_dir`` as one JSON per cell.

  PYTHONPATH=src python -m repro.launch.run_all [--out benchmarks/results/dryrun]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def cells():
    from repro.configs import ARCH_IDS, SHAPES, get_config
    for arch in ARCH_IDS:
        if arch == "opt_6_7b":
            continue                      # paper arch: bench suite covers it
        cfg = get_config(arch)
        for shape in SHAPES:
            if shape == "long_500k" and not cfg.supports_long_context():
                continue
            yield arch, shape


def run_cell(arch, shape, multi_pod, out_dir, timeout=1500, extra=()):
    tag = f"{arch}__{shape}__{'multi' if multi_pod else 'single'}"
    out_json = os.path.join(out_dir, tag + ".json")
    if os.path.exists(out_json):
        print(f"[run_all] skip {tag} (exists)")
        return True
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape, "--json-out", out_json]
    if multi_pod:
        cmd += ["--multi-pod", "--no-roofline"]
    cmd += list(extra)
    t0 = time.time()
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout,
                           env={**os.environ, "PYTHONPATH": "src",
                                "JAX_PLATFORMS": "cpu"})
        ok = r.returncode == 0
        if not ok:
            skip = "SKIP:" in (r.stdout + r.stderr)
            with open(out_json.replace(".json", ".log"), "w") as f:
                f.write(r.stdout + "\n---STDERR---\n" + r.stderr)
            if skip:
                with open(out_json, "w") as f:
                    json.dump({"arch": arch, "shape": shape,
                               "multi_pod": multi_pod, "skipped": True}, f)
                print(f"[run_all] {tag}: SKIP (documented)")
                return True
    except subprocess.TimeoutExpired:
        ok = False
        with open(out_json.replace(".json", ".log"), "w") as f:
            f.write(f"TIMEOUT after {timeout}s")
    print(f"[run_all] {tag}: {'OK' if ok else 'FAIL'} "
          f"({time.time()-t0:.0f}s)")
    return ok


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="benchmarks/results/dryrun")
    p.add_argument("--timeout", type=int, default=1500)
    p.add_argument("--only", default="", help="substring filter on arch")
    p.add_argument("--multi-only", action="store_true")
    p.add_argument("--single-only", action="store_true")
    args = p.parse_args()
    os.makedirs(args.out, exist_ok=True)
    results = {}
    for arch, shape in cells():
        if args.only and args.only not in arch:
            continue
        if not args.multi_only:
            results[(arch, shape, "single")] = run_cell(
                arch, shape, False, args.out, args.timeout)
        if not args.single_only:
            results[(arch, shape, "multi")] = run_cell(
                arch, shape, True, args.out, args.timeout)
    n_ok = sum(results.values())
    print(f"[run_all] {n_ok}/{len(results)} cells OK")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
