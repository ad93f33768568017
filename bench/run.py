"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload opt-6.7b-bcq3.chat --seed 7 \\
        --seconds 40 --trace 0

Finds the cell in ``BENCHMARK.json`` at the root of the checkout, serves
its traffic open-loop through the program's ``AsyncServeFrontend`` for
``--seconds`` seconds, checks the served tokens against the plain
reference, and prints one JSON object as the last line of standard
output: the end-to-end metrics with ``--trace 0``, the per-layer metrics
(from a profiler trace of the window's last seconds) with ``--trace 1``.
The numbers the check compared, each beside its limit, are the last
lines of standard error and the result's last key.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program (``src/``) is not beside it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")

    # the benchmark's modules import as the package ``bench``, never as
    # top-level names from the script's own directory
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path.insert(0, ROOT)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit("[bench] FAIL: the program (src/repro) is not in "
                         "this checkout")
    sys.path.insert(0, src)
    from bench import harness, spec
    bench = spec.load_benchmark(ROOT)
    spec.workload(bench, args.workload)           # fail early on a typo

    from repro.launch.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    import jax
    # every program goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"compile cache: {cache}")
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START)
    harness.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
