"""Benchmark harness — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--skip-slow]``

Prints ``name,us_per_call,derived`` CSV rows (plus per-benchmark claim
checks that assert the paper's headline numbers within model tolerance).
"""
import argparse
import sys
import time
import traceback


BENCHES = [
    ("table1_complexity", "benchmarks.bench_complexity"),
    ("fig6_lut_power", "benchmarks.bench_fig6_lut_power"),
    ("fig8_fanout", "benchmarks.bench_fig8_fanout"),
    ("fig11_generator", "benchmarks.bench_fig11_generator"),
    ("fig13_area", "benchmarks.bench_fig13_area"),
    ("fig15_energy", "benchmarks.bench_fig15_energy"),
    ("fig16_topsw", "benchmarks.bench_fig16_topsw"),
    ("table5_comparison", "benchmarks.bench_table5_comparison"),
    ("kernels", "benchmarks.bench_kernels"),
    ("serve", "benchmarks.bench_serve"),                       # paged engine
    ("table4_numerics", "benchmarks.bench_table4_numerics"),   # trains tiny LM
    ("fig17_tradeoff", "benchmarks.bench_fig17_tradeoff"),     # reuses it
]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--skip-slow", action="store_true",
                   help="skip the tiny-LM training benches")
    p.add_argument("--only", default="")
    args = p.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    print(f"compile_cache,{setup_compile_cache()}")
    failures = []
    for name, mod_name in BENCHES:
        if args.only and args.only not in name:
            continue
        if args.skip_slow and name in ("table4_numerics", "fig17_tradeoff"):
            print(f"{name},SKIPPED,--skip-slow")
            continue
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run()
            print(f"{name},{(time.time()-t0)*1e6:.0f},PASS")
        except AssertionError as e:
            failures.append(name)
            print(f"{name},{(time.time()-t0)*1e6:.0f},CLAIM-CHECK-FAIL: {e}")
        except Exception:
            failures.append(name)
            traceback.print_exc()
            print(f"{name},{(time.time()-t0)*1e6:.0f},ERROR")
    if failures:
        print(f"\nFAILED: {failures}")
        sys.exit(1)
    print("\nall benchmarks passed")


if __name__ == "__main__":
    main()
