"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per module.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig8,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller sample counts (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks import (bench_dispatch, fig4_7_cab_policies,
                            fig8_theory_vs_sim, fig9_12_grin_policies,
                            fig13_grin_vs_slsqp, fig14_runtime,
                            fig15_16_real_platform, grin_plus_gap)

    jobs = {
        "dispatch": lambda: bench_dispatch.run(smoke=args.fast),
        "fig4_7": lambda: fig4_7_cab_policies.run(
            n_completions=2500 if args.fast else 5000,
            warmup=500 if args.fast else 1000),
        "fig8": lambda: fig8_theory_vs_sim.run(
            n_completions=3000 if args.fast else 6000,
            warmup=600 if args.fast else 1200),
        "fig9_12": lambda: fig9_12_grin_policies.run(
            n_samples=4 if args.fast else 10,
            n_static=60 if args.fast else 200,
            n_completions=2000 if args.fast else 4000),
        "fig13": lambda: fig13_grin_vs_slsqp.run(
            n_runs=10 if args.fast else 30),
        "fig14": lambda: fig14_runtime.run(
            n_runs=15 if args.fast else 40),
        "fig15_16": lambda: fig15_16_real_platform.run(),
        "grin_plus": lambda: grin_plus_gap.run(
            n_runs=60 if args.fast else 200),
    }
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in jobs.items():
        if only and name not in only:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
