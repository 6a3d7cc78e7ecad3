"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig2_lru,...]

Prints ``name,us_per_call,derived`` CSV; JSON artifacts land in
experiments/bench/.  First run trains the tiny-moe artifact (~minutes);
subsequent runs hit the cache.
"""
from __future__ import annotations

import argparse
import sys
import time

SUITES = ["fig2_lru", "fig2_spec", "table1_quant", "table2_speed",
          "kernels", "serve"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes/grids for CI")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of suites")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig2_lru, fig2_spec, kernels_bench, serve_bench,
                            table1_quant, table2_speed)

    mods = {"fig2_lru": fig2_lru, "fig2_spec": fig2_spec,
            "table1_quant": table1_quant, "table2_speed": table2_speed,
            "kernels": kernels_bench, "serve": serve_bench}
    print("name,us_per_call,derived")
    failures = []
    for name in SUITES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mods[name].run(quick=args.quick)
            print(f"# [{name}] done in {time.time()-t0:.1f}s",
                  file=sys.stderr)
        except Exception as e:  # keep the harness going
            failures.append((name, repr(e)))
            print(f"# [{name}] FAILED: {e!r}", file=sys.stderr)
    if failures:
        sys.exit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
