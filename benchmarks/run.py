# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness:

  bench_rmetric    -> Fig. 1 (CDF of R), Fig. 2-4 (R vs size/variant/platform)
  bench_overlap    -> Fig. 9 (single vs multi stream) + lavaMD negative case
  bench_categorize -> Table 2 (dependency categorization)
  bench_roofline   -> §Roofline table from the dry-run artifacts (e)/(g)
  bench_serving    -> continuous-batching tokens/s vs sequential baseline

Run: PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single bench: "
                         "rmetric|overlap|categorize|roofline|serving")
    args = ap.parse_args()

    from benchmarks import (bench_categorize, bench_overlap, bench_rmetric,
                            bench_roofline, bench_serving)

    benches = {
        "categorize": bench_categorize.run,
        "overlap": bench_overlap.run,
        "rmetric": bench_rmetric.run,
        "roofline": bench_roofline.run,
        "serving": bench_serving.run,
    }
    if args.only:
        benches = {args.only: benches[args.only]}

    failures = 0
    for name, fn in benches.items():
        t0 = time.perf_counter()
        try:
            lines = fn()
        except Exception as e:  # report and continue
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
            failures += 1
            continue
        dt = (time.perf_counter() - t0) * 1e6
        print(f"{name}/_total,{dt:.0f},us", flush=True)
        for line in lines:
            print(line, flush=True)
        if name == "serving":
            # Refresh the committed baseline the regression sentinel
            # (repro.obs.baseline / `make bench-check`) gates against.
            print(f"# wrote {bench_serving.write_json(lines)}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
