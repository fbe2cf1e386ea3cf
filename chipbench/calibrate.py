"""Readings a cell's fixed numbers are set from; not part of a benchmark run.

    python3 chipbench/calibrate.py sweep --workload <cell> --seed <n> \
        --seconds <s> --rates 1,2,3
    python3 chipbench/calibrate.py limits --workload <cell> \
        --seeds 1,2,3 --seconds <s> [--control]

``sweep`` runs an open-loop cell at each arrival rate for a short window
(no drain: a request still queued at the close counts with its wait so
far) and prints, per rate, the time-to-first-token tail, the gap tail, the
share of due requests admitted by the close and the queue left: the knee
is the highest rate whose queue does not grow. ``limits`` runs the cell
once per seed, all in one process, and prints the widest served-token gap
of each run beside, with ``--control``, the float8 control's: the two
readings a limit lies between. Both need the TPU, like ``run.py``.
"""

from __future__ import annotations

import time

PROCESS_T0_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[calibrate] {msg}", file=sys.stderr, flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, "chipbench", ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench import harness

    if jax.devices()[0].platform != "tpu":
        log("needs a TPU")
        return 3
    cell = harness.load_cell(ROOT, args.workload)
    clock = harness.CompileClock()
    if args.mode == "sweep":
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = {**cell.mix, "rate_per_s": rate, "drain_s": 0.0}
            c = dataclasses.replace(cell, mix=mix, end_to_end=[
                {"name": n, "unit": "ms"} for n in ("ttft_p90_ms",
                                                    "itl_p95_ms")],
                per_layer=[])
            out = harness.run_cell(c, args.seed, args.seconds, trace=False,
                                   process_t0_ns=time.perf_counter_ns(),
                                   clock=clock, log=log, check=False)
            print(json.dumps({"rate_per_s": rate, **out["sweep"],
                              **{k: v["value"]
                                 for k, v in out["metrics"].items()}}),
                  flush=True)
        return 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter_ns()
        out = harness.run_cell(cell, seed, args.seconds, trace=False,
                               process_t0_ns=t0, clock=clock, log=log,
                               control=args.control)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          **{k: v["value"] for k, v in out["check"].items()},
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
