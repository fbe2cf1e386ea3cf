"""Record the small device trace that ``test_chipbench_devtrace.py`` reduces.

    python3 chipbench/tests/record_trace.py

Needs a TPU. Serves the tiny configuration of ``chipbench/testdata/``
(``tiny.json`` under ``tiny_open.json``) for a short traced window through
the harness and writes, beside them, ``tiny_trace.xplane.pb.gz`` (the
profiler's trace) and ``tiny_trace.json``: the engine's spans, the anchor
reading, and what each per-layer reader and each reduction gave on the
chip.
"""

from __future__ import annotations

import time

PROCESS_T0_NS = time.perf_counter_ns()

import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "chipbench", "testdata")
SECONDS = 0.08
SEED = 2 ** 33 + 17


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, "chipbench", ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import devtrace, harness, traffic
    from chipbench.dims import dims_of, load_config

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    conf = load_config(os.path.join(DATA, "tiny.json"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    per_layer = [{k: m[k] for k in ("name", "unit")}
                 for m in bench["per_layer"]
                 if m["name"] != "preemptions"]
    cell = harness.Cell(
        name="tiny.open", root=harness.Path(ROOT), conf=conf,
        dims=dims_of(conf),
        mix=traffic.load_mix(os.path.join(DATA, "tiny_open.json")),
        chips=1, end_to_end=[], per_layer=per_layer)
    read = harness._Profiler.read

    def keep_trace(prof):
        path = sorted(prof.dir.rglob("*.xplane.pb"))[-1]
        with gzip.open(os.path.join(DATA, "tiny_trace.xplane.pb.gz"),
                       "wb") as f:
            f.write(path.read_bytes())
        return read(prof)

    harness._Profiler.read = keep_trace
    kept: dict = {}
    result = harness.run_cell(
        cell, SEED, SECONDS, trace=True, process_t0_ns=PROCESS_T0_NS,
        clock=harness.CompileClock(), log=print, keep=kept)
    run = kept["run"]
    dt = run.traces[0]
    record = {
        "seed": SEED, "seconds": SECONDS, "anchor_ns": dt.t0 + dt.offset_ns,
        "t0_ns": run.t0_ns, "t1_ns": run.t1_ns, "peaks": run.peaks,
        "spans": [[s.track, s.name, s.t0_ns, s.t1_ns, s.args]
                  for s in run.spans],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "busy_s": devtrace.busy_s(dt), "window_s": dt.window_s,
        "n_ops": len(dt.ops), "n_modules": len(dt.modules),
        "breakdown": result["breakdown"], "device": result["device"],
    }
    with open(os.path.join(DATA, "tiny_trace.json"), "w") as f:
        json.dump(record, f)
    print(json.dumps({k: v for k, v in record.items() if k != "spans"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
