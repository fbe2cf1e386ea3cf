"""Run one benchmark cell once on the TPU and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. The cell, its configuration and its
traffic mix are found by name through ``BENCHMARK.json``. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
a ``breakdown``, and last ``check``: each number compared with the plain
reference beside its limit. The same numbers are the last lines of
standard error.

Exits non-zero without a result when JAX finds no TPU or fewer chips than
the cell asks for, and when the system under test (``src/repro``) is not
in the checkout. JAX's persistent compilation cache is kept in
``chipbench/.jax_cache`` inside the checkout, whatever the environment
says, so that only a cell's first run in a checkout compiles.
"""

from __future__ import annotations

import time

PROCESS_T0_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"the system under test is not in {ROOT} (no src/repro)")
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, "chipbench", ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}; jax {jax.__version__}")
    if dev.platform != "tpu":
        log(f"needs a TPU; JAX found platform {dev.platform!r}")
        return 3
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, JAX found {len(devices)}")
        return 3
    clock = harness.CompileClock()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              trace=bool(args.trace),
                              process_t0_ns=PROCESS_T0_NS, clock=clock,
                              log=log)
    for name, c in result["check"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
