import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST run before any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes and extract the roofline terms.

For each cell this proves (without hardware):
  * the sharding config is coherent (SPMD partitioning succeeds),
  * the step fits per-device HBM (``memory_analysis``),
  * and it yields HLO FLOPs / bytes / collective bytes for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out benchmarks/results/dryrun.json
"""

import argparse
import json
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro.core import hloanalysis, rmetric
from repro.launch import sharding, steps
from repro.launch.mesh import axis_sizes, make_production_mesh
from repro.models import transformer as T
from repro.optim import adamw

#: grad-accumulation (microbatch stream count) per arch for train_4k --
#: larger models need more microbatches to fit activations in HBM.  Max is
#: 16 (global batch 256 / data axis 16 must leave >= 1 row per device).
TRAIN_ACCUM: dict[str, int] = {
    "jamba-1.5-large-398b": 16,
    "internlm2-20b": 16,
    "gemma2-27b": 16,
    "mixtral-8x7b": 16,
    "qwen2-moe-a2.7b": 8,
    "qwen3-4b": 8,
    "phi4-mini-3.8b": 8,
    "mamba2-2.7b": 8,
    "paligemma-3b": 8,
    "whisper-medium": 4,
}

#: bf16 Adam moments where fp32 state cannot fit a single v5e pod.
MOMENT_DTYPE: dict[str, Any] = {
    "jamba-1.5-large-398b": jnp.bfloat16,
}

#: gather-once (ZeRO-2) weights: all archs whose full TP-sharded weights fit
#: HBM alongside activations; jamba's 50 GB/device full weights do not.
WEIGHT_GATHER_ONCE = frozenset(configs.list_archs()) - {"jamba-1.5-large-398b"}


def _spec_tree_for_batch(batch_shapes, mesh):
    return sharding.batch_specs(batch_shapes, mesh)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool):
    """Build + lower + compile one cell. Returns (compiled, lowered, meta)."""
    cfg = configs.get_config(arch)
    shape = configs.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = axis_sizes(mesh)
    n_chips = int(mesh.devices.size)

    params_shape = jax.eval_shape(
        lambda k: T.init_params(cfg, k), jax.random.PRNGKey(0))
    pspecs = sharding.param_specs(params_shape, mesh)
    params_in = sharding.shaped(params_shape, pspecs, mesh)

    if shape.kind == "train":
        accum = TRAIN_ACCUM.get(arch, 1)
        # each microbatch must still give >= 1 row per batch-sharded device
        batch_ways = sizes.get("pod", 1) * sizes.get("data", 1)
        accum = max(1, min(accum, shape.global_batch // batch_ways))
        opt_cfg = adamw.AdamWConfig(
            moment_dtype=MOMENT_DTYPE.get(arch, jnp.float32))
        opt_shape = jax.eval_shape(
            lambda p: adamw.init_state(p, opt_cfg.moment_dtype), params_shape)
        ospecs = sharding.opt_state_specs(pspecs)
        opt_in = sharding.shaped(opt_shape, ospecs, mesh)
        bshapes = steps.batch_shapes(
            cfg, global_batch=shape.global_batch, seq_len=shape.seq_len)
        bspecs = _spec_tree_for_batch(bshapes, mesh)
        batch_in = sharding.shaped(bshapes, bspecs, mesh)

        regather = None
        if arch in WEIGHT_GATHER_ONCE and accum > 1:
            regather = (sharding.to_named(sharding.drop_axis(pspecs), mesh),
                        sharding.to_named(pspecs, mesh))
        fn = steps.make_train_step(cfg, opt_cfg, accum=accum,
                                   regather_specs=regather)
        metrics_specs = {k: P() for k in ("loss", "ce", "aux", "grad_norm", "lr")}
        jitted = jax.jit(
            fn,
            in_shardings=(sharding.to_named(pspecs, mesh),
                          sharding.to_named(ospecs, mesh),
                          sharding.to_named(bspecs, mesh)),
            out_shardings=(sharding.to_named(pspecs, mesh),
                           sharding.to_named(ospecs, mesh),
                           sharding.to_named(metrics_specs, mesh)),
            donate_argnums=(0, 1),
        )
        args = (params_in, opt_in, batch_in)
        step_tokens = shape.global_batch * shape.seq_len
        model_flops = rmetric.model_flops(
            cfg.active_param_count(), step_tokens, backward=True)
    elif shape.kind == "prefill":
        bshapes = steps.batch_shapes(
            cfg, global_batch=shape.global_batch, seq_len=shape.seq_len)
        bspecs = _spec_tree_for_batch(bshapes, mesh)
        batch_in = sharding.shaped(bshapes, bspecs, mesh)
        cache_shape, _, _ = steps.decode_shapes(
            cfg, global_batch=shape.global_batch, seq_len=shape.seq_len)
        cspecs = sharding.cache_specs(cache_shape, mesh)
        lspec = sharding.logits_pspec(sizes, shape.global_batch, cfg.padded_vocab)

        fn = steps.make_prefill_step(cfg, max_seq=shape.seq_len)
        jitted = jax.jit(
            fn,
            in_shardings=(sharding.to_named(pspecs, mesh),
                          sharding.to_named(bspecs, mesh)),
            out_shardings=(jax.NamedSharding(mesh, lspec),
                           sharding.to_named(cspecs, mesh)),
        )
        args = (params_in, batch_in)
        step_tokens = shape.global_batch * shape.seq_len
        model_flops = rmetric.model_flops(
            cfg.active_param_count(), step_tokens, backward=False)
    else:  # decode
        cache_shape, tok_shape, len_shape = steps.decode_shapes(
            cfg, global_batch=shape.global_batch, seq_len=shape.seq_len)
        cspecs = sharding.cache_specs(cache_shape, mesh)
        cache_in = sharding.shaped(cache_shape, cspecs, mesh)
        tspec = sharding.batch_pspec(tok_shape.shape, sizes)
        tok_in = jax.ShapeDtypeStruct(
            tok_shape.shape, tok_shape.dtype,
            sharding=jax.NamedSharding(mesh, tspec))
        len_in = jax.ShapeDtypeStruct(
            len_shape.shape, len_shape.dtype,
            sharding=jax.NamedSharding(mesh, P()))
        lspec = sharding.logits_pspec(sizes, shape.global_batch, cfg.padded_vocab)

        fn = steps.make_decode_step(cfg)
        jitted = jax.jit(
            fn,
            in_shardings=(sharding.to_named(pspecs, mesh),
                          sharding.to_named(cspecs, mesh),
                          jax.NamedSharding(mesh, tspec),
                          jax.NamedSharding(mesh, P())),
            out_shardings=(jax.NamedSharding(mesh, lspec),
                           sharding.to_named(cspecs, mesh)),
            donate_argnums=(1,),
        )
        args = (params_in, cache_in, tok_in, len_in)
        model_flops = rmetric.model_flops(
            cfg.active_param_count(), shape.global_batch, backward=False)

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    meta = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "model_flops": model_flops,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    return compiled, lowered, meta


def analyse(compiled, meta: dict[str, Any]) -> dict[str, Any]:
    """Extract memory / cost / collective numbers from a compiled step.

    FLOPs/bytes/collective-bytes come from the trip-count-aware HLO walker
    (``repro.core.hloanalysis``): XLA's built-in cost analysis counts scan
    bodies once, under-reporting scanned programs by the trip count.
    """
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    cost = hloanalysis.analyse_hlo_text(hlo)
    flops, nbytes = cost.flops, cost.bytes

    terms = rmetric.roofline_from_cost(
        hlo_flops=flops, hlo_bytes=nbytes,
        collective_bytes=cost.collective_bytes, n_chips=meta["n_chips"])
    out = dict(meta)
    out.update({
        "hlo_flops": flops,
        "hlo_bytes": nbytes,
        "collective_bytes": cost.collective_bytes,
        "collective_breakdown": {
            k: v for k, v in cost.collective_by_op.items() if v},
        "mem_argument_bytes": mem.argument_size_in_bytes,
        "mem_output_bytes": mem.output_size_in_bytes,
        "mem_temp_bytes": mem.temp_size_in_bytes,
        "mem_generated_code_bytes": mem.generated_code_size_in_bytes,
        "t_compute_s": terms.compute,
        "t_memory_s": terms.memory,
        "t_collective_s": terms.collective,
        "bottleneck": terms.bottleneck,
        "t_serial_s": terms.total_serial,
        "t_overlapped_s": terms.total_overlapped,
        "roofline_fraction": terms.roofline_fraction(),
        "useful_flops_ratio": (
            meta["model_flops"] / (flops * meta["n_chips"])
            if flops else None),
        "paper_R": terms.as_stage_times().ratio(),
    })
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = False) -> dict[str, Any]:
    compiled, lowered, meta = lower_cell(arch, shape_name, multi_pod=multi_pod)
    if verbose:
        print(compiled.memory_analysis())  # proves it fits
        xla_flops, xla_bytes = rmetric.cost_analysis_scalars(
            compiled.cost_analysis())  # FLOPs/bytes for §Roofline
        print(f"[dryrun] xla cost_analysis: flops={xla_flops:.3e} "
              f"bytes={xla_bytes:.3e}")
    result = analyse(compiled, meta)
    print(f"[dryrun] {arch} x {shape_name} x {meta['mesh']}: "
          f"compile={meta['compile_s']}s bottleneck={result['bottleneck']} "
          f"frac={result['roofline_fraction']:.3f}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.all:
        cell_list = configs.cells()
        verbose = False
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cell_list = [(args.arch, args.shape)]
        verbose = True

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    results: list[dict[str, Any]] = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results if "error" not in r}

    for arch, shape_name in cell_list:
        for multi_pod in meshes:
            mesh_name = "2x16x16" if multi_pod else "16x16"
            if (arch, shape_name, mesh_name) in done:
                continue
            try:
                results.append(run_cell(arch, shape_name, multi_pod=multi_pod,
                                        verbose=verbose))
            except Exception as e:  # record the failure, keep sweeping
                traceback.print_exc()
                results.append({
                    "arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "error": f"{type(e).__name__}: {e}"})
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    n_err = sum("error" in r for r in results)
    print(f"[dryrun] {len(results) - n_err} ok, {n_err} failed")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
