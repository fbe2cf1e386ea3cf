"""Pass 2: Pallas kernel lint over the ``kernels/`` package.

Each kernel module exports ``KERNEL_META`` — the grid/BlockSpec layout
factory (``build_specs``) the kernel call itself uses, plus lint-time
shapes that exercise multi-block grids.  Because the specs the lint sees
are the specs the kernel runs with, a layout edit that stops matching the
wrapper-declared operand shapes fails here before it fails on a TPU.

Rules:

* **KRN001** — BlockSpec/grid inconsistency: block rank vs operand rank,
  block dims that don't divide the operand dims, last two block dims that
  break the TPU tiling rule (divisible by 8 and 128, or equal to the
  operand's dims — Mosaic refuses anything else at compile time), index
  maps whose arity doesn't match ``len(grid) + num_scalar_prefetch`` or
  that return the wrong number of coordinates. An operand left in HBM
  (``memory_space=pl.ANY``, no block) must be declared in the layout's
  ``hbm_operands`` with the rank the body's copies index, which must be
  the operand's; a blockless spec that is not declared is a finding.
* **KRN002** — a scalar-prefetch operand no index map ever reads: the
  kernel DMAs the scalars every step and then ignores them (a dead
  prefetch is almost always a page-table wiring bug). A prefetch that the
  body's own DMAs index instead is declared in ``KERNEL_META``'s
  ``dma_index_operands``; that declaration holds only while the layout
  has an HBM operand for such a DMA to read.
* **KRN003** — dtype contract between the quantized kernels and the
  ``kernels.quant`` pool layout: pools enter as the storage dtype, scales
  as f32 with the per-(page, kv-head) shape, output comes back in the
  query dtype (dequantization stays fused, never materialized).
* **KRN004** — ops<->ref oracle parity: every oracle parameter exists on
  the jitted wrapper, and wrapper extras are kernel-only knobs.
"""

from __future__ import annotations

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis import Finding

#: ops.* wrapper -> ref.* oracle, for KRN004 signature parity.
ORACLE_PAIRS = (
    ("matmul", "matmul_ref"),
    ("flash_attention", "attention_ref"),
    ("paged_attention", "paged_attention_ref"),
    ("paged_attention_multi", "paged_attention_multi_ref"),
    ("paged_attention_quant", "paged_attention_quant_ref"),
    ("paged_attention_multi_quant", "paged_attention_multi_quant_ref"),
    ("fwt", "fwt_ref"),
    ("nw_tile", "nw_ref"),
    ("nw_wavefront", "nw_full_ref"),
)

#: Wrapper-only parameters that tune the kernel schedule, not the math —
#: the oracle legitimately lacks them.
KERNEL_KNOBS = frozenset(
    {"interpret", "block_q", "block_k", "block_m", "block_n", "block",
     "row_tile", "chunk"})


class _Recorder:
    """Stands in for a scalar-prefetch ref inside an index map; records
    whether any map actually indexes it (KRN002)."""

    def __init__(self) -> None:
        self.used = False

    def __getitem__(self, _key):
        self.used = True
        return 0


def _check_spec(name: str, what: str, spec, op_shape, grid, n_prefetch: int,
                recorders, findings: list[Finding]) -> None:
    """KRN001 checks for one BlockSpec against its declared operand."""
    target = f"{name}:{what}"
    block = tuple(spec.block_shape)
    if len(block) != len(op_shape):
        findings.append(Finding(
            "KRN001", target,
            f"block rank {len(block)} != operand rank {len(op_shape)} "
            f"(block {block} vs operand {tuple(op_shape)})", "kernel"))
        return
    for d, (b, s) in enumerate(zip(block, op_shape)):
        if b is None:
            continue
        if b <= 0 or s % b:
            findings.append(Finding(
                "KRN001", target,
                f"block dim {d} = {b} does not tile operand dim {s}",
                "kernel"))
    if len(block) >= 2:
        for d, tile in ((len(block) - 2, 8), (len(block) - 1, 128)):
            b, s = block[d], op_shape[d]
            if b is not None and b % tile and b != s:
                findings.append(Finding(
                    "KRN001", target,
                    f"block dim {d} = {b} is neither a multiple of {tile} "
                    f"nor the whole operand dim {s} (TPU tiling rule)",
                    "kernel"))
    sig = inspect.signature(spec.index_map)
    arity = len(sig.parameters)
    want = len(grid) + n_prefetch
    if arity != want:
        findings.append(Finding(
            "KRN001", target,
            f"index_map takes {arity} args, grid+prefetch supply {want}",
            "kernel"))
        return
    coords = spec.index_map(*(list(range(len(grid))) + list(recorders)))
    if not isinstance(coords, tuple):
        coords = (coords,)
    if len(coords) != len(block):
        findings.append(Finding(
            "KRN001", target,
            f"index_map returns {len(coords)} coordinates for a rank-"
            f"{len(block)} block", "kernel"))


def check_layout(name: str, meta: dict) -> list[Finding]:
    """KRN001/KRN002 for one KERNEL_META entry."""
    findings: list[Finding] = []
    sp = meta["build"](**meta["lint_shapes"])
    grid = sp["grid"]
    n_prefetch = sp.get("num_scalar_prefetch", 0)
    in_specs = list(sp["in_specs"])
    operands = list(sp["operands"])
    if len(in_specs) != len(operands):
        findings.append(Finding(
            "KRN001", name,
            f"{len(in_specs)} in_specs for {len(operands)} declared "
            "operands", "kernel"))
        return findings
    if len(grid) != len(meta.get("grid_dims", grid)):
        findings.append(Finding(
            "KRN001", name,
            f"grid rank {len(grid)} != documented grid_dims "
            f"{meta['grid_dims']}", "kernel"))
    recorders = [_Recorder() for _ in range(n_prefetch)]
    hbm = sp.get("hbm_operands", {})
    for i, (spec, op) in enumerate(zip(in_specs, operands)):
        if i in hbm or spec.block_shape is None:
            _check_hbm(name, f"in[{i}]", spec, op, hbm.get(i), findings)
        else:
            _check_spec(name, f"in[{i}]", spec, op, grid, n_prefetch,
                        recorders, findings)
    _check_spec(name, "out", sp["out_specs"], sp["out_shape"], grid,
                n_prefetch, recorders, findings)
    index_ops = sp.get("prefetch_index_operands",
                       tuple(range(n_prefetch)))
    dma_ops = meta.get("dma_index_operands", ()) if hbm else ()
    for i, rec in enumerate(recorders):
        if i in index_ops and not rec.used and i not in dma_ops:
            findings.append(Finding(
                "KRN002", f"{name}:prefetch[{i}]",
                "scalar-prefetch operand is declared index-bearing but no "
                "index_map ever reads it (dead prefetch)", "kernel"))
    return findings


def _check_hbm(name: str, what: str, spec, op_shape, rank,
               findings: list[Finding]) -> None:
    """KRN001 for an operand the kernel leaves in HBM and copies itself."""
    target = f"{name}:{what}"
    if rank is None:
        findings.append(Finding(
            "KRN001", target,
            "blockless spec not declared in hbm_operands", "kernel"))
    elif spec.block_shape is not None or spec.memory_space != pl.ANY:
        findings.append(Finding(
            "KRN001", target,
            f"declared HBM-resident but its spec is blocked "
            f"({spec.block_shape}) in {spec.memory_space}", "kernel"))
    elif rank != len(op_shape):
        findings.append(Finding(
            "KRN001", target,
            f"copies index rank {rank}, operand {tuple(op_shape)} has rank "
            f"{len(op_shape)}", "kernel"))


def check_quant_contract() -> list[Finding]:
    """KRN003: the quant kernels accept pools in ``quant.storage_dtype``
    with per-(page, kv-head) f32 scales and return the query dtype."""
    from repro.kernels import ops, paged_attention, quant

    findings: list[Finding] = []
    b, h, hkv, hd, nb, bs = 2, 4, 2, 8, 9, 8
    for kind in quant.KV_DTYPES:
        if not quant.is_quantized(kind):
            continue
        code = quant.storage_dtype(kind)
        q = jax.ShapeDtypeStruct((b, h, hd), jnp.bfloat16)
        pool = jax.ShapeDtypeStruct((nb, bs, hkv, hd), code)
        scale = jax.ShapeDtypeStruct((nb, hkv), jnp.float32)
        table = jax.ShapeDtypeStruct((b, 4), jnp.int32)
        cur = jax.ShapeDtypeStruct((b,), jnp.int32)
        try:
            out = jax.eval_shape(
                functools.partial(ops.paged_attention_quant, interpret=True),
                q, pool, pool, scale, scale, table, cur)
        except Exception as e:  # noqa: BLE001 - any trace failure is the bug
            findings.append(Finding(
                "KRN003", f"paged_attention_quant[{kind}]",
                f"kernel rejects the quant.py pool layout: "
                f"{type(e).__name__}: {str(e).splitlines()[0]}", "kernel"))
            continue
        if out.dtype != q.dtype:
            findings.append(Finding(
                "KRN003", f"paged_attention_quant[{kind}]",
                f"output dtype {out.dtype} != query dtype {q.dtype} "
                "(dequant must stay fused in the kernel)", "kernel"))
        # The scale layout the kernel prefetches must be the one
        # quant.scales_of produces for a page of rows.
        rows = jnp.zeros((bs, hkv, hd), jnp.float32)
        sc = quant.scales_of(rows, kind)
        if sc.shape != (hkv,) or sc.dtype != jnp.float32:
            findings.append(Finding(
                "KRN003", f"quant.scales_of[{kind}]",
                f"per-page scale is {sc.shape} {sc.dtype}, kernel expects "
                "(kv_heads,) float32 per page", "kernel"))
    # The scale operand the kernel's BlockSpec tiles must be the layers'
    # stack of (num_blocks, kv_heads) scale pools: the layer axis first,
    # then the page axis, kv heads last.
    layers = 3
    sp = paged_attention.build_specs(b, hkv, h // hkv, hd, nb, bs, 4,
                                     quantized=True, layers=layers)
    for i in (3, 4):
        view = tuple(sp["operands"][i])
        if view != (layers, nb, hkv):
            findings.append(Finding(
                "KRN003", f"paged_attention:in[{i}]",
                f"kernel tiles scales as {view}, not the ({layers}, {nb}, "
                f"{hkv}) stack of per-page scale pools", "kernel"))
    return findings


def check_oracle_parity() -> list[Finding]:
    """KRN004: ops.* and ref.* agree on the math-relevant signature."""
    from repro.kernels import ops, ref

    findings: list[Finding] = []
    for op_name, ref_name in ORACLE_PAIRS:
        op_fn = getattr(ops, op_name, None)
        ref_fn = getattr(ref, ref_name, None)
        if op_fn is None or ref_fn is None:
            findings.append(Finding(
                "KRN004", f"{op_name}<->{ref_name}",
                "oracle pair is missing one side", "kernel"))
            continue
        op_params = set(inspect.signature(op_fn).parameters)
        ref_params = set(inspect.signature(ref_fn).parameters)
        missing = ref_params - op_params
        if missing:
            findings.append(Finding(
                "KRN004", op_name,
                f"oracle parameters {sorted(missing)} missing from the "
                "jitted wrapper", "kernel"))
        extras = op_params - ref_params - KERNEL_KNOBS
        if extras:
            findings.append(Finding(
                "KRN004", op_name,
                f"wrapper-only parameters {sorted(extras)} are not "
                "declared kernel knobs — the oracle can't cover them",
                "kernel"))
    return findings


def audit_kernels() -> list[Finding]:
    """Run the full kernel lint: every KERNEL_META layout, the quant dtype
    contract, and ops<->ref parity."""
    from repro.kernels import flash_attention, paged_attention

    findings: list[Finding] = []
    for mod in (flash_attention, paged_attention):
        for name, meta in mod.KERNEL_META.items():
            findings.extend(check_layout(name, meta))
    findings.extend(check_quant_contract())
    findings.extend(check_oracle_parity())
    return findings
