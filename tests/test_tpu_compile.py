"""Compile-only checks of the serving kernels for a described TPU v5e.

Interpret mode runs the kernel bodies on the CPU, but it does not apply the
TPU compiler's tiling and VMEM rules. These tests compile the paged-attention
kernel at qwen3-4b's published widths (H=32, Hkv=8, hd=128, bf16 queries, 16
rows per page) for one chip of a described ``v5e:2x2`` topology; nothing runs.
They also compile the serving programs that are donated the page pool, at
a tiny size, to show that the chip's compiler updates the pool in place.

The topology is described inside a fixture, never at import: the TPU library
may be loaded by one process at a time.
"""

import dataclasses
import functools
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import transformer as T
from repro.models.transformer import LayerSpec
from repro.runtime.serving import ServeConfig, StreamedBatchEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import devtrace  # noqa: E402

B, H, HKV, HD = 4, 32, 8, 128  # qwen3-4b heads, a 4-slot decode batch
NUM_BLOCKS, BLOCK_SIZE, N_PAGES = 512, 16, 18
SPEC_K = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("layers", [None, 36])
@pytest.mark.parametrize("variant", ["plain", "multi", "int8", "fp8"])
def test_paged_attention_compiles_for_v5e(variant, layers, one_chip,
                                          no_persistent_cache):
    """One layer's pool, and the stacked pools of qwen3-4b's 36 layers
    read at a traced layer index."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    stack = () if layers is None else (layers,)
    pool_dtype = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}.get(
        variant, jnp.bfloat16)
    q_len = SPEC_K + 1 if variant == "multi" else 1
    q = shape((B, q_len, H, HD) if q_len > 1 else (B, H, HD), jnp.bfloat16)
    pool = shape(stack + (NUM_BLOCKS, BLOCK_SIZE, HKV, HD), pool_dtype)
    table = shape((B, N_PAGES), jnp.int32)
    cur = shape((B,), jnp.int32)
    layer = None if layers is None else shape((), jnp.int32)
    scale = 1.0 / HD ** 0.5
    if variant in ("int8", "fp8"):
        scales = shape(stack + (NUM_BLOCKS, HKV), jnp.float32)
        fn = lambda q, k, v, ks, vs, t, c, ly: pa.paged_attention_kernel(  # noqa: E731
            q, k, v, t, c, scale=scale, k_scale=ks, v_scale=vs, layer=ly)
        args = (q, pool, pool, scales, scales, table, cur, layer)
    else:
        kern = (pa.paged_attention_multi_kernel if q_len > 1
                else pa.paged_attention_kernel)
        fn = lambda q, k, v, t, c, ly: kern(  # noqa: E731
            q, k, v, t, c, scale=scale, layer=ly)
        args = (q, pool, pool, table, cur, layer)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_keeps_its_op_name_at_cell_shapes(one_chip,
                                                          no_persistent_cache):
    """qwen3-4b's decode batch as the benchmark serves it: 32 rows, a table
    of 256 pages of 16 rows, the stacked pools of 36 layers of 1,400
    blocks. Inside a decode step the kernel must stay one operation whose
    instruction name reads ``paged_attention`` as the trace reduction
    parses it: a kernel renamed or split would leave
    ``paged_attn_roofline`` nothing or part to read."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def step(q, k, v, t, c, ly):
        return ops.paged_attention(q, k, v, t, c, scale=1.0 / HD ** 0.5,
                                   layer=ly, interpret=False) * 2

    pool = shape((36, 1400, BLOCK_SIZE, HKV, HD), jnp.bfloat16)
    text = jax.jit(step).lower(
        shape((32, H, HD), jnp.bfloat16), pool, pool,
        shape((32, 256), jnp.int32), shape((32,), jnp.int32),
        shape((), jnp.int32)).compile().as_text()
    kernels = [devtrace.op_name(line.strip().removeprefix("ROOT "))
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels == [devtrace.KERNEL]


# -- serving programs that are donated the pool ------------------------------

#: Pages of the tiny pool: many times what the programs' contexts touch,
#: so that a temporary of a layer's pool stands out from activations.
TINY_BLOCKS = 2048
_SHAPE = re.compile(r"= \(?(\w+)\[([\d,]*)\]")
_MOVES = re.compile(r" (copy|copy-start|dynamic-slice)\(")


def _tiny_engine(model: str) -> StreamedBatchEngine:
    """A tiny bf16 engine on the Pallas path: a dense GQA model with bf16
    or int8 pages, or a Mamba-2/attention hybrid with its slot state."""
    kind, _, kv_dtype = model.partition("-")
    cfg = dataclasses.replace(  # int8 pages tile kv heads by eights
        C.get_smoke_config("qwen3-4b"), n_heads=16, n_kv_heads=8,
        head_dim=128,
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)
    if kind == "hybrid":
        cfg = dataclasses.replace(
            cfg, n_layers=4, ssm_state=16, mamba_headdim=16,
            layer_unit=(LayerSpec("mamba", "dense"), LayerSpec("attn",
                                                               "dense")))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return StreamedBatchEngine(cfg, params, ServeConfig(
        max_seq=128, prefill_chunk=16, max_batch=4, paged=True,
        paged_kernel=True, block_size=16, num_blocks=TINY_BLOCKS,
        kv_dtype=kv_dtype or "fp32"))


def _big_moves(text: str, limit: int) -> list[str]:
    """Copies and dynamic slices (fused or not) of ``limit`` bytes or more
    in HBM. A pool this small may be staged whole in VMEM (a result in
    memory space ``S(1)``), which the compiler cannot do at a served size."""
    size = {"bf16": 2, "f16": 2, "s8": 1, "u8": 1, "f8e4m3fn": 1}
    found = []
    for line in text.splitlines():
        m, s = _MOVES.search(line), _SHAPE.search(line)
        if m and s and "S(1)}" not in line.split("=", 2)[1]:
            n = size.get(s.group(1), 4)
            for d in filter(None, s.group(2).split(",")):
                n *= int(d)
            if n >= limit:
                found.append(line.strip()[:160])
    return found


#: (model, program) pairs. An int8 pool's fused chunk is left out: its
#: page writes make the compiler relayout the whole pool (at qwen3-4b's size
#: that program does not fit the chip, with or without donation).
IN_PLACE_CASES = [(m, p) for m in ("dense", "dense-int8", "hybrid")
                  for p in ("decode", "chunk", "scatter")
                  if (m, p) != ("dense-int8", "chunk")]


@pytest.mark.parametrize("model,program", IN_PLACE_CASES)
def test_pool_programs_update_the_pool_in_place(model, program, one_chip,
                                                no_persistent_cache,
                                                monkeypatch):
    """Compiled for the chip, the decode step (with the Pallas kernel), a
    fused prefill chunk and the page scatter alias the whole pool and its
    slot state to their outputs and keep no temporary near the pool's
    size, and the decode step neither copies nor slices out a layer's
    pool in HBM."""
    # The engine runs here on the CPU; its kernel call is steered to the
    # Mosaic lowering that the chip compiles.
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    eng = _tiny_engine(model)
    kv, b = eng.kv, eng.scfg.max_batch
    pools = kv.pools
    if program == "decode":
        fn, args = eng._decode_step(), (
            eng.params, jnp.zeros((b, 1), jnp.int32), pools,
            kv.device_page_table(), jnp.zeros((b,), jnp.int32))
    elif program == "chunk":
        fn, args = eng.single._fused_chunk_fn(16, 16), (
            eng.params, pools, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 16), jnp.int32), jnp.int32(1))
    else:
        fn, args = kv._make_scatter(2), (
            pools, eng.servable.init_request_cache(),
            jnp.asarray([1, 2], jnp.int32), jnp.int32(1), jnp.int32(0))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(pools))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 4
    if program == "decode":
        k = next(c["k"] for c in pools["blocks"].values() if "k" in c)
        assert _big_moves(compiled.as_text(), k.nbytes // k.shape[0]) == []
