"""Compile-only checks of the serving kernels for a described TPU v5e.

Interpret mode runs the kernel bodies on the CPU, but it does not apply the
TPU compiler's tiling and VMEM rules. These tests compile the paged-attention
kernel at qwen3-4b's published widths (H=32, Hkv=8, hd=128, bf16 queries, 16
rows per page) for one chip of a described ``v5e:2x2`` topology; nothing runs.

The topology is described inside a fixture, never at import: the TPU library
may be loaded by one process at a time.
"""

import functools
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import paged_attention as pa

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


@pytest.mark.parametrize("variant", ["plain", "multi", "int8", "fp8"])
def test_paged_attention_compiles_for_v5e(variant, one_chip,
                                          no_persistent_cache):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool_dtype = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}.get(
        variant, jnp.bfloat16)
    q_len = SPEC_K + 1 if variant == "multi" else 1
    q = shape((B, q_len, H, HD) if q_len > 1 else (B, H, HD), jnp.bfloat16)
    pool = shape((NUM_BLOCKS, BLOCK_SIZE, HKV, HD), pool_dtype)
    table = shape((B, N_PAGES), jnp.int32)
    cur = shape((B,), jnp.int32)
    scale = 1.0 / HD ** 0.5
    if variant in ("int8", "fp8"):
        scales = shape((NUM_BLOCKS, HKV), jnp.float32)
        fn = lambda q, k, v, ks, vs, t, c: pa.paged_attention_kernel(  # noqa: E731
            q, k, v, t, c, scale=scale, k_scale=ks, v_scale=vs)
        args = (q, pool, pool, scales, scales, table, cur)
    else:
        kern = (pa.paged_attention_multi_kernel if q_len > 1
                else pa.paged_attention_kernel)
        fn = functools.partial(kern, scale=scale)
        args = (q, pool, pool, table, cur)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_keeps_its_op_name_at_cell_shapes(one_chip,
                                                          no_persistent_cache):
    """qwen3-4b's decode batch as the benchmark serves it: 32 rows, a table
    of 256 pages of 16 rows, a pool of 1,400 blocks. Inside a decode step
    the kernel must stay one operation whose instruction name reads
    ``paged_attention`` as the trace reduction parses it: a kernel renamed
    or split would leave ``paged_attn_roofline`` nothing or part to read."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def step(q, k, v, t, c):
        return ops.paged_attention(q, k, v, t, c, scale=1.0 / HD ** 0.5,
                                   interpret=False) * 2

    pool = shape((1400, BLOCK_SIZE, HKV, HD), jnp.bfloat16)
    text = jax.jit(step).lower(
        shape((32, H, HD), jnp.bfloat16), pool, pool,
        shape((32, 256), jnp.int32), shape((32,), jnp.int32)).compile(
    ).as_text()
    kernels = [devtrace.op_name(line.strip().removeprefix("ROOT "))
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels == [devtrace.KERNEL]
