"""The paged pool is updated in place: every serving program that takes the
pool and returns it is donated the pool, and the engine counts each such
run as ``serving.pool_donated_runs`` (a run that left the old buffers
alive beside the new ones would count as ``serving.pool_copied_runs``).

On the CPU backend these tests show that each compiled program aliases the
whole pool to its output, that a run consumes the pool it was handed, and
that the engine never reads a donated pool again while greedy tokens stay
the reference's. ``tests/test_tpu_compile.py`` compiles the same programs
for a described TPU v5e and shows that the chip's compiler keeps no
pool-sized temporary and no pool-sized copy; the CPU backend's scatter
writes a new buffer whatever is donated, so its temporaries say nothing
about the chip.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import transformer as T
from repro.models.transformer import LayerSpec
from repro.obs import Tracer
from repro.runtime.serving import (ServeConfig, ServingEngine,
                                   StreamedBatchEngine)

DONATED = "serving.pool_donated_runs"
COPIED = "serving.pool_copied_runs"


def _config(kind: str):
    cfg = C.get_smoke_config("qwen3-4b")
    if kind == "hybrid":  # a Mamba-2 layer and an attention layer a period
        cfg = dataclasses.replace(
            cfg, n_layers=4, ssm_state=16, mamba_headdim=16,
            layer_unit=(LayerSpec("mamba", "dense"), LayerSpec("attn",
                                                               "dense")))
    return cfg


@pytest.fixture(scope="module")
def models():
    out = {}
    for kind in ("dense", "hybrid"):
        cfg = _config(kind)
        out[kind] = cfg, T.init_params(cfg, jax.random.PRNGKey(0))
    return out


def _prompts(cfg, lens, seed=1):
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + i), (n,), 0, cfg.vocab_size))
        for i, n in enumerate(lens)]


def _pool_bytes(pools) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(pools))


def _program(eng, name: str):
    """(jitted program, its arguments) as the engine dispatches it."""
    kv, b = eng.kv, eng.scfg.max_batch
    if name == "decode":
        return eng._decode_step(), (
            eng.params, jnp.zeros((b, 1), jnp.int32), kv.pools,
            kv.device_page_table(), jnp.zeros((b,), jnp.int32))
    if name == "verify":
        k = eng.scfg.spec_k
        return eng._spec_jit, (
            eng.params, jnp.zeros((b, k + 1), jnp.int32), kv.pools,
            kv.device_page_table(), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32))
    if name == "chunk":
        chunk = eng.scfg.prefill_chunk
        return eng.single._fused_chunk_fn(chunk, chunk), (
            eng.params, kv.pools, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, chunk), jnp.int32), jnp.int32(1))
    if name == "scatter":
        return kv._make_scatter(2), (
            kv.pools, eng.servable.init_request_cache(),
            jnp.asarray([1, 2], jnp.int32), jnp.int32(1), jnp.int32(0))
    assert name == "cow_copy"
    kv._copy_block(1, 2)  # builds the jitted page copy
    return kv._copy_jit, (kv.pools, jnp.int32(1), jnp.int32(2))


PROGRAM_CASES = [
    (model, program)
    for model in ("dense", "dense-int8", "hybrid")
    for program in ("decode", "chunk", "scatter", "cow_copy")
] + [("dense", "verify"), ("dense-int8", "verify")]


@pytest.mark.parametrize("model,program", PROGRAM_CASES)
def test_program_aliases_the_pool(models, model, program):
    """The compiled program aliases every byte of the pool to its output,
    and running it consumes the pool it was handed."""
    kind, _, kv_dtype = model.partition("-")
    cfg, params = models[kind]
    eng = StreamedBatchEngine(cfg, params, ServeConfig(
        max_seq=64, prefill_chunk=16, max_batch=2, paged=True,
        block_size=16, num_blocks=64, kv_dtype=kv_dtype or "fp32",
        spec_decode=program == "verify", spec_k=2))
    fn, args = _program(eng, program)
    pools = args[{"decode": 2, "verify": 2, "chunk": 1}.get(program, 0)]
    mem = fn.lower(*args).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= _pool_bytes(pools)
    out = fn(*args)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(pools))
    new = out if program in ("scatter", "cow_copy") else out[-1]
    assert jax.tree.map(jnp.shape, new) == jax.tree.map(jnp.shape, pools)


def _reference(cfg, params, prompts, max_new):
    single = ServingEngine(cfg, params, ServeConfig(
        max_seq=96, prefill_chunk=16, max_new_tokens=max_new, max_batch=2))
    return [np.asarray(single.generate(p[None])[0]) for p in prompts]


def _expected_runs(eng, tracer) -> int:
    """Dispatches of programs that return the pool: decode and verify
    steps, fused chunks, readmission scatters and copy-on-write forks."""
    spans = tracer.spans()
    chunks = sum(s.name == "prefill_chunk" for s in spans)
    readmits = sum(s.name == "readmit" for s in spans)
    return eng.decode_steps + chunks + readmits + eng.kv.cow_forks


@pytest.mark.parametrize("scenario", ["preempt", "prefix_cow", "spec",
                                      "int8"])
def test_engine_donates_every_pool_run(models, scenario):
    """Preemption and readmission, a prefix-shared admission whose shared
    page is forked, speculative verify and int8 pages, all with donated
    pools: the greedy tokens are the reference's, no donated array is read
    again (it would raise), and every run that returned the pool counts as
    donated, none as copied."""
    cfg, params = models["dense"]
    kw = dict(max_seq=96, prefill_chunk=16, max_new_tokens=24, max_batch=2,
              paged=True, block_size=16, num_blocks=8)
    prompts = _prompts(cfg, [32, 32], seed=73)
    if scenario == "spec":
        kw.update(spec_decode=True, spec_k=2, num_blocks=None)
        # A repeated phrase gives the prompt-lookup drafter hits.
        prompts = [np.tile(p[:8], 4) for p in prompts]
    elif scenario == "int8":
        kw.update(kv_dtype="int8")
    elif scenario == "prefix_cow":
        kw.update(prefix_sharing=True, num_blocks=None)
        system = _prompts(cfg, [32], seed=41)[0]
        prompts = [np.concatenate([system, t])
                   for t in _prompts(cfg, [8, 12], seed=47)]
    tracer = Tracer(enabled=True)
    eng = StreamedBatchEngine(cfg, params, ServeConfig(**kw), tracer=tracer)
    if scenario == "prefix_cow":
        u0 = eng.submit(prompts[0])
        out = dict(eng.run())
        u1 = eng.submit(prompts[1])
        eng.step()  # admits u1 onto the registered prefix pages
        slot = next(s for s in eng.slots if s.uid == u1)
        assert eng.prefix_hits == 1
        assert eng.kv.ensure_write(slot.index, 0)  # forks shared page 0
        assert eng.kv.cow_forks == 1
        out.update(eng.run())
        uids = [u0, u1]
    else:
        uids = [eng.submit(p) for p in prompts]
        out = eng.run()
    if scenario in ("preempt", "int8"):
        assert eng.preemptions >= 1  # the pool genuinely squeezed
    if scenario == "spec":
        assert eng.spec_accepted >= 1
    if scenario == "int8":
        # Reference: the same int8 pool with room for both requests, so
        # that nothing is preempted.
        calm = StreamedBatchEngine(cfg, params, ServeConfig(
            **dict(kw, num_blocks=None)))
        ids = [calm.submit(p) for p in prompts]
        ref = calm.run()
        want = [ref[u] for u in ids]
    else:
        want = _reference(cfg, params, prompts, kw["max_new_tokens"])
    for u, w in zip(uids, want):
        np.testing.assert_array_equal(out[u], w)
    assert eng.metrics.value(COPIED) == 0
    assert eng.metrics.value(DONATED) == _expected_runs(eng, tracer) > 0


def test_stage_probe_keeps_the_pool_live(models):
    """``measure_stage_times`` times a donated decode step: it writes only
    the trash page, keeps the step's pools, and a request served after it
    gets the reference's tokens."""
    cfg, params = models["hybrid"]
    eng = StreamedBatchEngine(cfg, params, ServeConfig(
        max_seq=96, prefill_chunk=16, max_new_tokens=6, max_batch=2,
        paged=True, block_size=16))
    p0, p1 = _prompts(cfg, [24, 20], seed=5)
    u0 = eng.submit(p0)
    eng.step()  # u0 holds a slot while the probe runs
    eng.measure_stage_times(16)
    assert eng.metrics.value(DONATED) >= 2 and eng.metrics.value(COPIED) == 0
    u1 = eng.submit(p1)
    out = eng.run()
    want = _reference(cfg, params, [p0, p1], 6)
    np.testing.assert_array_equal(out[u0], want[0])
    np.testing.assert_array_equal(out[u1], want[1])


def test_substituted_step_runs_undonated(models):
    """A step put in place of ``_decode_jit`` from outside may hand back
    the pools it was given: the engine calls it as it is, undonated, and
    counts its runs as copied."""
    cfg, params = models["dense"]
    eng = StreamedBatchEngine(cfg, params, ServeConfig(
        max_seq=96, prefill_chunk=16, max_new_tokens=6, max_batch=2,
        paged=True, block_size=16))
    step = eng._decode_jit
    eng._decode_jit = lambda *args: step(*args)
    prompts = _prompts(cfg, [24, 20], seed=9)
    uids = [eng.submit(p) for p in prompts]
    out = eng.run()
    for u, w in zip(uids, _reference(cfg, params, prompts, 6)):
        np.testing.assert_array_equal(out[u], w)
    assert eng.metrics.value(COPIED) == eng.decode_steps > 0
