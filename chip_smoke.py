"""Bring-up check: serve qwen3-4b at its published widths on one TPU chip.

    python3 chip_smoke.py             # one chip: the serving path
    python3 chip_smoke.py --chips 4   # four chips: a sharded train step

One chip. qwen3-4b (36 layers, d_model 2560, GQA 32/8, vocab 151,936, bf16)
is built with seeded random weights on the device. Four seeded 256-token
prompts are served through ``StreamedBatchEngine`` on the paged KV pool with
the Pallas paged-attention kernel, 128-token prefill chunks and 32 greedy new
tokens each. The run checks that every request returned 32 in-vocabulary
tokens, that a second serve of the same prompts returns the same tokens, that
one decode step's logits are finite, and that the kernel agrees with the
plain-JAX reference (``ref.paged_attention_ref``) on the pool's pages.

Four chips. One AdamW train step of qwen3-4b at its published widths, cut to
2 layers so that the one-chip reference fits, runs on a (data=2, model=2)
mesh with the sharding rules of ``launch/sharding.py``; its loss and updated
weights are compared with the same step on device 0 alone, in this process.

The lines before the last report set-up facts (device, weight bytes, compile
and serve seconds, peak device memory), not benchmark numbers. The last line
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failed check raises, and the script then exits non-zero without it. Off
a TPU it exits non-zero at once. The persistent compilation cache is kept
where ``repro.launch.compile_cache`` says.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.configs as configs  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.runtime.serving import ServeConfig, StreamedBatchEngine  # noqa: E402

ARCH = "qwen3-4b"
N_REQUESTS, PROMPT_LEN, PREFILL_CHUNK, NEW_TOKENS = 4, 256, 128, 32
BLOCK_SIZE = 16
#: Kernel vs f32 reference: both read the same bf16 pages; the kernel
#: rounds the softmax weights and its output to bf16 (2**-8 relative each).
KERNEL_ATOL = KERNEL_RTOL = 2e-2
#: Sharded vs one-device train step (bf16 weights, f32 loss).
TRAIN_LAYERS = 2
LOSS_RTOL = 1e-3
#: Share of weights whose update may flip sign between the two (bf16
#: rounding noise around a zero gradient); a wrong sharding flips ~half.
MAX_FLIPPED_SHARE = 0.02


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, what: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


class CompileClock:
    """Sums XLA backend-compile seconds and persistent-cache hits."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# -- one chip: the serving path ----------------------------------------------


def init_params(cfg: T.ModelConfig, seed: int):
    """Seeded random weights, made on the device by one jitted program."""
    params = jax.jit(T.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def make_engine(cfg: T.ModelConfig, params, *, n_requests: int = N_REQUESTS,
                prompt_len: int = PROMPT_LEN,
                prefill_chunk: int = PREFILL_CHUNK,
                new_tokens: int = NEW_TOKENS) -> StreamedBatchEngine:
    """The continuous-batching engine on a paged pool, kernel on."""
    max_seq = -(-(prompt_len + new_tokens) // BLOCK_SIZE) * BLOCK_SIZE
    scfg = ServeConfig(
        max_seq=max_seq, prefill_chunk=prefill_chunk,
        max_new_tokens=new_tokens, max_batch=n_requests, paged=True,
        block_size=BLOCK_SIZE, paged_kernel=True)
    eng = StreamedBatchEngine(cfg, params, scfg)
    check(eng.scfg.paged_kernel is True, "the Pallas kernel is off")
    return eng


def make_prompts(cfg: T.ModelConfig, seed: int, *, n_requests: int = N_REQUESTS,
                 prompt_len: int = PROMPT_LEN) -> np.ndarray:
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (n_requests, prompt_len), 0,
        cfg.vocab_size, dtype=jnp.int32))


def serve(eng: StreamedBatchEngine, prompts: np.ndarray
          ) -> tuple[list[np.ndarray], float]:
    """Submit every prompt, run the engine dry; (outputs, seconds)."""
    t0 = time.perf_counter()
    uids = [eng.submit(p) for p in prompts]
    outs = eng.run()
    jax.block_until_ready(eng.kv.pools)
    return [outs[u] for u in uids], time.perf_counter() - t0


def check_outputs(cfg: T.ModelConfig, outputs: list[np.ndarray],
                  new_tokens: int = NEW_TOKENS) -> None:
    for i, out in enumerate(outputs):
        check(out.shape == (new_tokens,), f"request {i}: shape {out.shape}")
        check(((out >= 0) & (out < cfg.vocab_size)).all(),
              f"request {i}: token outside the vocabulary: {out}")


def _test_pages(kv, seed: int) -> tuple[jax.Array, jax.Array]:
    """A page table over distinct written pages of the pool, and per-row
    positions spread over each row's whole span."""
    rng = np.random.default_rng(seed)
    b, n_pages = kv.max_batch, kv.max_pages
    pages = 1 + rng.permutation(kv.num_blocks - 1)[: b * n_pages]
    cur = rng.integers(0, n_pages * kv.block_size, size=b)
    cur[0] = n_pages * kv.block_size - 1  # one row sees every page
    return (jnp.asarray(pages.reshape(b, n_pages), jnp.int32),
            jnp.asarray(cur, jnp.int32))


def decode_logits(cfg: T.ModelConfig, params, eng: StreamedBatchEngine,
                  seed: int) -> np.ndarray:
    """Logits of one paged decode step (kernel on) over the served pool."""
    table, cur = _test_pages(eng.kv, seed)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 2),
                              (eng.kv.max_batch, 1), 0, cfg.vocab_size)
    step = jax.jit(lambda p, t, c, pt, l: T.decode_step_paged(
        cfg, p, t, c, pt, l, paged_kernel=True)[0])
    logits = np.asarray(step(params, toks, eng.kv.pools, table, cur))
    check(logits.shape == (eng.kv.max_batch, 1, cfg.padded_vocab),
          f"decode logits shape {logits.shape}")
    check(np.isfinite(logits).all(), "non-finite decode logits")
    return logits


def kernel_vs_reference(cfg: T.ModelConfig, eng: StreamedBatchEngine,
                        seed: int) -> float:
    """Pallas paged attention vs ``ref.paged_attention_ref`` (f32, highest
    matmul precision) on layer 0's K/V pool; returns the max abs error."""
    layer = eng.kv.pools["blocks"]["layer0"]
    k_pool, v_pool = layer["k"][0], layer["v"][0]
    table, cur = _test_pages(eng.kv, seed)
    q = jax.random.normal(
        jax.random.PRNGKey(seed + 3),
        (eng.kv.max_batch, cfg.n_heads, cfg.head_dim)).astype(k_pool.dtype)
    scale = cfg.query_scale or 1.0 / math.sqrt(cfg.head_dim)
    got = ops.paged_attention(q, k_pool, v_pool, table, cur, scale=scale)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.paged_attention_ref, static_argnames="scale")(
            q, k_pool, v_pool, table, cur, scale=scale)
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want, np.float32)
    err = float(np.abs(got - want).max())
    np.testing.assert_allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL)
    return err


def run_serving(seed: int, clock: CompileClock) -> None:
    cfg = configs.get_config(ARCH)
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab_size}, "
        f"{jnp.dtype(cfg.param_dtype).name}")
    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"weights: {nbytes} bytes on the device in "
        f"{time.perf_counter() - t0:.1f}s")

    eng = make_engine(cfg, params)
    log(f"pool: {eng.kv.num_blocks} pages x {eng.kv.page_bytes} bytes, "
        f"paged_kernel={eng.scfg.paged_kernel}")
    prompts = make_prompts(cfg, seed)
    c0 = clock.seconds
    outs, cold_s = serve(eng, prompts)
    log(f"first serve (with compiles): {cold_s:.2f}s, "
        f"{clock.seconds - c0:.2f}s of it compiling")
    check_outputs(cfg, outs)
    again, warm_s = serve(eng, prompts)
    for a, b in zip(outs, again):
        np.testing.assert_array_equal(a, b)
    log(f"serve: {len(prompts)} requests x {prompts.shape[1]} prompt tokens "
        f"-> {NEW_TOKENS} new tokens each in {warm_s:.2f}s "
        f"(to block_until_ready, compiled)")
    log(f"request 0 tokens: {outs[0][:8].tolist()}...")

    logits = decode_logits(cfg, params, eng, seed)
    log(f"decode logits finite, |max| {float(np.abs(logits).max()):.3f}")
    err = kernel_vs_reference(cfg, eng, seed)
    log(f"paged kernel vs reference: max abs error {err:.3e} "
        f"(tolerance {KERNEL_ATOL} + {KERNEL_RTOL} x |ref|)")


# -- four chips: sharded train step ------------------------------------------


def sharded_step_matches_local(cfg: T.ModelConfig, devices, *, batch: int = 8,
                               seq: int = 128, accum: int = 2,
                               seed: int = 0) -> dict:
    """One train step on a (data=2, model=2) mesh over ``devices`` vs the
    same step on ``devices[0]`` alone. The first AdamW step moves each weight
    by lr * sign(grad) plus decay, so the two may differ only where a
    gradient's sign flips within rounding noise: by at most two such moves
    plus two bf16 ulps, and at few elements (a difference over half a move
    counts as a flip)."""
    from jax.sharding import SingleDeviceSharding

    from repro.launch import sharding, steps
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw

    opt_cfg = adamw.AdamWConfig()
    step_fn = steps.make_train_step(cfg, opt_cfg, accum=accum)

    def init(key):
        p = T.init_params(cfg, key)
        return p, adamw.init_state(p)

    p0, o0 = jax.jit(init, out_shardings=SingleDeviceSharding(devices[0]))(
        jax.random.PRNGKey(seed))
    host_p0, host_o0 = jax.device_get((p0, o0))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, seq), 0, cfg.vocab_size,
        dtype=jnp.int32))

    p1, o1, m1 = jax.jit(step_fn, donate_argnums=(0, 1))(
        p0, o0, {"tokens": jax.device_put(tokens, devices[0])})
    loss1 = float(m1["loss"])
    host_p1 = jax.device_get(p1)
    del p0, o0, p1, o1  # device 0 also holds a shard of the sharded step

    mesh = make_host_mesh(data=2, model=2, devices=devices)
    pspecs = sharding.param_specs(jax.eval_shape(lambda: host_p0), mesh)
    p_named = sharding.to_named(pspecs, mesh)
    o_named = sharding.to_named(sharding.opt_state_specs(pspecs), mesh)
    with jax.set_mesh(mesh):
        p_sh = jax.device_put(host_p0, p_named)
        o_sh = jax.device_put(host_o0, o_named)
        p2, _, m2 = jax.jit(step_fn, in_shardings=(p_named, o_named, None),
                            donate_argnums=(0, 1))(
            p_sh, o_sh, {"tokens": tokens})
    loss2 = float(m2["loss"])
    host_p2 = jax.device_get(p2)

    check(abs(loss1 - loss2) <= LOSS_RTOL * abs(loss1),
          f"loss local {loss1} vs sharded {loss2}")
    flipped = total = 0
    worst = 0.0
    for a0, a1, a2 in zip(jax.tree.leaves(host_p0), jax.tree.leaves(host_p1),
                          jax.tree.leaves(host_p2)):
        a0, a1, a2 = (np.asarray(a, np.float32) for a in (a0, a1, a2))
        diff = np.abs(a2 - a1)
        top = float(np.abs(a1).max())
        ulp = 2.0 ** (math.floor(math.log2(top)) - 7) if top > 0 else 0.0
        move = opt_cfg.lr * (1.0 + opt_cfg.weight_decay * top)
        bound = 2.0 * move + 2.0 * ulp
        check(float(diff.max()) <= bound,
              f"{a1.shape}: max difference {float(diff.max())} > {bound}")
        worst = max(worst, float(diff.max()))
        flipped += int((diff > 0.5 * move).sum())
        total += diff.size
    share = flipped / total
    check(share <= MAX_FLIPPED_SHARE, f"updates flip at {share} of weights")
    return dict(loss_local=loss1, loss_sharded=loss2, max_abs_diff=worst,
                flipped_share=share, n_params=total)


def run_sharded(seed: int) -> None:
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    cfg = dataclasses.replace(configs.get_config(ARCH),
                              n_layers=TRAIN_LAYERS)
    log(f"{ARCH} at published widths cut to {cfg.n_layers} of "
        f"{configs.get_config(ARCH).n_layers} layers: one train step on a "
        f"(data=2, model=2) mesh vs device 0 alone")
    t0 = time.perf_counter()
    res = sharded_step_matches_local(cfg, devices, seed=seed)
    log(f"loss local {res['loss_local']:.6f} vs sharded "
        f"{res['loss_sharded']:.6f}; updates flip at "
        f"{res['flipped_share']:.2e} of {res['n_params']} weights; max "
        f"|difference| {res['max_abs_diff']:.3e} "
        f"({time.perf_counter() - t0:.1f}s)")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path; 4: a sharded train step "
                         "against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"[chip_smoke] needs a TPU, JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    use_compile_cache()
    dev = jax.devices()[0]
    log(f"device: {dev.device_kind}, {len(jax.devices())} devices, "
        f"jax {jax.__version__}")
    clock = CompileClock()
    if args.chips == 4:
        run_sharded(args.seed)
    else:
        run_serving(args.seed, clock)
    stats = dev.memory_stats() or {}
    log(f"compile: {clock.seconds:.1f}s over {clock.compiles} XLA compiles, "
        f"{clock.cache_hits} persistent-cache hits")
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
