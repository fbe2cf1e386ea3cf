"""granite-4.0-h-small's layout served through the paged engine, against
the plain float32 reference of its benchmark family
(``chipbench/families/granite_hybrid.py``), at a small size on the CPU:
one unit of 10 layers (9 Mamba-2, 1 NoPE attention), d_model 64, 8 routed
experts top-3 of which a share holds 4, and the published multipliers.

The equality checks run the server in float32: the server and the
reference then compute the same equations in the same precision, in a
different order (chunked SSD against a token-by-token recurrence, paged
attention against a dense one, every held expert on every token against
a loop over experts), so they agree to float32 rounding. The tolerance
``F32_TOL`` is that rounding, as a share of the reference logits' spread:
10 layers of float32 arithmetic leave differences of about 1e-6 of it; a
wrong equation misses by the spread itself.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.runtime.kv_cache import PagedKVCache  # noqa: E402
from repro.runtime.serving import ServeConfig, StreamedBatchEngine  # noqa: E402

SEED = 2 ** 35 + 11
#: Float32 rounding of two orders of the same float32 equations, as a
#: share of the logits' spread (module docstring).
F32_TOL = 1e-4
#: The small widths: 16 Mamba-2 heads of 8 (d_inner 128 = 2 x 64), state
#: 16, heads 4/2 of 16, experts of 32, shared MLP of 64, vocabulary 512.
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             mamba_n_heads=16, mamba_d_head=8, mamba_d_state=16,
             intermediate_size=32, shared_intermediate_size=64,
             vocab_size=512, num_experts_per_tok=3)


@pytest.fixture(scope="module")
def fam():
    return harness.load_family(ROOT, "granite_hybrid")


def small_dims(fam, held=4, routed=8):
    conf = json.loads((ROOT / "chipbench" / "configs"
                       / "granite-4.0-h-small.json").read_text())
    conf.update(SMALL, num_local_experts=held,
                published={"num_local_experts": routed})
    return fam.dims(conf)


def f32(cfg, params):
    """The same server in float32 (weights widened, state kept in f32)."""
    cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32,
                              ssm_cache_dtype=jnp.float32)
    return cfg, jax.tree.map(lambda x: x.astype(jnp.float32), params)


@pytest.fixture(scope="module")
def served(fam):
    d = small_dims(fam)
    cfg = fam.program_config(d)
    params = fam.program_params(d, cfg.padded_vocab, SEED)
    cfg32, params32 = f32(cfg, params)
    return d, cfg32, params32


def reference_logits(fam, d, ids):
    """The family's reference over one sequence: (len(ids), vocab)."""
    key = fam.seed_key(SEED)
    embed, fnorm = fam._globals_f32(d, key)
    n = -(-len(ids) // fam.PAD) * fam.PAD
    padded = np.zeros((1, n), np.int32)
    padded[0, : len(ids)] = ids
    h = d.embed_mult * embed[jnp.asarray(padded)]
    for layer, kind in enumerate(d.kinds):
        w = fam._layer_f32(d, key, jnp.int32(layer), kind)
        h = fam._layer(d, w, h, kind, False)
    at = jnp.arange(len(ids), dtype=jnp.int32)
    logits, _ = fam._head(d, embed, fnorm, h[0], at,
                          jnp.zeros(len(ids), jnp.int32), False)
    return np.asarray(logits)


def _engine(cfg, params, **kw):
    base = dict(max_seq=128, prefill_chunk=16, max_new_tokens=12,
                max_batch=3, paged=True, block_size=16, paged_kernel=False)
    return StreamedBatchEngine(cfg, params, ServeConfig(**{**base, **kw}))


def _prompts(n_tokens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, SMALL["vocab_size"], n).astype(np.int32)
            for n in n_tokens]


def test_paged_prefill_then_decode_matches_the_reference(fam, served):
    """Fused chunks through the page table, then paged decode steps, give
    the reference's logits at every position they produce."""
    d, cfg, params = served
    prompt, = _prompts([40])
    follow = _prompts([6], seed=4)[0]
    kv = PagedKVCache(cfg, max_batch=2, max_seq=128, block_size=16,
                      num_blocks=12)
    slot = 1
    assert kv.alloc(slot, len(prompt) + len(follow))
    kv.publish(slot)
    eng = _engine(cfg, params)
    row = jnp.asarray(kv.page_table[slot:slot + 1])
    got = []
    for lo in range(0, len(prompt), 16):
        piece = jnp.asarray(prompt[None, lo: lo + 16])
        fn = eng.single._fused_chunk_fn(piece.shape[1], lo)
        n_ctx = kv.pages_for(lo + piece.shape[1])
        logits, kv.pools = fn(params, kv.pools, row[:, :n_ctx], piece,
                              jnp.int32(slot))
    got.append(np.asarray(logits)[0, -1])
    cur = np.zeros(2, np.int32)
    for i, tok in enumerate(follow):
        cur[slot] = len(prompt) + i
        toks = np.zeros((2, 1), np.int32)
        toks[slot, 0] = tok
        logits, kv.pools = T.decode_step_paged(
            cfg, params, jnp.asarray(toks), kv.pools,
            kv.device_page_table(), jnp.asarray(cur))
        got.append(np.asarray(logits)[slot, -1])
    ids = np.concatenate([prompt, follow])
    want = reference_logits(fam, d, ids)[len(prompt) - 1:]
    got = np.stack(got)[:, : d.vocab]
    np.testing.assert_allclose(got, want, atol=F32_TOL * want.std(), rtol=0)


def test_served_tokens_are_the_references_best(fam, served):
    """Through the engine's own loop (admission, interleaved ticks,
    sampling), each greedy token is the reference's best at its place."""
    d, cfg, params = served
    eng = _engine(cfg, params)
    prompts = _prompts([40, 23, 16])
    uids = [eng.submit(p) for p in prompts]
    out = eng.run()
    for p, u in zip(prompts, uids):
        ids = np.concatenate([p, out[u][:-1]])
        ref = reference_logits(fam, d, ids)[len(p) - 1:]
        gap = ref.max(-1) - np.take_along_axis(ref, out[u][:, None], -1)[:, 0]
        assert gap.max() <= F32_TOL * ref.std(), (u, gap)
        # Not the degenerate check where every token echoes its input.
        assert np.mean(out[u] == ids[len(p) - 1:]) < 0.5


def test_shares_add_up_to_the_uncut_layer(fam):
    """Two shares of 4 experts each, the shared MLP counted once, give the
    uncut layer of 8 experts: in the server's layer and in the reference."""
    d = small_dims(fam, held=8)
    key = fam.seed_key(SEED)
    w = fam._f32(fam.ffn_weights(d, key, 3, np.arange(8)))
    x = jax.random.normal(jax.random.PRNGKey(5), (24, d.d_model))

    def layer(lo, hi):
        return {"router": w["router"], "wg": w["w1"][lo:hi],
                "wi": w["w2"][lo:hi], "wo": w["w3"][lo:hi],
                "shared": {"wg": w["shared_w1"], "wi": w["shared_w2"],
                           "wo": w["shared_w3"]}}

    with jax.default_matmul_precision("highest"):
        whole, _ = moe.moe_apply_held(layer(0, 8), x[None], top_k=d.top_k)
        parts = [moe.moe_apply_held(layer(lo, lo + 4), x[None],
                                    top_k=d.top_k, offset=lo)[0]
                 for lo in (0, 4)]
        shared = moe.shared_apply(layer(0, 8), x)
    np.testing.assert_allclose(parts[0] + parts[1] - shared, whole,
                               atol=1e-5, rtol=0)
    want = fam.moe_share(d, w, x) + fam.shared_mlp(w, x)
    np.testing.assert_allclose(whole[0], want, atol=1e-5, rtol=0)
    # The reference's share is its held experts' part alone.
    w4 = dict(w, w1=w["w1"][:4], w2=w["w2"][:4], w3=w["w3"][:4])
    np.testing.assert_allclose(parts[0][0], fam.moe_share(d, w4, x)
                               + fam.shared_mlp(w, x), atol=1e-5, rtol=0)


def test_decode_row_ignores_its_neighbours(fam):
    """Dropless: a row's decode logits are the same bits whatever the
    other rows hold. The neighbours pick the row's own experts, which
    would overflow a capacity of ceil(4 x 3 x 1.25 / 8) = 2 rows."""
    d = small_dims(fam)
    cfg = fam.program_config(d)
    params = fam.program_params(d, cfg.padded_vocab, SEED)
    kv = PagedKVCache(cfg, max_batch=4, max_seq=64, block_size=16,
                      num_blocks=8)
    for s in range(4):
        assert kv.alloc(s, 8)
    table = kv.device_page_table()
    cur = jnp.full((4,), 5, jnp.int32)
    step = jax.jit(lambda t: T.decode_step_paged(
        cfg, params, t, kv.pools, table, cur)[0][3])
    same = step(jnp.full((4, 1), 7, jnp.int32))
    other = step(jnp.asarray([[100], [200], [300], [7]], jnp.int32))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(other))


def test_preempted_slot_resumes_as_if_uninterrupted(served):
    """Evicting a hybrid slot mid-decode (its pages and its SSM and conv
    rows gathered out) and readmitting it gives the same tokens."""
    _, cfg, params = served
    prompts = _prompts([40, 23])

    def serve(evict_after):
        eng = _engine(cfg, params)
        uids = [eng.submit(p) for p in prompts]
        while eng.queue:
            eng.step()
        for _ in range(evict_after):
            eng.step()
        if evict_after:
            ev = eng.evict(uids[0])
            for _ in range(2):
                eng.step()  # the other slot decodes, the free row idles
            eng.readmit(ev)
        out = eng.run()
        return [out[u] for u in uids]

    for got, want in zip(serve(3), serve(0)):
        np.testing.assert_array_equal(got, want)


def test_fused_and_unfused_prefill_leave_the_same_pool(served):
    """At float32 the fused prefill (state rows written in the pool chunk
    by chunk) and the legacy one (b=1 cache scattered after the last
    chunk) leave the pool bitwise equal, state rows and pages alike."""
    _, cfg, params = served
    prompts = _prompts([40, 23, 17])
    engines, outs = {}, {}
    for fused in (False, True):
        eng = _engine(cfg, params, fused_prefill=fused)
        assert eng.scfg.fused_prefill is fused
        uids = [eng.submit(p) for p in prompts]
        out = eng.run()
        engines[fused], outs[fused] = eng, [out[u] for u in uids]
    for g, w in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(g, w)
    legacy, fused = engines[False].kv.pools, engines[True].kv.pools
    for name, c in legacy["blocks"].items():
        for key, leaf in c.items():
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(fused["blocks"][name][key]),
                err_msg=f"{name}/{key}")


def test_fused_prefill_resolves_on_for_paged_hybrids(served):
    _, cfg, params = served
    eng = _engine(cfg, params)
    assert eng.scfg.arch_kind == "mamba" and eng.scfg.fused_prefill is True
    with pytest.raises(NotImplementedError, match="state_snapshots"):
        ServeConfig(max_seq=64, paged=True, fused_prefill=True,
                    state_snapshots=True, arch_kind="mamba")


def test_decode_counts_expert_pairs_on_the_device(served):
    """``moe.decode_pairs`` counts every live row's picks in every layer;
    ``moe.decode_pairs_here`` those on held experts; the counters restart
    with a new registry and free rows count nothing."""
    d, cfg, params = served
    eng = _engine(cfg, params)
    for p in _prompts([40, 23]):
        eng.submit(p)
    while eng.queue:
        eng.step()
    from repro.obs import MetricsRegistry
    eng.metrics = MetricsRegistry()
    c0 = eng.metrics.snapshot()["counters"]
    assert c0["moe.decode_pairs"] == c0["moe.decode_pairs_here"] == 0
    eng._decode_tick()
    eng._decode_tick()
    c = eng.metrics.snapshot()["counters"]
    assert c["moe.decode_pairs"] == 2 * 2 * d.top_k * d.n_layers
    assert 0 < c["moe.decode_pairs_here"] <= c["moe.decode_pairs"]
