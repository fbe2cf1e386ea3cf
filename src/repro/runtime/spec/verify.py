"""The batched verify step of speculative decode, fused on device.

One jitted target step scores ``k + 1`` positions per slot — the pending
token plus up to ``k`` draft tokens — through
``transformer.decode_step_multi[_paged]`` (per-slot variable-length query
blocks, causal masking inside the block), then applies the acceptance rule
in the same jitted graph:

  * **greedy** — accept the longest prefix of the draft that matches the
    target argmax chain; the position after it emits the target's own
    argmax (the "bonus" token).  By induction this emits exactly the
    tokens plain greedy decode would: position t's logits condition on
    drafts 1..t, which equal the greedy chain whenever they were accepted.
  * **temperature** — rejection sampling (Leviathan et al.): the n-gram
    drafter's proposal is a point mass, so draft token ``d_i`` is accepted
    with probability ``p_target(d_i)``; on rejection the emitted token is
    drawn from the residual ``p`` with ``d_i`` masked out (renormalized),
    and full acceptance ends with a fresh draw at the bonus position.
    Each emitted token is distributed exactly as a sample from the target
    — speculation changes latency, never the distribution.

The tick's only device-to-host transfer is the emitted-token block
``(B, k+1)`` plus the per-slot acceptance counts ``(B,)`` — the multi-token
analog of the fused single-token sampler (one int32 per slot per tick).
Padding rows (``d_len = 0`` and a zero pending token on inactive slots)
ride along exactly as they do in the plain decode tick.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.runtime.serving import slot_key

NEG_INF = -1e30


def greedy_accept(
    target: jax.Array,  # (B, T) int32: target argmax per position
    draft: jax.Array,  # (B, T-1) int32: proposed draft tokens
    d_len: jax.Array,  # (B,) int32: live draft length per slot (0..T-1)
) -> jax.Array:
    """Longest accepted prefix per slot: the number of leading positions
    where the draft token equals the target argmax, capped at ``d_len``.
    Equivalently the length of the longest common prefix of
    ``draft[:d_len]`` and ``target[:d_len]`` — the property the tests
    pin down."""
    idx = jnp.arange(draft.shape[1])[None, :]
    match = (draft == target[:, :-1]) & (idx < d_len[:, None])
    return jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)


def verify_greedy(
    logits: jax.Array,  # (B, T, V) f32 target logits
    draft: jax.Array,  # (B, T-1) int32
    d_len: jax.Array,  # (B,) int32
) -> tuple[jax.Array, jax.Array]:
    """Greedy acceptance.  Returns (emit (B, T) int32, n_accept (B,)).

    ``emit[b, :n_accept[b] + 1]`` are the tokens slot b produces this tick:
    the accepted draft prefix (which equals the target argmax there) plus
    the bonus token — the target argmax at the first unaccepted position.
    """
    target = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return target, greedy_accept(target, draft, d_len)


def _pos_keys(uids: jax.Array, steps: jax.Array, t: int, tag: int) -> Any:
    """(B, t) PRNG keys: the engine-wide ``slot_key(uid, step + i)`` stream
    with a ``tag`` fold on top (accept draws and sample draws at the same
    position must be independent)."""

    def one(u, s0):
        return jax.vmap(lambda i: jax.random.fold_in(
            slot_key(u, s0 + i), tag))(jnp.arange(t))

    return jax.vmap(one)(uids, steps)


def verify_sampled(
    logits: jax.Array,  # (B, T, V) f32 target logits
    draft: jax.Array,  # (B, T-1) int32
    d_len: jax.Array,  # (B,) int32
    uids: jax.Array,  # (B,) int32 request uids (key stream identity)
    steps: jax.Array,  # (B,) int32 tokens emitted so far per slot
    temperature: float,
) -> tuple[jax.Array, jax.Array]:
    """Temperature rejection-sampling acceptance (point-mass proposal).

    Accept draft ``d_i`` with probability ``p(d_i)`` (the proposal is a
    point mass, so ``min(1, p/q) = p(d_i)``); at the stopping position
    emit a draw from the residual distribution (``p`` with the rejected
    token masked, renormalized) — or, after full acceptance, a fresh draw
    from ``p`` at the bonus position.  Marginally every emitted token is
    an exact target sample.  Returns (emit (B, T), n_accept (B,)).
    """
    b, t, v = logits.shape
    scaled = logits / temperature
    p = jax.nn.softmax(scaled, axis=-1)

    idx = jnp.arange(t - 1)[None, :]
    p_draft = jnp.take_along_axis(
        p[:, :-1], draft[..., None].astype(jnp.int32), axis=-1)[..., 0]
    u = jax.vmap(jax.vmap(lambda k: jax.random.uniform(k)))(
        _pos_keys(uids, steps, t - 1, tag=1))  # (B, T-1)
    accept = (u < p_draft) & (idx < d_len[:, None])
    n_accept = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)

    sample_keys = _pos_keys(uids, steps, t, tag=2)
    full = jax.vmap(jax.vmap(jax.random.categorical))(
        sample_keys, scaled).astype(jnp.int32)  # (B, T)
    hot = jax.nn.one_hot(draft, v, dtype=bool)
    resid = jax.vmap(jax.vmap(jax.random.categorical))(
        sample_keys[:, :-1],
        jnp.where(hot, NEG_INF, scaled[:, :-1])).astype(jnp.int32)

    # Token at the stopping position i: rejection there (i < d_len) draws
    # from the residual, exhaustion of the draft (i == d_len) draws fresh.
    stop = jnp.concatenate(
        [jnp.where(idx < d_len[:, None], resid, full[:, :-1]),
         full[:, -1:]], axis=1)  # (B, T)
    draft_pad = jnp.concatenate(
        [draft, jnp.zeros((b, 1), jnp.int32)], axis=1)
    pos = jnp.arange(t)[None, :]
    emit = jnp.where(
        pos < n_accept[:, None], draft_pad,
        jnp.where(pos == n_accept[:, None], stop, full))
    return emit.astype(jnp.int32), n_accept


def make_verifier(
    cfg: Any, *, paged: bool, temperature: float = 0.0,
    paged_kernel: bool = False,
):
    """Build the engine's jitted verify step.

    Returns a function whose signature mirrors the engine's fused decode
    step, widened to the draft block:

      paged:      (params, toks (B,T), pools, page_table, cur, d_len[,
                   uids, steps]) -> (emit, n_accept, pools)
      contiguous: (params, toks, caches, cur, d_len[, uids, steps])
                   -> (emit, n_accept, caches)

    ``toks[:, 0]`` is each slot's pending token, ``toks[:, 1:]`` the draft
    (zero-padded past ``d_len``); the uids/steps tail exists only at
    temperature > 0 (per-slot rejection-sampling key streams). The paged
    step is donated the pools and updates them in place.
    """
    temp = float(temperature)
    kern = bool(paged_kernel)

    if paged:
        if temp > 0.0:
            def fn(params, toks, pools, page_table, cur, d_len, uids, steps):
                logits, pools = T.decode_step_multi_paged(
                    cfg, params, toks, pools, page_table, cur,
                    paged_kernel=kern)
                emit, n_accept = verify_sampled(
                    logits, toks[:, 1:], d_len, uids, steps, temp)
                return emit, n_accept, pools
        else:
            def fn(params, toks, pools, page_table, cur, d_len):
                logits, pools = T.decode_step_multi_paged(
                    cfg, params, toks, pools, page_table, cur,
                    paged_kernel=kern)
                emit, n_accept = verify_greedy(logits, toks[:, 1:], d_len)
                return emit, n_accept, pools
    else:
        if temp > 0.0:
            def fn(params, toks, caches, cur, d_len, uids, steps):
                logits, caches = T.decode_step_multi(
                    cfg, params, toks, caches, cur)
                emit, n_accept = verify_sampled(
                    logits, toks[:, 1:], d_len, uids, steps, temp)
                return emit, n_accept, caches
        else:
            def fn(params, toks, caches, cur, d_len):
                logits, caches = T.decode_step_multi(
                    cfg, params, toks, caches, cur)
                emit, n_accept = verify_greedy(logits, toks[:, 1:], d_len)
                return emit, n_accept, caches

    return jax.jit(fn, donate_argnums=2 if paged else ())
