"""Plain float32 reference of a dense GQA decoder, and the served-token check.

The forward pass follows the published description (pre-norm RMSNorm,
rotary positions on the first ``rotary_fraction`` of each head, optional
per-head q/k RMSNorm, causal grouped-query attention, SwiGLU, tied output
head) with every matrix product at ``Precision.HIGHEST``. It imports
nothing of the server: weights are drawn again from the seed by
``chipbench.weights``, one layer at a time, so it fits beside nothing else
on the chip once the server is freed.

``served_gaps`` runs it over each sampled request's prompt and served
tokens and returns, per served token, how far the reference's logit of
that token lies below the reference's best logit at that position. With
``control=True`` it also runs the same pass with every matrix operand
rounded to float8 (e4m3, scaled per row or column): the lower precision a
later change might be tempted to serve in. Its gaps are those of the token
the float8 pass puts first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.dims import Dims

HI = jax.lax.Precision.HIGHEST
#: Sequences are padded to a multiple of this, so few programs compile.
PAD = 512
#: Query rows per attention block (bounds the score matrix's memory).
QBLOCK = 256
_F8_MAX = 448.0


def _f8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` through float8 e4m3 with one scale per slice along
    ``axis`` (the largest magnitude maps to the format's largest value)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, low: bool):
    if low:
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, dev, eps: float):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + dev)


def _rope(x, pos, theta: float, rot: int):
    """Split-half rotary on the first ``rot`` dims of each head."""
    half = rot // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(d: Dims, w: dict, h: jax.Array, low: bool) -> jax.Array:
    """One decoder layer over a padded (L, d_model) sequence."""
    n = h.shape[0]
    pos = jnp.arange(n)
    hd, g = d.head_dim, d.n_heads // d.n_kv_heads
    x = _rms(h, w["attn_norm"], d.norm_eps)
    q = _mm(x, w["wq"], low).reshape(n, d.n_heads, hd)
    k = _mm(x, w["wk"], low).reshape(n, d.n_kv_heads, hd)
    v = _mm(x, w["wv"], low).reshape(n, d.n_kv_heads, hd)
    if d.qk_norm:
        q = _rms(q, w["q_norm"], d.norm_eps)
        k = _rms(k, w["k_norm"], d.norm_eps)
    rot = int(hd * d.rotary_fraction)
    q = _rope(q, pos, d.rope_theta, rot)
    k = _rope(k, pos, d.rope_theta, rot)
    if low:
        q, k, v = _f8(q, -1), _f8(k, -1), _f8(v, -1)
    q = q.reshape(n // QBLOCK, QBLOCK, d.n_kv_heads, g, hd)
    scale = 1.0 / math.sqrt(hd)

    def block(args):
        qb, i = args
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * scale
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if low:
            p = _f8(p, -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, (q, jnp.arange(n // QBLOCK)))
    h = h + _mm(o.reshape(n, d.q_width), w["wo"], low)
    x = _rms(h, w["mlp_norm"], d.norm_eps)
    up = jax.nn.silu(_mm(x, w["w_gate"], low)) * _mm(x, w["w_up"], low)
    return h + _mm(up, w["w_down"], low)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_f32(d: Dims, key, layer) -> dict:
    return {k: v.astype(jnp.float32)
            for k, v in W.layer_weights(d, key, layer).items()}


@functools.partial(jax.jit, static_argnums=(0,))
def _globals_f32(d: Dims, key):
    return (W.embed_weights(d, key).astype(jnp.float32),
            W.final_norm_weights(d, key).astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 6))
def _head(d: Dims, embed, fnorm, h, start, toks, low: bool):
    """Logits of ``len(toks)`` positions of ``h`` from ``start``, and the
    gap of each of ``toks`` below its position's best logit."""
    h = jax.lax.dynamic_slice_in_dim(h, start, toks.shape[0])
    x = _rms(h, fnorm, d.norm_eps)
    e = _f8(embed, -1) if low else embed
    logits = jnp.matmul(_f8(x, -1) if low else x, e.T, precision=HI)
    gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, toks[:, None], axis=-1)[:, 0]
    return logits, gap


@jax.jit
def _gap_of_argmax(ref_logits, low_logits):
    pick = jnp.argmax(low_logits, axis=-1)
    return jnp.max(ref_logits, axis=-1) - jnp.take_along_axis(
        ref_logits, pick[:, None], axis=-1)[:, 0]


def served_gaps(d: Dims, seed: int, requests, *, rows: int,
                control: bool = False) -> dict[str, np.ndarray]:
    """Gaps of the served tokens of ``requests`` (a list of (prompt,
    served) int arrays), all positions concatenated. ``rows`` bounds the
    served tokens of one request (the output head always reads that many
    positions, so that its program does not depend on the sample). A
    served token outside the vocabulary reads an infinite gap. ``control``
    adds ``"control"``: the gaps of the tokens the float8 pass puts first."""
    key = W.seed_key(seed)
    embed, fnorm = _globals_f32(d, key)
    seqs, hs, lows = [], [], []
    low_embed = _f8(embed, -1) if control else None
    for prompt, served in requests:
        prompt = np.asarray(prompt, np.int64)
        served = np.asarray(served, np.int64)
        if len(served) > rows:
            raise ValueError(f"{len(served)} served tokens, rows={rows}")
        toks = np.concatenate([prompt, served[:-1]])
        n = -(-(len(prompt) - 1 + rows) // PAD) * PAD
        ids = np.zeros(n, np.int32)
        ids[: len(toks)] = toks
        seqs.append((len(prompt), served))
        h = embed[jnp.asarray(ids)]
        hs.append(h)
        if control:
            lows.append(low_embed[jnp.asarray(ids)])
    for layer in range(d.n_layers):
        w = _layer_f32(d, key, jnp.int32(layer))
        hs = [_layer(d, w, h, False) for h in hs]
        if control:
            lows = [_layer(d, w, h, True) for h in lows]
    gaps, ctrl = [], []
    for i, (p_len, served) in enumerate(seqs):
        m = len(served)
        inside = served < d.vocab
        toks = np.zeros(rows, np.int32)
        toks[:m] = np.where(inside, served, 0)
        start = jnp.int32(p_len - 1)
        logits, gap = _head(d, embed, fnorm, hs[i], start, toks, False)
        gaps.append(np.where(inside, np.asarray(gap)[:m], np.inf))
        if control:
            low_logits, _ = _head(d, embed, fnorm, lows[i], start, toks, True)
            ctrl.append(np.asarray(_gap_of_argmax(logits, low_logits))[:m])
    out = {"program": np.concatenate(gaps)}
    if control:
        out["control"] = np.concatenate(ctrl)
    return out
