"""Shared neural-net layers (pure-pytree params, no framework dependency).

Conventions:
  * params are nested dicts of jnp arrays; init fns take a PRNG key.
  * compute runs in ``cfg.compute_dtype`` (bf16 on TPU); norms, softmax and
    the loss accumulate in fp32.
  * the chunked cross-entropy streams over sequence chunks so the full
    (B, S, V) logits tensor is never materialized -- an Independent-task
    stream (see repro.core.streams).
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

Params = dict[str, Any]


# ----------------------------------------------------------------------------
# Initializers
# ----------------------------------------------------------------------------


def dense_init(key, shape, dtype, *, scale: float | None = None) -> jax.Array:
    """Truncated-normal fan-in init (scale defaults to 1/sqrt(fan_in))."""
    fan_in = shape[0] if len(shape) > 1 else 1
    std = scale if scale is not None else 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------------------
# RMSNorm
# ----------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.zeros((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # "zero-centered" scale (gemma-style 1+scale keeps init at identity).
    return (x * (1.0 + p["scale"].astype(jnp.float32))).astype(dtype)


# ----------------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------------


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """(sin, cos) of shape positions.shape + (head_dim/2,) in fp32."""
    half = head_dim // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., S, H, D). sin/cos: (..., S, D/2) broadcast over heads."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    sin = sin[..., None, :]  # add head axis
    cos = cos[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def sinusoidal_positions(seq: int, d_model: int, dtype) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings (S, D)."""
    return sinusoidal_positions_at(
        jnp.arange(seq, dtype=jnp.float32), d_model, dtype)


def sinusoidal_positions_at(positions: jax.Array, d_model: int, dtype) -> jax.Array:
    """Sinusoidal embeddings at (possibly traced) positions: (..., D).

    Row ``p`` matches ``sinusoidal_positions(seq, ...)[p]`` bitwise, so
    decode steps can look up the embedding for a dynamic position.
    """
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(1, half - 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ----------------------------------------------------------------------------
# Feed-forward blocks
# ----------------------------------------------------------------------------


def ffn_init(key, d_model: int, d_ff: int, dtype, *, kind: str = "swiglu") -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "wi": dense_init(k1, (d_model, d_ff), dtype),
            "wg": dense_init(k2, (d_model, d_ff), dtype),
            "wo": dense_init(k3, (d_ff, d_model), dtype),
        }
    if kind == "gelu_mlp":
        return {
            "wi": dense_init(k1, (d_model, d_ff), dtype),
            "wo": dense_init(k3, (d_ff, d_model), dtype),
        }
    raise ValueError(f"unknown ffn kind {kind}")


def ffn_apply(p: Params, x: jax.Array, *, kind: str = "swiglu") -> jax.Array:
    if kind == "swiglu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]
    if kind == "geglu":
        return (jax.nn.gelu(x @ p["wg"], approximate=True) * (x @ p["wi"])) @ p["wo"]
    if kind == "gelu_mlp":
        return jax.nn.gelu(x @ p["wi"], approximate=True) @ p["wo"]
    raise ValueError(f"unknown ffn kind {kind}")


# ----------------------------------------------------------------------------
# Softcap (gemma2)
# ----------------------------------------------------------------------------


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# ----------------------------------------------------------------------------
# Chunked cross-entropy (vocab/sequence streaming)
# ----------------------------------------------------------------------------


def chunked_cross_entropy(
    hidden: jax.Array,  # (B, S, D) final hidden states
    out_embed: jax.Array,  # (V, D) output embedding (logits = h @ E^T)
    targets: jax.Array,  # (B, S) int32
    mask: jax.Array,  # (B, S) 0/1 loss mask
    *,
    chunk: int = 512,
    final_softcap: float = 0.0,
) -> jax.Array:
    """Mean CE over masked tokens, streaming over sequence chunks.

    Each chunk's (B, chunk, V) logits live only inside one scan step --
    Independent-task streaming of the loss (paper's partition-and-pipeline),
    essential for V=256k configs where full logits would be ~0.5 PB.
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    n_chunks = s // chunk
    assert s % chunk == 0, f"seq {s} % loss chunk {chunk} != 0"

    hc = hidden.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)  # (n, B, c, D)
    tc = targets.reshape(b, n_chunks, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    def step(carry, xs):
        loss_sum, count = carry
        h, t, m = xs
        logits = (h.astype(jnp.float32) @ out_embed.astype(jnp.float32).T)
        logits = softcap(logits, final_softcap)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * m.astype(jnp.float32)
        return (loss_sum + nll.sum(), count + m.sum()), None

    (loss_sum, count), _ = jax.lax.scan(step, (jnp.float32(0), jnp.float32(0)), (hc, tc, mc))
    return loss_sum / jnp.maximum(count, 1.0)
