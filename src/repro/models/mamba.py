"""Mamba2 (SSD, state-space duality) block — arXiv:2405.21060.

The chunked SSD algorithm *is* the paper's true-dependent streaming:
the sequence is partitioned into chunks (tasks); intra-chunk
compute is independent dense work, while the inter-chunk SSM state is a RAW
dependency handed from task to task — a 1-D wavefront.  We execute it with a
``lax.scan`` over chunks (see ``repro.core.streams.stream_scan``), so each
chunk's HBM traffic pipelines against the previous chunk's compute on TPU.

Shapes follow the minimal-SSD reference: x (B,S,H,P), dt (B,S,H), A (H,)
negative, B/C (B,S,N) single-group, state (B,H,P,N).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers

Params = dict[str, Any]

CONV_WIDTH = 4


# ----------------------------------------------------------------------------
# SSD core
# ----------------------------------------------------------------------------


def ssd_chunked(
    x: jax.Array,  # (B, S, H, P) already dt-weighted? no: raw inputs
    dt: jax.Array,  # (B, S, H) positive (softplus applied)
    a: jax.Array,  # (H,) negative
    b_: jax.Array,  # (B, S, N)
    c_: jax.Array,  # (B, S, N)
    *,
    chunk: int = 64,
    init_state: jax.Array | None = None,  # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b_.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        # Ragged tail (a prefill chunk grid need not tile the SSD chunk):
        # scan the aligned head, then carry the state through one short
        # tail chunk.  Bitwise identical to the aligned path when s % chunk
        # == 0 (this branch is never taken).
        main = (s // chunk) * chunk
        y_head, state = ssd_chunked(
            x[:, :main], dt[:, :main], a, b_[:, :main], c_[:, :main],
            chunk=chunk, init_state=init_state)
        y_tail, state = ssd_chunked(
            x[:, main:], dt[:, main:], a, b_[:, main:], c_[:, main:],
            chunk=s - main, init_state=state)
        return jnp.concatenate([y_head, y_tail], axis=1), state
    t = s // chunk

    f32 = jnp.float32
    xd = (x * dt[..., None]).astype(f32)  # dt-discretized input
    adt = (dt.astype(f32) * a.astype(f32)[None, None, :])  # (B,S,H) negative

    # chunked views: leading chunk axis for scan
    xc = xd.reshape(bsz, t, chunk, h, p).swapaxes(0, 1)  # (T,B,Q,H,P)
    ac = adt.reshape(bsz, t, chunk, h).swapaxes(0, 1)  # (T,B,Q,H)
    bc = b_.astype(f32).reshape(bsz, t, chunk, n).swapaxes(0, 1)  # (T,B,Q,N)
    cc = c_.astype(f32).reshape(bsz, t, chunk, n).swapaxes(0, 1)

    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), f32)

    idx = jnp.arange(chunk)
    tri = idx[:, None] >= idx[None, :]  # lower-triangular (Q,Q)

    def step(state, xs):
        xq, aq, bq, cq = xs  # per-chunk
        a_cs = jnp.cumsum(aq, axis=1)  # (B,Q,H) cumulative log-decay
        # L[i,j] = exp(cs_i - cs_j) for i >= j (intra-chunk decay matrix).
        # Mask BEFORE exp (segsum convention): exp of the masked upper
        # triangle would overflow (positive log-decays) and poison gradients
        # with inf * 0 = NaN.
        ldiff = a_cs[:, :, None, :] - a_cs[:, None, :, :]  # (B,Q,Q,H)
        l = jnp.exp(jnp.where(tri[None, :, :, None], ldiff, -jnp.inf))
        # Intra-chunk (dual quadratic form): Y_diag = (C B^T ∘ L) X
        scores = jnp.einsum("bqn,bkn->bqk", cq, bq)  # (B,Q,Q)
        y_diag = jnp.einsum("bqk,bqkh,bkhp->bqhp", scores, l, xq)
        # Contribution of the carried state: decay from chunk start.
        state_decay = jnp.exp(a_cs)  # (B,Q,H)
        y_off = jnp.einsum("bqn,bhpn,bqh->bqhp", cq, state, state_decay)
        # New chunk state: inputs decayed to the chunk end.
        decay_to_end = jnp.exp(a_cs[:, -1:, :] - a_cs)  # (B,Q,H)
        chunk_state = jnp.einsum("bqn,bqh,bqhp->bhpn", bq, decay_to_end, xq)
        total_decay = jnp.exp(a_cs[:, -1, :])  # (B,H)
        state = state * total_decay[:, :, None, None] + chunk_state
        return state, y_diag + y_off

    state, yc = jax.lax.scan(step, init_state, (xc, ac, bc, cc))
    y = yc.swapaxes(0, 1).reshape(bsz, s, h, p)
    return y.astype(x.dtype), state


def ssd_ref(
    x: jax.Array, dt: jax.Array, a: jax.Array, b_: jax.Array, c_: jax.Array,
    *, init_state: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Naive per-token recurrence oracle: h_t = exp(dt_t a) h_{t-1} + dt_t B_t x_t."""
    bsz, s, h, p = x.shape
    n = b_.shape[-1]
    f32 = jnp.float32
    state = init_state if init_state is not None else jnp.zeros((bsz, h, p, n), f32)

    def step(state, xs):
        xt, dtt, bt, ct = xs  # (B,H,P), (B,H), (B,N), (B,N)
        decay = jnp.exp(dtt.astype(f32) * a.astype(f32)[None])  # (B,H)
        inp = jnp.einsum("bn,bhp,bh->bhpn", bt.astype(f32), xt.astype(f32), dtt.astype(f32))
        state = state * decay[..., None, None] + inp
        y = jnp.einsum("bn,bhpn->bhp", ct.astype(f32), state)
        return state, y

    xs = (x.swapaxes(0, 1), dt.swapaxes(0, 1), b_.swapaxes(0, 1), c_.swapaxes(0, 1))
    state, ys = jax.lax.scan(step, state, xs)
    return ys.swapaxes(0, 1).astype(x.dtype), state


def ssd_decode_step(
    state: jax.Array,  # (B, H, P, N)
    x_t: jax.Array,  # (B, H, P)
    dt_t: jax.Array,  # (B, H)
    a: jax.Array,  # (H,)
    b_t: jax.Array,  # (B, N)
    c_t: jax.Array,  # (B, N)
) -> tuple[jax.Array, jax.Array]:
    """One-token SSM update (decode). Returns (y (B,H,P), new state)."""
    f32 = jnp.float32
    decay = jnp.exp(dt_t.astype(f32) * a.astype(f32)[None])
    inp = jnp.einsum("bn,bhp,bh->bhpn", b_t.astype(f32), x_t.astype(f32), dt_t.astype(f32))
    state = state * decay[..., None, None] + inp
    y = jnp.einsum("bn,bhpn->bhp", c_t.astype(f32), state)
    return y.astype(x_t.dtype), state


# ----------------------------------------------------------------------------
# Full Mamba2 block (projections + conv + gating)
# ----------------------------------------------------------------------------


def mamba_dims(d_model: int, *, expand: int = 2, headdim: int = 64, d_state: int = 128):
    d_inner = expand * d_model
    n_heads = d_inner // headdim
    conv_dim = d_inner + 2 * d_state
    return d_inner, n_heads, conv_dim


def mamba_init(
    key, *, d_model: int, expand: int = 2, headdim: int = 64, d_state: int = 128, dtype=jnp.float32
) -> Params:
    d_inner, n_heads, conv_dim = mamba_dims(d_model, expand=expand, headdim=headdim, d_state=d_state)
    d_in_proj = 2 * d_inner + 2 * d_state + n_heads
    ks = jax.random.split(key, 4)
    return {
        "in_proj": layers.dense_init(ks[0], (d_model, d_in_proj), dtype),
        "conv_w": layers.dense_init(ks[1], (CONV_WIDTH, conv_dim), dtype, scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(jnp.float32),
        "D": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "norm": layers.rmsnorm_init(d_inner, dtype),
        "out_proj": layers.dense_init(ks[3], (d_inner, d_model), dtype),
    }


def _split_proj(zxbcdt: jax.Array, d_inner: int, d_state: int, n_heads: int):
    z = zxbcdt[..., :d_inner]
    x = zxbcdt[..., d_inner : 2 * d_inner]
    b_ = zxbcdt[..., 2 * d_inner : 2 * d_inner + d_state]
    c_ = zxbcdt[..., 2 * d_inner + d_state : 2 * d_inner + 2 * d_state]
    dt = zxbcdt[..., 2 * d_inner + 2 * d_state :]
    return z, x, b_, c_, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv, width CONV_WIDTH.  xbc: (B,S,C), w: (W,C)."""
    pads = jnp.pad(xbc, ((0, 0), (CONV_WIDTH - 1, 0), (0, 0)))
    out = jnp.zeros_like(xbc)
    for i in range(CONV_WIDTH):  # width-4 unrolled shifts: cheap, fusable
        out = out + pads[:, i : i + xbc.shape[1]] * w[i][None, None, :]
    return out + b[None, None, :]


def mamba_apply(
    p: Params,
    u: jax.Array,  # (B, S, D)
    *,
    headdim: int = 64,
    d_state: int = 128,
    expand: int = 2,
    chunk: int = 64,
    state: jax.Array | None = None,
    conv_state: jax.Array | None = None,  # (B, W-1, conv_dim) decode carry
    decode: bool = False,
    norm_eps: float = 1e-6,  # the gated RMSNorm's epsilon
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Full block. Returns (out (B,S,D), cache {"ssm","conv"}); the SSM
    state is updated and returned in float32, whatever it is stored in."""
    bsz, s, d_model = u.shape
    d_inner, n_heads, conv_dim = mamba_dims(d_model, expand=expand, headdim=headdim, d_state=d_state)

    zxbcdt = u @ p["in_proj"]
    z, x, b_, c_, dt = _split_proj(zxbcdt, d_inner, d_state, n_heads)

    xbc = jnp.concatenate([x, b_, c_], axis=-1)  # (B,S,conv_dim)
    if decode:
        assert conv_state is not None and s == 1
        window = jnp.concatenate([conv_state, xbc], axis=1)  # (B,W,conv)
        conv = (window * p["conv_w"][None]).sum(axis=1, keepdims=True) + p["conv_b"][None, None]
        new_conv_state = window[:, 1:]
    else:
        # Chunked-prefill continuation: the previous chunk's tail enters the
        # causal conv window (zeros when starting fresh).
        head = (conv_state if conv_state is not None else
                jnp.zeros((bsz, CONV_WIDTH - 1, conv_dim), xbc.dtype))
        ext = jnp.concatenate([head.astype(xbc.dtype), xbc], axis=1)
        conv = _causal_conv(ext, p["conv_w"], p["conv_b"])[:, CONV_WIDTH - 1:]
        new_conv_state = ext[:, -(CONV_WIDTH - 1):]
    conv = jax.nn.silu(conv)
    x = conv[..., :d_inner].reshape(bsz, s, n_heads, headdim)
    b_ = conv[..., d_inner : d_inner + d_state]
    c_ = conv[..., d_inner + d_state :]

    a = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,) negative
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32)[None, None])

    if decode:
        assert state is not None
        y_t, new_state = ssd_decode_step(
            state.astype(jnp.float32), x[:, 0], dt[:, 0], a, b_[:, 0], c_[:, 0]
        )
        y = y_t[:, None]
    else:
        init = state.astype(jnp.float32) if state is not None else None
        y, new_state = ssd_chunked(x, dt, a, b_, c_, chunk=chunk, init_state=init)

    y = y + p["D"][None, None, :, None].astype(y.dtype) * x  # skip connection
    y = y.reshape(bsz, s, d_inner)
    y = layers.rmsnorm(p["norm"], y * jax.nn.silu(z), eps=norm_eps)
    out = y @ p["out_proj"]
    return out, {"ssm": new_state, "conv": new_conv_state}


def mamba_cache_init(bsz: int, d_model: int, *, expand=2, headdim=64, d_state=128, dtype=jnp.float32):
    d_inner, n_heads, conv_dim = mamba_dims(d_model, expand=expand, headdim=headdim, d_state=d_state)
    return {
        "ssm": jnp.zeros((bsz, n_heads, headdim, d_state), jnp.float32),
        "conv": jnp.zeros((bsz, CONV_WIDTH - 1, conv_dim), dtype),
    }
