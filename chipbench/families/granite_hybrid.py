"""Granite 4.0-H hybrids: Mamba-2 and NoPE grouped-query attention layers
in the published order, each layer followed by a mixture of experts plus
an ungated shared MLP (granite-4.0-h-small's layout, ``granitemoehybrid``).

The contract is ``families/dense_gqa.py``'s. The layer equations, which the
plain reference below follows, with ``rms`` the RMSNorm of weight
``1 + s`` (``s`` drawn from the seed, as in ``weights.py``):

- embedding: ``h = embedding_multiplier * E[tok]``;
- each layer: ``h += residual_multiplier * mixer(rms(h))``, then with
  ``x = rms(h)``: ``h += residual_multiplier * (moe(x) + shared(x))``;
- Mamba-2 mixer: ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv4(xBC) +
  b)``, causal; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t . S_t +
  D x_t``; ``out = rms(y * silu(z)) W_out``;
- attention: causal GQA with no positional encoding, scores
  ``q.k * attention_multiplier``;
- experts: router logits ``x W_r`` over every expert, the top
  ``num_experts_per_tok`` picked and gated by the softmax over the picked
  logits; expert ``e`` is ``(silu(x W1_e) * x W2_e) W3_e``; this chip holds
  experts ``[0, num_local_experts)`` and computes their part only;
- shared MLP: the same SwiGLU at ``shared_intermediate_size``, ungated;
- output: ``logits = rms(h) E^T / logits_scaling`` (tied).

The configuration file gives ``num_local_experts`` as the experts held on
this chip; the router's width is ``published.num_local_experts``. Weights
are drawn from the seed by name, layer and expert, rounded to bfloat16;
the server holds all of them in bfloat16, and so does its per-slot SSM
state (``ssm_cache_dtype``), updated in float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops
from chipbench.reference import HI, PAD, QBLOCK, _f8, _gap_of_argmax, _mm, _rms
from chipbench.weights import seed_key

BF16 = 2
CONV_WIDTH = 4
#: Spread of the norm weights around one.
NORM_STD = 0.1
#: Spread of the embedding rows (tied: also the output head): the
#: residual stream's embedding, ``12 E``, spreads 0.02 as ``dense_gqa``'s
#: does, small beside the layers' outputs, so that the logits are not
#: dominated by the input token. (At 0.1, the configuration's
#: ``initializer_range``, the input token's own logit stood some 50
#: spreads above the rest: every served token echoed its input, and the
#: float8 control never differed from the reference.)
EMBED_STD = 0.02 / 12
#: Mamba-2's initial ranges: ``A`` uniform in [1, 16], ``dt`` log-uniform
#: in [1e-3, 1e-1] (``dt_bias`` its inverse softplus).
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)

LEAF_IDS = {"mixer_norm": 1, "in_proj": 2, "conv_w": 3, "conv_b": 4,
            "A_log": 5, "D": 6, "dt_bias": 7, "gate_norm": 8, "out_proj": 9,
            "wq": 10, "wk": 11, "wv": 12, "wo": 13, "ffn_norm": 14,
            "router": 15, "w1": 16, "w2": 17, "w3": 18, "shared_w1": 19,
            "shared_w2": 20, "shared_w3": 21, "embed": 22, "final_norm": 23}


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    arch: str
    n_layers: int
    kinds: tuple[str, ...]  # "mamba" | "attention", per layer
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    n_experts: int  # the router's width
    experts_held: int  # experts [0, experts_held) are held here
    top_k: int
    expert_width: int
    shared_width: int
    mamba_heads: int
    mamba_head_dim: int
    d_state: int
    embed_mult: float
    residual_mult: float
    attention_mult: float
    logits_scaling: float
    norm_eps: float
    #: The configuration's values of the keys of ``FIXED``.
    settings: tuple[tuple[str, Any], ...] = ()

    @property
    def q_width(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.d_state

    @property
    def in_proj_width(self) -> int:
        return 2 * self.d_inner + 2 * self.d_state + self.mamba_heads

    @property
    def n_attention(self) -> int:
        return self.kinds.count("attention")

    @property
    def n_mamba(self) -> int:
        return self.kinds.count("mamba")


def dims(conf: dict[str, Any]) -> Dims:
    n = int(conf["num_hidden_layers"])
    return Dims(
        name=conf["name"], arch=conf["arch"], n_layers=n,
        kinds=tuple(conf["layer_types"][:n]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["hidden_size"]) // int(conf["num_attention_heads"]),
        vocab=int(conf["vocab_size"]),
        n_experts=int(conf["published"]["num_local_experts"]),
        experts_held=int(conf["num_local_experts"]),
        top_k=int(conf["num_experts_per_tok"]),
        expert_width=int(conf["intermediate_size"]),
        shared_width=int(conf["shared_intermediate_size"]),
        mamba_heads=int(conf["mamba_n_heads"]),
        mamba_head_dim=int(conf["mamba_d_head"]),
        d_state=int(conf["mamba_d_state"]),
        embed_mult=float(conf["embedding_multiplier"]),
        residual_mult=float(conf["residual_multiplier"]),
        attention_mult=float(conf["attention_multiplier"]),
        logits_scaling=float(conf["logits_scaling"]),
        norm_eps=float(conf["rms_norm_eps"]),
        settings=tuple((k, conf.get(k)) for k in FIXED))


# -- the server's configuration ------------------------------------------------

#: Configuration values the server and the reference compute alike only at
#: these settings.
FIXED = {"position_embedding_type": "nope", "mamba_n_groups": 1,
         "mamba_d_conv": CONV_WIDTH, "mamba_conv_bias": True,
         "mamba_proj_bias": False, "attention_bias": False,
         "hidden_act": "silu", "normalization_function": "rmsnorm",
         "tie_word_embeddings": True, "rope_scaling": None}


def program_config(d: Dims):
    """The server's ``ModelConfig`` for these sizes, in bfloat16, holding
    this chip's experts. Refuses, by key, what the server computes
    otherwise than the reference: the layer order (``layer_types``), a
    setting other than ``FIXED``'s, and the program's own architecture
    settings (``arch (...)``)."""
    from repro import configs

    base = configs.get_config(d.arch)
    unit = base.layer_unit
    kinds = tuple("attention" if s.mixer == "attn" else "mamba"
                  for s in unit) * max(1, d.n_layers // len(unit))
    cfg = dataclasses.replace(
        base, n_layers=d.n_layers, d_model=d.d_model, n_heads=d.n_heads,
        n_kv_heads=d.n_kv_heads, head_dim=d.head_dim, d_ff=d.expert_width,
        vocab_size=d.vocab, n_experts=d.n_experts, top_k=d.top_k,
        expert_d_ff=d.expert_width, shared_d_ff=d.shared_width,
        experts_held=d.experts_held,
        ssm_state=d.d_state, mamba_headdim=d.mamba_head_dim,
        query_scale=d.attention_mult, embed_multiplier=d.embed_mult,
        residual_multiplier=d.residual_mult,
        logits_scaling=d.logits_scaling, norm_eps=d.norm_eps,
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
        ssm_cache_dtype=jnp.bfloat16)
    same = {
        "layer_types": kinds == d.kinds,
        "num_hidden_layers": d.n_layers % len(unit) == 0,
        "mamba_n_heads": cfg.mamba_expand * d.d_model == d.d_inner,
        "arch (ffn)": all(s.ffn == "moe" and not s.cross_attn for s in unit),
        "arch (shared_gate)": not cfg.shared_gate,
        "arch (n_shared_experts)": cfg.n_shared_experts == 1,
        "arch (use_rope)": not cfg.use_rope,
        "arch (sinusoidal_pos)": not cfg.sinusoidal_pos,
        "arch (sliding_window)": not cfg.sliding_window,
        "arch (softcaps)": not (cfg.attn_softcap or cfg.final_softcap),
        "arch (sandwich_norm)": not cfg.sandwich_norm,
        "arch (embed_scale)": not cfg.embed_scale,
        "arch (qk_norm)": not cfg.qk_norm,
        "arch (expert_shards)": cfg.expert_shards == 1,
        "arch (tie_embeddings)": cfg.tie_embeddings,
    }
    for key, value in d.settings:
        same[key] = value == FIXED[key]
    differ = [key for key, ok in same.items() if not ok]
    if differ:
        raise ValueError(f"{d.name} ({d.arch}): the server and the "
                         f"granite_hybrid reference differ in "
                         f"{', '.join(differ)}")
    return cfg


# -- weights -------------------------------------------------------------------


def _key(key, name: str, layer, expert=None):
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    return k if expert is None else jax.random.fold_in(k, expert)


def _normal(key, name, layer, shape, std, expert=None) -> jax.Array:
    return (jax.random.normal(_key(key, name, layer, expert), shape,
                              jnp.float32) * std).astype(jnp.bfloat16)


def mixer_weights(d: Dims, key, layer, kind: str) -> dict[str, jax.Array]:
    """One layer's mixer and its norm, bfloat16; ``layer`` may be traced."""
    dm, h = d.d_model, d.mamba_heads
    w = {"mixer_norm": _normal(key, "mixer_norm", layer, (dm,), NORM_STD)}
    if kind == "attention":
        w.update(
            wq=_normal(key, "wq", layer, (dm, d.q_width), dm ** -0.5),
            wk=_normal(key, "wk", layer, (dm, d.kv_width), dm ** -0.5),
            wv=_normal(key, "wv", layer, (dm, d.kv_width), dm ** -0.5),
            wo=_normal(key, "wo", layer, (d.q_width, dm), d.q_width ** -0.5))
        return w
    lo, hi = A_RANGE
    a = jax.random.uniform(_key(key, "A_log", layer), (h,), jnp.float32,
                           lo, hi)
    dt = jnp.exp(jax.random.uniform(
        _key(key, "dt_bias", layer), (h,), jnp.float32,
        math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
    w.update(
        in_proj=_normal(key, "in_proj", layer, (dm, d.in_proj_width),
                        dm ** -0.5),
        conv_w=_normal(key, "conv_w", layer, (CONV_WIDTH, d.conv_dim),
                       CONV_WIDTH ** -0.5),
        conv_b=_normal(key, "conv_b", layer, (d.conv_dim,), NORM_STD),
        A_log=jnp.log(a).astype(jnp.bfloat16),
        D=(1.0 + jax.random.normal(_key(key, "D", layer), (h,)) * NORM_STD
           ).astype(jnp.bfloat16),
        dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.bfloat16),
        gate_norm=_normal(key, "gate_norm", layer, (d.d_inner,), NORM_STD),
        out_proj=_normal(key, "out_proj", layer, (d.d_inner, dm),
                         d.d_inner ** -0.5))
    return w


def ffn_weights(d: Dims, key, layer, experts) -> dict[str, jax.Array]:
    """One layer's expert block, bfloat16: its norm, the router over every
    expert, the experts of ``experts`` (stacked) and the shared MLP."""
    dm, f, fs = d.d_model, d.expert_width, d.shared_width
    experts = jnp.asarray(experts)

    def stack(name, shape, std):
        return jax.vmap(lambda e: _normal(key, name, layer, shape, std, e))(
            experts)

    return {
        "ffn_norm": _normal(key, "ffn_norm", layer, (dm,), NORM_STD),
        "router": _normal(key, "router", layer, (dm, d.n_experts),
                          dm ** -0.5),
        "w1": stack("w1", (dm, f), dm ** -0.5),
        "w2": stack("w2", (dm, f), dm ** -0.5),
        "w3": stack("w3", (f, dm), f ** -0.5),
        "shared_w1": _normal(key, "shared_w1", layer, (dm, fs), dm ** -0.5),
        "shared_w2": _normal(key, "shared_w2", layer, (dm, fs), dm ** -0.5),
        "shared_w3": _normal(key, "shared_w3", layer, (fs, dm), fs ** -0.5),
    }


def embed_weights(d: Dims, key) -> jax.Array:
    return _normal(key, "embed", 0, (d.vocab, d.d_model), EMBED_STD)


def final_norm_weights(d: Dims, key) -> jax.Array:
    return _normal(key, "final_norm", 0, (d.d_model,), NORM_STD)


def _program_layer(d: Dims, key, layer: int) -> dict:
    """Layer ``layer`` in ``repro.models.transformer``'s layout."""
    m = mixer_weights(d, key, layer, d.kinds[layer])
    f = ffn_weights(d, key, layer, np.arange(d.experts_held))
    if d.kinds[layer] == "attention":
        mixer = {k: m[k] for k in ("wq", "wk", "wv", "wo")}
    else:
        mixer = {k: m[k] for k in ("in_proj", "conv_w", "conv_b", "A_log",
                                   "D", "dt_bias", "out_proj")}
        mixer["norm"] = {"scale": m["gate_norm"]}
    return {
        "mixer_norm": {"scale": m["mixer_norm"]},
        "mixer": mixer,
        "ffn_norm": {"scale": f["ffn_norm"]},
        "ffn": {"router": f["router"], "wg": f["w1"], "wi": f["w2"],
                "wo": f["w3"],
                "shared": {"wg": f["shared_w1"], "wi": f["shared_w2"],
                           "wo": f["shared_w3"]}},
    }


def _unit_len(d: Dims) -> int:
    """Layers of one unit of the program's layer stack (one period)."""
    from repro import configs

    return len(configs.get_config(d.arch).layer_unit)


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _program_params(d: Dims, padded_vocab: int, key, unit: int) -> dict:
    reps = d.n_layers // unit
    blocks = {}
    for i in range(unit):
        per = [_program_layer(d, key, r * unit + i) for r in range(reps)]
        blocks[f"layer{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    embed = jnp.zeros((padded_vocab, d.d_model), jnp.bfloat16)
    embed = embed.at[: d.vocab].set(embed_weights(d, key))
    return {"embed": embed,
            "final_norm": {"scale": final_norm_weights(d, key)},
            "blocks": blocks}


def program_params(d: Dims, padded_vocab: int, seed: int) -> dict:
    """All of the server's weights on the device, from the seed, in one
    jitted call. Rows of the padded vocabulary past ``vocab`` are zero."""
    return jax.block_until_ready(
        _program_params(d, padded_vocab, seed_key(seed), _unit_len(d)))


def layer_params(d: Dims, kind: str) -> int:
    """Parameters of one layer as held here (norms included)."""
    dm = d.d_model
    if kind == "attention":
        mixer = dm * (d.q_width + 2 * d.kv_width) + d.q_width * dm
    else:
        mixer = (dm * d.in_proj_width + CONV_WIDTH * d.conv_dim + d.conv_dim
                 + 3 * d.mamba_heads + d.d_inner + d.d_inner * dm)
    ffn = (dm * d.n_experts + 3 * dm * d.expert_width * d.experts_held
           + 3 * dm * d.shared_width)
    return dm + mixer + dm + ffn


def param_bytes(d: Dims, padded_vocab: int) -> int:
    layers = sum(layer_params(d, k) for k in d.kinds)
    return BF16 * (layers + padded_vocab * d.d_model + d.d_model)


def state_bytes(d: Dims) -> int:
    """One slot's recurrent state as the server stores it (bfloat16): the
    SSM state and the conv tail of every Mamba-2 layer."""
    per = (d.mamba_heads * d.mamba_head_dim * d.d_state
           + (CONV_WIDTH - 1) * d.conv_dim)
    return BF16 * d.n_mamba * per


# -- the plain reference -------------------------------------------------------


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_f32(d: Dims, key, layer, kind: str) -> dict:
    w = mixer_weights(d, key, layer, kind)
    w.update(ffn_weights(d, key, layer, np.arange(d.experts_held)))
    return _f32(w)


@functools.partial(jax.jit, static_argnums=(0,))
def _globals_f32(d: Dims, key):
    return _f32((embed_weights(d, key), final_norm_weights(d, key)))


def _mamba(d: Dims, w: dict, x: jax.Array, low: bool) -> jax.Array:
    """The Mamba-2 mixer over (R, n, d_model) normed inputs, the state
    recurrence run token by token."""
    r, n, _ = x.shape
    di, ns, nh, hp = d.d_inner, d.d_state, d.mamba_heads, d.mamba_head_dim
    zxd = _mm(x, w["in_proj"], low)
    z, xbc, dt = (zxd[..., :di], zxd[..., di: di + d.conv_dim],
                  zxd[..., di + d.conv_dim:])
    pad = jnp.pad(xbc, ((0, 0), (CONV_WIDTH - 1, 0), (0, 0)))
    conv = sum(pad[:, j: j + n] * w["conv_w"][j] for j in range(CONV_WIDTH))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs = xbc[..., :di].reshape(r, n, nh, hp)
    b_, c_ = xbc[..., di: di + ns], xbc[..., di + ns:]
    dt = jax.nn.softplus(dt + w["dt_bias"])  # (R, n, H)
    a = -jnp.exp(w["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.einsum("rhpn,rn->rhp", s, c_t, precision=HI)
        return s, y + w["D"][:, None] * x_t

    s0 = jnp.zeros((r, nh, hp, ns), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(
        v.swapaxes(0, 1) for v in (xs, b_, c_, dt)))
    y = y.swapaxes(0, 1).reshape(r, n, di)
    g = _rms(y * jax.nn.silu(z), w["gate_norm"], d.norm_eps)
    return _mm(g, w["out_proj"], low)


def _attention(d: Dims, w: dict, x: jax.Array, low: bool) -> jax.Array:
    """Causal NoPE grouped-query attention over (R, n, d_model)."""
    r, n, _ = x.shape
    hd, g, kvh = d.head_dim, d.n_heads // d.n_kv_heads, d.n_kv_heads
    q = _mm(x, w["wq"], low).reshape(r, n, kvh, g, hd)
    k = _mm(x, w["wk"], low).reshape(r, n, kvh, hd)
    v = _mm(x, w["wv"], low).reshape(r, n, kvh, hd)
    if low:
        q, k, v = _f8(q, -1), _f8(k, -1), _f8(v, -1)
    q = q.reshape(r, n // QBLOCK, QBLOCK, kvh, g, hd).swapaxes(0, 1)
    pos = jnp.arange(n)

    def block(args):
        qb, i = args
        s = jnp.einsum("rqkgd,rskd->rkgqs", qb, k,
                       precision=HI) * d.attention_mult
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if low:
            p = _f8(p, -1)
        return jnp.einsum("rkgqs,rskd->rqkgd", p, v, precision=HI)

    o = jax.lax.map(block, (q, jnp.arange(n // QBLOCK)))
    o = o.swapaxes(0, 1).reshape(r, n, d.q_width)
    return _mm(o, w["wo"], low)


def moe_share(d: Dims, w: dict, x: jax.Array, low: bool = False
              ) -> jax.Array:
    """The held experts' part of the expert block for (N, d_model) normed
    tokens: routed over every expert, computed over the held ones."""
    logits = _mm(x, w["router"], low)
    top, idx = jax.lax.top_k(logits, d.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(x)
    for e in range(w["w1"].shape[0]):
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        h = jax.nn.silu(_mm(x, w["w1"][e], low)) * _mm(x, w["w2"][e], low)
        out = out + weight[:, None] * _mm(h, w["w3"][e], low)
    return out


def shared_mlp(w: dict, x: jax.Array, low: bool = False) -> jax.Array:
    h = jax.nn.silu(_mm(x, w["shared_w1"], low)) * _mm(x, w["shared_w2"], low)
    return _mm(h, w["shared_w3"], low)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer(d: Dims, w: dict, h: jax.Array, kind: str, low: bool):
    """One layer over padded (R, n, d_model) sequences."""
    x = _rms(h, w["mixer_norm"], d.norm_eps)
    mix = _attention if kind == "attention" else _mamba
    h = h + d.residual_mult * mix(d, w, x, low)
    r, n, dm = h.shape
    x = _rms(h, w["ffn_norm"], d.norm_eps).reshape(r * n, dm)
    out = moe_share(d, w, x, low) + shared_mlp(w, x, low)
    return h + d.residual_mult * out.reshape(r, n, dm)


@functools.partial(jax.jit, static_argnums=(0, 6))
def _head(d: Dims, embed, fnorm, h, at, toks, low: bool):
    """Logits of the positions ``at`` of one sequence's hidden states
    ``h``, and the gap of each of ``toks`` below its position's best."""
    x = _rms(h[at], fnorm, d.norm_eps)
    e = _f8(embed, -1) if low else embed
    logits = jnp.matmul(_f8(x, -1) if low else x, e.T,
                        precision=HI) / d.logits_scaling
    gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, toks[:, None], axis=-1)[:, 0]
    return logits, gap


def served_gaps(d: Dims, seed: int, requests, *, rows: int,
                control: bool = False) -> dict[str, np.ndarray]:
    """``reference.served_gaps``'s check for this family: per served token
    of ``requests`` ((prompt, served) int arrays), how far the reference's
    logit of it lies below the reference's best at its position; with
    ``control``, also the gaps of the tokens the float8 pass puts first.
    All sequences run as one batch, padded to one length."""
    key = seed_key(seed)
    embed, fnorm = _globals_f32(d, key)
    n = max(-(-(len(p) - 1 + rows) // PAD) * PAD for p, _ in requests)
    ids = np.zeros((len(requests), n), np.int32)
    for i, (prompt, served) in enumerate(requests):
        if len(served) > rows:
            raise ValueError(f"{len(served)} served tokens, rows={rows}")
        toks = np.concatenate([prompt, served[:-1]])
        ids[i, : len(toks)] = toks
    h = d.embed_mult * embed[jnp.asarray(ids)]
    low_h = (d.embed_mult * _f8(embed, -1)[jnp.asarray(ids)] if control
             else None)
    for layer, kind in enumerate(d.kinds):
        w = _layer_f32(d, key, jnp.int32(layer), kind)
        h = _layer(d, w, h, kind, False)
        if control:
            low_h = _layer(d, w, low_h, kind, True)
    gaps, ctrl = [], []
    for i, (prompt, served) in enumerate(requests):
        served = np.asarray(served, np.int64)
        m = len(served)
        inside = served < d.vocab
        toks = np.zeros(rows, np.int32)
        toks[:m] = np.where(inside, served, 0)
        at = jnp.asarray(len(prompt) - 1 + np.arange(rows, dtype=np.int32))
        logits, gap = _head(d, embed, fnorm, h[i], at, toks, False)
        gaps.append(np.where(inside, np.asarray(gap)[:m], np.inf))
        if control:
            low, _ = _head(d, embed, fnorm, low_h[i], at, toks, True)
            ctrl.append(np.asarray(_gap_of_argmax(logits, low))[:m])
    out = {"program": np.concatenate(gaps)}
    if control:
        out["control"] = np.concatenate(ctrl)
    return out


# -- what the metric readers count ----------------------------------------------

least_seconds = flops.least_seconds
paged_attn_call = flops.paged_attn_call


def attention_layers(d: Dims) -> int:
    """Paged-kernel calls of one decode step."""
    return d.n_attention


def expert_units(d: Dims) -> int:
    """Held experts times expert layers: the expert computations of one
    decode step (every layer has experts)."""
    return d.experts_held * d.n_layers


def _layer_macs(d: Dims, kind: str) -> float:
    """Multiply-adds of one token's matrix products in one layer: its
    mixer's projections, the router over every expert, its share of the
    picked experts (``top_k * held / experts`` of them, on average), and
    the shared MLP."""
    dm = d.d_model
    if kind == "attention":
        mixer = dm * (d.q_width + 2 * d.kv_width) + d.q_width * dm
    else:
        mixer = dm * d.in_proj_width + d.d_inner * dm
    experts = 3 * dm * d.expert_width * d.top_k * d.experts_held / d.n_experts
    return mixer + dm * d.n_experts + experts + 3 * dm * d.shared_width


def ssm_flops(d: Dims) -> int:
    """One token's recurrence in one Mamba-2 layer: the state's decay and
    update (3 per element), its read by ``C`` (2), and the width-4 conv."""
    return (5 * d.mamba_heads * d.mamba_head_dim * d.d_state
            + 2 * CONV_WIDTH * d.conv_dim)


def _token_body(d: Dims) -> float:
    return (sum(2 * _layer_macs(d, k) for k in d.kinds)
            + d.n_mamba * ssm_flops(d))


def _head_flops(d: Dims) -> int:
    return 2 * d.d_model * d.vocab


def _attention_flops(d: Dims, ctx_sum: int) -> int:
    return 4 * d.n_attention * d.n_heads * d.head_dim * ctx_sum


def token_flops(d: Dims, ctx: int) -> float:
    """One decoded token that sees ``ctx`` keys, output head included."""
    return _token_body(d) + _head_flops(d) + _attention_flops(d, ctx)


def chunk_flops(d: Dims, pos0: int, length: int) -> float:
    """A prefill chunk of ``length`` tokens from ``pos0``, with the output
    head for its last token only."""
    ctx_sum = length * pos0 + length * (length + 1) // 2
    return (_token_body(d) * length + _head_flops(d)
            + _attention_flops(d, ctx_sum))


def decode_step_bytes(d: Dims, ctxs) -> int:
    """HBM bytes one decode step over active rows of the given contexts
    must move: every weight once (bfloat16, the padded vocabulary's zero
    rows left out), each row's live keys and values, each row's recurrent
    state read and written, and each row's float32 logits."""
    kv = sum(ctxs) * d.n_attention * 2 * d.kv_width * BF16
    rows = len(ctxs)
    return (param_bytes(d, d.vocab) + kv + rows * 2 * state_bytes(d)
            + rows * d.vocab * 4)
