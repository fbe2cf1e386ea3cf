"""Seeded random weights, made on the device from ``--seed``.

Every weight is drawn by name and layer: leaf ``name`` of layer ``l`` is
``normal(fold_in(fold_in(key(seed), id(name)), l)) * std`` rounded to
bfloat16, the type it is served in. The server's whole parameter tree is
made by one jitted call (``program_params``); the reference draws one
layer at a time with the same function (``layer_weights``) and widens the
bfloat16 values to float32, so both see the same numbers without the
reference taking anything the server holds.

Norm weights are drawn as the deviation ``s`` from one: the server's
RMSNorm multiplies by ``1 + s``, and so does the reference.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.dims import Dims

#: Spread of the norm weights around one.
NORM_STD = 0.1
#: Spread of the embedding rows (tied: also the output head). Small beside
#: the layers' outputs, so the logits are not dominated by the input token.
EMBED_STD = 0.02

LEAF_IDS = {"attn_norm": 1, "wq": 2, "wk": 3, "wv": 4, "wo": 5, "q_norm": 6,
            "k_norm": 7, "mlp_norm": 8, "w_gate": 9, "w_up": 10,
            "w_down": 11, "embed": 12, "final_norm": 13}

_MASK31 = (1 << 31) - 1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a whole number of any size (31 bits at a time)."""
    key = jax.random.PRNGKey(seed & _MASK31)
    rest = seed >> 31
    while rest:
        key = jax.random.fold_in(key, rest & _MASK31)
        rest >>= 31
    return key


def layer_shapes(d: Dims) -> dict[str, tuple[tuple[int, ...], float]]:
    """Leaf name -> (shape, standard deviation) of one decoder layer."""
    out = {
        "attn_norm": ((d.d_model,), NORM_STD),
        "wq": ((d.d_model, d.q_width), d.d_model ** -0.5),
        "wk": ((d.d_model, d.kv_width), d.d_model ** -0.5),
        "wv": ((d.d_model, d.kv_width), d.d_model ** -0.5),
        "wo": ((d.q_width, d.d_model), d.q_width ** -0.5),
        "mlp_norm": ((d.d_model,), NORM_STD),
        "w_gate": ((d.d_model, d.d_ff), d.d_model ** -0.5),
        "w_up": ((d.d_model, d.d_ff), d.d_model ** -0.5),
        "w_down": ((d.d_ff, d.d_model), d.d_ff ** -0.5),
    }
    if d.qk_norm:
        out["q_norm"] = ((d.head_dim,), NORM_STD)
        out["k_norm"] = ((d.head_dim,), NORM_STD)
    return out


def _draw(key, name: str, index, shape, std: float) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), index)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def layer_weights(d: Dims, key, layer) -> dict[str, jax.Array]:
    """One layer's weights in bfloat16; ``layer`` may be traced."""
    return {name: _draw(key, name, layer, shape, std)
            for name, (shape, std) in layer_shapes(d).items()}


def embed_weights(d: Dims, key) -> jax.Array:
    """(vocab, d_model) bfloat16 embedding, also the tied output head."""
    return _draw(key, "embed", 0, (d.vocab, d.d_model), EMBED_STD)


def final_norm_weights(d: Dims, key) -> jax.Array:
    return _draw(key, "final_norm", 0, (d.d_model,), NORM_STD)


def program_params(d: Dims, padded_vocab: int, key) -> dict:
    """The server's parameter tree (``repro.models.transformer`` layout:
    layers stacked under ``blocks/layer0``). Rows of the padded vocabulary
    past ``vocab`` are zero, so their logits are 0 and never the largest."""
    stacked = jax.lax.map(lambda l: layer_weights(d, key, l),
                          jnp.arange(d.n_layers))
    embed = jnp.zeros((padded_vocab, d.d_model), jnp.bfloat16)
    embed = embed.at[: d.vocab].set(embed_weights(d, key))
    mixer = {"wq": stacked["wq"], "wk": stacked["wk"], "wv": stacked["wv"],
             "wo": stacked["wo"]}
    if d.qk_norm:
        mixer["q_norm"] = {"scale": stacked["q_norm"]}
        mixer["k_norm"] = {"scale": stacked["k_norm"]}
    layer = {
        "mixer_norm": {"scale": stacked["attn_norm"]},
        "mixer": mixer,
        "ffn_norm": {"scale": stacked["mlp_norm"]},
        "ffn": {"wi": stacked["w_up"], "wg": stacked["w_gate"],
                "wo": stacked["w_down"]},
    }
    return {"embed": embed,
            "final_norm": {"scale": final_norm_weights(d, key)},
            "blocks": {"layer0": layer}}


def make_program_params(d: Dims, padded_vocab: int, seed: int) -> dict:
    """All weights on the device, from the seed, in one jitted call."""
    fn = jax.jit(program_params, static_argnums=(0, 1))
    return jax.block_until_ready(fn(d, padded_vocab, seed_key(seed)))


def param_bytes(d: Dims, padded_vocab: int) -> int:
    per_layer = sum(math.prod(s) for s, _ in layer_shapes(d).values())
    return 2 * (d.n_layers * per_layer + padded_vocab * d.d_model + d.d_model)
