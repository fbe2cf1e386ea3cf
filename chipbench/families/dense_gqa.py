"""Dense grouped-query-attention decoders: pre-norm RMSNorm, rotary
positions, optional per-head q/k RMSNorm, SwiGLU and a tied output head
(qwen3-4b's layout).

Every configuration file names its architecture family (``"family"``);
the harness loads ``chipbench/families/<family>.py`` by path and reaches
the architecture only through that module. A family provides:

- ``dims(conf)``: a frozen, hashable sizes object with at least ``name``,
  ``arch``, ``n_layers`` and ``vocab``;
- ``program_config(d)``: the server's ``ModelConfig``; it refuses, naming
  the key, a value the program and the family's reference would compute
  differently;
- ``program_params(d, padded_vocab, seed)``: the server's weights, made
  on the device from the seed, and ``param_bytes(d, padded_vocab)``;
- ``served_gaps(d, seed, requests, *, rows, control)``: the plain float32
  reference's check of served tokens (``reference.served_gaps``);
- what the metric readers count: ``token_flops(d, ctx)``,
  ``chunk_flops(d, pos0, length)``, ``paged_attn_call(d, ctxs)``,
  ``least_seconds(flops, nbytes, peak_flops, peak_bw)`` and
  ``attention_layers(d)``, the paged-kernel calls of one decode step.

This family is a thin layer over ``dims.py``, ``weights.py``,
``reference.py`` and ``flops.py``.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from chipbench import flops, reference
from chipbench import weights as W
from chipbench.dims import Dims, dims_of

dims = dims_of
program_params = W.make_program_params
param_bytes = W.param_bytes
served_gaps = reference.served_gaps
token_flops = flops.token_flops
chunk_flops = flops.chunk_flops
paged_attn_call = flops.paged_attn_call
least_seconds = flops.least_seconds

#: The server's RMSNorm epsilon.
PROGRAM_NORM_EPS = 1e-6


def attention_layers(d: Dims) -> int:
    return d.n_layers


def program_config(d: Dims):
    """The server's ``ModelConfig`` for these sizes, in bfloat16. Refuses
    what the program computes otherwise than ``reference.py``: each key of
    the configuration file (or, for the program's own architecture
    settings, ``arch`` and the setting) is named."""
    from repro import configs
    from repro.models.transformer import LayerSpec

    base = configs.get_config(d.arch)
    cfg = dataclasses.replace(
        base, n_layers=d.n_layers, d_model=d.d_model, n_heads=d.n_heads,
        n_kv_heads=d.n_kv_heads, head_dim=d.head_dim, d_ff=d.d_ff,
        vocab_size=d.vocab, rope_theta=d.rope_theta, qk_norm=d.qk_norm,
        tie_embeddings=d.tied, param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16)
    same = {
        "arch (layer_unit)":
            cfg.layer_unit == (LayerSpec(mixer="attn", ffn="dense"),),
        "arch (ffn_kind)": cfg.ffn_kind == "swiglu",
        "arch (use_rope)": cfg.use_rope,
        "arch (sinusoidal_pos)": not cfg.sinusoidal_pos,
        "arch (sliding_window)": not cfg.sliding_window,
        "arch (attn_softcap)": not cfg.attn_softcap,
        "arch (final_softcap)": not cfg.final_softcap,
        "arch (sandwich_norm)": not cfg.sandwich_norm,
        "arch (embed_scale)": not cfg.embed_scale,
        "arch (query_scale)": cfg.query_scale is None,
        "arch (prefix_len)": not cfg.prefix_len,
        "arch (is_encoder_decoder)": not cfg.is_encoder_decoder,
        "tie_word_embeddings": cfg.tie_embeddings,
        "partial_rotary_factor": d.rotary_fraction == 1.0,
        "rms_norm_eps": d.norm_eps == PROGRAM_NORM_EPS,
    }
    differ = [key for key, ok in same.items() if not ok]
    if differ:
        raise ValueError(f"{d.name} ({d.arch}): the server and the dense_gqa "
                         f"reference differ in {', '.join(differ)}")
    return cfg
