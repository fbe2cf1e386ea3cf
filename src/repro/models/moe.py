"""Mixture-of-Experts FFN: top-k routing with capacity + one-hot dispatch.

Expert dispatch is Independent-task streaming: tokens are
partitioned across experts, each expert's batch is an independent task, and
with experts sharded over the ``model`` mesh axis the dispatch/combine
einsums lower to all-to-alls whose transfer overlaps expert compute.

The sequence is processed in chunks (``moe_chunk``) so the (N, E, C)
dispatch tensor of one chunk is in flight while the previous chunk computes
-- the same pipeline the paper builds with hStreams tasks.

Includes shared experts (qwen2-moe: dense experts always active, sigmoid
gated; granite: ungated) and an auxiliary load-balancing loss
(Switch-style).

Serving uses ``moe_apply_held`` instead: dropless, and told which experts
the layer holds (expert parallelism's share of a layer). The router scores
every expert; the layer computes its own experts' part of the result for
the tokens routed to them, and the absent experts add nothing.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers, meshutil

Params = dict[str, Any]


def moe_init(
    key,
    *,
    d_model: int,
    d_ff: int,  # per-expert hidden size
    n_experts: int,
    n_shared_experts: int = 0,
    shared_d_ff: int | None = None,
    dtype=jnp.float32,
    expert_shards: int = 1,
    n_experts_pad: int | None = None,
    n_held: int = 0,
    shared_gate: bool = True,
) -> Params:
    """``expert_shards``: store each expert as ``s`` half-width virtual
    experts (E*s, D, F/s) so EP divides the mesh axis (mixtral 8x2=16).
    ``n_experts_pad``: allocate dead expert slots so the stored expert count
    divides the axis (qwen2-moe 60 -> 64); the router never selects them.
    ``n_held``: store only this layer's share of the experts (0 = all).
    ``shared_gate``: gate the shared MLP by a sigmoid of the token."""
    ks = jax.random.split(key, 6)
    std = 1.0 / math.sqrt(d_model)
    e_store = (n_held or n_experts_pad or n_experts) * expert_shards
    f_shard = d_ff // expert_shards
    assert d_ff % expert_shards == 0, (d_ff, expert_shards)
    p: Params = {
        "router": layers.dense_init(ks[0], (d_model, n_experts), jnp.float32, scale=std),
        # Stacked expert weights: leading expert axis shards over `model` (EP).
        "wi": layers.dense_init(ks[1], (e_store, d_model, f_shard), dtype, scale=std),
        "wg": layers.dense_init(ks[2], (e_store, d_model, f_shard), dtype, scale=std),
        "wo": layers.dense_init(ks[3], (e_store, f_shard, d_model), dtype, scale=1.0 / math.sqrt(d_ff)),
    }
    if n_shared_experts > 0:
        sd = shared_d_ff if shared_d_ff is not None else n_shared_experts * d_ff
        p["shared"] = layers.ffn_init(ks[4], d_model, sd, dtype, kind="swiglu")
        if shared_gate:
            p["shared_gate"] = layers.dense_init(
                ks[5], (d_model, 1), dtype, scale=std)
    return p


def shared_apply(p: Params, tokens: jax.Array) -> jax.Array:
    """The shared MLP every token passes, gated when the layer has a
    ``shared_gate``; zero without shared experts."""
    if "shared" not in p:
        return jnp.zeros_like(tokens)
    y = layers.ffn_apply(p["shared"], tokens, kind="swiglu")
    if "shared_gate" in p:
        y = jax.nn.sigmoid(tokens @ p["shared_gate"]) * y
    return y


def route_topk(
    router_logits: jax.Array,  # (N, E) fp32
    *,
    top_k: int,
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k token-choice routing with per-expert capacity.

    Returns (dispatch (N,E,C) one-hot, combine (N,E,C) gate-weighted,
    aux_loss scalar).  Tokens overflowing an expert's capacity are dropped
    (Switch-style), matching production MoE behaviour at scale.
    """
    n, e = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # (N, k)
    # Renormalize the selected gates (mixtral-style).
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # expert_mask: (N, k, E) one-hot of selections.
    expert_mask = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
    # Position of each (token, slot) within its expert's queue, priority by
    # token order then slot order: cumsum over the flattened (N*k) axis.
    flat_mask = expert_mask.reshape(n * top_k, e)
    pos_in_expert = jnp.cumsum(flat_mask, axis=0) - flat_mask  # (N*k, E)
    pos_in_expert = (pos_in_expert * flat_mask).sum(-1).reshape(n, top_k)
    pos_in_expert = pos_in_expert.astype(jnp.int32)
    within_cap = pos_in_expert < capacity

    gate_vals = gate_vals * within_cap.astype(gate_vals.dtype)
    cap_onehot = jax.nn.one_hot(
        jnp.where(within_cap, pos_in_expert, capacity), capacity + 1, dtype=jnp.float32
    )[..., :capacity]  # (N, k, C); overflow rows are all-zero

    # (N, E, C) = sum over slots of expert-onehot x capacity-onehot.
    dispatch = jnp.einsum("nke,nkc->nec", expert_mask, cap_onehot)
    combine = jnp.einsum("nke,nkc,nk->nec", expert_mask, cap_onehot, gate_vals)

    # Switch aux loss: E * sum_e(frac_tokens_e * mean_prob_e).
    frac_tokens = expert_mask.sum((0, 1)) / n
    mean_prob = probs.mean(0)
    aux = e * jnp.sum(frac_tokens * mean_prob)
    return dispatch, combine, aux


def route_topk_indices(
    router_logits: jax.Array,  # (N, E) fp32
    *,
    top_k: int,
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Index-based top-k routing (no one-hot dispatch tensor).

    Returns (expert_idx (N,k), pos_in_expert (N,k), gates (N,k) with
    overflow zeroed, aux loss).  The (N,E,C) one-hot of ``route_topk`` costs
    O(N*E*C*D) FLOPs in the dispatch einsum; here dispatch becomes a gather
    (bytes, no FLOPs) — see EXPERIMENTS.md §Perf iteration "moe-gather".
    """
    n, e = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    expert_mask = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # (N,k,E)
    flat_mask = expert_mask.reshape(n * top_k, e)
    pos = jnp.cumsum(flat_mask, axis=0) - flat_mask
    pos = (pos * flat_mask).sum(-1).reshape(n, top_k).astype(jnp.int32)
    within = pos < capacity
    gate_vals = gate_vals * within.astype(gate_vals.dtype)

    frac_tokens = expert_mask.sum((0, 1)) / n
    aux = e * jnp.sum(frac_tokens * probs.mean(0))
    return gate_idx.astype(jnp.int32), pos, gate_vals, aux


def moe_apply(
    p: Params,
    x: jax.Array,  # (B, S, D)
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    moe_chunk: int = 1024,
    impl: str = "gather",  # "gather" (optimized) | "einsum" (baseline)
    expert_shards: int = 1,  # virtual expert TP folded into EP (see below)
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux loss).  Streams over sequence chunks.

    ``expert_shards > 1`` splits each expert's FFN into ``s`` half-width
    virtual experts along d_ff (wi/wg column split, wo row split — partial
    outputs sum), so an arch with E < mesh-model-axis still gets true expert
    parallelism (mixtral: 8 experts x 2 shards = 16 divides the axis).  The
    weights must be stored pre-split: (E*s, D, F/s).
    """
    b, s, d = x.shape
    e = p["router"].shape[1]  # routable experts
    e_pad = p["wi"].shape[0] // expert_shards  # stored (padded) experts
    chunk = min(moe_chunk, s)
    assert s % chunk == 0, f"seq {s} % moe chunk {chunk} != 0"
    n_chunks = s // chunk
    n_tok = b * chunk
    capacity = max(1, int(math.ceil(n_tok * top_k * capacity_factor / e)))

    xc = x.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)  # (n_chunks, B, c, D)

    def one_chunk_einsum(tokens, logits):
        dispatch, combine, aux = route_topk(logits, top_k=top_k, capacity=capacity)
        xe = jnp.einsum("nec,nd->ecd", dispatch.astype(tokens.dtype), tokens)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"])) * jnp.einsum(
            "ecd,edf->ecf", xe, p["wi"])
        ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])
        y = jnp.einsum("nec,ecd->nd", combine.astype(ye.dtype), ye)
        return y, aux

    def one_chunk_gather(tokens, logits):
        eidx, pos, gates, aux = route_topk_indices(
            logits, top_k=top_k, capacity=capacity)
        # slot table: (E_pad, C) -> token id (n_tok = sentinel -> zero row);
        # dead padding experts keep the sentinel everywhere.
        slot_tok = jnp.full((e_pad, capacity), n_tok, jnp.int32)
        ok = pos < capacity
        oob = jnp.int32(2**30)  # mode="drop" does NOT drop -1 (it wraps)
        slot_tok = slot_tok.at[
            jnp.where(ok, eidx, oob), jnp.where(ok, pos, oob)
        ].set(jnp.broadcast_to(jnp.arange(n_tok, dtype=jnp.int32)[:, None],
                               (n_tok, top_k)), mode="drop")
        tokens_pad = jnp.concatenate(
            [tokens, jnp.zeros((1, d), tokens.dtype)], axis=0)
        xe = tokens_pad[slot_tok]  # (E_pad, C, D): gather, not einsum
        # NOTE: we deliberately do NOT pin xe's sharding here.  Two attempts
        # (P("model",None,None) and P("model","data",None)) both INCREASED
        # collective traffic 2.1-2.5x: XLA's choice of sinking the dispatch
        # all-reduce past the expert matmuls beats forcing materialization
        # (EXPERIMENTS.md §Perf, refuted iterations 5a/5b).
        if expert_shards > 1:
            # replicate each expert's batch for its d_ff shards
            xe = jnp.repeat(xe, expert_shards, axis=0)  # (E_pad*s, C, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"])) * jnp.einsum(
            "ecd,edf->ecf", xe, p["wi"])
        ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])  # (E_pad*s, C, D) partials
        if expert_shards > 1:
            ye = ye.reshape(e_pad, expert_shards, capacity, d).sum(axis=1)
        # combine: gather each token's k expert outputs (bytes, no FLOPs)
        ye_pad = jnp.concatenate(
            [ye.reshape(e_pad * capacity, d),
             jnp.zeros((1, d), ye.dtype)], axis=0)
        flat_idx = jnp.where(ok, eidx * capacity + pos, e_pad * capacity)
        picked = ye_pad[flat_idx]  # (N, k, D)
        y = (picked * gates[..., None].astype(picked.dtype)).sum(axis=1)
        return y, aux

    def one_chunk(carry, xch):
        tokens = xch.reshape(n_tok, d)
        logits = tokens.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        if impl == "einsum":
            assert expert_shards == 1, "einsum impl predates expert shards"
            y, aux = one_chunk_einsum(tokens, logits)
        else:
            y, aux = one_chunk_gather(tokens, logits)
        if "shared" in p:
            y = y + shared_apply(p, tokens)
        return carry + aux, y.reshape(b, chunk, d)

    aux_total, yc = jax.lax.scan(one_chunk, jnp.float32(0.0), xc)
    y = yc.swapaxes(0, 1).reshape(b, s, d)
    return y, aux_total / n_chunks


def moe_ref_dense(p: Params, x: jax.Array, *, top_k: int) -> jax.Array:
    """Droppless oracle: every token runs through its top-k experts exactly
    (no capacity), used by tests to bound the dispatch error."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    logits = tokens.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    def per_expert(eidx):
        h = jax.nn.silu(tokens @ p["wg"][eidx]) * (tokens @ p["wi"][eidx])
        return h @ p["wo"][eidx]

    all_out = jax.vmap(per_expert)(jnp.arange(p["wi"].shape[0]))  # (E, N, D)
    sel = jnp.take_along_axis(
        all_out.transpose(1, 0, 2), gate_idx[..., None], axis=1
    )  # (N, k, D)
    y = (sel * gate_vals[..., None].astype(sel.dtype)).sum(1)
    if "shared" in p:
        y = y + shared_apply(p, tokens)
    return y.reshape(b, s, d)


def moe_apply_held(
    p: Params,
    x: jax.Array,  # (B, S, D)
    *,
    top_k: int,
    offset: int = 0,  # the first expert this layer holds
    expert_shards: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """Dropless expert layer over the experts it holds.

    Routes each token over every expert (top-k of the router's logits,
    gates the softmax over the k selected logits) and computes the part of
    the result that the held experts ``[offset, offset + held)`` give, plus
    the shared MLP. Every held expert runs on every token (capacity = the
    tokens), weighted by the token's gate for it, 0 where the token did not
    pick it: nothing is dropped, and a token's output does not depend on
    the other tokens of the batch. Returns (output (B, S, D), the (B, S)
    count of each token's picks that this layer holds).
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    logits = tokens.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    top, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top, axis=-1)  # (N, k)
    held = p["wi"].shape[0] // expert_shards
    local = idx - offset
    here = (local >= 0) & (local < held)  # (N, k)
    pick = jax.nn.one_hot(jnp.where(here, local, held), held,
                          dtype=jnp.float32)  # out-of-share picks: all zero
    weight = jnp.einsum("nk,nke->ne", gates, pick)  # (N, held)
    if expert_shards > 1:  # each virtual half-expert takes its expert's gate
        weight = jnp.repeat(weight, expert_shards, axis=1)
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", tokens, p["wg"])) * jnp.einsum(
        "nd,edf->enf", tokens, p["wi"])
    ye = jnp.einsum("enf,efd->end", h, p["wo"])
    y = jnp.einsum("end,ne->nd", ye.astype(jnp.float32), weight)
    y = y.astype(x.dtype) + shared_apply(p, tokens)
    return y.reshape(b, s, d), here.sum(-1).astype(jnp.int32).reshape(b, s)
