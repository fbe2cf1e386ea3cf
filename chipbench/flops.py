"""Operations and bytes the algorithm needs, from shapes alone.

Counts are of the model as specified, not of what the server happens to
compute: the vocabulary is the real one (not padded), padding rows of a
batch count nothing, attention counts only the keys a query may see, the
output head counts once per chunk of a prompt (only the last position's
logits are needed, and the server computes no others), and the
paged-attention kernel's bytes are the live keys and values of each
active row (not whole pages).
"""

from __future__ import annotations

from chipbench.dims import Dims

BF16 = 2


def layer_params(d: Dims) -> int:
    """Weights of all layers' matrix products that one token passes."""
    per_layer = (d.d_model * (d.q_width + 2 * d.kv_width)
                 + d.q_width * d.d_model + 3 * d.d_model * d.d_ff)
    return d.n_layers * per_layer


def head_params(d: Dims) -> int:
    return d.d_model * d.vocab


def attention_flops(d: Dims, ctx: int) -> int:
    """Scores and weighted values of one query over ``ctx`` keys, all
    layers and heads."""
    return 4 * d.n_layers * d.n_heads * d.head_dim * ctx


def token_flops(d: Dims, ctx: int) -> int:
    """One decoded token at a position that sees ``ctx`` keys (itself
    included), output head too."""
    return 2 * (layer_params(d) + head_params(d)) + attention_flops(d, ctx)


def chunk_flops(d: Dims, pos0: int, length: int) -> int:
    """A prefill chunk of ``length`` tokens from position ``pos0``, with
    the output head for its last token."""
    ctx_sum = length * pos0 + length * (length + 1) // 2
    return (2 * layer_params(d) * length + 2 * head_params(d)
            + attention_flops(d, 1) * ctx_sum)


def paged_attn_call(d: Dims, ctxs) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's paged-attention call over active rows
    with the given live contexts: keys and values read once in bfloat16,
    queries read and outputs written once."""
    flops = sum(4 * d.n_heads * d.head_dim * c for c in ctxs)
    kv = sum(2 * c * d.kv_width * BF16 for c in ctxs)
    qo = len(ctxs) * 2 * d.q_width * BF16
    return flops, kv + qo


def least_seconds(flops: int, nbytes: int, peak_flops: float,
                  peak_bw: float) -> float:
    return max(flops / peak_flops, nbytes / peak_bw)
