"""granite-4.0-h-small [hybrid]: 40L d_model=4096, Mamba-2 (128 heads of
64, d_state 128, one group, conv 4 with bias) and NoPE GQA attention (32/8
heads of 128) in a 10-layer unit M M M M M A M M M M, every layer a MoE of
72 experts (width 768, top-10, gates the softmax over the 10 picked
logits) plus an ungated shared MLP of width 1536; embedding x12, each
sublayer's output x0.22 into the residual, attention scale 1/128, logits
/16, RMSNorm eps 1e-5, tied vocabulary of 100,352
[hf:ibm-granite/granite-4.0-h-small config.json].
"""

from repro.configs.base import LayerSpec, ModelConfig, smoke_reduce

ARCH_ID = "granite-4.0-h-small"

#: Position of the attention layer in each 10-layer unit.
ATTENTION_AT = 5


def _unit() -> tuple[LayerSpec, ...]:
    return tuple(
        LayerSpec(mixer="attn" if i == ATTENTION_AT else "mamba", ffn="moe")
        for i in range(10))


CONFIG = ModelConfig(
    name=ARCH_ID,
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    layer_unit=_unit(),
    use_rope=False,  # position_embedding_type "nope"
    query_scale=0.0078125,  # attention_multiplier
    n_experts=72,
    top_k=10,
    expert_d_ff=768,
    n_shared_experts=1,
    shared_d_ff=1536,
    shared_gate=False,
    ssm_state=128,
    mamba_headdim=64,
    mamba_expand=2,
    ssd_chunk=256,
    tie_embeddings=True,
    embed_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
    remat="full",
)

#: One unit, 8 routed experts of which a share holds 4 (experts_held is
#: set by whoever builds a share), the published multipliers.
SMOKE = smoke_reduce(CONFIG, n_layers=10, n_experts=8, top_k=3,
                     n_shared_experts=1, shared_d_ff=64,
                     query_scale=0.0078125)

#: 36 of 40 mixers are Mamba-2 (O(1) state); the 4 attention layers'
#: decode cost is linear in KV length -> long_500k runs.
SUPPORTS_LONG_CONTEXT = True

