"""whisper-medium [audio]: 24L d_model=1024 16H (kv=16) d_ff=4096 vocab=51865
— enc-dec, conv frontend (STUB: input_specs supplies precomputed frame
embeddings (B, 1500, d_model)) [arXiv:2212.04356; unverified].

Notes: the real model caps decoder positions at 448; the assigned
prefill_32k/decode_32k shapes are synthetic stress configs exercised on the
backbone only.
"""

from repro.configs.base import LayerSpec, ModelConfig, smoke_reduce

ARCH_ID = "whisper-medium"

CONFIG = ModelConfig(
    name=ARCH_ID,
    n_layers=24,  # decoder layers; encoder has its own 24 below
    d_model=1024,
    n_heads=16,
    n_kv_heads=16,
    head_dim=64,
    d_ff=4096,
    vocab_size=51865,
    layer_unit=(LayerSpec(mixer="attn", ffn="dense", cross_attn=True),),
    ffn_kind="gelu_mlp",
    use_rope=False,
    sinusoidal_pos=True,
    n_encoder_layers=24,
    encoder_seq=1500,
    tie_embeddings=True,
)

SMOKE = smoke_reduce(CONFIG)

SUPPORTS_LONG_CONTEXT = False
