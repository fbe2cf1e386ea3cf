"""Operation and byte counts at qwen3-4b and phi4-mini-3.8b widths, against
counts worked by hand from the published sizes."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from chipbench import flops  # noqa: E402
from chipbench.dims import dims_of, load_config  # noqa: E402

# phi4-mini-3.8b's published sizes (microsoft/Phi-4-mini-instruct
# config.json); no cell runs it, so its sizes live here.
PHI4 = {"name": "phi4-mini-3.8b", "arch": "phi4-mini-3.8b",
        "num_hidden_layers": 32, "hidden_size": 3072,
        "intermediate_size": 8192, "num_attention_heads": 24,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 200064,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": True}


def dims(name):
    if name == PHI4["name"]:
        return dims_of(PHI4)
    return dims_of(load_config(ROOT / "chipbench" / "configs"
                               / f"{name}.json"))


# qwen3-4b: d 2560, q 32 x 128 = 4096, kv 8 x 128 = 1024, ff 9728, 36 layers.
#   per layer: 2560 * (4096 + 2 * 1024) = 15,728,640 (q, k, v)
#            + 4096 * 2560             = 10,485,760 (o)
#            + 3 * 2560 * 9728         = 74,711,040 (gate, up, down)
#            = 100,925,440; x 36 = 3,633,315,840
#   head: 2560 * 151,936 = 388,956,160
# phi4-mini-3.8b: d 3072, q 24 x 128 = 3072, kv 1024, ff 8192, 32 layers.
#   per layer: 3072 * 5120 + 3072 * 3072 + 3 * 3072 * 8192
#            = 15,728,640 + 9,437,184 + 75,497,472 = 100,663,296;
#   x 32 = 3,221,225,472; head: 3072 * 200,064 = 614,596,608
HAND = {
    "qwen3-4b": dict(layers=3_633_315_840, head=388_956_160,
                     attn_per_key=4 * 36 * 32 * 128),
    "phi4-mini-3.8b": dict(layers=3_221_225_472, head=614_596_608,
                           attn_per_key=4 * 32 * 24 * 128),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_params_at_published_widths(name):
    d, h = dims(name), HAND[name]
    assert flops.layer_params(d) == h["layers"]
    assert flops.head_params(d) == h["head"]
    assert flops.attention_flops(d, 1) == h["attn_per_key"]


def test_qwen3_token_and_chunk():
    d = dims("qwen3-4b")
    # one decoded token seeing 1,000 keys:
    # 2 * (3,633,315,840 + 388,956,160) + 589,824 * 1,000
    assert flops.token_flops(d, 1000) == 8_044_544_000 + 589_824_000
    # a 256-token chunk from position 256 sees 256 * 256 + 256 * 257 / 2
    # = 98,432 keys in all; the head runs for its last token only:
    # 2 * 3,633,315,840 * 256 + 2 * 388,956,160 + 589,824 * 98,432
    assert flops.chunk_flops(d, 256, 256) == (
        1_860_257_710_080 + 777_912_320 + 58_057_555_968)


def test_phi4_chunk():
    d = dims("phi4-mini-3.8b")
    # 512 tokens from 0: keys 512 * 513 / 2 = 131,328;
    # 2 * 3,221,225,472 * 512 + 2 * 614,596,608 + 393,216 * 131,328
    assert flops.chunk_flops(d, 0, 512) == (
        3_298_534_883_328 + 1_229_193_216 + 51_640_270_848)


@pytest.mark.parametrize("name,q_bytes", [("qwen3-4b", 4096 * 2),
                                          ("phi4-mini-3.8b", 3072 * 2)])
def test_paged_attention_call(name, q_bytes):
    d = dims(name)
    f, b = flops.paged_attn_call(d, [1000, 2000])
    # 3,000 live keys: 4 * heads * 128 FLOPs each; K and V of 8 kv heads
    # x 128 in bfloat16 = 4,096 B a key; each of 2 rows reads its query
    # and writes its output.
    assert f == 4 * d.n_heads * 128 * 3000
    assert b == 3000 * 4096 + 2 * 2 * q_bytes


def test_least_seconds_takes_the_larger_bound():
    # 197e9 FLOPs at 197 TFLOP/s = 1 ms; 819 MB at 819 GB/s = 1 ms
    assert flops.least_seconds(197e9, 1.638e9, 197e12, 819e9) == \
        pytest.approx(2e-3)
    assert flops.least_seconds(394e9, 819e6, 197e12, 819e9) == \
        pytest.approx(2e-3)
