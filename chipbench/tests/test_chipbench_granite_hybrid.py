"""The ``granite_hybrid`` family: its counts worked by hand at the cut's
sizes (``configs/granite-4.0-h-small.json``), its refusals by key, its
weights' size against the server's tree, and a tiny hybrid cell rehearsed
through the harness on the CPU with no file under ``chipbench/`` changed."""

import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness  # noqa: E402
from minicell import CPU_PEAKS, ROOT  # noqa: E402
from test_chipbench_rehearsal import _files  # noqa: E402

CONF = json.loads((ROOT / "chipbench" / "configs"
                   / "granite-4.0-h-small.json").read_text())
CELL = "granite-4.0-h-small.longbatch"


@pytest.fixture(scope="module")
def fam():
    return harness.load_family(ROOT, "granite_hybrid")


@pytest.fixture(scope="module")
def cut(fam):
    return fam.dims(CONF)


def test_the_cut_is_one_period_and_nine_experts(cut):
    assert cut.kinds == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (cut.n_experts, cut.experts_held, cut.top_k) == (72, 9, 10)
    assert (cut.d_inner, cut.conv_dim, cut.in_proj_width) == (
        8192, 8448, 16768)
    assert CONF["reduced"] == ["num_hidden_layers", "num_local_experts"]
    assert CONF["published"] == {"num_hidden_layers": 40,
                                 "num_local_experts": 72}


# The cut's sizes: d 4096; Mamba-2 128 heads of 64, state 128, conv 8448
# (8192 + 2 x 128), in_proj 16768 (2 x 8192 + 2 x 128 + 128); attention
# q 4096, kv 1024 x 2; 72 experts of 768, top-10, 9 held; shared 1536.
#   Mamba-2 layer, one token's multiply-adds:
#     mixer   4096 x 16768 + 8192 x 4096           = 102,236,160
#     router  4096 x 72                            =     294,912
#     experts 3 x 4096 x 768 x 10 x 9 / 72          =  11,796,480
#     shared  3 x 4096 x 1536                      =  18,874,368
#     = 133,201,920
#   attention layer: 4096 x 6144 + 4096 x 4096 = 41,943,040 for the mixer,
#     the rest as above: 72,908,800
#   9 x 133,201,920 + 72,908,800 = 1,271,726,080; x 2 = 2,543,452,160
#   recurrence, per Mamba-2 layer: 5 x 128 x 64 x 128 + 2 x 4 x 8448
#     = 5,310,464; x 9 = 47,794,176
#   head 2 x 4096 x 100,352 = 822,083,584; attention 4 x 32 x 128 = 16,384
#     a key (one layer)
BODY = 2_543_452_160 + 47_794_176
HEAD = 822_083_584
# Parameters held: Mamba-2 layer 4096 + 102,286,976 (mixer: the above
# plus conv 4 x 8448, its bias 8448, A_log/D/dt_bias 3 x 128, gate norm
# 8192) + 4096 + 104,103,936 (router 294,912, 9 experts 84,934,656,
# shared 18,874,368); attention layer 4096 + 41,943,040 + 4096 +
# 104,103,936; embedding 411,041,792; final norm 4096.
PARAMS = (9 * (8192 + 102_286_976 + 104_103_936)
          + 8192 + 41_943_040 + 104_103_936 + 411_041_792 + 4096)


def test_counts_worked_by_hand(fam, cut):
    assert PARAMS == 2_414_692_992
    assert fam.param_bytes(cut, 100_352) == 2 * PARAMS == 4_829_385_984
    # 9 layers x (128 x 64 x 128 + 3 x 8448) in bfloat16
    assert fam.state_bytes(cut) == 2 * 9 * (1_048_576 + 25_344) == 19_330_560
    assert fam.token_flops(cut, 1000) == BODY + HEAD + 16_384 * 1000 \
        == 3_429_713_920
    # ctx_sum = 512 x 512 + 512 x 513 / 2 = 393,472
    assert fam.chunk_flops(cut, 512, 512) == (
        BODY * 512 + HEAD + 16_384 * 393_472) == 1_333_986_852_864
    # weights, K/V of 5000 keys x (2 x 1024 x 2 B), 2 rows' state read and
    # written, 2 rows of float32 logits
    assert fam.decode_step_bytes(cut, [2000, 3000]) == (
        4_829_385_984 + 20_480_000 + 2 * 2 * 19_330_560 + 2 * 100_352 * 4
    ) == 4_927_991_040
    assert fam.paged_attn_call(cut, [1000]) == (
        4 * 32 * 128 * 1000, 1000 * 4096 + 2 * 4096 * 2)
    assert fam.attention_layers(cut) == 1
    assert fam.expert_units(cut) == 90


def test_weights_are_the_servers_tree(fam, cut):
    """The server's tree (shapes only, nothing allocated) holds what
    ``param_bytes`` counts, in bfloat16, in the program's layout."""
    cfg = fam.program_config(cut)
    tree = jax.eval_shape(lambda k: fam._program_params(
        cut, cfg.padded_vocab, k, 10), jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(tree)
    assert {x.dtype for x in leaves} == {np.dtype("bfloat16")}
    assert sum(x.size * 2 for x in leaves) == fam.param_bytes(
        cut, cfg.padded_vocab)
    assert tree["blocks"]["layer5"]["mixer"]["wq"].shape == (1, 4096, 4096)
    assert tree["blocks"]["layer0"]["ffn"]["wg"].shape == (1, 9, 4096, 768)
    assert tree["blocks"]["layer0"]["ffn"]["router"].shape == (1, 4096, 72)
    assert cfg.n_repeats == 1 and cfg.experts_held == 9
    assert cfg.ssm_cache_dtype == np.dtype("bfloat16")


@pytest.mark.parametrize("change,named", [
    ({"layer_types": ["mamba"] * 4 + ["attention"] + ["mamba"] * 35},
     ["layer_types"]),
    ({"position_embedding_type": "rope"}, ["position_embedding_type"]),
    ({"mamba_n_groups": 8, "tie_word_embeddings": False},
     ["mamba_n_groups", "tie_word_embeddings"]),
    ({"mamba_n_heads": 64}, ["mamba_n_heads"]),
    ({"num_hidden_layers": 15}, ["layer_types", "num_hidden_layers"]),
])
def test_program_config_refuses_by_key(fam, change, named):
    with pytest.raises(ValueError) as e:
        fam.program_config(fam.dims(dict(CONF, **change)))
    assert str(e.value).endswith("differ in " + ", ".join(named)), e.value


#: The cell at a small size: granite's layout (one period, the published
#: multipliers), 8 routed experts top-3, 4 held, 4 slots.
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             mamba_n_heads=16, mamba_d_head=8, mamba_d_state=16,
             intermediate_size=32, shared_intermediate_size=64,
             vocab_size=512, num_experts_per_tok=3, num_local_experts=4,
             published={"num_hidden_layers": 40, "num_local_experts": 8},
             server={"max_batch": 4, "max_seq": 256, "block_size": 16,
                     "prefill_chunk": 32, "num_blocks": 48,
                     "chunk_jit_cap": 64, "page_jit_cap": 64},
             limits={"logit_gap": 0.05})
SMALL_MIX = {"loop": "closed",
             "prompt": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                        "round_up": 32, "min": 32, "max": 96},
             "output": {"dist": "uniform", "min": 8, "max": 24},
             "order": 0, "check": {"requests": 4, "tokens": 400}}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The real cell's entries and files, copied into a scratch checkout,
    with the configuration and mix shrunk; run for 1.5 s on the CPU."""
    before = _files(ROOT / "chipbench")
    tmp = tmp_path_factory.mktemp("checkout")
    for d in ("metrics", "families", "configs", "traffic"):
        shutil.copytree(ROOT / "chipbench" / d, tmp / "chipbench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    (tmp / "chipbench" / "configs" / "granite-4.0-h-small.json").write_text(
        json.dumps(dict(CONF, **SMALL)))
    (tmp / "chipbench" / "traffic" / "longbatch.json").write_text(
        json.dumps(SMALL_MIX))
    cell = harness.load_cell(tmp, CELL)
    kept: dict = {}
    out = harness.run_cell(
        cell, 2 ** 37 + 9, 1.5, trace=False,
        process_t0_ns=time.perf_counter_ns(), clock=harness.CompileClock(),
        log=lambda m: None,
        peak_table={jax.devices()[0].device_kind: CPU_PEAKS}, keep=kept)
    return tmp, out, kept["run"], before


def test_a_hybrid_cell_needs_only_new_files(rehearsal):
    tmp, out, r, before = rehearsal
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert r.cell.family.__name__ == "chipbench_family_granite_hybrid"
    assert r.compiles_in_window == (0, 0)
    assert _files(ROOT / "chipbench") == before


def test_expert_rows_read_the_device_counter(rehearsal):
    """Rows per held expert per tick: the live rows of a tick times each
    row's picks on one held expert, 3 of 8 in expectation (the cell's
    128 x 10 / 72 at full occupancy)."""
    tmp, _, r, _ = rehearsal
    got = harness.reader(tmp, "expert_rows_per_tick.longbatch")(r)
    ticks = [s for s in r.spans
             if s.name == "decode_tick" and s.t0_ns >= r.t0_ns]
    rows = sum(len(s.args["uids"]) for s in ticks) / len(ticks)
    routed = (r.counters1["moe.decode_pairs"]
              - r.counters0["moe.decode_pairs"])
    assert routed == rows * len(ticks) * 3 * 10
    assert got == pytest.approx(rows * 3 / 8, rel=0.25)
    # Readers of the device trace find nothing in an untraced run.
    for m in ("decode_hbm_roofline.longbatch", "mfu.longbatch",
              "paged_attn_roofline.longbatch", "decode_step_ms.longbatch"):
        assert harness.reader(tmp, m)(r) is None
    assert harness.reader(tmp, "host_ms_per_tick.longbatch")(r) > 0
