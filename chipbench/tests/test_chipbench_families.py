"""The ``dense_gqa`` family gives what the modules under it give when
called directly (weights bit for bit, the reference's gaps, the operation
counts worked by hand in ``test_chipbench_flops.py``), refuses by key what
the server and its reference compute differently, and the harness passes
a configuration's other server keys through to ``ServeConfig``."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import flops, harness, reference  # noqa: E402
from chipbench import weights as W  # noqa: E402
from chipbench.dims import load_config  # noqa: E402
from minicell import CONFIG, ROOT, make_root  # noqa: E402

SEED = 2 ** 36 + 5


@pytest.fixture(scope="module")
def dense():
    return harness.load_family(ROOT, "dense_gqa")


@pytest.fixture(scope="module")
def mini(dense):
    return dense.dims(dict(CONFIG, family="dense_gqa"))


def test_params_are_the_weights_modules_bit_for_bit(dense, mini):
    padded = dense.program_config(mini).padded_vocab
    got = dense.program_params(mini, padded, SEED)
    want = W.make_program_params(mini, padded, SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert dense.param_bytes(mini, padded) == W.param_bytes(mini, padded)


def test_served_gaps_are_the_references(dense, mini):
    rng = np.random.default_rng(3)
    requests = [(rng.integers(0, mini.vocab, n), rng.integers(0, mini.vocab, m))
                for n, m in ((40, 9), (64, 16))]
    got = dense.served_gaps(mini, SEED, requests, rows=16, control=True)
    want = reference.served_gaps(mini, SEED, requests, rows=16, control=True)
    assert set(got) == set(want) == {"program", "control"}
    for k in want:
        assert np.array_equal(got[k], want[k])


def test_counts_at_qwen3_widths(dense):
    conf = load_config(ROOT / "chipbench" / "configs" / "qwen3-4b.json")
    d = dense.dims(conf)
    # The hand-worked counts of test_chipbench_flops.py.
    assert dense.token_flops(d, 1000) == 8_044_544_000 + 589_824_000
    assert dense.chunk_flops(d, 256, 256) == (
        1_860_257_710_080 + 777_912_320 + 58_057_555_968)
    assert dense.paged_attn_call(d, [1000, 2000]) == (
        4 * 32 * 128 * 3000, 3000 * 4096 + 2 * 2 * 4096 * 2)
    assert dense.attention_layers(d) == d.n_layers == 36
    assert dense.least_seconds is flops.least_seconds


# phi4-mini-3.8b's published sizes (microsoft/Phi-4-mini-instruct).
PHI4 = dict(CONFIG, name="phi4-mini-3.8b", arch="phi4-mini-3.8b",
            num_hidden_layers=32, hidden_size=3072, intermediate_size=8192,
            num_attention_heads=24, num_key_value_heads=8, head_dim=128,
            vocab_size=200064, rope_theta=10000.0, rms_norm_eps=1e-05,
            partial_rotary_factor=0.75, qk_norm=False)


@pytest.mark.parametrize("conf,named", [
    (PHI4, ["partial_rotary_factor", "rms_norm_eps"]),
    (dict(CONFIG, rms_norm_eps=1e-5), ["rms_norm_eps"]),
    (dict(CONFIG, tie_word_embeddings=False), ["tie_word_embeddings"]),
    (dict(CONFIG, arch="mixtral-8x7b"),
     ["arch (layer_unit)", "arch (sliding_window)"]),
])
def test_program_config_refuses_by_key(dense, conf, named):
    with pytest.raises(ValueError) as e:
        dense.program_config(dense.dims(conf))
    msg = str(e.value)
    assert msg.endswith("differ in " + ", ".join(named)), msg


def _engine(tmp_path, **server):
    root = make_root(tmp_path)
    cell = harness.load_cell(root, "mini.burst")
    cell.conf["server"] = dict(cell.server, **server)
    cfg = cell.family.program_config(cell.dims)
    params = cell.family.program_params(cell.dims, cfg.padded_vocab, SEED)
    return harness.build_engine(cell, cfg, params, tracer=None)


def test_other_server_keys_reach_serve_config(tmp_path):
    eng = _engine(tmp_path, decode_interleave=2, prefix_min_pages=3)
    assert eng.scfg.decode_interleave == 2
    assert eng.scfg.prefix_min_pages == 3
    assert eng.scfg.paged_kernel is True and eng.scfg.max_batch == 4


def test_server_keys_the_harness_sets_are_refused(tmp_path):
    with pytest.raises(ValueError, match=r"\['max_new_tokens', 'paged'\]"):
        _engine(tmp_path, paged=False, max_new_tokens=3)
