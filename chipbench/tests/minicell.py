"""A tiny cell of the benchmark that exists only in the tests: its
configuration, traffic mix and one extra metric are written as files into a
scratch checkout beside a copy of the real metric readers, with entries in
that checkout's ``BENCHMARK.json``, and the harness runs it on the CPU."""

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from chipbench import harness  # noqa: E402

CONFIG = {
    "name": "mini", "arch": "qwen3-4b", "source": "test only",
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "qk_norm": True,
    "server": {"max_batch": 4, "max_seq": 256, "block_size": 16,
               "prefill_chunk": 32, "num_blocks": 48, "chunk_jit_cap": 64,
               "page_jit_cap": 64},
    "limits": {"logit_gap": 0.01},
}
MIX = {
    "loop": "open", "rate_per_s": 25.0, "lead_s": 0.2, "drain_s": 20.0,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
               "round_up": 32, "min": 32, "max": 64},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 16},
    "order": 0, "check": {"requests": 4, "tokens": 40},
}
#: A per-layer metric added as a file: admissions in the window.
NEW_METRIC = '''"""Scheduler: requests admitted inside the window."""


def read(run):
    return float(sum(1 for tl in run.timelines.values()
                     if tl.admit_ns and run.t0_ns <= tl.admit_ns < run.t1_ns))
'''
CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def make_root(tmp: Path, mix=MIX) -> Path:
    """A checkout holding the benchmark's code plus this test's files."""
    cb = tmp / "chipbench"
    shutil.copytree(ROOT / "chipbench" / "metrics", cb / "metrics")
    (cb / "configs").mkdir()
    (cb / "traffic").mkdir()
    (cb / "configs" / "mini.json").write_text(json.dumps(CONFIG))
    (cb / "traffic" / "burst.json").write_text(json.dumps(mix))
    (cb / "metrics" / "admitted.py").write_text(NEW_METRIC)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "mini", "source": "test only",
                             "file": "chipbench/configs/mini.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "mini.burst", "config": "mini",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] != "setup_s" and m["name"] != "tokens_per_s":
            m["workloads"].append("mini.burst")
    bench["end_to_end"].append({"name": "admitted", "unit": "requests",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["mini.burst"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run(root: Path, **kw):
    cell = harness.load_cell(root, "mini.burst")
    kind = jax.devices()[0].device_kind
    kept: dict = {}
    out = harness.run_cell(cell, 2 ** 35 + 3, kw.pop("seconds", 1.5),
                           trace=False, process_t0_ns=time.perf_counter_ns(),
                           clock=harness.CompileClock(), log=lambda m: None,
                           peak_table={kind: CPU_PEAKS}, keep=kept, **kw)
    return out, kept["run"]
