"""A whole run of the harness on the CPU at a tiny size, past the look for a
chip: traffic, engine, window, readers, result line and the check against
the plain reference. The cell's configuration, traffic mix, architecture
family and one metric exist only in this test (``minicell``), as files and
``BENCHMARK.json`` entries, with no edit to a file under ``chipbench/``:
the harness finds them by name."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness  # noqa: E402
from minicell import MIX, ROOT, make_root, run  # noqa: E402


def _files(d: Path) -> dict[str, str]:
    """Digest of every file under ``d``, caches left out."""
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in d.rglob("*") if p.is_file() and not any(
                part.startswith(".") or part == "__pycache__"
                for part in p.relative_to(d).parts)}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    before = _files(ROOT / "chipbench")
    root = make_root(tmp_path_factory.mktemp("checkout"))
    out, r = run(root)
    return out, r, before


def test_result_line_shape(rehearsal):
    out, _, _ = rehearsal
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == len(jax.devices())
    assert set(line["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s",
                                    "admitted"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["check"]["logit_gap"]["limit"] == 0.01


def test_window_is_served_and_checked(rehearsal):
    out, r, _ = rehearsal
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == len(r.window_uids) > 10
    assert r.compiles_in_window == (0, 0)
    assert out["metrics"]["admitted"]["value"] > 0
    assert 0 <= out["check"]["logit_gap"]["value"] <= 0.01


def test_a_new_family_needs_only_new_files(rehearsal):
    out, r, before = rehearsal
    assert out["correct"] is True
    # The harness built, weighed and checked the server through the family
    # file the test wrote into the checkout, once each.
    assert r.cell.family.__name__ == "chipbench_family_mini_gqa"
    assert r.cell.family.CALLS == ["program_config", "program_params",
                                   "served_gaps"]
    assert not (ROOT / "chipbench" / "families" / "mini_gqa.py").exists()
    assert _files(ROOT / "chipbench") == before


@pytest.mark.parametrize("family", ["no_such_family", None])
def test_unknown_family_fails_at_load_cell(tmp_path, family):
    root = make_root(tmp_path)
    path = root / "chipbench" / "configs" / "mini.json"
    conf = json.loads(path.read_text())
    if family is None:
        del conf["family"]
    else:
        conf["family"] = family
    path.write_text(json.dumps(conf))
    with pytest.raises(ValueError, match="unknown architecture family") as e:
        harness.load_cell(root, "mini.burst")
    assert "['dense_gqa', 'mini_gqa']" in str(e.value)


def test_closed_loop_keeps_every_slot_busy(tmp_path):
    mix = dict(MIX, loop="closed")
    root = make_root(tmp_path, mix)
    out, r = run(root, seconds=1.0)
    assert out["correct"] is True and out["failed"] == 0
    ticks = [s for s in r.spans if s.name == "decode_tick"
             and r.t0_ns <= s.t0_ns and s.t1_ns < r.t1_ns]
    # A tick inside an admission runs without the slot being filled.
    slots = [s.args["slots"] for s in ticks]
    assert max(slots) == 4 and sum(slots) / len(slots) >= 3
    assert harness.reader(root, "tokens_per_s")(r) > 0


def _cli(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen3-4b.chat",
         "--seed", str(2 ** 40), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_a_machine_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "platform cpu" in p.stderr


def test_cli_refuses_a_checkout_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
