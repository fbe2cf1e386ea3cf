"""``chip_smoke.py``'s serving phases, driven on the CPU at the smoke preset
(Pallas kernels in interpret mode), and its refusal to run off a TPU."""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

import repro.configs as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_phases(smoke):
    cfg = C.get_smoke_config(smoke.ARCH)
    params = smoke.init_params(cfg, seed=0)
    shape = dict(n_requests=4, prompt_len=32)
    eng = smoke.make_engine(cfg, params, prefill_chunk=16, new_tokens=4,
                            **shape)
    assert eng.scfg.paged_kernel is True
    prompts = smoke.make_prompts(cfg, 0, **shape)
    outs, _ = smoke.serve(eng, prompts)
    smoke.check_outputs(cfg, outs, new_tokens=4)
    again, _ = smoke.serve(eng, prompts)
    assert [a.tolist() for a in again] == [a.tolist() for a in outs]
    smoke.decode_logits(cfg, params, eng, seed=0)
    assert smoke.kernel_vs_reference(cfg, eng, seed=0) < 1e-4


def test_refuses_to_run_off_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "cpu" in err
    assert '"ok"' not in out


def test_fails_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=env, timeout=120)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
