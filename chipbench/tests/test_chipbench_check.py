"""The check that decides ``correct`` fails where it must, at a tiny size
on the CPU: the float8 control (the plain reference computed a precision
below the bfloat16 the configuration states) reads over the limit, and so
does a whole run of the harness whose timed path is broken underneath, in
each way a served cell can be: a decode step that hands back its cache
unchanged, and a token altered where it is produced."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness  # noqa: E402
from minicell import CONFIG, make_root, run  # noqa: E402

LIMIT = CONFIG["limits"]["logit_gap"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def test_sound_run_passes_and_control_fails(root):
    out, _ = run(root, control=True)
    assert out["correct"] is True
    assert out["check"]["logit_gap"]["value"] <= LIMIT
    assert out["check"]["control_logit_gap"]["value"] > LIMIT


def _broken(monkeypatch, wrap):
    build = harness.build_engine

    def build_broken(*a, **kw):
        eng = build(*a, **kw)
        eng._decode_jit = wrap(eng._decode_jit)
        return eng

    monkeypatch.setattr(harness, "build_engine", build_broken)


def cache_unchanged(step):
    def f(params, toks, pools, *rest):
        nxt, _ = step(params, toks, pools, *rest)
        return nxt, pools
    return f


def token_altered(step):
    def f(*args):
        nxt, pools = step(*args)
        return (nxt + 1) % CONFIG["vocab_size"], pools
    return f


@pytest.mark.parametrize("fault", [cache_unchanged, token_altered])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    _broken(monkeypatch, fault)
    out, _ = run(root)
    assert out["correct"] is False
    assert out["check"]["logit_gap"]["value"] > LIMIT
    assert jax.devices()[0].platform == "cpu"
