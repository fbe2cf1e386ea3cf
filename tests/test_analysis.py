"""Stream-safety analyzer tests.

Each known-bad fixture (tests/analysis_fixtures/) must trip *exactly* its
rule ID; the real engine and kernels must trip none.  The runtime
sanitizer must verifiably fire on a deliberately corrupted pool.
"""

import inspect
import types

import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.analysis import (RULES, Finding, apply_waivers, astlint,
                            kernelcheck, poolcheck, synccheck)
from repro.runtime.kv_cache import (BlockAllocator, PagedKVCache,
                                    PoolInvariantError)

from analysis_fixtures import (bad_blockspec, budget_violation, hidden_sync,
                               refcount_leak)


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Rule registry / waivers


def test_rule_catalog_complete():
    for prefix in ("STR", "KRN", "POOL"):
        assert any(r.startswith(prefix) for r in RULES)
    for rid, desc in RULES.items():
        assert desc, rid


def test_waivers_match_by_rule_and_target():
    f1 = Finding("STR001", "transformer/paged:decode", "x", "sync")
    f2 = Finding("KRN001", "flash_attention:in[0]", "y", "kernel")
    waivers = [{"rule": "STR001", "target": "transformer/paged",
                "reason": "known"}]
    unwaived, waived = apply_waivers([f1, f2], waivers)
    assert waived == [f1]
    assert unwaived == [f2]


# ---------------------------------------------------------------------------
# Fixtures: each trips exactly its rule


def test_fixture_hidden_sync_trips_str001():
    findings = astlint.lint_source(
        inspect.getsource(hidden_sync), "hidden_sync")
    assert _rules(findings) == {"STR001"}


def test_fixture_budget_violation_trips_str002():
    findings, reports = [], []
    scfg = types.SimpleNamespace(max_batch=2)
    synccheck.audit_step(
        path="fixture:budget", fn=budget_violation.build_step(),
        builder=budget_violation.build_step,
        region_args=[("x", jnp.zeros((2, 8), jnp.float32))],
        out_regions=("a", "b", "c"), scfg=scfg,
        findings=findings, reports=reports)
    assert _rules(findings) == {"STR002"}
    assert reports[0].d2h_arrays > reports[0].budget_arrays


def test_fixture_bad_blockspec_trips_krn001():
    findings = kernelcheck.check_layout(
        "bad_kernel", bad_blockspec.KERNEL_META["bad_kernel"])
    assert _rules(findings) == {"KRN001"}


def test_size_one_head_block_trips_krn001_tiling():
    """A K/V block holding one KV head of a (pages, rows, Hkv, hd) pool
    — (1, bs, 1, hd) — breaks the TPU tiling rule on its second-last dim,
    which the TPU compiler refuses; the lint must flag it statically."""
    from jax.experimental import pallas as pl

    def build():
        spec = pl.BlockSpec((1, 8, 1, 128), lambda b, h, j, pt: (pt[b, j], 0, h, 0))
        return dict(grid=(2, 2, 4), num_scalar_prefetch=1,
                    prefetch_index_operands=(0,), in_specs=[spec],
                    out_specs=pl.BlockSpec((1, 1, 4, 128),
                                           lambda b, h, j, pt: (b, h, 0, 0)),
                    operands=[(9, 8, 2, 128)], out_shape=(2, 2, 4, 128))

    findings = kernelcheck.check_layout(
        "one_head_block", dict(build=build, lint_shapes={}))
    assert _rules(findings) == {"KRN001"}
    assert [f.target for f in findings] == ["one_head_block:in[0]"]
    assert "tiling" in findings[0].message


def _hbm_layout(hbm_operands):
    """A one-operand layout whose operand stays in HBM (``pl.ANY``) and
    whose page-table prefetch only the body's copies read."""
    from jax.experimental import pallas as pl

    def build():
        return dict(grid=(2, 4), num_scalar_prefetch=1,
                    prefetch_index_operands=(0,),
                    in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                    hbm_operands=hbm_operands,
                    out_specs=pl.BlockSpec((1, 8, 128),
                                           lambda b, j, pt: (b, 0, 0)),
                    operands=[(9, 8, 2, 128)], out_shape=(2, 8, 128))
    return build


@pytest.mark.parametrize("hbm_operands,found", [
    ({0: 4}, []),
    # undeclared: the operand is flagged, and with no HBM operand declared
    # the DMA-indexed prefetch has nothing to read it
    ({}, [("KRN001", "in[0]"), ("KRN002", "prefetch[0]")]),
    ({0: 3}, [("KRN001", "in[0]")])])
def test_hbm_operand_is_declared_with_its_rank(hbm_operands, found):
    findings = kernelcheck.check_layout("hbm_kernel", dict(
        build=_hbm_layout(hbm_operands), lint_shapes={},
        dma_index_operands=(0,)))
    assert [(f.rule, f.target) for f in findings] == [
        (rule, f"hbm_kernel:{what}") for rule, what in found]


def test_dma_indexed_prefetch_needs_a_copy_to_read_it():
    """A prefetch declared DMA-indexed in a layout with no HBM operand is
    as dead as one no index map reads, and so is an undeclared one."""
    from jax.experimental import pallas as pl

    def build():
        return dict(grid=(2, 4), num_scalar_prefetch=1,
                    prefetch_index_operands=(0,),
                    in_specs=[pl.BlockSpec((1, 8, 128),
                                           lambda b, j, pt: (b, 0, 0))],
                    out_specs=pl.BlockSpec((1, 8, 128),
                                           lambda b, j, pt: (b, 0, 0)),
                    operands=[(2, 8, 128)], out_shape=(2, 8, 128))

    for meta in (dict(build=build, lint_shapes={}, dma_index_operands=(0,)),
                 dict(build=_hbm_layout({0: 4}), lint_shapes={})):
        findings = kernelcheck.check_layout("dead_prefetch", meta)
        assert [(f.rule, f.target) for f in findings] == [
            ("KRN002", "dead_prefetch:prefetch[0]")]


def test_fixture_refcount_leak_trips_pool001():
    kv = _small_pool()
    assert kv.alloc(0, 20)
    kv.publish(0)
    assert poolcheck.audit_pool(kv) == []
    refcount_leak.leak(kv)
    findings = poolcheck.audit_pool(kv)
    assert _rules(findings) == {"POOL001"}


def test_unjitted_step_trips_str003():
    findings, reports = [], []
    scfg = types.SimpleNamespace(max_batch=1)
    synccheck.audit_step(
        path="fixture:unjitted", fn=lambda x: (x, x),
        builder=budget_violation.build_step,
        region_args=[("x", jnp.zeros((4,), jnp.float32))],
        out_regions=("a", "b"), scfg=scfg,
        findings=findings, reports=reports)
    assert "STR003" in _rules(findings)


# ---------------------------------------------------------------------------
# The real stack is clean


def test_real_engine_paths_clean():
    findings, reports = synccheck.audit_matrix(
        archs=["transformer"], modes=["paged", "contiguous"])
    assert findings == []
    assert any(r.path.endswith(":decode") for r in reports)
    # Every decode tick stays within its declared budget.
    for r in reports:
        assert r.d2h_arrays <= r.budget_arrays, r


def test_kernel_lint_clean():
    assert kernelcheck.audit_kernels() == []


def test_pool_audit_clean():
    assert poolcheck.audit_pools() == []


@pytest.mark.slow
def test_full_matrix_clean():
    findings, reports = synccheck.audit_matrix()
    assert findings == []
    audited = {r.path.split(":")[0] for r in reports}
    want = {f"{a}/{m}" for a, ms in synccheck.ARCH_MODES.items()
            for m in ms}
    assert audited == want


# ---------------------------------------------------------------------------
# Allocator invariants + runtime sanitizer


def _small_pool(**kw):
    cfg = C.get_smoke_config("qwen3-4b")
    kw.setdefault("kv_dtype", "fp32")
    return PagedKVCache(cfg, max_batch=2, max_seq=64, block_size=16,
                        num_blocks=9, **kw)


def test_allocator_check_invariants_tracks_holders():
    alloc = BlockAllocator(8)
    a = alloc.alloc(3)
    b = alloc.alloc(2)
    alloc.check_invariants([a, b])
    alloc.incref(a)
    alloc.check_invariants([a, b, a])
    alloc.free(a)
    alloc.check_invariants([a, b])
    with pytest.raises(PoolInvariantError) as ei:
        alloc.check_invariants([b])  # a's pages have no holder
    assert ei.value.rule == "POOL001"


def test_allocator_free_list_corruption_detected():
    alloc = BlockAllocator(8)
    pages = alloc.alloc(2)
    alloc._free.append(pages[0])  # allocated page back on the free list
    with pytest.raises(PoolInvariantError) as ei:
        alloc.check_invariants()
    assert ei.value.rule == "POOL003"


def test_sanitizer_attaches_and_fires(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    kv = _small_pool()
    assert getattr(kv, "sanitized", False)
    assert kv.alloc(0, 20)
    kv.publish(0)
    assert kv.sanitize_checks >= 2  # every mutation audited
    refcount_leak.leak(kv)
    with pytest.raises(PoolInvariantError) as ei:
        kv.alloc(1, 8)  # next mutation runs the suite and catches it
    assert ei.value.rule == "POOL001"


def test_sanitizer_off_by_default(monkeypatch):
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    kv = _small_pool()
    assert not getattr(kv, "sanitized", False)
    assert kv.sanitize_checks == 0


def test_quant_pool_invariants_cover_scales():
    kv = _small_pool(kv_dtype="int8")
    assert kv.alloc(0, 20)
    kv.publish(0)
    kv.check_invariants()
    # Drop a layer's scale pool: POOL005 must notice the pages lost
    # their scales.
    layer = next(iter(kv.pools["blocks"]))
    broken_layer = dict(kv.pools["blocks"][layer])
    victim = next(k for k in broken_layer if k.endswith("_scale"))
    del broken_layer[victim]
    kv.pools = {**kv.pools,
                "blocks": {**kv.pools["blocks"], layer: broken_layer}}
    with pytest.raises(PoolInvariantError) as ei:
        kv.check_invariants()
    assert ei.value.rule == "POOL005"


def test_mutation_site_audit_flags_unsanctioned():
    src = (
        "class BlockAllocator:\n"
        "    def rogue(self, p):\n"
        "        self._ref[p] += 1\n")
    mod = types.SimpleNamespace()
    import ast as _ast
    import unittest.mock as _mock
    with _mock.patch("inspect.getsource", return_value=src):
        findings = poolcheck.audit_mutation_sites(mod)
    assert _rules(findings) == {"POOL004"}
    assert "BlockAllocator.rogue" in findings[0].target
