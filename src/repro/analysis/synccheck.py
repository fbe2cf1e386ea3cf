"""Pass 1: jaxpr-level sync/transfer audit of every engine hot path.

For each ``ServableModel`` arch x serving mode the audit builds a real
(tiny) engine, traces its hot-path callables to jaxprs with
``jax.make_jaxpr`` (tracing only — nothing compiles or runs), and checks:

* **STR001** — tracing raises a concretization error (the step coerces a
  device value on the Python side) or the jaxpr embeds a host callback;
  the Python glue between steps is linted separately (``astlint``).
* **STR002** — the outputs the host fetches per tick (declared via
  ``@transfer_budget(d2h_outputs=...)`` on the step's builder) exceed the
  declared array count or per-slot byte budget.
* **STR003** — a tick-path callable is not jit-compiled at all.
* **STR005** — the dependency category *derived from the traced graph*
  (``core.dependency.step_footprint`` + ``unroll_stream``) disagrees with
  ``tuning.workload.classify_workload`` for the same regime.

Hot paths per engine: the batched decode tick, the speculative verify
step, the prefill-chunk step (legacy and fused), and the page
scatter/gather.  The audited modes are the ones ``validate_arch``
accepts (quant / speculation are transformer-only; fused prefill is the
paged default for transformer and mamba, and whisper's only paged mode
is the legacy one).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.analysis import Finding
from repro.analysis.budget import TransferBudget, budget_of
from repro.core import dependency as dep
from repro.models import transformer as T
from repro.tuning import workload as W

#: One smoke config per served arch kind (the zoo's taxonomy).
ARCH_SMOKE = {
    "transformer": "qwen3-4b",
    "mamba": "mamba2-2.7b",
    "whisper": "whisper-medium",
}

#: Serving modes per arch; quant/spec and the legacy paged prefill beside
#: the fused one are audited for the transformer only
#: (``ServeConfig.validate_arch`` rejects quant/spec elsewhere).
ARCH_MODES = {
    "transformer": ("contiguous", "paged", "paged_legacy", "quant", "spec"),
    "mamba": ("contiguous", "paged"),
    "whisper": ("contiguous", "paged"),
}

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "callback"}

# Audit geometry: tiny but multi-chunk / multi-page.
_MAX_SEQ = 64
_CHUNK = 16
_MAX_BATCH = 2
_SPEC_K = 3


@dataclasses.dataclass
class PathReport:
    """Measured vs declared D2H for one traced path (BENCH_analysis)."""

    path: str
    d2h_arrays: int
    budget_arrays: int
    d2h_bytes_per_slot: float
    budget_bytes_per_slot: int | None
    category: str | None = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# -- jaxpr plumbing ----------------------------------------------------------


def _sub_jaxprs(value) -> Iterable:
    if hasattr(value, "jaxpr"):  # ClosedJaxpr
        yield value.jaxpr
    elif hasattr(value, "eqns"):  # Jaxpr
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _find_callbacks(jaxpr, acc: list[str]) -> list[str]:
    """Host-callback primitives anywhere in the (nested) jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _CALLBACK_PRIMS:
            acc.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _find_callbacks(sub, acc)
    return acc


def _trace(fn, args):
    """(closed_jaxpr, out_shape, error): tracing only, nothing compiles."""
    try:
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        return closed, out_shape, None
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerIntegerConversionError) as e:
        return None, None, e


def _labels(region_args: Sequence[tuple[str, Any]]) -> tuple[list, list[str]]:
    """Flatten (region, value) pairs to (leaf args, per-leaf region labels)."""
    flat, labels = [], []
    for region, value in region_args:
        leaves = jax.tree_util.tree_leaves(value)
        flat.extend(leaves)
        labels.extend([region] * len(leaves))
    return flat, labels


def _out_labels(out_shape, regions: Sequence[str]) -> list[str]:
    """Per-leaf labels for a top-level output tuple."""
    outs = out_shape if isinstance(out_shape, tuple) else (out_shape,)
    assert len(outs) == len(regions), (len(outs), regions)
    labels = []
    for region, o in zip(regions, outs):
        labels.extend([region] * len(jax.tree_util.tree_leaves(o)))
    return labels


def audit_step(
    *,
    path: str,
    fn,
    builder,
    region_args: Sequence[tuple[str, Any]],
    out_regions: Sequence[str],
    scfg,
    findings: list[Finding],
    reports: list[PathReport],
) -> tuple[frozenset[str], frozenset[str], Any]:
    """Trace one jitted step and audit it; returns (reads, writes,
    out_shape) — empty sets when tracing failed."""
    budget = budget_of(builder) or TransferBudget()
    if not hasattr(fn, "lower"):
        findings.append(Finding(
            "STR003", path,
            f"tick-path callable {getattr(fn, '__name__', fn)!r} is not "
            "jit-compiled (every Python-level call on the tick path "
            "serializes dispatch)", "sync"))
    args = [a for _, a in region_args]
    closed, out_shape, err = _trace(fn, args)
    if err is not None:
        findings.append(Finding(
            "STR001", path,
            f"tracing hit a host sync: {type(err).__name__}: "
            f"{str(err).splitlines()[0]}", "sync"))
        return frozenset(), frozenset(), None
    callbacks = _find_callbacks(closed.jaxpr, [])
    if callbacks:
        findings.append(Finding(
            "STR001", path,
            f"step embeds host callbacks {callbacks} (a device->host "
            "round-trip inside the jitted step)", "sync"))

    outs = out_shape if isinstance(out_shape, tuple) else (out_shape,)
    fetched = []
    for i in budget.d2h_outputs:
        fetched.extend(jax.tree_util.tree_leaves(outs[i]))
    n_arrays = len(fetched)
    n_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in fetched)
    per_slot = n_bytes / max(1, scfg.max_batch)
    limit = budget.bytes_limit(scfg)
    reports.append(PathReport(
        path=path, d2h_arrays=n_arrays, budget_arrays=budget.d2h_arrays,
        d2h_bytes_per_slot=per_slot, budget_bytes_per_slot=limit))
    if n_arrays > budget.d2h_arrays:
        findings.append(Finding(
            "STR002", path,
            f"{n_arrays} fetched output arrays > declared "
            f"d2h_arrays={budget.d2h_arrays}", "sync"))
    if limit is not None and per_slot > limit:
        findings.append(Finding(
            "STR002", path,
            f"{per_slot:.0f} fetched bytes/slot > declared "
            f"d2h_bytes_per_slot={limit}", "sync"))

    flat_in, in_labels = _labels(region_args)
    assert len(flat_in) == len(closed.jaxpr.invars), path
    reads, writes = dep.step_footprint(
        closed, in_labels, _out_labels(out_shape, out_regions))
    return reads, writes, out_shape


# -- engine construction -----------------------------------------------------


def build_engine(arch: str, mode: str):
    """A tiny real engine for (arch, mode) — traced, never run."""
    from repro.runtime.serving import ServeConfig, StreamedBatchEngine

    cfg = C.get_smoke_config(ARCH_SMOKE[arch])
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    kw: dict[str, Any] = dict(
        max_seq=_MAX_SEQ, prefill_chunk=_CHUNK, max_new_tokens=8,
        max_batch=_MAX_BATCH, paged=mode != "contiguous", block_size=16)
    if mode == "paged_legacy":
        kw["fused_prefill"] = False
    elif mode == "quant":
        kw["kv_dtype"] = "int8"
    elif mode == "spec":
        kw.update(spec_decode=True, spec_k=_SPEC_K)
    return StreamedBatchEngine(cfg, params, ServeConfig(**kw))


def _carrier(arch: str) -> str:
    return "state" if arch == "mamba" else "kv"


def audit_engine(eng, arch: str, mode: str) -> tuple[list[Finding],
                                                     list[PathReport]]:
    """Audit every hot path of one built engine."""
    findings: list[Finding] = []
    reports: list[PathReport] = []
    scfg = eng.scfg
    b = scfg.max_batch
    car = _carrier(arch)
    tag = f"{arch}/{mode}"
    servable = eng.servable
    toks1 = jnp.zeros((b, 1), jnp.int32)
    cur = jnp.zeros((b,), jnp.int32)

    # decode tick --------------------------------------------------------
    if eng.paged:
        dec_args = [("params", eng.params), ("tokens", toks1),
                    (car, eng.kv.pools),
                    ("page_table", eng.kv.device_page_table()),
                    ("pos", cur)]
    else:
        dec_args = [("params", eng.params), ("tokens", toks1),
                    (car, eng.caches), ("pos", cur)]
    d_reads, d_writes, d_out = audit_step(
        path=f"{tag}:decode", fn=eng._decode_jit,
        builder=type(servable).decode_fn,
        region_args=dec_args, out_regions=("emit", car),
        scfg=scfg, findings=findings, reports=reports)
    decode_carried = car in d_reads and car in d_writes
    decode_width = 1

    # speculative verify -------------------------------------------------
    if scfg.spec_decode:
        k = scfg.spec_k
        spec_args = [("params", eng.params),
                     ("draft", jnp.zeros((b, k + 1), jnp.int32)),
                     (car, eng.kv.pools),
                     ("page_table", eng.kv.device_page_table()),
                     ("pos", cur), ("draft_len", jnp.zeros((b,), jnp.int32))]
        s_reads, s_writes, s_out = audit_step(
            path=f"{tag}:spec_verify", fn=eng._spec_jit,
            builder=type(servable).make_verifier,
            region_args=spec_args, out_regions=("emit", "n_accept", car),
            scfg=scfg, findings=findings, reports=reports)
        if s_out is not None:
            decode_width = int(s_out[0].shape[1])
            decode_carried = car in s_reads and car in s_writes

    # prefill chunk ------------------------------------------------------
    chunk = scfg.prefill_chunk
    tokens = jnp.zeros((1, chunk), jnp.int32)
    if eng.paged and bool(scfg.fused_prefill):
        n_ctx = eng.kv.pages_for(chunk)
        pf_args = [("params", eng.params), (car, eng.kv.pools),
                   ("page_table", jnp.zeros((1, n_ctx), jnp.int32)),
                   ("prompt", tokens), ("slot", jnp.int32(0))]
        pf_fn = eng.single._fused_chunk_fn(chunk, 0)
        pf_builder = type(eng.single)._fused_chunk_fn
    else:
        enc = servable.probe_enc_out()
        caches = T.init_cache(
            eng.cfg, 1, scfg.max_seq,
            enc_seq=enc.shape[1] if enc is not None else None, ring=False)
        pf_args = [("params", eng.params), (car, caches),
                   ("prompt", tokens), ("enc", enc), ("prefix", None)]
        pf_fn = eng.single._prefill_chunk_fn(chunk, True, 0)
        pf_builder = type(eng.single)._prefill_chunk_fn
    p_reads, p_writes, _ = audit_step(
        path=f"{tag}:prefill_chunk", fn=pf_fn, builder=pf_builder,
        region_args=pf_args, out_regions=("logits", car),
        scfg=scfg, findings=findings, reports=reports)
    prefill_carried = car in p_reads and car in p_writes

    # page scatter / gather ----------------------------------------------
    if eng.paged:
        enc = servable.probe_enc_out()
        src = T.init_cache(
            eng.cfg, 1, scfg.max_seq,
            enc_seq=enc.shape[1] if enc is not None else None, ring=False)
        pages = jnp.zeros((1,), jnp.int32)
        audit_step(
            path=f"{tag}:page_scatter", fn=eng.kv._make_scatter(1),
            builder=type(eng.kv)._make_scatter,
            region_args=[(car, eng.kv.pools), ("src", src),
                         ("page_table", pages), ("slot", jnp.int32(0)),
                         ("row0", jnp.int32(0))],
            out_regions=(car,), scfg=scfg, findings=findings,
            reports=reports)
        audit_step(
            path=f"{tag}:page_gather", fn=eng.kv._make_gather(1),
            builder=type(eng.kv)._make_gather,
            region_args=[(car, eng.kv.pools), ("page_table", pages),
                         ("slot", jnp.int32(0))],
            out_regions=("evicted",), scfg=scfg, findings=findings,
            reports=reports)

    _derive_categories(
        arch, scfg, tag=tag, decode_width=decode_width,
        decode_carried=decode_carried, prefill_carried=prefill_carried,
        prefill_reads=p_reads, findings=findings, reports=reports)
    return findings, reports


# -- category derivation (STR005) --------------------------------------------


def _check_category(tag: str, derived, desc, findings: list[Finding],
                    reports: list[PathReport], *, which: str,
                    **classify_kw) -> None:
    expected, ok = W.crosscheck_category(derived, desc, **classify_kw)
    for r in reports:
        if r.path == f"{tag}:{which}":
            r.category = derived.value
    if not ok:
        findings.append(Finding(
            "STR005", f"{tag}:{which}",
            f"category derived from the traced graph is {derived.value}, "
            f"classify_workload predicts {expected.value}", "sync"))


def _derive_categories(
    arch: str, scfg, *, tag: str, decode_width: int, decode_carried: bool,
    prefill_carried: bool, prefill_reads: frozenset[str],
    findings: list[Finding], reports: list[PathReport],
) -> None:
    """Re-derive each path's paper category from its traced footprint and
    cross-check the hand-modeled classifier (rule STR005)."""
    car = _carrier(arch)
    chunk = scfg.prefill_chunk
    whisper = arch == "whisper"
    head = ("encode", ("audio",), ("enc",)) if whisper else None
    shared = ("enc",) if (whisper and "enc" in prefill_reads) else ()

    # Chunked prefill: one request, 4 chunks -> the RAW carrier chain.
    derived = dep.classify(dep.unroll_stream(
        f"{tag}-prefill", per_task_reads=("prompt",),
        carrier=car if prefill_carried else None,
        shared_reads=shared, n_tasks=4, head=head))
    desc = W.WorkloadDescriptor(
        prompt_len_mean=4 * chunk, prompt_len_max=4 * chunk,
        max_new_tokens=4, n_requests=1)
    _check_category(tag, derived, desc, findings, reports,
                    which="prefill_chunk", prefill_chunk=chunk, arch=arch)

    # One-shot prefill: a single chunk is one sequential stage (SYNC).
    derived = dep.classify(dep.unroll_stream(
        f"{tag}-oneshot", per_task_reads=("prompt",),
        carrier=car if prefill_carried else None,
        shared_reads=shared, n_tasks=1, head=head,
        sequential_kernel=whisper))
    desc = W.WorkloadDescriptor(
        prompt_len_mean=chunk, prompt_len_max=chunk, max_new_tokens=4,
        n_requests=1)
    _check_category(tag, derived, desc, findings, reports,
                    which="prefill_oneshot", prefill_chunk=chunk, arch=arch)

    # Decode-dominated batch: the step's emit width says whether decode is
    # the per-token kernel re-running on resident state (ITERATIVE) or the
    # verify-chunk RAW chain speculation restructures it into.
    max_new = 64
    desc = W.WorkloadDescriptor(
        prompt_len_mean=chunk, prompt_len_max=chunk,
        max_new_tokens=max_new, n_requests=scfg.max_batch)
    spec = decode_width > 1
    if spec and decode_carried:
        n_steps = min(8, -(-max_new // decode_width))
        derived = dep.classify(dep.unroll_stream(
            f"{tag}-spec", per_task_reads=("draft",), carrier=car,
            n_tasks=n_steps))
    elif decode_carried:
        derived = dep.classify(dep.unroll_stream(
            f"{tag}-decode", per_task_reads=("prompt",),
            n_tasks=scfg.max_batch, kernel_iterations=max_new))
    else:
        # A decode step that does not read its own carrier is broken in a
        # way the classifier cannot predict: surface as INDEPENDENT and
        # let the mismatch fire.
        derived = dep.Category.INDEPENDENT
    which = "spec_verify" if spec else "decode"
    _check_category(
        tag, derived, desc, findings, reports, which=which,
        prefill_chunk=chunk, spec_decode=spec,
        spec_k=max(0, decode_width - 1), arch=arch)


# -- top-level matrix --------------------------------------------------------


def audit_matrix(
    archs: Sequence[str] | None = None,
    modes: Sequence[str] | None = None,
) -> tuple[list[Finding], list[PathReport]]:
    """Audit every requested arch x mode; also AST-lints the tick-path
    modules once.  Returns (findings, per-path reports)."""
    from repro.analysis import astlint
    from repro.runtime import kv_cache, model_iface, serving

    findings: list[Finding] = []
    reports: list[PathReport] = []
    for mod in (serving, kv_cache, model_iface):
        findings.extend(astlint.lint_module(mod))
    for arch, arch_modes in ARCH_MODES.items():
        if archs and arch not in archs:
            continue
        for mode in arch_modes:
            if modes and mode not in modes:
                continue
            eng = build_engine(arch, mode)
            f, r = audit_engine(eng, arch, mode)
            findings.extend(f)
            reports.extend(r)
    return findings, reports
