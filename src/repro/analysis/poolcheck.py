"""Pass 3: pool-invariant audit + opt-in runtime sanitizer.

The checkable invariant spec itself lives next to the data it guards —
``BlockAllocator.check_invariants`` and ``PagedKVCache.check_invariants``
in ``runtime.kv_cache`` raise ``PoolInvariantError`` (tagged with a rule
ID) on refcount non-conservation (POOL001), cross-slot page aliasing /
table-ownership drift (POOL002), free-list corruption (POOL003) and quant
scales detached from their page (POOL005).

This module adds the two ways those invariants get exercised:

* **Static audit (POOL004)** — parses ``kv_cache.py`` and verifies every
  mutation of the protected bookkeeping attributes happens inside a
  sanctioned method.  A mutation from an unsanctioned method is exactly
  the kind of site the runtime checks can miss (nothing re-validates
  after it runs), so it must either be added to the sanctioned list —
  which also enrolls it in the sanitizer — or be refactored away.
* **Runtime sanitizer** — ``attach_sanitizer(kv)`` wraps every mutating
  ``PagedKVCache`` method so the full invariant suite runs after each
  call.  ``PagedKVCache.__init__`` attaches it automatically when
  ``REPRO_SANITIZE`` is set, which is how the nightly slow tier runs.
"""

from __future__ import annotations

import ast
import functools
import inspect

from repro.analysis import Finding

#: PagedKVCache methods the sanitizer wraps: everything that mutates pool
#: bookkeeping (pages, tables, ownership, registry, pool arrays).
SANITIZED_METHODS = (
    "alloc", "map_shared", "shield", "publish", "ensure_write", "truncate",
    "release", "register_prefix", "reclaim_for", "clear_prefixes",
    "clear_stranded_prefixes", "load_prefixes", "scatter",
)

#: POOL004 spec: per class, the bookkeeping attributes nothing outside the
#: sanctioned methods may mutate.  Sanctioned methods are exactly the
#: sites the runtime invariant checks (and the sanitizer) cover.
PROTECTED = {
    "BlockAllocator": dict(
        attrs={"_free", "_ref"},
        methods={"__init__", "alloc", "incref", "free"},
    ),
    "PrefixRegistry": dict(
        attrs={"_entries", "_block_use"},
        methods={"__init__", "get", "put", "pop_lru", "clear",
                 "drop_stranded", "_retain", "_release"},
    ),
    "PagedKVCache": dict(
        attrs={"_owned", "page_table", "pools"},
        methods={"__init__", "alloc", "map_shared", "shield", "publish",
                 "ensure_write", "truncate", "release", "scatter",
                 "load_prefixes", "_copy_block", "adopt"},
    ),
}

#: Method calls that mutate a container in place.
_MUTATORS = {
    "append", "extend", "insert", "remove", "pop", "popitem", "clear",
    "update", "setdefault", "move_to_end", "sort", "reverse", "fill",
}


def _self_attr(node: ast.AST) -> str | None:
    """The protected-attr name if ``node`` is rooted at ``self.<attr>``
    (through any chain of subscripts/attributes), else None."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"):
            return node.attr
        node = node.value
    return None


class _MutationScan(ast.NodeVisitor):
    """Collect (attr, lineno) mutations of self.<attr> in one function."""

    def __init__(self, attrs: set[str]):
        self.attrs = attrs
        self.hits: list[tuple[str, int]] = []

    def _check_target(self, target: ast.AST, lineno: int) -> None:
        name = _self_attr(target)
        if name in self.attrs:
            self.hits.append((name, lineno))

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            for el in ast.walk(t) if isinstance(t, (ast.Tuple, ast.List)) \
                    else (t,):
                self._check_target(el, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_target(node.target, node.lineno)
        self.generic_visit(node)

    def visit_Delete(self, node: ast.Delete) -> None:
        for t in node.targets:
            self._check_target(t, node.lineno)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in _MUTATORS:
            name = _self_attr(fn.value)
            if name in self.attrs:
                self.hits.append((name, node.lineno))
        self.generic_visit(node)


def audit_mutation_sites(module=None) -> list[Finding]:
    """POOL004: every mutation of protected pool bookkeeping must live in
    a sanctioned (invariant-covered) method."""
    if module is None:
        from repro.runtime import kv_cache as module
    tree = ast.parse(inspect.getsource(module))
    findings: list[Finding] = []
    for cls in tree.body:
        if not isinstance(cls, ast.ClassDef) or cls.name not in PROTECTED:
            continue
        spec = PROTECTED[cls.name]
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            scan = _MutationScan(spec["attrs"])
            scan.visit(fn)
            if scan.hits and fn.name not in spec["methods"]:
                attrs = sorted({a for a, _ in scan.hits})
                lines = sorted({ln for _, ln in scan.hits})
                findings.append(Finding(
                    "POOL004", f"{cls.name}.{fn.name}",
                    f"mutates protected {attrs} at line(s) {lines} but is "
                    "not a sanctioned mutation site — add it to "
                    "poolcheck.PROTECTED (and the sanitizer) or refactor "
                    "the mutation into a sanctioned method", "pool"))
    return findings


def audit_pool(kv, path: str = "pool") -> list[Finding]:
    """Run the live invariant suite on one pool; violations come back as
    findings tagged with the rule the raising check carries."""
    from repro.runtime.kv_cache import PoolInvariantError

    try:
        kv.check_invariants()
    except PoolInvariantError as e:
        return [Finding(e.rule, path, str(e), "pool")]
    return []


def attach_sanitizer(kv) -> None:
    """Wrap every mutating ``PagedKVCache`` method of ``kv`` so the full
    invariant suite runs after each call (``REPRO_SANITIZE=1``)."""
    for name in SANITIZED_METHODS:
        fn = getattr(kv, name, None)
        if fn is None:
            continue

        def make(wrapped):
            @functools.wraps(wrapped)
            def guard(*args, **kwargs):
                out = wrapped(*args, **kwargs)
                kv.check_invariants()
                return out
            return guard

        setattr(kv, name, make(fn))
    kv.sanitized = True


def audit_pools() -> list[Finding]:
    """Full pass 3: the static POOL004 audit plus a live-pool invariant
    run over a small exercised pool per kv dtype."""
    import jax.numpy as jnp

    import repro.configs as C
    from repro.models import transformer as T
    from repro.runtime.kv_cache import PagedKVCache

    findings = audit_mutation_sites()
    cfg = C.get_smoke_config("qwen3-4b")
    for kv_dtype in ("fp32", "int8"):
        kv = PagedKVCache(cfg, max_batch=2, max_seq=64, block_size=16,
                          num_blocks=9, kv_dtype=kv_dtype)
        # Exercise the mutation surface, then audit: alloc/shield/publish,
        # a token append past the first page, truncate, and release.
        assert kv.alloc(0, 20)
        kv.shield(0)
        kv.publish(0)
        kv.ensure_write(0, 20)
        kv.truncate(0, 17)
        assert kv.alloc(1, 8)
        kv.publish(1)
        findings.extend(audit_pool(kv, f"PagedKVCache[{kv_dtype}]"))
        kv.release(0)
        kv.release(1)
        findings.extend(audit_pool(kv, f"PagedKVCache[{kv_dtype}]/drained"))
    return findings
