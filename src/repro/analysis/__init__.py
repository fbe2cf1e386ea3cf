"""Stream-safety analyzer: static auditing of the serving stack.

Three passes, one CLI (``python -m repro.analysis`` / ``make
lint-streams``):

* **synccheck** — trace every engine hot path (decode tick, spec verify,
  prefill chunk, page scatter/gather, for each ``ServableModel`` arch x
  serving mode) to jaxprs, audit the device->host traffic against the
  ``@transfer_budget`` declarations, lint the Python tick path for
  hidden syncs, and re-derive each path's paper dependency category from
  the traced graph (cross-checked against ``tuning.workload``).
* **kernelcheck** — lint every Pallas kernel's BlockSpec/grid layout
  against the wrapper's declared shapes, scalar-prefetch usage, quant
  dtype contracts, and ``ops.* <-> ref.*`` oracle signature parity.
* **poolcheck** — the checkable invariant spec for ``BlockAllocator`` /
  ``PagedKVCache`` / ``PrefixRegistry``: a static audit of the mutation
  sites plus the runtime sanitizer behind ``REPRO_SANITIZE=1``.

Findings carry stable rule IDs (the catalog below); known exceptions
live in a waiver file (``stream_waivers.json``) matched by rule + target
substring.  This module stays import-light: passes are imported lazily
by the CLI so the runtime can use ``analysis.budget`` without cost.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

from repro.analysis.budget import (  # noqa: F401  (re-exported contract)
    TransferBudget, budget_of, host_fetch, tick_path, transfer_budget)

#: Stable rule catalog.  IDs never change meaning; new rules get new IDs.
RULES = {
    "STR001": "hidden host sync on a tick path (implicit D2H: int()/"
              "bool()/float()/.item()/branching on a device value)",
    "STR002": "transfer budget exceeded (more D2H arrays/bytes per tick "
              "than the @transfer_budget declaration)",
    "STR003": "un-jitted Python-level callable on the tick path",
    "STR004": "SYNC-classified data re-staged H2D per tick (should be "
              "staged once per admission)",
    "STR005": "dependency category derived from the traced jaxpr "
              "disagrees with tuning.workload.classify_workload",
    "KRN001": "BlockSpec/grid inconsistent with the wrapper's declared "
              "operand shapes (rank, arity, divisibility, TPU tiling)",
    "KRN002": "scalar-prefetch operand never used as an index by any "
              "BlockSpec index_map, nor by the body's declared DMAs",
    "KRN003": "quant kernel dtype contract broken against quant.py "
              "scale/code layouts",
    "KRN004": "ops.* wrapper signature diverges from its ref.* oracle",
    "POOL001": "refcount conservation violated (allocator refs != mapped "
               "pages + registry retentions)",
    "POOL002": "page aliasing / page-table row inconsistent with slot "
               "ownership (trash rows excepted)",
    "POOL003": "free-list corruption (duplicates, overlap with live "
               "refs, or leaked pages)",
    "POOL004": "unaudited pool mutation site (mutates protected state "
               "outside the sanitizer manifest)",
    "POOL005": "quant scales do not travel with their page (missing or "
               "mislaid scale leaves)",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One analyzer finding, addressable by (rule, target) for waivers."""

    rule: str
    target: str  # dotted path of the audited object, e.g. "transformer/paged:decode"
    message: str
    pass_name: str = ""  # "sync" | "kernel" | "pool"

    def to_dict(self) -> dict[str, str]:
        return {"rule": self.rule, "target": self.target,
                "message": self.message, "pass": self.pass_name}

    def __str__(self) -> str:  # the CLI's one-line rendering
        return f"{self.rule} [{self.target}] {self.message}"


def load_waivers(path: str | None) -> list[dict[str, str]]:
    """Waiver file: ``{"waivers": [{"rule", "target", "reason"}]}``."""
    if path is None:
        return []
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return []
    waivers = data.get("waivers", [])
    for w in waivers:
        if "rule" not in w or "target" not in w:
            raise ValueError(f"waiver missing rule/target: {w!r}")
    return waivers


def apply_waivers(findings: list[Finding],
                  waivers: list[dict[str, str]]) -> tuple[list[Finding],
                                                          list[Finding]]:
    """Split findings into (unwaived, waived) by rule + target substring."""
    unwaived, waived = [], []
    for f in findings:
        if any(w["rule"] == f.rule and w["target"] in f.target
               for w in waivers):
            waived.append(f)
        else:
            unwaived.append(f)
    return unwaived, waived
