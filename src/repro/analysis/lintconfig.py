"""reprolint configuration: rule enablement, path scopes, options.

Path scoping is substring-based over the posix display path: a rule
with ``include=("repro/serving",)`` only runs on files whose path
contains that fragment, and ``exclude`` wins over ``include``.  That is
the per-module allowlist mechanism — e.g. the determinism rule only
polices core/serving/retrieval/routing (a notebook-style launch script
may legitimately use ad-hoc RNG), and the ``out_shardings`` check only
polices the serving executors (the dry-run harness jits against
ShapeDtypeStruct spec stand-ins where shardings ride the arguments).

``DEFAULT_CONFIG`` is the repo contract checked by CI.  A JSON file
passed via ``--config`` overlays it::

    {"rules": {"RPL004": {"options": {"budget_bytes": 33554432},
               "exclude": ["repro/kernels/experimental"]}}}
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, Tuple

VMEM_BUDGET_BYTES = 16 * 2 ** 20   # ~16 MiB VMEM per TPU core

#: dim-symbol bindings the VMEM estimator assumes when a BlockSpec
#: dimension is a bare name: the production-shape values each kernel is
#: deployed with (gemma3-12b head_dim 256 bounds D/Dv; block sizes as
#: written at the call sites).  Tests override these per variant.
DEFAULT_DIM_BINDINGS: Dict[str, int] = {
    # attention / decode
    "D": 256, "Dv": 256, "block_q": 128, "block_kv": 128,
    # decode: query heads per kv head (largest GQA group shipped: 8)
    "G": 8,
    # paged decode: largest shipping page size; query and kv heads of
    # the widest shipped GQA config; pages per block at those widths
    # (pages_per_block: one page of 64 x 8 x 512 f32 is 1 MiB)
    "ps": 64, "H": 64, "Hkv": 8, "ppb": 1,
    # dense retrieval: 128-aligned hashed-n-gram embedding, k<=64
    "E": 128, "block_d": 128, "k": 64,
    # bm25 hashed vocab tile
    "block_v": 512,
    # mamba2 ssd chunk scan
    "chunk": 128, "hd": 128, "N": 256,
}


@dataclass
class RuleConfig:
    enabled: bool = True
    include: Tuple[str, ...] = ()     # empty = everywhere
    exclude: Tuple[str, ...] = ()
    options: Dict[str, Any] = field(default_factory=dict)

    def applies_to(self, path: str) -> bool:
        if any(frag in path for frag in self.exclude):
            return False
        if self.include and not any(f in path for f in self.include):
            return False
        return True


@dataclass
class LintConfig:
    rules: Dict[str, RuleConfig] = field(default_factory=dict)

    def rule(self, rule_id: str) -> RuleConfig:
        return self.rules.setdefault(rule_id, RuleConfig())

    def overlay(self, data: Dict[str, Any]) -> "LintConfig":
        """Merge a ``--config`` JSON dict (shallow per rule)."""
        for rid, spec in (data.get("rules") or {}).items():
            rc = self.rule(rid)
            if "enabled" in spec:
                rc.enabled = bool(spec["enabled"])
            if "include" in spec:
                rc.include = tuple(spec["include"])
            if "exclude" in spec:
                rc.exclude = tuple(spec["exclude"])
            rc.options.update(spec.get("options") or {})
        return self

    @classmethod
    def from_file(cls, path: str) -> "LintConfig":
        return make_default_config().overlay(
            json.loads(Path(path).read_text()))


def make_default_config() -> LintConfig:
    return LintConfig(rules={
        # wall-clock discipline: everywhere (the serving plane is
        # virtual-time-replayable end to end; launch scripts time with
        # perf_counter like the Gateway does)
        "RPL001": RuleConfig(),
        # unseeded RNG only polices the deterministic serving core —
        # bit-for-bit replay is a tested invariant there
        "RPL002": RuleConfig(include=(
            "repro/core", "repro/serving", "repro/retrieval",
            "repro/routing", "repro/data", "repro/kernels")),
        "RPL003": RuleConfig(options={
            # the out_shardings sub-check polices the serving
            # executors; the dry-run harness jits spec stand-ins where
            # shardings ride the ShapeDtypeStruct arguments instead
            "out_shardings_include": ["repro/serving"],
        }),
        "RPL004": RuleConfig(
            include=("repro/kernels",),
            options={
                "budget_bytes": VMEM_BUDGET_BYTES,
                "bindings": dict(DEFAULT_DIM_BINDINGS),
                # per-file overrides keyed by path fragment
                "per_file_bindings": {},
                # in/out blocks are double-buffered by the pipeline
                "pipeline_buffers": 2,
                "default_dtype": "float32",
                "operand_dtypes": {},
            }),
        "RPL005": RuleConfig(),
        # exception hygiene polices the paths where a swallowed
        # exception silently erodes SLO accounting
        "RPL006": RuleConfig(include=(
            "repro/serving", "repro/retrieval", "repro/routing")),
        # metric hygiene: names, single registration, injected clocks
        # (everywhere — bench/launch scripts bind metrics too)
        "RPL007": RuleConfig(),
    })


DEFAULT_CONFIG = make_default_config()
