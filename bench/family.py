"""Harness files found by name: a per-layer metric's reader, and the
architecture family of a configuration.

A configuration file's ``model_type`` names ``families/<model_type>.py``
beside ``configs/``; that module owns everything in the harness that
depends on the architecture, and nothing else here names a family.
It provides:

* ``program_config(file_cfg, pcfg)``: the file's cut (every key of the
  configuration's ``reduced``) applied to the program's ``ModelConfig``;
* ``check_widths(file_cfg, pcfg)``: raises unless the program's
  configuration holds the file's published widths;
* ``LEAVES``: leaf name -> ``(kind, fan-in dim)`` of every parameter
  leaf, as ``weights.make_params`` initialises them;
* ``layer(lp, x, *, cfg, control, name, index)``: one layer of the
  float32 reference.  ``reference.logit_gaps`` walks the program's
  layers, prefix first, then each block's period positions in order;
  ``name`` is the layer's place in the tree (``"prefix.<j>"`` or
  ``"blocks.p<k>"``, a static string: layers of one place compute
  alike, as the program's periodic blocks are built) and ``index`` its
  number in that order (an int32 scalar, traced under ``jit``, so one
  compiled layer serves every block);
* ``shape(cfg, per_chip=False)``, ``prefill_flops(s, n)``,
  ``decode_flops(s, ctx)``, and the decode kernel's trace name
  ``decode_kernel`` with its bytes ``decode_bytes(s, lengths,
  page_size)``: the counts the per-layer readers divide by.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType
from typing import Dict


def by_name(bench: Path, kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py``, loaded as a module: a per-layer
    metric's reader (``metrics``) or an architecture's family
    (``families``)."""
    path = Path(bench) / kind / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in path.parent.glob("*.py"))
        raise ValueError(f"no file for {name!r}: {path} does not exist "
                         f"({kind}: {known})")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def of(bench: Path, cfg: Dict) -> ModuleType:
    """The family that a configuration file's ``model_type`` names."""
    if "model_type" not in cfg:
        raise ValueError("the configuration names no model_type")
    return by_name(bench, "families", cfg["model_type"])
