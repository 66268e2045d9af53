"""Sharding resolution: logical axes -> PartitionSpecs.

Production rule set (see DESIGN.md §5):

* ``model`` axis: vocab > d_ff/d_ff_expert/d_inner > heads > kv_heads >
  kv_lora > head_dim — first candidate whose dim divides the axis size
  (**divisibility fallback**: e.g. 40 heads on a 16-way model axis fall
  back to head_dim; if nothing divides, the tensor is replicated over
  ``model`` and the event is recorded for the roofline report).
* ``data`` axis (weights): ZeRO/FSDP-style extra sharding of large
  tensors over the data axis, preferring the d_model dim.
* ``batch`` leaves (activations, KV caches) shard over ("pod","data")
  when divisible, else "data", else replicated (long_500k's batch=1).
* ``experts``: sharded over "data" in expert-parallel (EP) mode —
  the shard_map all-to-all path in ``repro.models.moe``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.schema import ParamSpec

MODEL_PRIORITY = ("vocab", "d_ff", "d_ff_expert", "d_inner", "heads",
                  "kv_heads", "kv_lora", "head_dim")
FSDP_MIN_SIZE = 1 << 18          # don't FSDP-shard small tensors

# fallback events (logical description) — read by the dry-run report
FALLBACK_LOG: List[str] = []


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """Axis name -> size, for a ``Mesh`` or the ``AbstractMesh`` seen
    while tracing under one."""
    return dict(mesh.shape)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_entry(dim: int, mesh: Mesh):
    """PartitionSpec entry for a ``batch`` dim of size ``dim`` (and the
    mesh axes it uses): all batch axes when they divide it, else
    ``data``, else replicated."""
    ba = batch_axes(mesh)
    sizes = mesh_axis_sizes(mesh)
    prod = int(np.prod([sizes[a] for a in ba]))
    if dim % prod == 0:
        return ba if len(ba) > 1 else ba[0], set(ba)
    if "data" in sizes and dim % sizes["data"] == 0:
        return "data", {"data"}
    return None, set()


def resolve_spec(ps: ParamSpec, mesh: Mesh, *, fsdp: bool = True,
                 ep: bool = False, log_name: str = "") -> P:
    """Resolve one ParamSpec to a PartitionSpec."""
    sizes = mesh_axis_sizes(mesh)
    n = len(ps.shape)
    entries: List[Optional[object]] = [None] * n
    used: set = set()

    # --- batch (activation / cache tensors) — first batch dim only
    for i, (ax, dim) in enumerate(zip(ps.axes, ps.shape)):
        if ax == "batch":
            entry, u = batch_entry(dim, mesh)
            if not (u & used):
                entries[i], used = entry, used | u
            break

    # --- expert parallelism
    if ep and "data" not in used and "data" in sizes:
        for i, (ax, dim) in enumerate(zip(ps.axes, ps.shape)):
            if ax == "experts" and dim % sizes["data"] == 0:
                entries[i] = "data"
                used.add("data")
                break

    # --- model axis by priority
    if "model" in sizes:
        placed = False
        for name in MODEL_PRIORITY:
            for i, (ax, dim) in enumerate(zip(ps.axes, ps.shape)):
                if ax == name and entries[i] is None and dim % sizes["model"] == 0:
                    entries[i] = "model"
                    used.add("model")
                    placed = True
                    break
            if placed:
                break
        if not placed and any(a in MODEL_PRIORITY for a in ps.axes):
            FALLBACK_LOG.append(
                f"{log_name or ps.axes}: no dim divisible by model={sizes['model']}"
                f" shape={ps.shape} axes={ps.axes} -> replicated")

    # --- FSDP over data axis for big weight tensors
    has_batch = "batch" in ps.axes
    if (fsdp and not has_batch and "data" not in used and "data" in sizes
            and int(np.prod(ps.shape)) >= FSDP_MIN_SIZE):
        # prefer d_model, else the largest remaining divisible dim
        order = sorted(range(n), key=lambda i: (ps.axes[i] != "d_model",
                                                -ps.shape[i]))
        for i in order:
            if entries[i] is None and ps.axes[i] != "layers" \
                    and ps.shape[i] % sizes["data"] == 0:
                entries[i] = "data"
                used.add("data")
                break

    return P(*entries)


def leaf_name(path) -> str:
    """'blocks/p0/attn/wq'-style name for a tree_map_with_path key path
    (shared by spec resolution, the fallback audit, and tests)."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def specs_for_schema(schema, mesh: Mesh, *, fsdp: bool = True,
                     ep: bool = False):
    """PartitionSpec tree matching a ParamSpec tree."""
    def f(path, ps):
        return resolve_spec(ps, mesh, fsdp=fsdp, ep=ep,
                            log_name=leaf_name(path))

    return jax.tree_util.tree_map_with_path(
        f, schema, is_leaf=lambda x: isinstance(x, ParamSpec))


def shardings_for_schema(schema, mesh: Mesh, **kw):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        specs_for_schema(schema, mesh, **kw),
        is_leaf=lambda x: isinstance(x, P))


def model_axis_fallbacks(schema, mesh: Mesh, *, fsdp: bool = False):
    """Audit the ``model``-axis coverage of a schema on a mesh.

    Returns ``(sharded, fallbacks)``: names of leaves that carry a
    MODEL_PRIORITY logical axis and resolve WITH / WITHOUT a ``model``
    entry on this mesh.  A non-empty ``fallbacks`` list on an ``mp>1``
    serve mesh means those tensors silently replicate over the model
    axis (the divisibility fallback) — surfaced by the serving-mesh
    validation and asserted empty in the dp×mp executor tests.
    """
    sharded: List[str] = []
    fallbacks: List[str] = []

    def f(path, ps):
        if not any(a in MODEL_PRIORITY for a in ps.axes):
            return ps
        name = leaf_name(path)
        spec = resolve_spec(ps, mesh, fsdp=fsdp, log_name=name)
        hit = any(e == "model" for e in spec)
        (sharded if hit else fallbacks).append(name)
        return ps

    jax.tree_util.tree_map_with_path(
        f, schema, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sharded, fallbacks


def input_sharding(mesh: Mesh, batch: int, rank: int) -> NamedSharding:
    """Batch-sharded activation input: (B, ...) with B maybe indivisible."""
    entry, _ = batch_entry(batch, mesh)
    return NamedSharding(mesh, P(entry, *([None] * (rank - 1))))


def opt_state_spec_like(param_spec: P, ps: ParamSpec, mesh: Mesh) -> P:
    """ZeRO-1: optimizer moments shard like the param (already FSDP'd)."""
    return param_spec
