"""specs_for_schema on a 2-axis (dp×mp) serve mesh.

Spec resolution only reads the mesh's axis names and shape, so these
tests run on a 1-CPU host against a stub mesh object — no forced
devices needed.  They pin the dp×mp serving contract:

* param leaves with head/FFN/vocab logical axes land on ``model``;
* slot-cache leaves land on ``data`` (batch dim) AND ``model``
  (kv-head dim) — the decode chunk combines both axes;
* nothing that CAN shard on the model axis silently replicates.
"""
import dataclasses
from types import SimpleNamespace

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import build_model
from repro.models.schema import ParamSpec
from repro.sharding import (leaf_name, model_axis_fallbacks, resolve_spec,
                            specs_for_schema)


def stub_mesh(dp: int, mp: int):
    """Duck-typed mesh: resolve_spec only touches axis_names and
    shape."""
    return SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": dp, "model": mp})


@pytest.fixture(scope="module")
def qwen():
    cfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                              dtype="float32")
    return cfg, build_model(cfg)


def _leaf_specs(schema, mesh, **kw):
    """{path: (ParamSpec, PartitionSpec)} over a schema tree."""
    import jax
    out = {}
    jax.tree_util.tree_map_with_path(
        lambda path, ps: out.setdefault(leaf_name(path), ps),
        schema, is_leaf=lambda x: isinstance(x, ParamSpec))
    specs = specs_for_schema(schema, mesh, **kw)
    flat = {}
    jax.tree_util.tree_map_with_path(
        lambda path, spec: flat.setdefault(leaf_name(path),
                                           (out[leaf_name(path)], spec)),
        specs, is_leaf=lambda x: isinstance(x, P))
    return flat


def test_param_leaves_land_on_model_axis(qwen):
    cfg, model = qwen
    mesh = stub_mesh(4, 2)
    flat = _leaf_specs(model.schema, mesh, fsdp=False)
    # attention + MLP + embed: the model-capable dims partition on mp=2
    assert "model" in flat["blocks/p0/attn/wq"][1]     # heads
    assert "model" in flat["blocks/p0/attn/wk"][1]     # kv_heads
    assert "model" in flat["blocks/p0/mlp/w_gate"][1]  # d_ff
    assert "model" in flat["blocks/p0/mlp/w_down"][1]  # d_ff
    assert "model" in flat["embed"][1]                 # vocab
    # norms have no model-capable axis: replicated, by design
    assert flat["final_norm"][1] == resolve_spec(
        flat["final_norm"][0], mesh, fsdp=False)
    assert all(e is None for e in flat["final_norm"][1])
    # fsdp=False (serving): no data-axis entries on any weight leaf
    for name, (ps, spec) in flat.items():
        assert not any(e == "data" for e in spec), (name, spec)


def test_nothing_model_capable_silently_replicates(qwen):
    cfg, model = qwen
    sharded, fallbacks = model_axis_fallbacks(model.schema, stub_mesh(4, 2))
    assert not fallbacks, fallbacks
    assert any("attn/wq" in n for n in sharded)
    # a head count whose head_dim fallback is also indivisible IS
    # reported (heads=3 and head_dim=63 both odd on mp=2)
    bad_cfg = dataclasses.replace(cfg, n_heads=3, n_kv_heads=3,
                                  head_dim=63, d_ff=510, vocab_size=500,
                                  vocab_pad_multiple=1)
    bad = build_model(bad_cfg)
    _, bad_fb = model_axis_fallbacks(bad.schema, stub_mesh(4, 2))
    assert any("attn/wq" in n for n in bad_fb), bad_fb


def test_slot_cache_leaves_combine_data_and_model(qwen):
    cfg, model = qwen
    mesh = stub_mesh(4, 2)
    flat = _leaf_specs(model.cache_schema(8, 64), mesh)
    pos_ps, pos_spec = flat["pos"]
    assert tuple(pos_spec) == ("data",)
    k_ps, k_spec = flat["blocks/p0/k"]
    # (layers, batch, seq, kv_heads, head_dim): slots on data, kv heads
    # on model — the dp×mp decode-chunk cache layout
    assert k_ps.axes == ("layers", "batch", "seq", "kv_heads", "head_dim")
    assert tuple(k_spec) == (None, "data", None, "model", None)


def test_indivisible_slots_replicate_gracefully(qwen):
    """5 slots on dp=4: the batch entry falls back to replicated
    rather than erroring — the executor layer is what enforces
    divisibility for the serving slot pool."""
    cfg, model = qwen
    flat = _leaf_specs(model.cache_schema(5, 64), stub_mesh(4, 2))
    assert flat["pos"][1] == resolve_spec(flat["pos"][0], stub_mesh(4, 2))
    assert all(e is None for e in flat["pos"][1])
    # kv heads still ride the model axis even when slots replicate
    assert tuple(flat["blocks/p0/k"][1])[3] == "model"
