"""A second architecture enters the harness by files alone: a throwaway
family (``families/toyfam.py``), its configuration and its cell are
written into the temporary checkout, and the width check, the weights,
the reference's walk over the layers and the per-layer counts all take
it.  The toy has what Qwen2 lacks: a prefix layer before the stacked
blocks, three layer kinds a period (``p0`` and ``p1`` with the same
leaves but computing differently, ``p2`` with a leaf of its own,
``w_router``), a layer that reads its number in the walk, a cut beyond
depth (``num_local_experts``) and its own decode kernel and bytes."""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
import readers
from conftest import BENCH, tiny_config
import reference
import run
import tracefile
import tracewin
import weights

TOY_FAMILY = '''
import dataclasses

from reference import _mm

LEAVES = {"embed": ("embed", None), "lm_head": ("proj", -1),
          "final_norm": ("norm", None), "c": ("bias", None),
          "w_router": ("proj", -2)}
decode_kernel = "latent_decode"


def program_config(file_cfg, pcfg):
    return dataclasses.replace(
        pcfg, n_layers=file_cfg["num_hidden_layers"],
        moe=dataclasses.replace(pcfg.moe,
                                n_experts=file_cfg["num_local_experts"]))


def check_widths(file_cfg, pcfg):
    if pcfg.d_model != file_cfg["hidden_size"]:
        raise ValueError(f"hidden_size {pcfg.d_model} is not the file's")


def layer(lp, x, *, cfg, control, name, index):
    """Doubles, adds the layer's constant (subtracts it at ``p1``, whose
    leaves are ``p0``'s) and a tenth of the layer's number, so the
    order, the count and the place all count."""
    import jax.numpy as jnp
    sign = -1.0 if name == "blocks.p1" else 1.0
    y = 2.0 * x + sign * lp["c"] + 0.1 * index
    if "w_router" in lp:
        y = y + jnp.tanh(_mm(x, lp["w_router"], control)).sum(
            -1, keepdims=True)
    return y


def shape(cfg, per_chip=False):
    s = {"layers": cfg["num_hidden_layers"],
         "experts": cfg["num_local_experts"],
         "row": 2 * cfg["hidden_size"] * 2}
    if per_chip:
        s["experts"] //= cfg["serving"]["mp"]
    return s


def prefill_flops(s, n):
    return 1000.0 * s["layers"] * n


def decode_flops(s, ctx):
    return 10.0 * s["experts"] * ctx


def decode_bytes(s, lengths, page_size):
    return float(s["row"] * sum(-(-n // page_size) * page_size
                                for n in lengths))
'''

D, E, V, N_BLOCKS = 8, 4, 32, 2
CELL = "toy.rag"


def toy_config(**over) -> dict:
    cfg = {"source": "toy family (CPU tests only)", "model_type": "toyfam",
           "hidden_size": D, "num_hidden_layers": 1 + 3 * N_BLOCKS,
           "num_local_experts": E, "vocab_size": V, "rms_norm_eps": 1e-6,
           "published": {"num_local_experts": 16},
           "serving": {"mp": 2, "page_size": 4, "sync_every": 1}}
    cfg.update(over)
    return {k: v for k, v in cfg.items() if v is not None}


def add_cell(root, name: str, cfg: dict) -> None:
    """A configuration and a cell added as files and entries only."""
    (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    (root / f"bench/limits/{name}.rag.json").write_text(json.dumps(
        {"logit_gap": 0.09, "checked_tokens": 16}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "toy",
                            "file": f"bench/configs/{name}.json",
                            "reduced": ["num_local_experts"],
                            "why": "CPU tests"})
    spec["workloads"].append({"name": f"{name}.rag", "config": name,
                              "traffic": "tiny_rag", "chips": 1,
                              "why": "CPU tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture
def toy(checkout):
    (checkout / "bench/families/toyfam.py").write_text(TOY_FAMILY)
    add_cell(checkout, "toy", toy_config())
    return run.load_cell(checkout, CELL)


def toy_shapes():
    f32 = jnp.float32

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, f32)
    return {"embed": sds(V, D), "final_norm": sds(D), "lm_head": sds(V, D),
            "prefix": [{"c": sds(D)}],
            "blocks": {"p0": {"c": sds(N_BLOCKS, D)},
                       "p1": {"c": sds(N_BLOCKS, D)},
                       "p2": {"c": sds(N_BLOCKS, D),
                              "w_router": sds(N_BLOCKS, D, E)}}}


def test_program_config_and_width_check(toy):
    from repro.core.config import ModelConfig, MoEConfig
    fam = toy["family"]
    pcfg = fam.program_config(toy["cfg"], ModelConfig(
        d_model=D, moe=MoEConfig(n_experts=16)))
    assert (pcfg.n_layers, pcfg.moe.n_experts) == (7, E)
    fam.check_widths(toy["cfg"], pcfg)
    with pytest.raises(ValueError, match="hidden_size"):
        fam.check_widths(dict(toy["cfg"], hidden_size=2 * D), pcfg)


def test_weights_take_the_family_leaves(toy):
    fam = toy["family"]
    params = weights.make_params(toy_shapes(), 7, fam.LEAVES)
    w = np.asarray(params["blocks"]["p2"]["w_router"])
    assert w.shape == (N_BLOCKS, D, E)
    assert 0.5 < w.std() * math.sqrt(D) < 1.5       # N(0, 1/fan_in)
    qwen2 = family.of(toy["bench"], {"model_type": "qwen2"})
    with pytest.raises(ValueError, match="w_router"):
        weights.make_params(toy_shapes(), 7, qwen2.LEAVES)


def _toy_gaps(p, ids, n):
    """The toy model in numpy, its layers in the program's order."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    layers = [("prefix.0", p["prefix"][0])] + [
        (f"blocks.{pos}", {k: v[i] for k, v in p["blocks"][pos].items()})
        for i in range(N_BLOCKS) for pos in ("p0", "p1", "p2")]
    x = p["embed"][ids]
    for index, (name, lp) in enumerate(layers):
        sign = -1.0 if name == "blocks.p1" else 1.0
        y = 2.0 * x + sign * lp["c"] + 0.1 * index
        if "w_router" in lp:
            y = y + np.tanh(x @ lp["w_router"]).sum(-1, keepdims=True)
        x = y
    x = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6)
    logits = (x * p["final_norm"]) @ p["lm_head"].T
    s = len(ids)
    rows = logits[s - n - 1:s - 1]
    return rows.max(-1) - rows[np.arange(n), ids[s - n:]]


def test_reference_walks_every_layer_in_order(toy):
    fam = toy["family"]
    params = weights.make_params(toy_shapes(), 11, fam.LEAVES)
    assert [(name, i) for name, _, i in reference.layer_order(params)] == [
        ("prefix.0", None), ("blocks.p0", 0), ("blocks.p1", 0),
        ("blocks.p2", 0), ("blocks.p0", 1), ("blocks.p1", 1),
        ("blocks.p2", 1)]
    ids = np.random.default_rng(0).integers(0, V, 12).astype(np.int32)
    got = reference.logit_gaps(params, toy["cfg"], fam, [ids], [5])["gaps"]
    np.testing.assert_allclose(got, _toy_gaps(params, ids, 5), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("where,leaf", [
    (("blocks", "p0"), "blocks.p0.spare"),
    ((), "mtp.spare"),
])
def test_reference_refuses_a_leaf_it_does_not_read(toy, where, leaf):
    fam = toy["family"]
    params = weights.make_params(toy_shapes(), 3, fam.LEAVES)
    node = params
    for k in where:
        node = node[k]
    if where:
        node["spare"] = jnp.zeros((N_BLOCKS, D))
    else:
        node["mtp"] = {"spare": jnp.zeros(D)}
    ids = np.arange(1, 9, dtype=np.int32)
    with pytest.raises(ValueError, match=leaf.replace(".", r"\.")):
        reference.logit_gaps(params, toy["cfg"], fam, [ids], [3])


@pytest.mark.parametrize("model_type,message", [
    ("nosuch", r"'nosuch'.*families/nosuch\.py"),
    (None, "names no model_type"),
])
def test_unknown_model_type_is_refused(checkout, model_type, message):
    add_cell(checkout, "other", toy_config(model_type=model_type))
    with pytest.raises(ValueError, match=message):
        run.load_cell(checkout, "other.rag")


def test_counts_are_the_family_s(toy):
    """A hand-made slice: one admission of two prompts (5 and 7 real
    tokens, padded to 8), one sync (both slots live, one token each),
    one decode chunk holding seven calls of the toy's kernel (one a
    layer) beside a Qwen2 kernel call and a fusion."""
    ops = [("fusion.1", "fusion", 0.300, 0.301),
           ("paged_flash_decode.1", "custom-call", 0.301, 0.302)]
    ops += [(f"latent_decode.{j}", "custom-call", 0.31 + 0.01 * j,
             0.32 + 0.01 * j) for j in range(7)]
    ctx = tracewin.context(
        toy, peak={"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
        chips=1, t_a=0.0, t_b=1.0, clock_pc=0.0,
        trace=tracefile.Trace(ops={0: ops},
                              modules={0: [("jit_decode_chunk", 0.3, 0.4)]}),
        host=[], syncs=[(0.2, np.array([True, True]), np.array([1, 1]))],
        admits=[(0.1, np.array([0, 1]), np.array([8, 8]),
                 np.array([5, 7]))],
        breakdowns=[])
    assert ctx.shape == {"layers": 7, "experts": E, "row": 32}
    assert ctx.shape_chip == {"layers": 7, "experts": E // 2, "row": 32}
    # prefill 1000 x 7 layers x (5 + 7); decode 10 x 4 experts x (6 + 8)
    assert readers.mfu(ctx) == pytest.approx(100 * (84000 + 560) / 1e6)
    # 7 layers x 32 B x 2 slots x 12 positions (9 in pages of 4), over
    # 1e6 B/s, against 7 calls of 0.01 s
    assert readers.paged_roofline(ctx) == pytest.approx(
        100 * 7 * 32 * 24 / 1e6 / 0.07)


# PR 12's reference (before the families) on the tree below: the
# program's smoke Qwen2 tree (2 layers, hidden 256, 4 heads, tied
# head) with weights from seed 20261019, over random prompts of 600
# and 40 ids whose last 6 and 4 are the "served" tokens
PINNED = {
    "gaps": [4.230127811431885, 4.240793228149414, 3.975160598754883,
             2.132660388946533, 2.320892095565796, 3.2652547359466553,
             1.800173282623291, 4.9679718017578125, 2.374375343322754,
             4.539705276489258],
    "control_gaps": [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
                     0.009454011917114258, 0.0, 0.0],
}


def test_qwen2_reference_gives_the_pinned_gaps():
    """The Qwen2 layer, moved into its family and run by the walk,
    reads what the reference read before the move (the prompt of 600
    crosses a query block of 512)."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = tiny_config()
    fam = family.of(BENCH, cfg)
    pcfg = fam.program_config(cfg, get_config(cfg["program_config"],
                                              cfg["program_variant"]))
    fam.check_widths(cfg, pcfg)
    params = weights.make_params(build_model(pcfg).param_shapes(),
                                 20261019, fam.LEAVES)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(1, 512, n).astype(np.int32) for n in (600, 40)]
    got = reference.logit_gaps(params, cfg, fam, seqs, [6, 4],
                               control=True)
    for k, want in PINNED.items():
        np.testing.assert_allclose(got[k], want, rtol=0, atol=1e-5)
