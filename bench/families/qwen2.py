"""Qwen2: dense grouped-query attention with q/k/v biases, rotary
embeddings over halves, SwiGLU MLP and pre-norm RMSNorm (Qwen1.5
included).  Every part of the harness that depends on the architecture,
for configuration files whose ``model_type`` is ``"qwen2"``.

Counts are of the work the algorithm requires, not of what the
program happens to compute: matrix products at 2 FLOPs per
multiply-add (biases and norms are left out), causal attention over
the keys a position may see, and the LM head only where a token is
read out (the last prompt position, then every decode step).  Prompt
padding is waste and is never counted.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable

import numpy as np

from reference import _mm, _rms, _rope

Q_CHUNK = 512            # query rows per attention block of the reference

# leaf name -> (kind, index of its fan-in dim counted from the end)
LEAVES = {
    "embed": ("embed", None), "lm_head": ("proj", -1),
    "final_norm": ("norm", None), "ln1": ("norm", None),
    "ln2": ("norm", None),
    "wq": ("proj", -3), "wk": ("proj", -3), "wv": ("proj", -3),
    "wo": ("proj2", None), "bq": ("bias", None), "bk": ("bias", None),
    "bv": ("bias", None), "w_gate": ("proj", -2), "w_up": ("proj", -2),
    "w_down": ("proj", -2),
}

# the Pallas paged flash-decode kernel as the trace names it
decode_kernel = "paged_flash_decode"


# ---------------------------------------------------------------------------
# the program's configuration
# ---------------------------------------------------------------------------

def program_config(file_cfg: Dict, pcfg):
    """The file's cut applied to the program's ``ModelConfig``: its
    depth, and the serving path's flash decode."""
    return dataclasses.replace(
        pcfg, n_layers=file_cfg["num_hidden_layers"],
        use_flash_decode=bool(file_cfg["serving"]["flash_decode"]))


def check_widths(file_cfg: Dict, pcfg) -> None:
    c = file_cfg
    got = {"hidden_size": pcfg.d_model, "intermediate_size": pcfg.d_ff,
           "num_attention_heads": pcfg.n_heads,
           "num_key_value_heads": pcfg.n_kv_heads,
           "vocab_size": pcfg.vocab_size, "rope_theta": pcfg.rope_theta,
           "rms_norm_eps": pcfg.norm_eps,
           "tie_word_embeddings": pcfg.tie_embeddings,
           "torch_dtype": pcfg.dtype}
    bad = {k: (v, c[k]) for k, v in got.items() if v != c[k]}
    if bad or not pcfg.qkv_bias or pcfg.padded_vocab != pcfg.vocab_size:
        raise ValueError(f"program config differs from the file: {bad}")


# ---------------------------------------------------------------------------
# one layer of the float32 reference
# ---------------------------------------------------------------------------

def layer(lp, x, *, cfg: Dict, control: bool, name: str, index):
    """Every Qwen2 layer computes alike: ``name`` and ``index`` are
    not read."""
    import jax
    import jax.numpy as jnp
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    at, ml = lp["attn"], lp["mlp"]
    s, d = x.shape
    _, h, dh = at["wq"].shape
    hkv = at["wk"].shape[1]
    hn = _rms(x, lp["ln1"], eps)
    q = _mm(hn, at["wq"].reshape(d, h * dh), control).reshape(s, h, dh)
    k = _mm(hn, at["wk"].reshape(d, hkv * dh), control).reshape(s, hkv, dh)
    v = _mm(hn, at["wv"].reshape(d, hkv * dh), control).reshape(s, hkv, dh)
    q = _rope(q + at["bq"], theta)
    k = jnp.repeat(_rope(k + at["bk"], theta), h // hkv, axis=1)
    v = jnp.repeat(v + at["bv"], h // hkv, axis=1)
    outs = []
    for c0 in range(0, s, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK]
        sc = jnp.einsum("qhd,khd->hqk", qc, k,
                        precision="highest") / math.sqrt(dh)
        mask = np.arange(s)[None, :] <= np.arange(c0, c0 + len(qc))[:, None]
        sc = jnp.where(jnp.asarray(mask)[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision="highest"))
    att = jnp.concatenate(outs, 0).reshape(s, h * dh)
    x = x + _mm(att, at["wo"].reshape(h * dh, d), control)
    hn = _rms(x, lp["ln2"], eps)
    ff = jax.nn.silu(_mm(hn, ml["w_gate"], control)) * _mm(
        hn, ml["w_up"], control)
    return x + _mm(ff, ml["w_down"], control)


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def shape(cfg: Dict, *, per_chip: bool = False) -> Dict[str, int]:
    """The sizes the counts read, from a configuration file; with
    ``per_chip=True`` the heads, KV heads, FFN width and vocabulary are
    one tensor-parallel shard's (``serving`` ``mp``)."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    s = {"d": d, "h": h, "hkv": int(cfg["num_key_value_heads"]),
         "dh": int(cfg.get("head_dim") or d // h),
         "f": int(cfg["intermediate_size"]), "v": int(cfg["vocab_size"]),
         "layers": int(cfg["num_hidden_layers"]), "bytes": 2}
    if per_chip:
        mp = int(cfg["serving"]["mp"])
        for k in ("h", "hkv", "f", "v"):
            s[k] //= mp
    return s


def layer_matmul_params(s: Dict[str, int]) -> int:
    d, h, hkv, dh, f = s["d"], s["h"], s["hkv"], s["dh"], s["f"]
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f


def prefill_flops(s: Dict[str, int], n: int) -> float:
    """One prompt of ``n`` tokens, first token read out."""
    attn = 4 * s["h"] * s["dh"] * n * (n + 1) / 2
    per_layer = 2 * layer_matmul_params(s) * n + attn
    return s["layers"] * per_layer + 2 * s["v"] * s["d"]


def decode_flops(s: Dict[str, int], ctx: int) -> float:
    """One decode step of one sequence attending ``ctx`` keys."""
    per_layer = 2 * layer_matmul_params(s) + 4 * s["h"] * s["dh"] * ctx
    return s["layers"] * per_layer + 2 * s["v"] * s["d"]


def decode_bytes(s: Dict[str, int], lengths: Iterable[int],
                 page_size: int) -> float:
    """Bytes one paged decode-attention call must move for one layer:
    the whole pages that hold each slot's valid keys and values, the
    query read and the output written."""
    lengths = list(lengths)
    kv_row = s["hkv"] * s["dh"] * s["bytes"]
    pages = sum(-(-max(int(n), 1) // page_size) for n in lengths)
    kv = 2 * pages * page_size * kv_row
    qo = 2 * len(lengths) * s["h"] * s["dh"] * s["bytes"]
    return float(kv + qo)
