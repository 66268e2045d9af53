"""Operations and bytes the served model needs, from its shapes.

Counts are of the work the algorithm requires, not of what the
program happens to compute: matrix products at 2 FLOPs per
multiply-add (biases and norms are left out), causal attention over
the keys a position may see, and the LM head only where a token is
read out (the last prompt position, then every decode step).  Prompt
padding is waste and is never counted.

``shape(cfg)`` reads a configuration file of ``bench/configs``; with
``per_chip=True`` the heads, KV heads, FFN width and vocabulary are one
tensor-parallel shard's (``mesh`` ``mp``).
"""
from __future__ import annotations

from typing import Dict, Iterable


def shape(cfg: Dict, *, per_chip: bool = False) -> Dict[str, int]:
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


def paged_decode_bytes(s: Dict[str, int], lengths: Iterable[int],
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
