"""Plain references for what the timed path produced.

* :func:`logit_gaps` — the model: a float32 forward pass at the
  highest matmul precision, each layer as the configuration's family
  (``families/<model_type>.py``) writes it from the published
  architecture, then the final RMSNorm and the LM head.  It runs one
  sequence and one layer at a time, in the program's layer order, over
  the exact token ids the engine received (PAD included) followed by
  the tokens it served, and reads the weights the benchmark made, by
  name, in the program's layout; a leaf it would not read is refused.
  With ``control=True`` the same pass also runs with every matrix
  product in float8 (e4m3, per-row activation and per-column weight
  scales), the precision below the configuration's bfloat16.
* :class:`ExactBM25` — exact BM25 in float64 over the corpus text.
* :func:`route_logits` — the router's state features and MLP in float64.
* :func:`prompt_ids` — the prompt templates and the hashed tokenizer.

Nothing here imports the program.
"""
from __future__ import annotations

import hashlib
import re
from functools import partial
from typing import Dict, List, Sequence

import numpy as np

HEAD_CHUNK = 16384       # vocabulary rows per LM-head block
F8_MAX = 448.0           # largest float8_e4m3fn


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _mm(a, w, control: bool):
    """a (..., k) @ w (k, n) in float32, or through float8 (control)."""
    import jax.numpy as jnp
    if control:
        sa = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / F8_MAX + 1e-30
        sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / F8_MAX + 1e-30
        a = (a / sa).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sa
        w = (w / sw).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sw
    return jnp.matmul(a, w, precision="highest")


def _rms(x, w, eps: float):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x (S, heads, dh): rotate the two halves of each head."""
    import jax.numpy as jnp
    s, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _readout(x, xc, norm_w, head_w, served, *, eps: float):
    """Per position: the reference's best logit minus its logit of the
    served token; and, when ``xc`` (the control's hidden states) is
    given, minus its logit of the control's own top token."""
    import jax.numpy as jnp
    nw = norm_w.astype(jnp.float32)
    x = _rms(x, nw, eps)
    n = x.shape[0]
    rows = jnp.arange(n)
    rmax = jnp.full(n, -jnp.inf)
    rsv = jnp.zeros(n)
    cmax = jnp.full(n, -jnp.inf)
    r_at_c = jnp.zeros(n)
    if xc is not None:
        xc = _rms(xc, nw, eps)
    for v0 in range(0, head_w.shape[0], HEAD_CHUNK):
        w = head_w[v0:v0 + HEAD_CHUNK].astype(jnp.float32).T
        r = jnp.matmul(x, w, precision="highest")
        rmax = jnp.maximum(rmax, r.max(-1))
        inside = (served >= v0) & (served < v0 + w.shape[1])
        rsv = jnp.where(inside, r[rows, jnp.clip(served - v0, 0,
                                                 w.shape[1] - 1)], rsv)
        if xc is not None:
            c = _mm(xc, w, True)
            ci = c.argmax(-1)
            better = c[rows, ci] > cmax
            cmax = jnp.where(better, c[rows, ci], cmax)
            r_at_c = jnp.where(better, r[rows, ci], r_at_c)
    return rmax - rsv, rmax - r_at_c


def _path(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def layer_order(params) -> List[tuple]:
    """``(name, tree, i)`` of each layer in the program's order: the
    prefix layers (``i`` None), then for each stacked block ``i`` its
    period positions ``p0, p1, ...``."""
    import jax
    out = [(f"prefix.{j}", lp, None)
           for j, lp in enumerate(params.get("prefix", []))]
    blocks = params.get("blocks", {})
    if blocks:
        n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        pos = sorted(blocks, key=lambda k: int(k[1:]))
        out += [(f"blocks.{k}", blocks[k], i)
                for i in range(n) for k in pos]
    return out


def _leaves_read(fn, tree, x, index) -> set:
    """Paths of the leaves of ``tree`` that ``fn(tree, x, index=index)``
    depends on."""
    import jax
    from jax.extend.core import Literal
    jp = jax.make_jaxpr(lambda t, y, k: fn(t, y, index=k))(
        tree, x, index).jaxpr
    live = {v for v in jp.outvars if not isinstance(v, Literal)}
    for eqn in reversed(jp.eqns):
        if eqn.effects or any(v in live for v in eqn.outvars):
            live.update(v for v in eqn.invars if not isinstance(v, Literal))
    paths = [_path(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        tree)[0]]
    return {p for p, v in zip(paths, jp.invars) if v in live}


def check_read(params, cfg: Dict, family) -> None:
    """Refuse a parameter tree with a leaf that the reference would not
    read: the walk reads the embedding, the final norm, the LM head and
    each layer of :func:`layer_order`, and the family's ``layer`` reads
    the leaves its result depends on (traced once per place in the
    tree, as every layer of one place computes alike)."""
    import jax
    import jax.numpy as jnp
    d = params["embed"].shape[-1]
    x = jax.ShapeDtypeStruct((2, d), jnp.float32)
    index = jax.ShapeDtypeStruct((), jnp.int32)
    read = {"embed", "final_norm", "lm_head"}
    places = {}
    for name, lp, i in layer_order(params):
        places.setdefault(name, (lp, i))
    for name, (lp, i) in places.items():
        cut = 0 if i is None else 1
        tree = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[cut:], a.dtype), lp)
        fn = partial(family.layer, cfg=cfg, control=False, name=name)
        read |= {f"{name}.{p}" for p in _leaves_read(fn, tree, x, index)}
    unread = [p for p in (_path(q) for q, _ in
                          jax.tree_util.tree_flatten_with_path(params)[0])
              if p not in read]
    if unread:
        raise ValueError(f"the reference reads no {unread} (family "
                         f"{cfg.get('model_type')!r})")


def logit_gaps(params, cfg: Dict, family, seqs: Sequence[np.ndarray],
               n_out: Sequence[int], *, control: bool = False,
               device=None) -> Dict[str, List[float]]:
    """``seqs[i]`` is a prompt as the engine received it followed by
    the ``n_out[i]`` tokens it served.  Returns the per-token gaps of
    the served tokens (``gaps``) and, with ``control``, of the float8
    pass's own picks (``control_gaps``).  ``family`` is the
    configuration's (``family.py``), whose ``layer`` each layer runs,
    told its place in the tree and its number in the walk.
    ``device`` pins the pass to one device (the weights' layers are
    copied there one at a time)."""
    import jax
    import jax.numpy as jnp
    check_read(params, cfg, family)
    eps = float(cfg["rms_norm_eps"])
    order = layer_order(params)
    put = (lambda a: jax.device_put(a, device)) if device else (lambda a: a)
    layer, layer_c = (jax.jit(partial(family.layer, cfg=cfg, control=c),
                              static_argnames="name")
                      for c in (False, True))
    readout = jax.jit(partial(_readout, eps=eps))
    embed_w, norm_w = put(params["embed"]), put(params["final_norm"])
    head_w = put(params["lm_head"]) if "lm_head" in params else embed_w
    out: Dict[str, List[float]] = {"gaps": [], "control_gaps": []}
    for ids, n in zip(seqs, n_out):
        ids = np.asarray(ids, np.int32)
        s = len(ids)
        x = jnp.take(embed_w, put(jnp.asarray(ids)), axis=0).astype(
            jnp.float32)
        xc = x
        for k, (name, tree, i) in enumerate(order):
            lp = jax.tree_util.tree_map(
                lambda a: put(a if i is None else a[i]), tree)
            index = put(np.int32(k))
            x = layer(lp, x, name=name, index=index)
            if control:
                xc = layer_c(lp, xc, name=name, index=index)
            del lp
        # positions s-n-1 .. s-2 predict the n served tokens
        rd = slice(s - n - 1, s - 1)
        served = put(jnp.asarray(ids[s - n:]))
        gap, cgap = readout(x[rd], xc[rd] if control else None, norm_w,
                            head_w, served)
        out["gaps"] += np.asarray(gap, np.float64).tolist()
        if control:
            out["control_gaps"] += np.asarray(cgap, np.float64).tolist()
    return out


# ---------------------------------------------------------------------------
# tokens, prompts, retrieval, routing
# ---------------------------------------------------------------------------

WORD_RE = re.compile(r"[a-z0-9]+")
PAD, BOS, N_RESERVED = 0, 1, 4

GUARDED_TEMPLATE = """You are a careful question-answering assistant.
Use ONLY the information in CONTEXT to answer the QUESTION.
If the answer is not in CONTEXT, respond with: "I don't know."

CONTEXT:
{retrieved_passages}

QUESTION:
{question}

Answer (one short sentence):"""

AUTO_TEMPLATE = """Answer the QUESTION using the CONTEXT below.

CONTEXT: {retrieved_passages}

QUESTION: {question}

Answer:"""


def words(text: str) -> List[str]:
    return WORD_RE.findall(text.lower())


def hash_mod(word: str, mod: int) -> int:
    d = hashlib.blake2s(word.encode(), digest_size=8).digest()
    return int.from_bytes(d, "little") % mod


def prompt_ids(mode: str, question: str, passages: Sequence[str],
               vocab: int, max_len: int) -> List[int]:
    template = {"guarded": GUARDED_TEMPLATE, "auto": AUTO_TEMPLATE}[mode]
    text = template.format(retrieved_passages="\n\n".join(passages),
                           question=question)
    ids = [BOS] + [N_RESERVED + hash_mod(w, vocab - N_RESERVED)
                   for w in words(text)]
    return ids[:max_len] + [PAD] * max(0, max_len - len(ids))


class ExactBM25:
    """BM25 (k1, b) over hashed terms, in float64."""

    def __init__(self, texts: Sequence[str], *, dim: int, k1: float,
                 b: float):
        self.dim, self.k1, self.b = dim, k1, b
        tf = np.zeros((len(texts), dim), np.float64)
        for i, t in enumerate(texts):
            for w in words(t):
                tf[i, hash_mod(w, dim)] += 1.0
        self.tf = tf
        dl = tf.sum(1)
        df = (tf > 0).sum(0)
        n = len(texts)
        self.idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        norm = k1 * (1 - b + b * dl[:, None] / (dl.mean() + 1e-6))
        self.sat = tf * (k1 + 1) / (tf + norm)

    def query_vector(self, text: str) -> np.ndarray:
        v = np.zeros(self.dim)
        for w in words(text):
            v[hash_mod(w, self.dim)] += 1.0
        return v

    def scores(self, text: str) -> np.ndarray:
        return self.sat @ (self.idf * self.query_vector(text))

    def topk_ok(self, text: str, ids: Sequence[int], k: int) -> bool:
        """``ids`` are a top-``k`` set: distinct, ``min(k, n)`` of
        them, none scoring below the k-th best (ties may go either
        way)."""
        s = self.scores(text)
        want = min(k, len(s))
        if len(ids) != want or len(set(ids)) != want:
            return False
        kth = np.sort(s)[::-1][want - 1]
        return bool(np.all(s[list(ids)] >= kth - 1e-9 * max(1.0, abs(kth))))


WH_WORDS = ("what", "who", "when", "where", "why", "how", "which")


def route_state(text: str, bm25: ExactBM25, embed_dim: int,
                n_meta: int) -> np.ndarray:
    """The router's state: hashed unigram+bigram question embedding
    (L2-normalised) and retrieval-score metadata."""
    ws = words(text)
    emb = np.zeros(embed_dim)
    for i, w in enumerate(ws):
        emb[hash_mod(w, embed_dim)] += 1.0
        if i + 1 < len(ws):
            emb[hash_mod(w + "_" + ws[i + 1], embed_dim)] += 0.5
    nrm = np.linalg.norm(emb)
    emb = emb / nrm if nrm > 0 else emb
    s = bm25.scores(text)
    order = np.argsort(-s, kind="stable")
    top = s[order[:5]]
    gap = top[0] - top[1] if len(top) > 1 else 0.0
    stats = [top[0], top.mean(), top.std(), gap]
    qv = bm25.query_vector(text)
    terms = np.nonzero(qv)[0]
    if len(terms):
        by_idf = terms[np.argsort(-bm25.idf[terms], kind="stable")][:2]
        present = bm25.tf[order[:5]][:, by_idf] > 0
        both = present.all(1).astype(float)
        cooc = [both.max(initial=0.0), both.mean(), present[:, 0].mean(),
                present[:, -1].mean()]
    else:
        cooc = [0.0] * 4
    smax = stats[0] + 1e-6
    meta = [len(ws) / 20.0, len(text) / 120.0,
            float(any(w in WH_WORDS for w in ws)),
            float(ws[0] in WH_WORDS) if ws else 0.0,
            stats[0] / 10.0, stats[1] / 10.0, stats[2] / 10.0,
            stats[3] / 10.0, stats[3] / smax, stats[1] / smax,
            float(len(set(ws)) / max(len(ws), 1)),
            float(sum(1 for w in ws if any(c.isdigit() for c in w))) / 5.0,
            *cooc]
    meta = (meta + [0.0] * n_meta)[:n_meta]
    return np.concatenate([emb, meta])


def route_logits(state: np.ndarray, mlp: Dict[str, np.ndarray]
                 ) -> np.ndarray:
    """ReLU MLP ``w0,b0, w1,b1, ...`` in float64."""
    n = len([k for k in mlp if k.startswith("w")])
    x = np.asarray(state, np.float64)
    for i in range(n):
        x = x @ np.asarray(mlp[f"w{i}"], np.float64) + np.asarray(
            mlp[f"b{i}"], np.float64)
        if i < n - 1:
            x = np.maximum(x, 0.0)
    return x


def route_ok(logits: np.ndarray, action: int, tol: float = 1e-3) -> bool:
    """The routed action is the reference's argmax, or within ``tol``
    of it (float32 features against float64 ones)."""
    return bool(logits[action] >= logits.max() - tol)
