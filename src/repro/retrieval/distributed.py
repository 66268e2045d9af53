"""Distributed retrieval: corpus sharded over the mesh.

Production RAG serves corpora that don't fit one device.  The doc-major
matrix — the dense (docs × hashed-vocab) BM25 TF matrix, or the dense
retriever's (docs × embed) embedding matrix — shards over the mesh's
data axis; each shard scores its local block (the Pallas bm25 /
dense_topk kernels on TPU) and emits a local top-k; a gather + final
top-k merges candidates.  Both retrievers share ONE merge path
(:func:`distributed_topk` — score_fn is the only thing that differs),
so communication per query is O(shards × k) scores + ids for either,
independent of corpus size.

Used by the retrieval dry-run (tests/test_distributed_retrieval.py and
tests/test_dense_retrieval.py run it on a real 8-device host mesh) and
available to the serving pipeline via :class:`DistributedBM25` /
:class:`DistributedDenseIndex` (exported from ``repro.retrieval``).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _merge_local_topk(scores, *, k: int, axis: str, d_local: int):
    """Local top-k -> globalized ids -> all-gather -> final top-k.

    The shared merge tail of every sharded retriever: ``scores`` is the
    (Q, D_loc) block this shard scored; candidates gather in shard
    order, so exact-score ties resolve to the lowest global doc id —
    identical to ``lax.top_k`` over the unsharded score row.
    """
    top_s, top_i = jax.lax.top_k(scores, k)             # local candidates
    shard = jax.lax.axis_index(axis)
    top_i = top_i + shard * d_local
    all_s = jax.lax.all_gather(top_s, axis, axis=1, tiled=True)
    all_i = jax.lax.all_gather(top_i, axis, axis=1, tiled=True)
    best_s, pos = jax.lax.top_k(all_s, k)
    best_i = jnp.take_along_axis(all_i, pos, axis=1)
    return best_s, best_i


def distributed_topk(mesh: Mesh, score_fn: Callable, doc_arrays: Sequence,
                     qv: jax.Array, *, k: int = 10, axis: str = "data"
                     ) -> Tuple[jax.Array, jax.Array]:
    """Top-k over a corpus sharded on ``axis`` — any scoring function.

    ``doc_arrays`` are doc-major arrays (leading dim D, sharded over
    ``axis``); ``qv`` is the replicated (Q, F) query matrix;
    ``score_fn(*doc_arrays_local, qv) -> (Q, D_loc)`` scores one local
    shard.  Returns (scores (Q, k), global doc ids (Q, k)).
    """
    n_axis = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    D = doc_arrays[0].shape[0]
    assert D % n_axis == 0, (D, n_axis)
    d_local = D // n_axis

    def body(*args):
        *docs_loc, q = args
        scores = score_fn(*docs_loc, q)
        return _merge_local_topk(scores, k=k, axis=axis, d_local=d_local)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(P(axis, *([None] * (a.ndim - 1)))
                       for a in doc_arrays) + (P(None, None),),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)(*doc_arrays, qv)


# ---------------------------------------------------------------------------
# BM25
# ---------------------------------------------------------------------------


def _bm25_local_scores(tf_loc, dl_loc, qv, *, avg_len, k1, b):
    """BM25 over the local doc shard.  tf_loc: (D_loc, V); qv: (Q, V)."""
    norm = k1 * (1 - b + b * dl_loc[:, None] / avg_len)
    sat = tf_loc * (k1 + 1) / (tf_loc + norm)
    return qv @ sat.T                                   # (Q, D_loc)


def distributed_bm25_topk(mesh: Mesh, tf: jax.Array, doc_len: jax.Array,
                          qv: jax.Array, *, k: int = 10, k1: float = 1.2,
                          b: float = 0.75, axis: str = "data"
                          ) -> Tuple[jax.Array, jax.Array]:
    """BM25 top-k over a corpus sharded on ``axis``.

    tf: (D, V) global TF matrix (sharded on docs); qv: (Q, V) replicated
    idf-weighted query vectors.  Returns (scores (Q,k), doc_ids (Q,k)).
    """
    avg_len = float(np.asarray(jnp.mean(doc_len))) + 1e-6
    return distributed_topk(
        mesh, partial(_bm25_local_scores, avg_len=avg_len, k1=k1, b=b),
        (tf, doc_len), qv, k=k, axis=axis)


def distributed_dense_topk(mesh: Mesh, emb: jax.Array, qe: jax.Array, *,
                           k: int = 10, axis: str = "data"
                           ) -> Tuple[jax.Array, jax.Array]:
    """Dense top-k over a doc-embedding matrix sharded on ``axis``.

    emb: (D, E) doc embeddings (sharded on docs); qe: (Q, E) replicated
    query embeddings.  Returns (scores (Q,k), doc_ids (Q,k)).
    """
    return distributed_topk(
        mesh, lambda emb_loc, q: q @ emb_loc.T, (emb,), qe, k=k, axis=axis)


class DistributedBM25:
    """Drop-in scorer over a sharded corpus for the serving pipeline.

    ``topk`` takes (Q, V) raw query term counts and returns
    ``(ids, scores)`` — the same order as every other scorer in the
    package (``BM25Index.topk``, ``DenseIndex.topk``, the
    :class:`~repro.retrieval.hybrid.Retriever` protocol), so swapping
    scorers behind an adapter cannot silently transpose the pair.
    """

    def __init__(self, mesh: Mesh, tf: np.ndarray, doc_len: np.ndarray,
                 idf: np.ndarray, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        ax_spec = NamedSharding(mesh, P(axis, None))
        self.tf = jax.device_put(jnp.asarray(tf), ax_spec)
        self.doc_len = jax.device_put(jnp.asarray(doc_len),
                                      NamedSharding(mesh, P(axis)))
        self.idf = jnp.asarray(idf)

    def topk(self, query_tf: np.ndarray, k: int = 10):
        """query_tf: (Q, V) query term counts -> (global ids, scores)."""
        qv = jnp.asarray(query_tf) * self.idf[None, :]
        with self.mesh:
            s, i = distributed_bm25_topk(self.mesh, self.tf, self.doc_len,
                                         qv, k=k, axis=self.axis)
        return np.asarray(i), np.asarray(s)


class DistributedDenseIndex:
    """Sharded dense retrieval: doc embeddings on the mesh's data axis.

    ``topk`` takes pre-encoded (Q, E) query embeddings and returns
    ``(ids, scores)``, the package-wide scorer order (see
    :class:`DistributedBM25`).
    """

    def __init__(self, mesh: Mesh, emb: np.ndarray, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.emb = jax.device_put(jnp.asarray(emb),
                                  NamedSharding(mesh, P(axis, None)))

    def topk(self, query_emb: np.ndarray, k: int = 10):
        """query_emb: (Q, E) encoded queries -> (global ids, scores)."""
        with self.mesh:
            s, i = distributed_dense_topk(self.mesh, self.emb,
                                          jnp.asarray(query_emb), k=k,
                                          axis=self.axis)
        return np.asarray(i), np.asarray(s)
