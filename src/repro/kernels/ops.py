"""Public jit'd wrappers for the Pallas kernels.

On TPU the kernels compile to Mosaic; on CPU they execute in interpret
mode (the kernel body runs as traced jnp on host).  Any other platform
is refused rather than silently interpreted.  Block sizes default to
MXU-aligned tiles and shrink to fit small inputs.

The decode kernels also run under the sharded executor's mesh: Mosaic
kernels cannot be partitioned automatically, so when traced under a
mesh context they are wrapped in ``shard_map`` — slots on the batch
axes, kv heads on ``model``.  Attention is independent per slot and
per kv head, so each device runs the kernel on its own shard.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.bm25 import bm25_pallas
from repro.kernels.dense_topk import _dense_topk_padded
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import (flash_decode_pallas,
                                        paged_flash_decode_pallas)
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.sharding import batch_entry


def _interpret() -> bool:
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and run interpreted on CPU; "
        f"platform {platform!r} is neither")


def _decode_shards(n_slots: int, n_kv_heads: int, n_pages: int = 0):
    """``(mesh, slot_axes, head_axis)`` for running a decode kernel per
    shard, or None when not traced under a mesh.  Slots shard like the
    slot cache (and only when the page pool shards the same way, so a
    slot's pages are local); kv heads shard on ``model`` when it
    divides them.  An axis that does not apply is None (replicated)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    slot, _ = batch_entry(n_slots, mesh)
    if n_pages and batch_entry(n_pages, mesh)[0] != slot:
        slot = None
    mp = dict(mesh.shape).get("model", 1)
    head = "model" if mp > 1 and n_kv_heads % mp == 0 else None
    return mesh, slot, head


@partial(jax.jit, static_argnames=("k1", "b"))
def bm25_scores(query_tf, tf, doc_len, idf, *, k1: float = 1.2,
                b: float = 0.75):
    """BM25 scores: (Q, V) query term-counts -> (Q, D).

    Thin host-side prep + the blocked Pallas contraction.
    """
    avg = doc_len.mean() + 1e-6
    norm = (k1 * (1 - b + b * doc_len / avg))[:, None].astype(jnp.float32)
    wq = (query_tf * idf[None, :]).astype(jnp.float32)
    Q, V = wq.shape
    D = tf.shape[0]
    bq = 8 if Q % 8 == 0 else 1
    bd = 128 if D % 128 == 0 else (64 if D % 64 == 0 else D)
    bv = 512 if V % 512 == 0 else V
    return bm25_pallas(wq, tf.astype(jnp.float32), norm, k1=k1,
                       block_q=bq, block_d=bd, block_v=bv,
                       interpret=_interpret())


@partial(jax.jit, static_argnames=("k", "block_q", "block_d"))
def dense_topk(q, docs, *, k: int = 10, block_q: int = 8,
               block_d: int = 128):
    """Fused dense retrieval: (Q, E) queries × (D, E) docs -> top-k.

    Returns (scores (Q, k) float32 descending, doc ids (Q, k) int32).
    Both axes pad to block multiples — zero query rows just produce
    discarded output rows, and the kernel masks the padded doc tail to
    -inf — so any (Q, D) tiles with full-width blocks; the full (Q, D)
    score matrix is never materialized.
    """
    Q, E = q.shape
    D = docs.shape[0]
    # align edge cases with the numpy oracle (DenseIndex.topk): empty
    # corpus / non-positive k return empty candidate rows, and k clamps
    # to the corpus size, instead of tripping kernel asserts
    if k <= 0 or D == 0:
        return (jnp.zeros((Q, 0), jnp.float32),
                jnp.zeros((Q, 0), jnp.int32))
    k = min(k, D)
    bd = min(block_d, D)
    pad_d = -D % bd
    if pad_d:
        docs = jnp.pad(docs, ((0, pad_d), (0, 0)))
    pad_q = -Q % block_q
    if pad_q:
        q = jnp.pad(q, ((0, pad_q), (0, 0)))
    s, i = _dense_topk_padded(q.astype(jnp.float32),
                              docs.astype(jnp.float32), k=k, n_docs=D,
                              block_q=block_q, block_d=bd,
                              interpret=_interpret())
    return (s[:Q], i[:Q]) if pad_q else (s, i)


@partial(jax.jit, static_argnames=("causal", "block_q", "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128):
    """GQA flash attention: q (B, Sq, H, D), k/v (B, Skv, Hkv, D[v])."""
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // Hkv
    kx = jnp.repeat(k, G, axis=2) if G > 1 else k
    vx = jnp.repeat(v, G, axis=2) if G > 1 else v
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = kx.transpose(0, 2, 1, 3).reshape(B * H, Skv, D)
    vf = vx.transpose(0, 2, 1, 3).reshape(B * H, Skv, Dv)
    out = flash_attention_pallas(qf, kf, vf, causal=causal,
                                 block_q=block_q, block_kv=block_kv,
                                 interpret=_interpret())
    return out.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_kv",))
def flash_decode(q, k, v, lengths, *, block_kv: int = 128):
    """Single-query GQA attention over a slotted KV cache.

    q: (B, H, D) — one query per slot; k/v: (B, L, Hkv, D[v]) — the
    full-length slot cache; lengths: (B,) valid kv length per slot
    (>= 1).  Returns (B, H, Dv).  The kernel groups the query heads of
    each kv head in one tile, so the grouped cache is only transposed
    to kv-head-major — never expanded; a cache length that block_kv
    does not divide is padded to full blocks and masked.
    """
    L = k.shape[1]
    bk = min(block_kv, L)
    pad = -L % bk

    def local(q, k, v, lengths):
        kf = k.transpose(0, 2, 1, 3)              # (B, Hkv, L, D)
        vf = v.transpose(0, 2, 1, 3)
        if pad:
            # keep full-width kv blocks for any cache length; the padded
            # tail is masked by the kernel's length check
            kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return flash_decode_pallas(q, kf, vf, jnp.maximum(lengths, 1),
                                   block_kv=bk, interpret=_interpret())

    shards = _decode_shards(q.shape[0], k.shape[2])
    if shards is None:
        return local(q, k, v, lengths)
    mesh, s, h = shards
    kv = P(s, None, h, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(s, h, None), kv, kv, P(s)),
                         out_specs=P(s, h, None), check_vma=False)(
        q, k, v, lengths)


@jax.jit
def paged_flash_decode(q, k_pages, v_pages, table, lengths):
    """Single-query GQA attention through a paged KV cache.

    q: (B, H, D) — one query per slot; k/v_pages: (num_pages,
    page_size, Hkv, D[v]) — the executor's global page pools; table:
    (B, max_blocks) int32 page ids per slot; lengths: (B,) valid kv
    length (>= 1).  Returns (B, H, Dv).  The pools go to the kernel as
    they lie, never transposed or gathered: it copies each page's
    contiguous ``(page_size, Hkv, D)`` slab with its own DMAs, a block
    of ``ppb`` pages per grid step into double-buffered VMEM, the next
    block's copies in flight while this one is computed; ``ppb`` comes
    from the shapes (about 1 MiB of K plus V: 16 pages at Qwen1.5-32B's
    widths; ``flash_decode.pages_per_block``).  Table entries clamp
    into range, and the kernel never reads pages past a slot's length.
    """
    shards = _decode_shards(q.shape[0], k_pages.shape[2], k_pages.shape[0])

    def local(q, k_pages, v_pages, table, lengths):
        NP = k_pages.shape[0]
        tab = table.astype(jnp.int32)
        if shards is not None and shards[1] is not None:
            # this shard holds pool pages [i * NP, (i + 1) * NP): the
            # allocator keeps a slot's pages on the shard owning the slot
            tab = tab - jax.lax.axis_index(shards[1]) * NP
        return paged_flash_decode_pallas(q, k_pages, v_pages,
                                         jnp.clip(tab, 0, NP - 1),
                                         jnp.maximum(lengths, 1),
                                         interpret=_interpret())

    if shards is None:
        return local(q, k_pages, v_pages, table, lengths)
    mesh, s, h = shards
    pool = P(s, None, h, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(s, h, None), pool, pool, P(s, None),
                                   P(s)),
                         out_specs=P(s, h, None), check_vma=False)(
        q, k_pages, v_pages, table, lengths)


@partial(jax.jit, static_argnames=("chunk",))
def ssd_chunk_scan(x, B_, C_, dt, A_log, *, chunk: int = 128):
    """Mamba2 SSD: x (B, S, H, hd), B_/C_ (B, S, G, N), dt (B, S, H).

    Returns y (B, S, H, hd).  Groups are expanded to heads and heads
    folded into the grid batch dim before the kernel.
    """
    Bsz, S, H, hd = x.shape
    N = B_.shape[-1]
    G = B_.shape[2]
    a = -jnp.exp(A_log.astype(jnp.float32))
    da = (dt.astype(jnp.float32) * a).transpose(0, 2, 1)        # (B, H, S)
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    xdt = xdt.transpose(0, 2, 1, 3).reshape(Bsz * H, S, hd)
    rep = H // G
    Bx = jnp.repeat(B_, rep, axis=2) if rep > 1 else B_
    Cx = jnp.repeat(C_, rep, axis=2) if rep > 1 else C_
    Bf = Bx.transpose(0, 2, 1, 3).reshape(Bsz * H, S, N).astype(jnp.float32)
    Cf = Cx.transpose(0, 2, 1, 3).reshape(Bsz * H, S, N).astype(jnp.float32)
    daf = da.reshape(Bsz * H, S)
    y = ssd_scan_pallas(xdt, Bf, Cf, daf, chunk=chunk,
                        interpret=_interpret())
    return y.reshape(Bsz, H, S, hd).transpose(0, 2, 1, 3).astype(x.dtype)
