"""Flash-decode Pallas kernels: single-query attention over a slotted or
paged KV cache with per-slot length masking.

This is the decode-side companion of ``flash_attention.py``.  The grid
is (slots, kv_heads, kv_blocks) with the kv axis innermost; the running
max / denominator / accumulator in VMEM scratch implement a split-KV
online-softmax reduction — kv blocks are reduced sequentially on TPU
without ever materializing the full (1, L) score row in one tile.

GQA is handled by the q/out block layout: q is viewed as
``(B, Hkv, G, D)`` and one program takes the ``(G, D)`` tile of all
``G = H // Hkv`` query heads that share a kv head, so each kv tile is
read once per kv head (not G times) and every block's last two dims
are either the full array dims or (8, 128)-aligned, as the TPU
compiler requires.

Per-slot lengths ride as a scalar-prefetch (SMEM) operand, next to the
flattened block table in the paged kernel.  The k/v index maps clamp
the kv block to the slot's last valid one, so blocks past a slot's
length are neither fetched (the pipeline skips a repeated block
index) nor computed (``pl.when``).  Positions ``>= length[slot]`` are
masked inside the last block.  Because positions 0..length-1 are
always populated (length >= 1), the first kv block holds at least one
unmasked entry and the online softmax never sees an all-masked state.

The tests run the kernels in interpret mode on CPU against the dense
oracles in ``ref.py``; ``tests/test_tpu_compile.py`` compiles them for
a described TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                         l_scr, acc_scr, *, scale: float, block_kv: int,
                         n_kv: int):
    ki = pl.program_id(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_kv < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)       # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)       # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (G, bk)
        kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kv_pos < length, s, NEG_INF)
        m_prev = m_scr[...]                        # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def _paged_flash_decode_kernel(len_ref, tab_ref, *refs, **kw):
    # tab_ref is the scalar-prefetched block table — already consumed by
    # the k/v index maps (they gather the page for grid step ki), so the
    # body is exactly the dense online-softmax reduction over one page.
    del tab_ref
    _flash_decode_kernel(len_ref, *refs, **kw)


def _last_block(ki, length, block_kv: int):
    """Grid step ``ki`` clamped to the slot's last valid kv block."""
    return jnp.minimum(ki, (length - 1) // block_kv)


def paged_flash_decode_pallas(q, k_pages, v_pages, table, lengths, *,
                              interpret: bool = False):
    """Split-KV decode attention through a per-slot block table.

    q: (B, H, D); k/v_pages: (num_pages, Hkv, page_size, D[v]) —
    kv-head-major page pools; table: (B, max_blocks) int32 page ids
    (entries past the slot's length are never read); lengths: (B,)
    valid kv length (>= 1).

    The grid is (B, Hkv, max_blocks) with the page axis innermost; the
    lengths and the flattened table ride as scalar-prefetch operands so
    the k/v index maps resolve ``table[b, ki]`` *before* the tile fetch
    — the kernel gathers pages straight out of the pool, never
    materializing a contiguous (B, L) cache row.  Masking and the
    online softmax are identical to the dense kernel.  Returns
    (B, H, Dv).
    """
    B, H, D = q.shape
    Hkv, ps = k_pages.shape[1], k_pages.shape[2]
    Dv = v_pages.shape[3]
    G = H // Hkv
    MB = table.shape[1]

    lens = lengths.astype(jnp.int32)
    table = table.reshape(-1).astype(jnp.int32)
    q = q.reshape(B, Hkv, G, D)

    def kv_map(b, h, ki, lens, tab):
        return (tab[b * MB + _last_block(ki, lens[b], ps)], h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_paged_flash_decode_kernel,
                          scale=1.0 / (D ** 0.5), block_kv=ps, n_kv=MB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, MB),
            in_specs=[
                pl.BlockSpec((1, 1, G, D),
                             lambda b, h, ki, lens, tab: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, ps, D), kv_map),
                pl.BlockSpec((1, 1, ps, Dv), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dv),
                                   lambda b, h, ki, lens, tab: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),      # running max
                pltpu.VMEM((G, 1), jnp.float32),      # running denom
                pltpu.VMEM((G, Dv), jnp.float32),     # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(lens, table, q, k_pages, v_pages)
    return out.reshape(B, H, Dv)


def flash_decode_pallas(q, k, v, lengths, *, block_kv: int = 128,
                        interpret: bool = False):
    """q: (B, H, D); k/v: (B, Hkv, L, D[v]) — kv-head-major so the G
    query heads of kv head ``h`` read it in place; lengths: (B,) int32
    valid kv length per slot (must be >= 1).  Returns (B, H, Dv)."""
    B, H, D = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    G = H // Hkv
    block_kv = min(block_kv, L)
    assert L % block_kv == 0, (L, block_kv)
    n_kv = L // block_kv

    lens = lengths.astype(jnp.int32)
    q = q.reshape(B, Hkv, G, D)

    def kv_map(b, h, ki, lens):
        return (b, h, _last_block(ki, lens[b], block_kv), 0)

    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, scale=1.0 / (D ** 0.5),
                          block_kv=block_kv, n_kv=n_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, G, D),
                             lambda b, h, ki, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_kv, D), kv_map),
                pl.BlockSpec((1, 1, block_kv, Dv), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dv),
                                   lambda b, h, ki, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),      # running max
                pltpu.VMEM((G, 1), jnp.float32),      # running denom
                pltpu.VMEM((G, Dv), jnp.float32),     # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(lens, q, k, v)
    return out.reshape(B, H, Dv)
