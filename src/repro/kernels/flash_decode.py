"""Flash-decode Pallas kernels: single-query attention over a slotted or
paged KV cache with per-slot length masking.

This is the decode-side companion of ``flash_attention.py``.  Both
kernels reduce a slot's kv positions block by block with a split-KV
online softmax: the running max, denominator and accumulator live in
float32 VMEM scratch, so the full (1, L) score row is never
materialized.  Per-slot lengths ride as a scalar-prefetch (SMEM)
operand; positions ``>= length[slot]`` are masked, and because
positions 0..length-1 are always populated (length >= 1) the first kv
block holds an unmasked entry and the softmax never sees an all-masked
state.

Dense (``flash_decode_pallas``): the grid is (slots, kv_heads,
kv_blocks) with the kv axis innermost.  q is viewed as
``(B, Hkv, G, D)`` and one program takes the ``(G, D)`` tile of the
``G = H // Hkv`` query heads that share a kv head, so each kv tile is
read once per kv head.  The k/v index maps clamp the kv block to the
slot's last valid one, so blocks past a slot's length are neither
fetched (the pipeline skips a repeated block index) nor computed.

Paged (``paged_flash_decode_pallas``): the page pools stay in HBM in
the executor's ``(num_pages, page_size, Hkv, D)`` layout, and the
kernel gathers them with its own DMAs, a block of ``ppb`` pages of one
slot per grid step, every kv head at once (see its docstring).

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


def _last_block(ki, length, block_kv: int):
    """Grid step ``ki`` clamped to the slot's last valid kv block."""
    return jnp.minimum(ki, (length - 1) // block_kv)


#: K plus V bytes one grid step of the paged kernel gathers.  A block
#: this size spreads the fixed cost of a grid step and of the block's
#: math over 1.3 us of HBM traffic at 819 GB/s, and its two buffers and
#: float32 temporaries stay well inside the scoped VMEM limit.  On a v5e
#: chip at the longform cell's shapes 512 KiB read 1.08 ms a call, 1 MiB
#: 0.85 ms and 2 MiB 0.83 ms.
_BLOCK_BYTES = 1024 * 1024


def pages_per_block(page_size: int, n_kv_heads: int, kv_width: int,
                    itemsize: int, max_blocks: int) -> int:
    """Pages the paged kernel gathers per grid step, from shapes alone.

    ``kv_width`` is the K plus V head width (D + Dv).  As many pages as
    fit :data:`_BLOCK_BYTES` of K plus V, at least one and at most the
    table's ``max_blocks``: 16 at Qwen1.5-32B's widths (page 16, 8 kv
    heads of 128, bf16: 64 KiB of K plus V a page).
    """
    page = page_size * n_kv_heads * kv_width * itemsize
    return max(1, min(max_blocks, _BLOCK_BYTES // page))


def _paged_flash_decode_kernel(len_ref, tab_ref, q_ref, k_hbm, v_hbm,
                               o_ref, k_buf, v_buf, sems, buf_ref, m_scr,
                               l_scr, acc_scr, *, scale: float, ps: int,
                               ppb: int, mb: int, n_kv: int):
    b, j = pl.program_id(0), pl.program_id(1)
    rows = ps * n_kv                  # rows of one page, kv head minor
    block = ppb * ps                  # positions of one block
    length = len_ref[b]

    def copies(s, jj, buf):
        """(live, (K copy, V copy)) for each page of block ``jj`` of
        slot ``s`` into buffer ``buf``; a page past the slot's length is
        not live: it is neither copied nor waited for."""
        n_live = (len_ref[s] + ps - 1) // ps - jj * ppb
        for i in range(ppb):
            page = tab_ref[s * mb + jnp.minimum(jj * ppb + i, mb - 1)]
            dst = pl.ds(i * rows, rows)
            yield i < n_live, (
                pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, dst],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, dst],
                                      sems.at[1, buf]))

    def start(s, jj, buf):
        for live, (ck, cv) in copies(s, jj, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(s, jj, buf):
        for live, (ck, cv) in copies(s, jj, buf):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when((b == 0) & (j == 0))
    def _first():
        # rows of a block past its live pages are never copied: zeroed
        # once, they hold zeros or earlier pool rows, never VMEM garbage
        # that could make a NaN of p * v where p is 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        buf_ref[0] = 0
        start(b, j, 0)

    @pl.when(j * block < length)
    def _step():
        cur = buf_ref[0]
        last = (j + 1) * block >= length
        nb = jnp.where(last, b + 1, b)

        @pl.when(nb < pl.num_programs(0))
        def _prefetch():
            start(nb, jnp.where(last, 0, j + 1), 1 - cur)
            buf_ref[0] = 1 - cur

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        wait(b, j, cur)
        q = q_ref[0].astype(jnp.float32)               # (H, d)
        k = k_buf[cur].astype(jnp.float32)             # (block * n_kv, d)
        v = v_buf[cur].astype(jnp.float32)             # (block * n_kv, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (H, block*n_kv)
        # row r of the block holds position r // n_kv of kv head
        # r % n_kv; query head i reads kv head i // G
        n_q = q.shape[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, block * n_kv), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (n_q, 1), 0)
        own = col % n_kv == head // (n_q // n_kv)
        s = jnp.where(own & (j * block + col // n_kv < length), s, NEG_INF)
        m_prev = m_scr[...]                            # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

        @pl.when(last)
        def _finish():
            o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                        ).astype(o_ref.dtype)


def paged_flash_decode_pallas(q, k_pages, v_pages, table, lengths, *,
                              interpret: bool = False):
    """Split-KV decode attention through a per-slot block table.

    q: (B, H, D); k/v_pages: (num_pages, page_size, Hkv, D[v]) -- the
    executor's page pools as they lie; table: (B, max_blocks) int32 page
    ids in ``[0, num_pages)`` (entries past the slot's length are never
    read); lengths: (B,) valid kv length (>= 1).  Returns (B, H, Dv).

    Layout.  The pools stay in HBM (``memory_space=pl.ANY``).  A page
    is viewed as ``(page_size * Hkv, D)`` rows, position-major with the
    kv head minor: the same bytes as the pool's tiled layout, so the
    reshape is a bitcast and no pool is copied or transposed.

    Block.  The grid is ``(B, ceil(max_blocks / ppb))``, the block axis
    innermost and both axes sequential.  One step covers ``ppb`` pages
    of one slot: the kernel copies each live page's contiguous slab of
    K and of V with ``make_async_copy`` into a VMEM buffer of
    ``(ppb * page_size * Hkv, D)`` rows.  Pages past the slot's length
    are neither copied nor computed, and a step whose block lies past
    the length does nothing.

    Double-buffering.  Two buffers per pool alternate.  A live step
    first starts the copies of the next live block (this slot's next
    block, else the next slot's first) into the other buffer, then
    waits for its own and computes, so the gather overlaps the math.
    The first step of the grid starts the first block's copies.

    ``ppb`` comes from shapes alone (:func:`pages_per_block`): as many
    pages as fit 1 MiB of K plus V, clamped to ``[1, max_blocks]`` --
    16 at Qwen1.5-32B's widths (a block of 256 positions, 2048 rows).

    Heads.  All H query rows meet the whole block in one
    ``(H, D) x (D, rows)`` product, and a score counts only where the
    row's kv head (``row % Hkv``) is the query head's own
    (``i // G``) and its position is below the length; every other
    score is ``-inf`` before the softmax, so it weighs exactly 0 in
    ``p @ v``.  The MXU loads the block's K and V tiles once either
    way; streaming H rows through them in place of G per head costs
    little.  On a v5e chip at the longform cell's shapes (64 slots of
    2048-2304 positions, bf16, 512 KiB blocks) this read 1.11 ms a call
    against 2.14 ms for per-head strided sublane reads of rows
    ``h::Hkv``; per-head DMA destinations do not compile, since a kv
    head is one row of the pool's (8, 128) tile.  As in the dense
    kernel, q, K and V enter the products as float32, and scores,
    running max, denominator and accumulator are float32.
    """
    B, H, D = q.shape
    NP, ps, Hkv = k_pages.shape[:3]
    Dv = v_pages.shape[3]
    MB = table.shape[1]
    ppb = pages_per_block(ps, Hkv, D + Dv, k_pages.dtype.itemsize, MB)

    lens = lengths.astype(jnp.int32)
    table = table.reshape(-1).astype(jnp.int32)
    # a page as (page_size * Hkv, D) rows: the same bytes (a bitcast)
    k_pages = k_pages.reshape(NP, ps * Hkv, D)
    v_pages = v_pages.reshape(NP, ps * Hkv, Dv)

    out = pl.pallas_call(
        functools.partial(_paged_flash_decode_kernel,
                          scale=1.0 / (D ** 0.5), ps=ps, ppb=ppb, mb=MB,
                          n_kv=Hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, pl.cdiv(MB, ppb)),
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, j, lens, tab: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, Dv),
                                   lambda b, j, lens, tab: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppb * ps * Hkv, D), k_pages.dtype),
                pltpu.VMEM((2, ppb * ps * Hkv, Dv), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),     # (K | V, buffer)
                pltpu.SMEM((1,), jnp.int32),         # buffer of this block
                pltpu.VMEM((H, 1), jnp.float32),     # running max
                pltpu.VMEM((H, 1), jnp.float32),     # running denominator
                pltpu.VMEM((H, Dv), jnp.float32),    # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lens, table, q, k_pages, v_pages)
    return out


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
