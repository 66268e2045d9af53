"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler is asked to compile for a
described ``v5e:2x2`` topology, which refuses what interpret mode
cannot catch (block layouts off the (8, 128) tiling, VMEM overuse).
Every kernel is called directly with ``interpret=False`` — never
through ``repro.kernels.ops``, which runs them interpreted on CPU —
and its compiled program must hold a ``tpu_custom_call``.

Shapes are the served widths: Qwen1.5-32B decode attention (40 query
heads, 8 KV heads, head_dim 128) over 16 slots of 4096 positions (and
the paged kernel at the longform cell's exact pool), and retrieval over
4096 documents.  The topology is described inside a
module fixture, never at import: only the worker that runs this file
loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bm25 import bm25_pallas
from repro.kernels.dense_topk import _dense_topk_padded
from repro.kernels.flash_decode import (flash_decode_pallas,
                                        paged_flash_decode_pallas)

SLOTS, HEADS, KV_HEADS, HEAD_DIM, MAX_LEN = 16, 40, 8, 128, 4096
DOCS, QUERIES, VOCAB_HASH, EMBED = 4096, 8, 4096, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_decode_compiles(one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kv = spec((SLOTS, KV_HEADS, MAX_LEN, HEAD_DIM), jnp.bfloat16)
    hlo = _compiled_text(
        lambda q, k, v, n: flash_decode_pallas(q, k, v, n, interpret=False),
        spec((SLOTS, HEADS, HEAD_DIM), jnp.bfloat16), kv, kv,
        spec((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("page_size", [16, 32, 64])
def test_paged_flash_decode_compiles(one_chip, page_size):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    blocks = MAX_LEN // page_size + 1
    # the executor's pool layout: (pages, page_size, kv heads, head_dim)
    pool = spec((SLOTS * blocks, page_size, KV_HEADS, HEAD_DIM),
                jnp.bfloat16)
    hlo = _compiled_text(
        lambda q, k, v, t, n: paged_flash_decode_pallas(q, k, v, t, n,
                                                        interpret=False),
        spec((SLOTS, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
        spec((SLOTS, blocks), jnp.int32), spec((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo


def _pool_ops(hlo: str, pool_dims: str):
    """Instructions producing a pool-sized array other than the
    parameters and free bitcasts (a copy, transpose or fusion would
    move the whole pool on every call)."""
    found = []
    for line in hlo.splitlines():
        _, eq, rhs = line.partition(" = ")
        if not eq or f"[{pool_dims}" not in rhs.split(" ", 1)[0]:
            continue
        op = rhs.split(" ", 1)[1].split("(", 1)[0] if " " in rhs else ""
        if op not in ("parameter", "bitcast"):
            found.append(line.strip()[:160])
    return found


def test_paged_flash_decode_longform_shapes_read_the_pool_in_place(one_chip):
    """The served longform shapes: 64 slots of 145 blocks of 16
    positions, 40 query / 8 kv heads of 128, bf16.  The wrapper's local
    logic (table clipped into the pool, lengths floored at 1) compiles
    to one Mosaic kernel that reads the pools where they lie: nothing
    pool-sized is copied or transposed."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    slots, blocks, page = 64, 145, 16
    pages = slots * blocks
    pool = spec((pages, page, KV_HEADS, HEAD_DIM), jnp.bfloat16)

    def local(q, k, v, table, lengths):
        return paged_flash_decode_pallas(
            q, k, v, jnp.clip(table, 0, pages - 1),
            jnp.maximum(lengths, 1), interpret=False)

    hlo = _compiled_text(local, spec((slots, HEADS, HEAD_DIM), jnp.bfloat16),
                         pool, pool, spec((slots, blocks), jnp.int32),
                         spec((slots,), jnp.int32))
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert _pool_ops(hlo, f"{pages},") == []


def test_dense_topk_compiles(one_chip):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    hlo = _compiled_text(
        lambda q, d: _dense_topk_padded(q, d, k=10, n_docs=DOCS, block_q=8,
                                        block_d=128, interpret=False),
        spec((QUERIES, EMBED)), spec((DOCS, EMBED)))
    assert "tpu_custom_call" in hlo


def test_bm25_compiles(one_chip):
    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    hlo = _compiled_text(
        lambda wq, tf, norm: bm25_pallas(wq, tf, norm, interpret=False),
        spec((QUERIES, VOCAB_HASH)), spec((DOCS, VOCAB_HASH)),
        spec((DOCS, 1)))
    assert "tpu_custom_call" in hlo
