"""RPL004 VMEM estimator vs hand-computed block-shape x dtype math.

Every expectation below is derived by hand from the BlockSpec shapes in
``src/repro/kernels/*.py``:

    total = (sum(in-block bytes) + sum(out-block bytes)) * 2 buffers
            + scratch bytes

so a change to any kernel's tiling shows up here as a concrete byte
delta, not just a pass/fail.
"""
from __future__ import annotations

import ast
from pathlib import Path

import pytest

from repro.analysis.lintconfig import (DEFAULT_CONFIG,
                                       DEFAULT_DIM_BINDINGS,
                                       VMEM_BUDGET_BYTES)
from repro.analysis.rules.pallas_vmem import (UnboundDim, estimate_site,
                                              extract_sites)
from repro.analysis.walker import import_table, run_lint
from repro.kernels.flash_decode import pages_per_block

KERNELS = Path(__file__).resolve().parents[1] / "src" / "repro" / "kernels"


def sites_of(fname: str):
    tree = ast.parse((KERNELS / fname).read_text())
    return extract_sites(tree, import_table(tree))


def site_by_kernel(fname: str, kernel: str):
    for s in sites_of(fname):
        if s.kernel == kernel:
            return s
    raise AssertionError(f"no pallas_call with kernel {kernel} in {fname}")


# -- flash_decode (dense): lengths ride scalar-prefetch (SMEM); blocks
#    (1,1,G,D) + 2x(1,1,block_kv,D|Dv); out (1,1,G,Dv); scratch
#    (G,1)+(G,1)+(G,Dv) f32.  G=5 is Qwen1.5-32B's 40/8 head grouping ----


def test_flash_decode_hand_math():
    site = site_by_kernel("flash_decode.py", "_flash_decode_kernel")
    b = {"G": 5, "D": 128, "Dv": 128, "block_kv": 128}
    est = estimate_site(site, bindings=b)
    in_elems = 5 * 128 + 128 * 128 + 128 * 128
    assert est.in_bytes == in_elems * 4 == 133632
    assert est.out_bytes == 5 * 128 * 4 == 2560
    assert est.scratch_bytes == (5 + 5 + 5 * 128) * 4 == 2600
    assert est.total_bytes == (133632 + 2560) * 2 + 2600 == 274984


def test_flash_decode_int8_kv():
    site = site_by_kernel("flash_decode.py", "_flash_decode_kernel")
    b = {"G": 5, "D": 128, "Dv": 128, "block_kv": 128}
    est = estimate_site(site, bindings=b,
                        operand_dtypes={"k": "int8", "v": "int8"})
    # q stays f32 (out_shape dtype is q.dtype), k/v blocks drop to 1 B
    assert est.in_bytes == 5 * 128 * 4 + 128 * 128 + 128 * 128
    assert est.out_bytes == 2560
    assert est.total_bytes == (35328 + 2560) * 2 + 2600 == 78376


# -- paged flash decode: PrefetchScalarGridSpec, lengths + table are
#    scalar-prefetch (SMEM); the page pools stay in HBM
#    (memory_space=pl.ANY) and count nothing: the kernel's own DMAs
#    gather a block of ppb pages into two double-buffered VMEM scratches
#    (2, ppb*ps*Hkv, D|Dv).  q block (1,H,D), out block (1,H,Dv);
#    scratch (H,1)+(H,1)+(H,Dv) f32; the DMA semaphores and the SMEM
#    buffer index hold no VMEM.  Served: H=40, Hkv=8, D=Dv=128, bf16,
#    ppb = 1 MiB // (ps*Hkv*(D+Dv)*2 B), so a block is 2048 rows of 128
#    whatever the page size ----------------------------------------------

SERVED = {"H": 40, "Hkv": 8, "D": 128, "Dv": 128}
BF16 = {"q": "bfloat16", "k_pages": "bfloat16", "v_pages": "bfloat16"}


def test_paged_flash_decode_skips_scalar_prefetch_operand():
    site = site_by_kernel("flash_decode.py", "_paged_flash_decode_kernel")
    assert site.num_scalar_prefetch == 2
    assert site.operands[:2] == ["lens", "table"]   # SMEM, not estimated
    assert site.operands[2:] == ["q", "k_pages", "v_pages"]
    est = estimate_site(site, bindings={**SERVED, "ps": 16, "ppb": 16},
                        operand_dtypes=BF16)
    # the two pools are memory_space=ANY: q's block is the only in-block
    assert est.in_bytes == 40 * 128 * 2 == 10240


@pytest.mark.parametrize("ps,ppb,expected_total", [
    # in = out = 40*128*2 = 10240; K = V = 2*(ppb*ps*8)*128*2 = 1048576;
    # scratch = 2*1048576 + (40+40)*4 + 40*128*4 = 2117952
    (16, 16, (10240 + 10240) * 2 + 2117952),  # 16*16*8 = 2048 rows
    (32, 8, (10240 + 10240) * 2 + 2117952),   # 8*32*8 = 2048 rows
    (64, 4, (10240 + 10240) * 2 + 2117952),   # 4*64*8 = 2048 rows
])
def test_paged_flash_decode_page_size_sweep(ps, ppb, expected_total):
    assert pages_per_block(ps, 8, 256, 2, 1 << 20) == ppb
    site = site_by_kernel("flash_decode.py", "_paged_flash_decode_kernel")
    est = estimate_site(site, bindings={**SERVED, "ps": ps, "ppb": ppb},
                        operand_dtypes=BF16)
    assert est.scratch_bytes == 2117952
    assert est.total_bytes == expected_total == 2158912


def test_paged_flash_decode_int8_kv_pages():
    # int8 pools: ppb = 1 MiB // (64*8*256*1) = 8, 8*64*8 = 4096 rows;
    # K = V = 2*4096*128*1 = 1048576 (the scratch takes the pools'
    # dtype); q stays f32: in = out = 40*128*4 = 20480
    assert pages_per_block(64, 8, 256, 1, 1 << 20) == 8
    site = site_by_kernel("flash_decode.py", "_paged_flash_decode_kernel")
    est = estimate_site(
        site, bindings={**SERVED, "ps": 64, "ppb": 8},
        operand_dtypes={"k_pages": "int8", "v_pages": "int8"})
    assert est.in_bytes == 40 * 128 * 4 == 20480
    assert est.scratch_bytes == 2 * 1048576 + (40 + 40) * 4 + 40 * 128 * 4
    assert est.total_bytes == (20480 + 20480) * 2 + 2117952 == 2199872


# -- dense_topk: in (block_q,E)+(block_d,E); out 2x(block_q,k) f32/i32;
#    scratch (block_q,k) f32 + (block_q,k) i32 ----------------------------


def test_dense_topk_hand_math():
    site = site_by_kernel("dense_topk.py", "_dense_topk_kernel")
    b = {"block_q": 8, "E": 64, "block_d": 128, "k": 16}
    est = estimate_site(site, bindings=b)
    assert est.in_bytes == (8 * 64 + 128 * 64) * 4 == 34816
    assert est.out_bytes == 2 * 8 * 16 * 4 == 1024
    assert est.scratch_bytes == 2 * 8 * 16 * 4 == 1024
    assert est.total_bytes == (34816 + 1024) * 2 + 1024 == 72704


def test_dense_topk_out_dtypes_resolved_per_output():
    # scores ShapeDtypeStruct is jnp.float32, ids jnp.int32 — both 4 B,
    # asserted via a bf16 corpus NOT changing the out bytes
    site = site_by_kernel("dense_topk.py", "_dense_topk_kernel")
    b = {"block_q": 8, "E": 64, "block_d": 128, "k": 16}
    est = estimate_site(site, bindings=b,
                        operand_dtypes={"q": "bfloat16",
                                        "docs": "bfloat16"})
    assert est.in_bytes == (8 * 64 + 128 * 64) * 2
    assert est.out_bytes == 1024                 # literal dtypes win


def test_unbound_dim_raises_with_symbol():
    site = site_by_kernel("dense_topk.py", "_dense_topk_kernel")
    with pytest.raises(UnboundDim) as exc:
        estimate_site(site, bindings={"block_q": 8, "E": 64})
    assert exc.value.symbol in ("block_d", "k")


# -- the whole kernel directory under the production-shape contract -------


def test_all_kernels_under_default_budget():
    res = run_lint([str(KERNELS)], config=DEFAULT_CONFIG)
    rpl004 = [f for f in res.findings if f.rule == "RPL004"]
    assert rpl004 == [], [f.message for f in rpl004]


def test_every_kernel_site_extracts_and_estimates():
    total_sites = 0
    for fname in sorted(p.name for p in KERNELS.glob("*.py")):
        for site in sites_of(fname):
            total_sites += 1
            est = estimate_site(site, bindings=DEFAULT_DIM_BINDINGS)
            assert 0 < est.total_bytes <= VMEM_BUDGET_BYTES, (
                fname, site.kernel, est.total_bytes)
    assert total_sites == 6      # the six shipped pallas_call sites


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
