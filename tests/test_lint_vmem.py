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
#    scalar-prefetch ----------------------------------------------------


def test_paged_flash_decode_skips_scalar_prefetch_operand():
    site = site_by_kernel("flash_decode.py", "_paged_flash_decode_kernel")
    assert site.num_scalar_prefetch == 2
    assert site.operands[:2] == ["lens", "table"]   # SMEM, not estimated
    assert site.operands[2:] == ["q", "k_pages", "v_pages"]


@pytest.mark.parametrize("ps,expected_total", [
    (16, (18944 + 2560) * 2 + 2600),   # in = (5*128+16*128*2)*4 = 18944
    (32, (35328 + 2560) * 2 + 2600),   # in = (5*128+32*128*2)*4 = 35328
    (64, (68096 + 2560) * 2 + 2600),   # in = (5*128+64*128*2)*4 = 68096
])
def test_paged_flash_decode_page_size_sweep(ps, expected_total):
    site = site_by_kernel("flash_decode.py", "_paged_flash_decode_kernel")
    est = estimate_site(site, bindings={"G": 5, "D": 128, "Dv": 128,
                                        "ps": ps})
    assert est.total_bytes == expected_total


def test_paged_flash_decode_int8_kv_pages():
    site = site_by_kernel("flash_decode.py", "_paged_flash_decode_kernel")
    est = estimate_site(
        site, bindings={"G": 5, "D": 128, "Dv": 128, "ps": 64},
        operand_dtypes={"k_pages": "int8", "v_pages": "int8"})
    in_bytes = 5 * 128 * 4 + 64 * 128 + 64 * 128
    assert est.in_bytes == in_bytes
    assert est.total_bytes == (in_bytes + 2560) * 2 + 2600


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
