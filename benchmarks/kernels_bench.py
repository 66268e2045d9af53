"""Kernel micro-benchmarks: Pallas (interpret on CPU) vs jnp oracle.

On CPU the numbers measure the reference path and interpret overhead —
the structural artifact (block shapes, VMEM footprint per tile) is the
TPU-relevant output.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_artifact
from repro.kernels import ref
from repro.kernels.ops import bm25_scores


def _time(fn, *args, iters=5):
    # one warmup invocation; jax.block_until_ready handles pytrees, so
    # no isinstance probe (which used to re-invoke the closure and skew
    # every reported number)
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def main() -> dict:
    out = {}
    # BM25 scoring at the paper testbed scale
    Q, D, V = 8, 640, 4096
    key = jax.random.PRNGKey(0)
    qtf = (jax.random.uniform(key, (Q, V)) < 0.003).astype(jnp.float32)
    tf = jnp.round(jax.random.uniform(key, (D, V)) * 3)
    dl = tf.sum(1)
    idf = jax.random.uniform(key, (V,)) + 0.1

    t_pallas = _time(lambda: bm25_scores(qtf, tf, dl, idf))
    k1, b = 1.2, 0.75
    norm = (k1 * (1 - b + b * dl / (dl.mean() + 1e-6)))[:, None]
    ref_fn = jax.jit(lambda: ref.bm25_ref(qtf * idf[None], tf, norm))
    t_ref = _time(ref_fn)
    out["bm25"] = {"us_pallas_interp": round(t_pallas, 1),
                   "us_jnp_ref": round(t_ref, 1),
                   "shape": f"Q{Q}xD{D}xV{V}",
                   "vmem_tile_bytes": (8 * 512 + 128 * 512 + 8 * 128) * 4}

    # flash attention tile accounting (structural)
    for (bq, bkv, d) in [(128, 128, 128), (256, 512, 128)]:
        vmem = (bq * d + 2 * bkv * d + bq * d + bq * 2) * 4
        out[f"flash_tile_{bq}x{bkv}"] = {
            "vmem_bytes_per_tile": vmem,
            "fits_16MB_vmem": vmem < 16 * 2**20}

    # flash decode: kernel (interpret) vs dense oracle at slot-cache shape
    from repro.kernels import flash_decode
    S, L, H, Hkv, D = 8, 512, 4, 4, 64
    q = jax.random.normal(key, (S, H, D))
    kc = jax.random.normal(key, (S, L, Hkv, D))
    vc = jax.random.normal(key, (S, L, Hkv, D))
    lens = (jnp.arange(S) * 61 % L + 1).astype(jnp.int32)
    t_fd = _time(lambda: flash_decode(q, kc, vc, lens))
    lens_f = jnp.repeat(lens, H)
    fd_ref = jax.jit(lambda: ref.flash_decode_ref(
        q.reshape(S * H, D),
        kc.transpose(0, 2, 1, 3).reshape(S * H, L, D),
        vc.transpose(0, 2, 1, 3).reshape(S * H, L, D), lens_f))
    t_fd_ref = _time(fd_ref)
    out["flash_decode"] = {
        "us_pallas_interp": round(t_fd, 1),
        "us_jnp_ref": round(t_fd_ref, 1),
        "shape": f"S{S}xL{L}xH{H}xD{D}",
        "vmem_tile_bytes": (D + 2 * 128 * D + D + 2) * 4}

    # paged flash decode: the same KV content laid out as a page pool +
    # block table, at several page sizes, vs the dense kernel above
    from repro.kernels import paged_flash_decode
    from repro.kernels.flash_decode import pages_per_block
    dense_out = flash_decode(q, kc, vc, lens)
    for ps in (16, 32, 64):
        MB = L // ps
        NPg = S * MB
        kp = kc.reshape(NPg, ps, Hkv, D)
        vp = vc.reshape(NPg, ps, Hkv, D)
        table = jnp.arange(NPg, dtype=jnp.int32).reshape(S, MB)
        t_paged = _time(lambda: paged_flash_decode(q, kp, vp, table, lens))
        paged_out = paged_flash_decode(q, kp, vp, table, lens)
        out[f"paged_flash_decode_ps{ps}"] = {
            "us_pallas_interp": round(t_paged, 1),
            "us_dense_pallas_interp": round(t_fd, 1),
            "page_size": ps, "num_pages": NPg,
            "shape": f"S{S}xL{L}xH{H}xD{D}",
            "matches_dense": bool(jnp.allclose(dense_out, paged_out,
                                               rtol=1e-5, atol=1e-5)),
            # VMEM: two buffers of a block of ppb pages of K and of V +
            # accumulator + (m, l) running stats
            "vmem_tile_bytes": (2 * 2 * pages_per_block(ps, Hkv, 2 * D, 4,
                                                        MB) * ps * Hkv * D
                                + H * D + 2 * H) * 4}

    save_artifact("kernels_bench", out)
    for k, v in out.items():
        print(k, v)
    return {"bm25_us": out["bm25"]["us_pallas_interp"]}


if __name__ == "__main__":
    print(main())
