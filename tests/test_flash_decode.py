"""Flash-decode kernel (interpret mode) vs the dense jnp oracle, and
the model decode path wired through it."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_decode, paged_flash_decode
from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode_pallas, pages_per_block


def _key(i):
    return jax.random.PRNGKey(i)


def _fold(q, k, v, lengths):
    """Expand GQA kv heads and fold (B, H) for the reference."""
    B, H, D = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    kf = (jnp.repeat(k, G, 2) if G > 1 else k) \
        .transpose(0, 2, 1, 3).reshape(B * H, L, D)
    vf = (jnp.repeat(v, G, 2) if G > 1 else v) \
        .transpose(0, 2, 1, 3).reshape(B * H, L, D)
    lf = jnp.broadcast_to(lengths[:, None], (B, H)).reshape(B * H)
    return q.reshape(B * H, D), kf, vf, lf


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,L,H,Hkv,Dh,block_kv", [
    (2, 128, 4, 4, 64, 64),
    (4, 96, 4, 2, 32, 32),     # GQA grouping, 3 kv blocks
    (3, 64, 8, 1, 128, 64),    # MQA
    (1, 128, 2, 2, 64, 128),   # single kv block
    (2, 100, 4, 2, 32, 64),    # L not a block multiple -> padded tail
])
def test_flash_decode_matches_ref(B, L, H, Hkv, Dh, block_kv, dtype):
    q = jax.random.normal(_key(0), (B, H, Dh), dtype)
    k = jax.random.normal(_key(1), (B, L, Hkv, Dh), dtype)
    v = jax.random.normal(_key(2), (B, L, Hkv, Dh), dtype)
    # ragged per-slot lengths including the 1 and full-L extremes
    lens = jnp.asarray(
        np.linspace(1, L, B).round().astype(np.int32))
    got = flash_decode(q, k, v, lens, block_kv=block_kv)
    qf, kf, vf, lf = _fold(q, k, v, lens)
    want = ref.flash_decode_ref(qf, kf, vf, lf).reshape(B, H, Dh)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_decode_masks_stale_tail():
    """Garbage beyond a slot's length must not change its output — the
    continuous engine's freed-slot / stale-tail invariant."""
    B, L, H, D = 2, 64, 2, 32
    q = jax.random.normal(_key(3), (B, H, D))
    k = jax.random.normal(_key(4), (B, L, H, D))
    v = jax.random.normal(_key(5), (B, L, H, D))
    lens = jnp.array([40, 64], jnp.int32)
    o1 = flash_decode(q, k, v, lens, block_kv=32)
    k2 = k.at[0, 40:].set(7.0)
    v2 = v.at[0, 40:].set(-3.0)
    o2 = flash_decode(q, k2, v2, lens, block_kv=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-6, atol=1e-6)


def test_flash_decode_split_kv_invariance():
    """Same result for any kv block split (online-softmax associativity)."""
    B, L, H, D = 2, 96, 2, 32
    q = jax.random.normal(_key(6), (B, H, D))
    k = jax.random.normal(_key(7), (B, H, L, D))   # kv-head-major
    v = jax.random.normal(_key(8), (B, H, L, D))
    lens = jnp.array([29, 96], jnp.int32)
    outs = [flash_decode_pallas(q, k, v, lens, block_kv=bk, interpret=True)
            for bk in (16, 32, 96)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-6, atol=1e-6)


def _paged_case(B, MB, ps, H, Hkv, Dh, seed=0):
    """Random page pools + a table of distinct pages per slot."""
    NP = B * MB + 3
    q = jax.random.normal(_key(seed), (B, H, Dh))
    kp = jax.random.normal(_key(seed + 1), (NP, ps, Hkv, Dh))
    vp = jax.random.normal(_key(seed + 2), (NP, ps, Hkv, Dh))
    perm = np.random.default_rng(seed).permutation(NP)[:B * MB]
    table = jnp.asarray(perm.reshape(B, MB).astype(np.int32))
    lens = jnp.asarray(np.linspace(1, MB * ps, B).round().astype(np.int32))
    return q, kp, vp, table, lens


def _ppb(ps, Hkv, Dh):
    """The kernel's pages per grid step for float32 pools, unclamped."""
    return pages_per_block(ps, Hkv, 2 * Dh, 4, 1 << 30)


# Cases with MB = 0 take MB = 2 * ppb + 1 from the shapes, so the table
# spans three blocks and is not a multiple of ppb.  Lengths:
#   spread - evenly spaced from 1 to MB * ps;
#   edges  - 1, ps, ppb * ps, ppb * ps + 1 and MB * ps - 1 (block and
#            page boundaries);
#   idle   - the engine's idle slots (length 0, table rows all out of
#            range) between live ones;
#   stale  - table entries past each slot's length point at pages of
#            other slots or out of range.
@pytest.mark.parametrize("ps", [8, 16, 32])
@pytest.mark.parametrize("B,MB,H,Hkv,Dh,lens", [
    (3, 4, 4, 2, 32, "spread"),     # GQA grouping
    (2, 6, 2, 2, 64, "spread"),
    (1, 2, 4, 1, 32, "spread"),     # MQA, tiny table
    (5, 0, 40, 8, 128, "edges"),    # G = 5, Qwen1.5-32B's grouping
    (5, 0, 32, 4, 128, "idle"),     # G = 8
    (4, 0, 8, 8, 128, "stale"),     # G = 1
])
def test_paged_flash_decode_matches_ref(B, MB, H, Hkv, Dh, lens, ps):
    ppb = _ppb(ps, Hkv, Dh)
    MB = MB or 2 * ppb + 1
    q, kp, vp, table, spread = _paged_case(B, MB, ps, H, Hkv, Dh)
    NP = kp.shape[0]
    live = np.ones(B, bool)
    if lens == "spread":
        lens = spread
    elif lens == "edges":
        lens = jnp.array([1, ps, ppb * ps, ppb * ps + 1, MB * ps - 1],
                         jnp.int32)
    elif lens == "idle":
        live = np.array([1, 0, 1, 0, 1], bool)
        lens = jnp.array([ppb * ps + 1, 0, MB * ps, 0, 3], jnp.int32)
        table = jnp.where(jnp.asarray(live)[:, None], table, NP + 7)
    else:
        lens = jnp.array([ps + 1, 2 * ppb * ps - 1, 1, MB * ps], jnp.int32)
        used = (np.asarray(lens)[:, None] + ps - 1) // ps
        past = np.arange(MB)[None, :] >= used
        other = np.roll(np.asarray(table), 1, axis=0)
        junk = np.where(np.arange(B)[:, None] % 2, other, NP + 100)
        table = jnp.asarray(np.where(past, junk, np.asarray(table)))
    got = paged_flash_decode(q, kp, vp, table, lens)
    want = ref.paged_flash_decode_ref(q, kp, vp, table, lens)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)


def test_paged_flash_decode_matches_dense_gather():
    """Gathering the table's pages into contiguous rows and running the
    dense kernel must agree with reading through the table in place."""
    B, MB, ps, H, Hkv, Dh = 2, 4, 16, 4, 2, 32
    q, kp, vp, table, lens = _paged_case(B, MB, ps, H, Hkv, Dh, seed=9)
    rows_k = kp[table].reshape(B, MB * ps, Hkv, Dh)
    rows_v = vp[table].reshape(B, MB * ps, Hkv, Dh)
    dense = flash_decode(q, rows_k, rows_v, lens, block_kv=ps)
    paged = paged_flash_decode(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)


def test_paged_flash_decode_ignores_unallocated_tail():
    """Table entries past a slot's length may point at any page (the
    engine zero-fills) — scribbling on those pages must not change the
    slot's output."""
    B, MB, ps, H, Hkv, Dh = 2, 4, 8, 2, 2, 32
    q, kp, vp, table, lens = _paged_case(B, MB, ps, H, Hkv, Dh, seed=4)
    lens = jnp.array([10, 32], jnp.int32)   # slot 0 uses 2 of 4 pages
    o1 = paged_flash_decode(q, kp, vp, table, lens)
    junk = table[0, 2]
    kp2 = kp.at[junk].set(11.0)
    vp2 = vp.at[junk].set(-5.0)
    # redirect the tail blocks too: both junk content and junk ids
    table2 = table.at[0, 3].set(table[1, 0])
    o2 = paged_flash_decode(q, kp2, vp2, table2, lens)
    np.testing.assert_allclose(np.asarray(o1[0]), np.asarray(o2[0]),
                               rtol=1e-6, atol=1e-6)


def test_model_decode_flash_path_matches_dense():
    """`use_flash_decode=True` decode == the dense cached-attention path
    on a real GQA model, including ragged per-slot cache positions."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                              dtype="float32")
    cfg_fd = dataclasses.replace(cfg, use_flash_decode=True)
    m, m_fd = build_model(cfg), build_model(cfg_fd)
    params = m.init(jax.random.PRNGKey(0))
    B, T = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg.vocab_size)
    c1, c2 = m.init_cache(B, 16), m_fd.init_cache(B, 16)
    l1, c1 = m.prefill(params, {"tokens": toks[:, :8]}, c1)
    l2, c2 = m_fd.prefill(params, {"tokens": toks[:, :8]}, c2)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)
    for t in range(8, T):
        l1, c1 = m.decode(params, {"tokens": toks[:, t:t + 1]}, c1)
        l2, c2 = m_fd.decode(params, {"tokens": toks[:, t:t + 1]}, c2)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"flash-decode step {t}")


SCRIPT_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, numpy as np

from repro.configs import get_config
from repro.data.tokenizer import trim_at_eos as trim
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serving.continuous import ContinuousEngine

cfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                          dtype="float32")
ref_model = build_model(cfg)
fd_model = build_model(dataclasses.replace(cfg, use_flash_decode=True))
params = ref_model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [list(rng.integers(4, cfg.vocab_size, size=n))
           for n in (10, 7, 12, 5, 9, 11)]
want = ContinuousEngine(ref_model, params, num_slots=4, max_len=64,
                        max_new_cap=16, sync_every=4, prefill_batch=2
                        ).generate_many(prompts, max_new_tokens=12)
mesh = make_serving_mesh("dp=2,mp=2", model_cfg=cfg)
for paged in (False, True):
    eng = ContinuousEngine(fd_model, params, num_slots=4, max_len=64,
                           max_new_cap=16, sync_every=4, prefill_batch=2,
                           mesh=mesh, paged=paged, page_size=8)
    got = eng.generate_many(prompts, max_new_tokens=12)
    for i, (x, y) in enumerate(zip(want, got)):
        assert trim(x.tokens) == trim(y.tokens), (paged, i)
print("FLASH-SHARDED-PARITY-OK")
"""


@pytest.mark.multidevice
def test_flash_decode_sharded_dp2_mp2_token_parity():
    """Under a dp=2,mp=2 mesh the decode kernels run per shard (slots
    on data, kv heads on model) and the dense and paged engines stay
    token-identical to the single-device jnp attention path."""
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT_SHARDED],
        env=dict(os.environ, PYTHONPATH=str(root / "src"),
                 JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert "FLASH-SHARDED-PARITY-OK" in out.stdout, out.stderr[-2000:]
