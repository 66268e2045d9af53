"""ShardedExecutor: slot-dimension mesh sharding of the continuous
engine.

In-process, the test process owns a single CPU device, so the 1-device
mesh test covers the NamedSharding/jit-out-shardings code path and its
token parity with the single-device executor; the REAL 8-device layout
runs in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` (the same pattern as test_moe_multidevice) and checks
token parity, per-device slot ownership, and the one-KV-allocation
invariant."""
import dataclasses
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.tokenizer import trim_at_eos as _trim
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serving.continuous import ContinuousEngine


@pytest.fixture(scope="module")
def qwen():
    cfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_sharded_1device_mesh_token_parity(qwen):
    """On a 1-device mesh the sharded executor must be token-identical
    to the single-device executor (mixed prompt lengths, slot reuse)."""
    cfg, model, params = qwen
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(4, cfg.vocab_size, size=n))
               for n in (10, 7, 10, 5, 7)]
    single = ContinuousEngine(model, params, num_slots=3, max_len=64,
                              max_new_cap=16, sync_every=4,
                              prefill_batch=3)
    a = single.generate_many(prompts, max_new_tokens=12)
    mesh = make_serving_mesh("dp=1")
    sharded = ContinuousEngine(model, params, num_slots=3, max_len=64,
                               max_new_cap=16, sync_every=4,
                               prefill_batch=3, mesh=mesh)
    b = sharded.generate_many(prompts, max_new_tokens=12)
    for i, (x, y) in enumerate(zip(a, b)):
        assert _trim(x.tokens) == _trim(y.tokens), i
    assert sharded.stats.cache_allocations == 2
    assert sharded.stats.n_admitted == 5


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, numpy as np

from repro.configs import get_config
from repro.data.tokenizer import trim_at_eos as trim
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serving.continuous import ContinuousEngine

cfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                          dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
lens = (10, 7, 10, 5, 7, 9, 9, 12, 6, 10)
prompts = [list(rng.integers(4, cfg.vocab_size, size=n)) for n in lens]

single = ContinuousEngine(model, params, num_slots=8, max_len=64,
                          max_new_cap=16, sync_every=4, prefill_batch=4)
a = single.generate_many(prompts, max_new_tokens=12)

mesh = make_serving_mesh("dp=8")
sharded = ContinuousEngine(model, params, num_slots=8, max_len=64,
                           max_new_cap=16, sync_every=4, prefill_batch=4,
                           mesh=mesh)
b = sharded.generate_many(prompts, max_new_tokens=12)
for i, (x, y) in enumerate(zip(a, b)):
    assert trim(x.tokens) == trim(y.tokens), (i, trim(x.tokens),
                                              trim(y.tokens))

# slot rows live on all 8 devices, partitioned on the data axis
for leaf in jax.tree_util.tree_leaves(sharded.executor._cache):
    assert len(leaf.sharding.device_set) == 8, leaf.shape
assert "data" in str(
    jax.tree_util.tree_leaves(sharded.executor._cache)[0].sharding.spec)
# the one-allocation invariant holds for the sharded executor too
assert sharded.stats.cache_allocations == 2
assert single.stats.cache_allocations == 2

# indivisible slot counts are rejected up front
try:
    ContinuousEngine(model, params, num_slots=3, max_len=64, mesh=mesh)
except ValueError:
    pass
else:
    raise AssertionError("num_slots=3 on dp=8 must be rejected")
print("SHARDED-8DEV-PARITY-OK")
"""


@pytest.mark.multidevice
def test_sharded_8device_token_parity():
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"},
        capture_output=True, text=True, timeout=900)
    assert "SHARDED-8DEV-PARITY-OK" in out.stdout, out.stderr[-2000:]


SCRIPT_MP = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, numpy as np

from repro.configs import get_config
from repro.data.tokenizer import trim_at_eos as trim
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serving.continuous import ContinuousEngine

cfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                          dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
lens = (10, 7, 10, 5, 7, 9, 12, 6)
prompts = [list(rng.integers(4, cfg.vocab_size, size=n)) for n in lens]

single = ContinuousEngine(model, params, num_slots=4, max_len=64,
                          max_new_cap=16, sync_every=4, prefill_batch=4)
a = single.generate_many(prompts, max_new_tokens=12)

mesh = make_serving_mesh("dp=4,mp=2", model_cfg=cfg)
sharded = ContinuousEngine(model, params, num_slots=4, max_len=64,
                           max_new_cap=16, sync_every=4, prefill_batch=4,
                           mesh=mesh)
b = sharded.generate_many(prompts, max_new_tokens=12)
for i, (x, y) in enumerate(zip(a, b)):
    assert trim(x.tokens) == trim(y.tokens), (i, trim(x.tokens),
                                              trim(y.tokens))

# params are VERIFIABLY tensor-parallel on the model axis — the mp>1
# silent-replication bug would leave every shard the full tensor
ex = sharded.executor
wq = ex.params["blocks"]["p0"]["attn"]["wq"]       # (layers, d, H, Dh)
assert {s.data.shape for s in wq.addressable_shards} == \
    {(2, 256, 2, 64)}, wq.sharding.spec            # H: 4 -> 2 per shard
wg = ex.params["blocks"]["p0"]["mlp"]["w_gate"]    # (layers, d, d_ff)
assert {s.data.shape for s in wg.addressable_shards} == \
    {(2, 256, 256)}, wg.sharding.spec              # d_ff: 512 -> 256
emb = ex.params["embed"]                           # (padded_vocab, d)
assert {s.data.shape for s in emb.addressable_shards} == \
    {(256, 256)}, emb.sharding.spec                # vocab: 512 -> 256
# no model-capable param leaf silently replicates on this mesh
from repro.sharding import model_axis_fallbacks
_, fallbacks = model_axis_fallbacks(model.schema, mesh)
assert not fallbacks, fallbacks

# the slot cache combines slots-on-data with kv-heads-on-model, and
# the prefill scratch rows shard over data (prefill_batch 4 = dp)
kv = ex._cache["blocks"]["p0"]["k"]   # (layers, S, max_len, Hkv, Dh)
assert {s.data.shape for s in kv.addressable_shards} == \
    {(2, 1, 64, 2, 64)}, kv.sharding.spec
pk = ex._pcache["blocks"]["p0"]["k"]
assert "data" in str(pk.sharding.spec) and "model" in str(pk.sharding.spec)
assert sharded.stats.cache_allocations == 2

# an mp the resolver can't place (heads AND the head_dim fallback
# both indivisible) is rejected up front with the config + offending
# tensors named, not as an XLA failure at first decode
bad = dataclasses.replace(cfg, n_heads=6, n_kv_heads=6, head_dim=63)
try:
    make_serving_mesh("dp=2,mp=4", model_cfg=bad)
except ValueError as e:
    assert bad.name in str(e) and "wq" in str(e), e
else:
    raise AssertionError("mp=4 on 6 heads / head_dim 63 must be rejected")
print("SHARDED-MP-PARITY-OK")
"""


@pytest.mark.multidevice
def test_sharded_dp4_mp2_tensor_parallel_parity():
    """dp=4,mp=2: token parity with the single-device executor AND
    proof the params are actually partitioned on the model axis."""
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT_MP],
        env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"},
        capture_output=True, text=True, timeout=900)
    assert "SHARDED-MP-PARITY-OK" in out.stdout, out.stderr[-2000:]


SCRIPT_CHAOS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, numpy as np

from repro.configs import get_config
from repro.data.tokenizer import trim_at_eos as trim
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serving.continuous import ContinuousEngine
from repro.serving.faults import (ChaosExecutor, ChaosInjector, FaultPlan,
                                  FaultSpec)

cfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                          dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [list(rng.integers(4, cfg.vocab_size, size=n))
           for n in (10, 7, 9, 5, 8, 11)]
mesh = make_serving_mesh("dp=4,mp=2", model_cfg=cfg)

def build(chaos=None, **kw):
    return ContinuousEngine(model, params, num_slots=4, max_len=64,
                            max_new_cap=16, sync_every=2, prefill_batch=2,
                            mesh=mesh, chaos=chaos, **kw)

clean = build().generate_many(prompts, max_new_tokens=10)

# injected NaN poison on one slot of the REAL dp=4,mp=2 executor: only
# that slot's request fails, it is quarantined, and the surviving
# peers' tokens are bit-identical to the clean run
plan = FaultPlan(specs=(FaultSpec(site="executor.decode", kind="nan",
                                  start=1, count=1, slots=(2,)),))
eng = build(ChaosInjector(plan))
assert isinstance(eng.executor, ChaosExecutor)
rids = [eng.reserve_rid() for _ in prompts]
for rid, p in zip(rids, prompts):
    eng.submit(rid, p, 10)
done = eng.run()
outs = [done[r] for r in rids]
failed = [i for i, o in enumerate(outs) if o.failed]
assert len(failed) == 1 and outs[failed[0]].transient, failed
assert eng.stats.n_nan_trips == 1 and eng.quarantined_slots == {2}
for i, o in enumerate(outs):
    if i not in failed:
        assert trim(o.tokens) == trim(clean[i].tokens), i
# the quarantined slot returns to service after reset
assert eng.reset_quarantine() == [2]
more = eng.generate_many(prompts[:2], max_new_tokens=6)
assert all(not o.failed for o in more)

# a transient decode fault aborts the chunk; with one requeue allowed
# every request still completes, token-identical to the clean run
plan2 = FaultPlan(specs=(FaultSpec(site="executor.decode", kind="raise",
                                   start=1, count=1),))
eng2 = build(ChaosInjector(plan2), max_requeues=1)
outs2 = eng2.generate_many(prompts, max_new_tokens=10)
assert all(not o.failed for o in outs2)
assert eng2.stats.n_exec_faults == 1 and eng2.stats.n_requeued > 0
for i, (o, c) in enumerate(zip(outs2, clean)):
    assert trim(o.tokens) == trim(c.tokens), i
print("SHARDED-CHAOS-OK")
"""


@pytest.mark.multidevice
def test_chaos_on_sharded_dp4_mp2():
    """ChaosExecutor over the REAL ShardedExecutor on a forced-8-device
    dp=4,mp=2 mesh: injected decode faults quarantine / requeue exactly
    as on the fake, with surviving peers token-identical."""
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT_CHAOS],
        env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"},
        capture_output=True, text=True, timeout=900)
    assert "SHARDED-CHAOS-OK" in out.stdout, out.stderr[-2000:]


def test_mp_divisibility_check_names_config():
    """check_mp_divisibility fails fast (no devices needed), derived
    from the real resolver — it names the config and the tensors that
    would silently replicate; resolvable configs pass, including ones
    that only shard via the head_dim divisibility fallback."""
    from repro.launch.mesh import check_mp_divisibility
    cfg = get_config("qwen1.5-32b", "smoke")
    check_mp_divisibility(cfg, 2)          # 4 heads / 512 d_ff: fine
    check_mp_divisibility(cfg, 1)          # mp=1 never checks
    # heads=6 on mp=4 still shards — via the head_dim=64 fallback —
    # so the resolver-backed check accepts what the executor can place
    check_mp_divisibility(
        dataclasses.replace(cfg, n_heads=6, n_kv_heads=6), 4)
    bad = dataclasses.replace(cfg, n_heads=6, n_kv_heads=6, head_dim=63)
    with pytest.raises(ValueError, match="qwen-smoke.*wq"):
        check_mp_divisibility(bad, 4, spec="dp=2,mp=4")
    # d_ff=500 on mp=8: the MLP tensors have no fallback dim
    with pytest.raises(ValueError, match="mlp/w_gate"):
        check_mp_divisibility(dataclasses.replace(cfg, d_ff=500), 8)
