"""Serve Qwen1.5-32B widths through the Gateway on a TPU, in one process.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # dp=1,mp=4 tensor parallel on four
                                       # chips, compared with one chip

The served path is the one users call: ``Gateway`` -> routing policy ->
BM25 retrieval -> ``ContinuousEngineBackend`` -> ``ContinuousEngine``
-> executor, with a paged KV cache and the Pallas paged flash-decode
kernel.  The model is the published Qwen1.5-32B configuration with its
depth cut to 4 of 64 layers and random bf16 weights from a seed; no
weights are downloaded.  Prompts are padded to 2048 tokens.

Checks, each of which fails the run:

* JAX sees a TPU (no CPU fallback);
* every request routed to generation completes with >= 1 token;
* the compiled decode-chunk program holds a ``tpu_custom_call`` (the
  kernel compiled; it was not interpreted);
* the kernels agree with their jnp oracles at the served shapes;
* the served tokens agree with the jnp attention path on a dense cache
  (teacher-forced; see ``check_against_reference``);
* with ``--four-chips``: per-chip shards hold 10 query heads, 2 KV
  heads, d_ff 6848 and vocab 38016, and the four-chip engine passes the
  same reference check and is compared with the one-chip engine.

The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

N_LAYERS = 4                 # of the published 64
NUM_SLOTS = 8
PREFILL_BATCH = 4
MAX_PROMPT = 2048
MAX_NEW = 16
PAGE_SIZE = 16
N_REQUESTS = 12
SLO = "quality_first"
SEED = 0                     # the engine's weights; also the kernel check
# A served token passes the reference check when its reference logit is
# within TIE_TOL * (max - mean) of the reference maximum: greedy picks on
# random weights over a 152k vocab meet near-ties, where summation order
# alone may flip the argmax.  At least MIN_EXACT of the tokens must be
# the reference argmax exactly.
TIE_TOL = 0.05
MIN_EXACT = 0.5
KERNEL_TOL = 2e-2            # bf16 kernel output vs float32 oracle


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev.platform!r}); this check does not fall back to CPU")
    return dev


def smoke_config():
    from repro.configs import get_config
    full = get_config("qwen1.5-32b")
    return full, dataclasses.replace(full, n_layers=N_LAYERS,
                                     use_flash_decode=True)


def make_testbed():
    """A small routing testbed and a trained quality_first policy."""
    from repro.core.config import RouterConfig, TestbedConfig
    from repro.core.offline_log import build_testbed
    from repro.routing import MLPPolicy, Request, get_slo_profile
    cfg = TestbedConfig(n_train=100, n_eval=40, n_paragraphs=120,
                        router=RouterConfig(n_epochs=3))
    data, index, _pipe, train_log, _eval = build_testbed(cfg)
    policy = MLPPolicy.train(train_log,
                             train_log.rewards(get_slo_profile(SLO)),
                             cfg.router)
    reqs = [Request(qid=q.qid, question=q, slo=SLO)
            for q in data.questions[-cfg.n_eval:][:N_REQUESTS]]
    return cfg, index, policy, reqs


def make_backend(model_cfg, index, mesh_spec=None):
    from repro.launch.serve import continuous_backend
    # every prompt pads to MAX_PROMPT, so prefill compiles once; prefix
    # sharing is off so no suffix-length prefill compiles either
    return continuous_backend(
        model_cfg, index, mesh_spec=mesh_spec, num_slots=NUM_SLOTS,
        max_prompt_len=MAX_PROMPT, max_new_tokens=MAX_NEW,
        prefill_batch=PREFILL_BATCH, paged=True, page_size=PAGE_SIZE,
        prefix_sharing=False)


class Recorder:
    """Keeps each request's prompt ids and generation as the engine
    sees them (the Gateway's outcomes carry counts, not tokens)."""

    def __init__(self, engine):
        self.prompts, self.outputs = {}, {}
        submit, run = engine.submit, engine.run

        def recording_submit(rid, prompt, *args, **kw):
            self.prompts[rid] = list(prompt)
            return submit(rid, prompt, *args, **kw)

        def recording_run():
            done = run()
            self.outputs.update(done)
            return done

        engine.submit, engine.run = recording_submit, recording_run

    def rows(self):
        rids = sorted(self.prompts)
        return ([self.prompts[r] for r in rids],
                [list(self.outputs[r].tokens) for r in rids],
                [self.outputs[r] for r in rids])


def check_decode_kernel(backend) -> None:
    t0 = time.perf_counter()
    hlo = backend.engine.executor.compiled_decode_text()
    secs = time.perf_counter() - t0
    check("tpu_custom_call" in hlo, "decode chunk compiled without a kernel")
    print(f"# decode-chunk program: tpu_custom_call present "
          f"(compile {secs:.1f}s)")


def serve(backend, testbed, label: str):
    from repro.routing import Gateway
    cfg, index, policy, reqs = testbed
    rec = Recorder(backend.engine)
    gw = Gateway(policy, backend, router_cfg=cfg.router, index=index,
                 max_batch=len(reqs), adaptive_refusal=False)
    t0 = time.perf_counter()
    stats = gw.serve(reqs)
    wall = time.perf_counter() - t0
    prompts, outputs, gens = rec.rows()
    failed = [g.failed for g in gens if g.failed]
    check(not failed, f"{label}: failed requests {failed}")
    check(len(gens) >= 8, f"{label}: only {len(gens)} reached the engine")
    check(all(g.n_steps >= 1 for g in gens), f"{label}: empty generation")
    es = backend.engine.stats
    print(f"# {label}: served {stats.served} requests, {len(gens)} "
          f"generated ({sum(map(len, outputs))} tokens, "
          f"{len(reqs) - len(gens)} refused), actions "
          f"{dict(sorted(stats.action_counts.items()))}, prefills "
          f"{es.n_prefills}, decode chunks {es.n_decode_chunks}, "
          f"wall {wall:.1f}s incl. prefill compile")
    return prompts, outputs


def check_against_reference(model_cfg, params, prompts, outputs, label):
    """Teacher-forced comparison with the jnp attention path.

    The reference is the same model with ``use_flash_decode=False`` on a
    dense cache: it prefills each prompt and then decodes the served
    tokens.  At every step the served token is compared with the
    reference logits: it must be the reference argmax or within
    ``TIE_TOL`` of it (see the module constants).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model

    ref = build_model(dataclasses.replace(model_cfg, use_flash_decode=False))
    prefill = jax.jit(ref.prefill)
    decode = jax.jit(ref.decode, donate_argnums=(2,))

    @jax.jit
    def judge(logits, served):
        lg = logits[:, -1].astype(jnp.float32)
        top, mean = lg.max(-1), lg.mean(-1)
        got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        return (top - got) / jnp.maximum(top - mean, 1e-6), \
            lg.argmax(-1) == served

    plen = len(prompts[0])
    check(all(len(p) == plen for p in prompts), "ragged prompt lengths")
    batch = PREFILL_BATCH
    max_len = plen + max(map(len, outputs))       # one cache shape
    gaps, exact = [], []
    for i in range(0, len(prompts), batch):
        rows = list(range(i, min(i + batch, len(prompts))))
        fill = rows + [rows[0]] * (batch - len(rows))    # one shape
        toks = jnp.asarray([prompts[r] for r in fill], jnp.int32)
        steps = max(len(outputs[r]) for r in rows)
        cache = ref.init_cache(batch, max_len)
        logits, cache = prefill(params, {"tokens": toks}, cache)
        for t in range(steps):
            served = np.array([outputs[r][min(t, len(outputs[r]) - 1)]
                               for r in fill], np.int32)
            g, e = judge(logits, jnp.asarray(served))
            g, e = np.asarray(g), np.asarray(e)
            for j, r in enumerate(rows):
                if t < len(outputs[r]):
                    gaps.append(float(g[j]))
                    exact.append(bool(e[j]))
            logits, cache = decode(params, {"tokens": jnp.asarray(
                served[:, None])}, cache)
    gaps = np.asarray(gaps)
    share = float(np.mean(exact))
    print(f"# {label} vs jnp reference (teacher-forced, dense cache): "
          f"{len(gaps)} tokens, exact argmax {share:.3f}, relative gap "
          f"p50 {np.median(gaps):.2e} max {gaps.max():.2e} "
          f"(limit {TIE_TOL}, exact >= {MIN_EXACT})")
    check(gaps.max() <= TIE_TOL and share >= MIN_EXACT,
          f"{label}: served tokens disagree with the jnp reference")


def check_kernels(model_cfg):
    """Both decode kernels vs their float32 oracles at the served shapes
    (slots, heads, head_dim, page pool), with ragged lengths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import flash_decode, paged_flash_decode, ref
    from repro.kernels.flash_decode import pages_per_block

    B, H, Hkv, D = (NUM_SLOTS, model_cfg.n_heads, model_cfg.n_kv_heads,
                    model_cfg.head_dim)
    page_size, max_len = PAGE_SIZE, MAX_PROMPT + MAX_NEW
    MB = max_len // page_size + 1
    NP = B * MB
    ks = jax.random.split(jax.random.PRNGKey(SEED), 5)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (NP, page_size, Hkv, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (NP, page_size, Hkv, D), jnp.bfloat16)
    table = jax.random.permutation(ks[3], NP).reshape(B, MB)
    lens = jnp.asarray(np.linspace(1, max_len, B).round(), jnp.int32)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.paged_flash_decode_ref)(
            q.astype(f32), kp.astype(f32), vp.astype(f32), table, lens)
    got = paged_flash_decode(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=KERNEL_TOL,
                               atol=KERNEL_TOL)
    err = float(jnp.max(jnp.abs(got.astype(f32) - want)))
    k = kp[table].reshape(B, MB * page_size, Hkv, D)
    v = vp[table].reshape(B, MB * page_size, Hkv, D)
    got_d = flash_decode(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(got_d, np.float32),
                               np.asarray(want), rtol=KERNEL_TOL,
                               atol=KERNEL_TOL)
    err_d = float(jnp.max(jnp.abs(got_d.astype(f32) - want)))
    ppb = pages_per_block(page_size, Hkv, 2 * D, kp.dtype.itemsize, MB)
    print(f"# kernels vs float32 oracle at B={B} H={H} Hkv={Hkv} D={D} "
          f"pages={NP}x{page_size}, {ppb} pages a grid step: paged "
          f"max|err| {err:.2e}, dense max|err| {err_d:.2e} (tolerance "
          f"{KERNEL_TOL})")


def check_shards(backend, model_cfg, mp: int):
    """Every param shard of the tensor-parallel engine holds 1/mp of the
    heads, KV heads, FFN and vocab."""
    p = backend.engine.executor.params
    want = {
        ("attn", "wq"): model_cfg.n_heads // mp,
        ("attn", "wk"): model_cfg.n_kv_heads // mp,
        ("mlp", "w_gate"): model_cfg.d_ff // mp,
    }
    got = {}
    for (blk, name), n in want.items():
        leaf = p["blocks"]["p0"][blk][name]
        shapes = {s.data.shape for s in leaf.addressable_shards}
        dim = -2 if blk == "attn" else -1
        check({sh[dim] for sh in shapes} == {n}, f"{name} shards {shapes}")
        got[name] = n
    emb = {s.data.shape[0] for s in p["embed"].addressable_shards}
    check(emb == {model_cfg.padded_vocab // mp}, f"embed shards {emb}")
    got["vocab"] = emb.pop()
    print(f"# per-chip shards: {got['wq']} query heads, {got['wk']} KV "
          f"heads, d_ff {got['w_gate']}, vocab {got['vocab']}")


def release(backend) -> None:
    """Free the engine's device buffers (params, caches, slot state) now,
    so the next engine has device 0's memory to itself."""
    import jax
    ex = backend.engine.executor
    for leaf in jax.tree_util.tree_leaves((backend.engine.params, vars(ex))):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def run_one_chip(model_cfg, testbed):
    backend = make_backend(model_cfg, testbed[1])
    check_decode_kernel(backend)
    prompts, outputs = serve(backend, testbed, "one chip")
    check_kernels(model_cfg)
    check_against_reference(model_cfg, backend.engine.params, prompts,
                            outputs, "one chip")


def run_four_chips(model_cfg, testbed):
    backend = make_backend(model_cfg, testbed[1], mesh_spec="dp=1,mp=4")
    check_shards(backend, model_cfg, 4)
    check_decode_kernel(backend)
    prompts4, outputs4 = serve(backend, testbed, "four chips")
    release(backend)

    backend = make_backend(model_cfg, testbed[1])
    prompts1, outputs1 = serve(backend, testbed, "one chip")
    check(prompts1 == prompts4, "the two engines saw different prompts")
    same = sum(a == b for a, b in zip(outputs1, outputs4))
    print(f"# four chips vs one chip: {same}/{len(outputs1)} requests "
          f"token-identical")
    check_against_reference(model_cfg, backend.engine.params, prompts4,
                            outputs4, "four chips")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp=1,mp=4 tensor-parallel engine "
                         "on four chips and its one-chip comparison")
    args = ap.parse_args()
    dev = require_tpu()
    import jax
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    n_dev = len(jax.devices())
    if args.four_chips and n_dev != 4:
        sys.exit(f"chip_smoke: --four-chips needs 4 devices, found {n_dev}")
    print(f"# device: {dev.platform} {dev.device_kind} x{n_dev}")
    print(f"# compile cache: {enable_compile_cache()}")
    full, model_cfg = smoke_config()
    print(f"# model: {full.name} ({full.source}) d_model {full.d_model}, "
          f"{full.n_heads} heads / {full.n_kv_heads} KV heads x "
          f"{full.head_dim}, d_ff {full.d_ff}, vocab {full.vocab_size}, "
          f"{full.dtype}, random weights (seed {SEED})")
    print(f"# cuts: layers {full.n_layers} -> {model_cfg.n_layers}; "
          f"{NUM_SLOTS} slots x {MAX_PROMPT + MAX_NEW} positions, paged "
          f"KV (page {PAGE_SIZE}, prefix sharing off), prefill batch "
          f"{PREFILL_BATCH}, {MAX_NEW} new tokens")
    t0 = time.perf_counter()
    testbed = make_testbed()
    print(f"# testbed + {SLO} policy: {time.perf_counter() - t0:.1f}s, "
          f"{len(testbed[3])} requests")
    run = run_four_chips if args.four_chips else run_one_chip
    run(model_cfg, testbed)
    print(f"# peak device memory: {peak_bytes(dev)} bytes on device 0")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))


if __name__ == "__main__":
    main()
