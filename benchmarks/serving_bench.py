"""Serving throughput: padded per-bucket Engine vs continuous engine.

Mixed-action synthetic workload (the paper testbed's questions, BM25
retrieval at each routed depth) with heterogeneous per-request
generation lengths — most answers are short, a tail is long, exactly
the EOS behaviour a real model produces — served two ways:

* **padded**: requests bucketed by action, each bucket one serial
  prefill+decode `Engine.generate` call (the pre-continuous Gateway
  execution model).  A bucket decodes until its LAST request finishes,
  so every short request burns wasted decode steps waiting for the
  bucket's longest, and a fresh KV cache is allocated per call.
* **continuous**: a bounded slot pool (`num_slots` << workload) in one
  `ContinuousEngine`; a request frees its slot the moment it finishes
  and the next queued request is admitted mid-stream, across action
  buckets, so the decode batch only ever does useful work.

Both paths produce the same useful tokens (each request's own length,
trimmed at its own EOS); tokens/s counts useful tokens only, so the
padded path's run-to-bucket-max waste shows up as time, not tokens.
Decode tokens/s is isolated by differencing a prefill-only run
(length 1) from the full run.  The prefill-only run admits in full
`prefill_batch` groups while the full run also admits smaller
mid-stream groups, so some extra prefill dispatch time is charged to
the continuous engine's decode — the isolation is conservative for the
continuous side.  Per-request latency is completion time since
workload start (padded requests inherit their bucket's serial position
and its longest member — head-of-line blocking the continuous engine
does not have).

A third engine variant, **continuous_sharded**, runs the same workload
through the slot-sharded ``ShardedExecutor`` on a 1-device mesh (the
mesh axis shows executor overhead, not parallel speedup, on this host)
— its decode tokens/s lands next to the single-device executor's in the
artifact.  Two forced-8-host-device probes (subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) check the
sharded path's token parity on the mixed-action workload and report
throughput: ``--mesh dp=8`` (slot data parallel) and the
**continuous_sharded_mp** engine row (``--mesh-mp dp=4,mp=2``: slots on
``data`` × params tensor-parallel on ``model``, with the model-axis
sharding of the params asserted on-device).  Host devices share the
same CPU, so the probes are correctness smokes, not speedup claims.

A fourth variant, **paged**, serves the same workload through the
paged KV executor (block-table pages + copy-on-write prefix sharing)
with a page pool deliberately sized BELOW the dense cache's byte
budget at equal ``max_len`` — the row records greedy token parity
against the dense oracle, prefix-hit rate, prefill-tokens-avoided,
decode tokens/s, the slots-per-GiB arithmetic, and a fixed-rate
open-loop latency row.  ``--quick`` runs just the paged-vs-dense
parity + prefix-hit smoke and merges the row into BENCH_serving.json
(the CI bench-smoke entry point).

Writes ``benchmarks/artifacts/BENCH_serving.json`` AND repo-root
``BENCH_serving.json`` (the perf-trajectory file).

    PYTHONPATH=src:. python benchmarks/serving_bench.py \
        [--mesh dp=8] [--mesh-mp dp=4,mp=2]
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import save_artifact
from repro.configs import get_config
from repro.core.config import RetrievalConfig
from repro.data.synthetic_squad import SyntheticSquad
from repro.data.tokenizer import EOS, HashTokenizer
from repro.generation.prompts import build_prompt
from repro.models import build_model
from repro.retrieval.bm25 import BM25Index
from repro.routing.registry import get_action_space
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Engine
from repro.serving.slo_budget import LatencyReservoir

N_REQUESTS = 32
GATEWAY_BATCH = 16     # Gateway.step micro-batch (the old serving unit)
NUM_SLOTS = 4          # continuous slot pool (<< micro-batch: constant
                       # admission pressure keeps every row useful)
MAX_PROMPT = 48
MAX_NEW = 64
MAX_LEN = MAX_PROMPT + MAX_NEW
# per-request generation lengths: 3 short answers per long one — the
# heterogeneous-termination pattern continuous batching exists for
LENGTHS = (2, 4, 4, 64)
SYNC_EVERY = 4
REPEATS = 5            # best-of-N walls (the container CPU is noisy)
# paged engine: pool deliberately SMALLER than the dense cache at equal
# max_len (48*8 = 384 KV positions vs dense 4*112 = 448) — the bench
# demonstrates the same slot concurrency under a tighter memory budget
PAGE_SIZE = 8
PAGED_POOL_PAGES = 48


def build_workload():
    """(prompt_tokens, action_idx, gen_len) per request, mixed across
    the paper5 non-refuse actions (deep-k, shallow-k, auto)."""
    data = SyntheticSquad(n_paragraphs=120, n_questions=N_REQUESTS, seed=0)
    index = BM25Index.build([p.text for p in data.paragraphs],
                            RetrievalConfig(vocab_hash_dim=1024))
    space = get_action_space()
    gen_actions = [a for a in space if a.mode != "refuse"]
    tok = HashTokenizer(512)
    workload = []
    for i, q in enumerate(data.questions):
        action = gen_actions[i % len(gen_actions)]
        idx, _ = index.topk(q.text, action.k) if action.k else ([], None)
        passages = [index.texts[j] for j in idx]
        prompt = build_prompt(action.mode, q.text, passages)
        workload.append((tok.encode(prompt, bos=True, max_len=MAX_PROMPT),
                         action.idx, LENGTHS[(i // len(gen_actions))
                                             % len(LENGTHS)]))
    return workload


def _micro_batches(workload):
    for i in range(0, len(workload), GATEWAY_BATCH):
        yield workload[i:i + GATEWAY_BATCH]


def run_padded(engine, workload, prefill_only=False):
    """The old Gateway execution model: per micro-batch, requests are
    bucketed by routed action and every bucket is a serial
    prefill+decode `Engine.generate` call.  A bucket decodes to its
    LONGEST member's length; only each request's own `gen_len` tokens
    count as useful."""
    t0 = time.perf_counter()
    useful = 0
    lat = []
    for mb in _micro_batches(workload):
        buckets = defaultdict(list)
        for prompt, a, n in mb:
            buckets[a].append((prompt, 1 if prefill_only else n))
        for a in sorted(buckets):
            prompts = [p for p, _ in buckets[a]]
            lens = [n for _, n in buckets[a]]
            res = engine.generate(prompts, max_new_tokens=max(lens))
            for row, n in zip(res.tokens, lens):
                # credit only tokens up to the request's own budget AND
                # its own EOS — the bucket keeps decoding for its
                # longest member, but those are not useful tokens
                eos = np.nonzero(row == EOS)[0]
                own = eos[0] + 1 if eos.size else res.n_steps
                useful += int(min(n, own))
            done_at = (time.perf_counter() - t0) * 1e3
            lat += [done_at] * len(prompts)  # bucket completes together
    return useful, time.perf_counter() - t0, lat


def run_continuous(engine, workload, prefill_only=False):
    """The continuous Gateway model: each micro-batch's action buckets
    all feed the bounded slot pool of ONE engine; finished slots admit
    queued requests mid-stream.  (finished_at is the engine's
    perf_counter timestamp, so t0 shares that clock.)"""
    t0 = time.perf_counter()
    useful = 0
    lat = []
    for mb in _micro_batches(workload):
        rids = []
        for prompt, _, n in mb:
            rid = engine.reserve_rid()
            engine.submit(rid, prompt, 1 if prefill_only else n)
            rids.append(rid)
        done = engine.run()
        useful += sum(done[r].n_steps for r in rids)
        lat += [(done[r].finished_at - t0) * 1e3 for r in rids]
    return useful, time.perf_counter() - t0, lat


def _token_run(engine, workload):
    """One pass through a continuous engine, returning the trimmed
    greedy tokens per request (for dense-vs-paged parity)."""
    from repro.data.tokenizer import trim_at_eos as trim
    toks = []
    for mb in _micro_batches(workload):
        rids = []
        for prompt, _, n in mb:
            rid = engine.reserve_rid()
            engine.submit(rid, prompt, n)
            rids.append(rid)
        done = engine.run()
        toks += [trim(done[r].tokens) for r in rids]
    return toks


def _paged_extras(paged_eng, dense_eng, workload, mcfg) -> dict:
    """The paged engine row's correctness + memory fields: greedy token
    parity against the dense oracle (one fresh paired pass), cumulative
    prefix-sharing stats, and the slots-per-HBM arithmetic at equal
    ``max_len``.  Byte counts use the same ``kv_quant.cache_bytes``
    accounting the executors report, plus the paged path's block-table
    and position metadata."""
    from repro.serving.kv_quant import cache_bytes
    ex = paged_eng.executor
    parity = _token_run(dense_eng, workload) == _token_run(paged_eng,
                                                           workload)
    st = paged_eng.stats
    quant = bool(mcfg.kv_quant_int8)
    dense_b = mcfg.n_layers * cache_bytes(
        ex.num_slots, ex.max_len, mcfg.n_kv_heads, mcfg.head_dim, quant)
    pool_b = mcfg.n_layers * cache_bytes(
        ex.num_pages, ex.page_size, mcfg.n_kv_heads, mcfg.head_dim, quant)
    # block table (int32 per slot x block) + per-slot position register
    meta_b = ex.num_slots * ex.max_blocks * 4 + ex.num_slots * 4
    paged_b = pool_b + meta_b
    row = {
        "token_parity": bool(parity),
        "page_size": ex.page_size,
        "num_pages": ex.num_pages,
        "max_concurrent": st.max_concurrent,
        "prefix_hit_rate": round(st.prefill_tokens_avoided
                                 / max(st.prompt_tokens_total, 1), 4),
        "prefill_tokens_avoided": int(st.prefill_tokens_avoided),
        "prompt_tokens_total": int(st.prompt_tokens_total),
        "n_deferred_admissions": st.n_deferred_admissions,
        "n_pages_evicted": st.n_pages_evicted,
        "n_cow_forks": st.n_cow_forks,
        "kv_bytes_dense": dense_b,
        "kv_bytes_paged": paged_b,
        "slots_per_gib_dense": round(ex.num_slots * 2**30 / dense_b, 1),
        "slots_per_gib_paged": round(ex.num_slots * 2**30 / paged_b, 1),
    }
    assert parity, "paged engine diverged from dense greedy decode"
    return row


# --- open-loop serving: offered-load sweep, goodput under SLO ---------------

# offered rates (req/s of *virtual* time) swept against the smoke
# model: low -> comfortable, high -> over-offered so shedding engages
OPEN_LOOP_RATES = (25.0, 100.0, 400.0, 1600.0)
OPEN_LOOP_N = 96             # requests per rate (seeded Poisson trace)
OPEN_LOOP_DEADLINE_MS = 250.0
OPEN_LOOP_QUANTUM_S = 0.01   # virtual seconds charged per gateway pump


def run_open_loop(model, mcfg, params, rates=OPEN_LOOP_RATES,
                  engine_kw=None) -> dict:
    """Seeded Poisson traces through AsyncGateway over the continuous
    engine in VIRTUAL time: per offered rate, one goodput-under-SLO +
    p50/p99-latency row.  Deterministic — same seed, same rows — so the
    CI smoke job can assert on the artifact.  ``engine_kw`` flows into
    the backend's ContinuousEngine (e.g. ``paged=True``)."""
    import numpy as _np
    from repro.core.config import RetrievalConfig as _RC
    from repro.obs import MetricsRegistry, Tracer
    from repro.routing import FixedPolicy
    from repro.routing.engine_backend import ContinuousEngineBackend
    from repro.serving.streaming import AdmissionConfig, AsyncGateway
    from repro.serving.traffic import sweep_offered_load

    data = SyntheticSquad(n_paragraphs=120, n_questions=24, seed=0)
    index = BM25Index.build([p.text for p in data.paragraphs],
                            _RC(vocab_hash_dim=1024))

    def make_gateway(clock):
        backend = ContinuousEngineBackend.create(
            model, params, HashTokenizer(mcfg.vocab_size), index,
            num_slots=NUM_SLOTS, max_prompt_len=MAX_PROMPT,
            max_new_tokens=8, sync_every=SYNC_EVERY, clock=clock.now,
            **(engine_kw or {}))
        # telemetry plane on the same virtual clock: each row's
        # "stages" key is the trace-derived per-stage p50/p99 table
        return AsyncGateway(
            FixedPolicy(1), backend,
            state_fn=lambda qs: _np.zeros((len(qs), 1)),
            clock=clock.now, deadline_ms=OPEN_LOOP_DEADLINE_MS,
            admission=AdmissionConfig(max_backlog=3 * NUM_SLOTS),
            tracer=Tracer(clock.now), metrics=MetricsRegistry(clock.now))

    rows = sweep_offered_load(
        make_gateway, data.questions, list(rates),
        n_requests=OPEN_LOOP_N, deadline_ms=OPEN_LOOP_DEADLINE_MS,
        seed=0, service_quantum_s=OPEN_LOOP_QUANTUM_S)
    for r in rows:
        print(f"open-loop rate={r['rate']:7.1f}/s  "
              f"goodput={r['goodput']:7.2f}/s  shed={r['shed']:3d}  "
              f"p50={r['latency_p50_ms']}ms p99={r['latency_p99_ms']}ms")
    return {
        "deadline_ms": OPEN_LOOP_DEADLINE_MS, "n_per_rate": OPEN_LOOP_N,
        "num_slots": NUM_SLOTS, "arrival": "poisson(seed=0)",
        "service_quantum_s": OPEN_LOOP_QUANTUM_S,
        "rows": rows,
        # trace-derived per-stage latency at the comfortable operating
        # point (stage -> {n, p50_ms, p99_ms} of virtual time)
        "stage_breakdown": rows[min(1, len(rows) - 1)].get("stages", {}),
        # headline: shedding engages under over-offered load
        "shed_at_max_rate": rows[-1]["shed"],
        "shed_at_min_rate": rows[0]["shed"],
    }


def tracer_overhead_row(repeats: int = 7, n_requests: int = 400) -> dict:
    """Hot-path cost of the telemetry plane: the same seeded open-loop
    replay through the host-only simulator backend, once with a live
    Tracer + MetricsRegistry attached and once with the no-op defaults,
    best-of-N REAL wall each.  Virtual time pins the schedule (same
    pumps, same admissions, token-identical outcomes), so the wall
    difference is pure instrumentation cost — asserted within 5%."""
    from repro.core.config import RetrievalConfig as _RC
    from repro.generation.simulator import SimulatedGenerator
    from repro.obs import MetricsRegistry, Tracer
    from repro.routing import FixedPolicy
    from repro.routing.backends import SimulatorBackend
    from repro.serving.pipeline import RAGPipeline
    from repro.serving.streaming import AdmissionConfig, AsyncGateway
    from repro.serving.traffic import (LoadGenerator, PoissonProcess,
                                       VirtualClock, build_trace)

    data = SyntheticSquad(n_paragraphs=120, n_questions=24, seed=0)
    index = BM25Index.build([p.text for p in data.paragraphs],
                            _RC(vocab_hash_dim=1024))
    tok = HashTokenizer(512)

    def one_run(traced: bool) -> float:
        clock = VirtualClock()
        pipe = RAGPipeline(index, SimulatedGenerator(tok))
        backend = SimulatorBackend(pipe, stream_slots=NUM_SLOTS,
                                   service_polls=2, clock=clock.now)
        kw = ({"tracer": Tracer(clock.now),
               "metrics": MetricsRegistry(clock.now)} if traced else {})
        gw = AsyncGateway(
            FixedPolicy(1), backend,
            state_fn=lambda qs: np.zeros((len(qs), 1)),
            clock=clock.now, deadline_ms=OPEN_LOOP_DEADLINE_MS,
            admission=AdmissionConfig(max_backlog=3 * NUM_SLOTS), **kw)
        trace = build_trace(data.questions, PoissonProcess(200.0, seed=0),
                            n_requests, deadline_ms=OPEN_LOOP_DEADLINE_MS)
        t0 = time.perf_counter()
        LoadGenerator(gw, trace).run_virtual(
            clock, service_quantum_s=OPEN_LOOP_QUANTUM_S)
        return time.perf_counter() - t0

    one_run(False)
    one_run(True)                                   # warmup both paths
    # interleave so both paths sample the same noise windows (shared-
    # container CPU), best-of-N each
    base, traced = 9e9, 9e9
    for _ in range(repeats):
        base = min(base, one_run(False))
        traced = min(traced, one_run(True))
    pct = round((traced - base) / base * 100.0, 2)
    row = {"base_wall_s": round(base, 4),
           "traced_wall_s": round(traced, 4),
           "tracer_overhead_pct": pct,
           "repeats": repeats, "n_requests": n_requests}
    print(f"tracer overhead: {pct}% "
          f"(base {base:.4f}s vs traced {traced:.4f}s, best of {repeats})")
    assert pct <= 5.0, f"tracer hot-path overhead {pct}% exceeds 5%"
    return row


def _one_device_mesh():
    """A 1-device ("data","model") mesh regardless of host flags."""
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh("dp=1", devices=jax.devices()[:1])


def _sharded_probe(mesh_spec: str) -> dict:
    """Re-exec this benchmark in a CPU subprocess with dp*mp forced host
    devices: token parity (single-device vs sharded executor) on the
    mixed-action workload, plus the sharded decode throughput (and,
    with mp>1, an on-device check that params shard on the model
    axis).  A probe that fails raises."""
    parts = dict(kv.split("=") for kv in mesh_spec.split(","))
    ndev = int(parts.get("dp", 1)) * int(parts.get("mp", 1))
    root = Path(__file__).resolve().parents[1]
    # a CPU parity probe on forced host devices: the parent may hold
    # the accelerator, which one process at a time can use
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
               PYTHONPATH=f"{root / 'src'}:{root}")
    res = subprocess.run(
        [sys.executable, __file__, "--probe", mesh_spec],
        env=env, capture_output=True, text=True, timeout=1200)
    for line in res.stdout.splitlines():
        if line.startswith("PROBE_JSON:"):
            return json.loads(line[len("PROBE_JSON:"):])
    raise RuntimeError(f"sharded probe {mesh_spec} failed:\n"
                       f"{(res.stderr or res.stdout)[-800:]}")


def probe_main(mesh_spec: str) -> None:
    """Subprocess body (XLA_FLAGS already set before jax imported)."""
    from repro.data.tokenizer import trim_at_eos as trim
    from repro.launch.mesh import make_serving_mesh
    from repro.sharding import mesh_axis_sizes, model_axis_fallbacks

    mcfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                               dtype="float32")
    mesh = make_serving_mesh(mesh_spec, model_cfg=mcfg)
    ndev = len(jax.devices())
    mp = mesh_axis_sizes(mesh)["model"]
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    workload = build_workload()[:2 * ndev]
    slots = ndev

    outs = {}
    for name, mesh_arg in (("single", None), ("sharded", mesh)):
        eng = ContinuousEngine(model, params, num_slots=slots,
                               max_len=MAX_LEN, max_new_cap=MAX_NEW,
                               sync_every=SYNC_EVERY, prefill_batch=slots,
                               mesh=mesh_arg)
        tokens = []
        walls = []
        for trial in range(2):            # trial 0 = compile warmup
            rids = []
            t0 = time.perf_counter()
            for prompt, _, n in workload:
                rid = eng.reserve_rid()
                eng.submit(rid, prompt, n)
                rids.append(rid)
            done = eng.run()
            walls.append(time.perf_counter() - t0)
            tokens = [trim(done[r].tokens) for r in rids]
        if mesh_arg is not None and mp > 1:
            # params must be PARTITIONED on the model axis, not
            # replicated per device (the mp>1 silent-replication bug):
            # on-device shard-shape check on one tensor, resolver audit
            # over the whole schema
            wq = eng.executor.params["blocks"]["p0"]["attn"]["wq"]
            shapes = {s.data.shape for s in wq.addressable_shards}
            want_heads = mcfg.n_heads // mp
            assert all(sh[-2] == want_heads for sh in shapes), (
                mesh_spec, shapes)
            _, fallbacks = model_axis_fallbacks(model.schema, mesh)
            assert not fallbacks, fallbacks
        outs[name] = {"tokens": tokens, "wall_s": walls[-1],
                      "useful": sum(len(t) for t in tokens),
                      "allocations": eng.stats.cache_allocations}
    parity = outs["single"]["tokens"] == outs["sharded"]["tokens"]
    # measured, not assumed: true only when mp>1 AND the asserts above
    # confirmed every model-capable leaf actually partitioned
    report = {
        "mesh": mesh_spec, "devices": ndev, "n_requests": len(workload),
        "num_slots": slots, "token_parity": bool(parity),
        "params_model_sharded": mp > 1,
        "cache_allocations": outs["sharded"]["allocations"],
        "sharded_tokens_per_s": round(
            outs["sharded"]["useful"] / outs["sharded"]["wall_s"], 1),
        "single_tokens_per_s": round(
            outs["single"]["useful"] / outs["single"]["wall_s"], 1),
    }
    assert parity, "sharded executor diverged from single-device greedy"
    print("PROBE_JSON:" + json.dumps(report))


def main(mesh_probe: str = "dp=8", mp_probe: str = "dp=4,mp=2") -> dict:
    mcfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                               dtype="float32")
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    workload = build_workload()

    out = {"n_requests": N_REQUESTS, "num_slots": NUM_SLOTS,
           "gen_lengths": list(LENGTHS), "max_prompt_len": MAX_PROMPT,
           "model": mcfg.name, "n_buckets": len({a for _, a, _ in workload}),
           "useful_tokens": sum(n for _, _, n in workload)}
    # ONE engine instance per execution model, reused across all trials
    # — jit caches are per instance, so fresh engines would put seconds
    # of retrace/compile inside every timed window
    engines = {
        "padded": Engine(model, params, max_len=MAX_LEN),
        "continuous": ContinuousEngine(
            model, params, num_slots=NUM_SLOTS, max_len=MAX_LEN,
            max_new_cap=MAX_NEW, sync_every=SYNC_EVERY,
            prefill_batch=NUM_SLOTS),
        "continuous_sharded": ContinuousEngine(
            model, params, num_slots=NUM_SLOTS, max_len=MAX_LEN,
            max_new_cap=MAX_NEW, sync_every=SYNC_EVERY,
            prefill_batch=NUM_SLOTS, mesh=_one_device_mesh()),
        "paged": ContinuousEngine(
            model, params, num_slots=NUM_SLOTS, max_len=MAX_LEN,
            max_new_cap=MAX_NEW, sync_every=SYNC_EVERY,
            prefill_batch=NUM_SLOTS, paged=True, page_size=PAGE_SIZE,
            num_pages=PAGED_POOL_PAGES),
    }
    runners = (("padded", run_padded), ("continuous", run_continuous),
               ("continuous_sharded", run_continuous),
               ("paged", run_continuous))
    best = {}
    for name, runner in runners:
        runner(engines[name], workload)                # warmup (compile)
        runner(engines[name], workload, prefill_only=True)
        best[name] = {"decode_t": 9e9, "decode_tok": 0, "full": (0, 9e9, [])}
    # interleave trials so both engines sample the same noise windows
    # (shared-container CPU); the prefill-only and full runs of a trial
    # are paired back-to-back so their difference correlates the noise
    for _ in range(REPEATS):
        for name, runner in runners:
            tok_pre, t_pre, _ = runner(engines[name], workload,
                                       prefill_only=True)
            full = runner(engines[name], workload)
            # a trial whose full wall lands under its prefill-only wall
            # is noise (possible when prefill is nearly free, e.g. the
            # paged engine cache-hot) — skip it rather than divide by a
            # clamped epsilon
            d_t = full[1] - t_pre
            if 0 < d_t < best[name]["decode_t"]:
                best[name]["decode_t"] = d_t
                best[name]["decode_tok"] = full[0] - tok_pre
            if full[1] < best[name]["full"][1]:
                best[name]["full"] = full
    for name, _runner in runners:
        tok_full, t_full, lat = best[name]["full"]
        decode_tok = best[name]["decode_tok"]
        decode_t = best[name]["decode_t"]
        if decode_t >= 9e9 or decode_tok <= 0:
            # no trial isolated cleanly: report the end-to-end rate
            # (prefill charged to decode — a conservative lower bound)
            decode_tok, decode_t = tok_full, t_full
        # the one shared home for serving percentiles (p50/p95/p99) —
        # no more ad-hoc np.percentile math per bench
        res = LatencyReservoir()
        res.extend(lat)
        pct = res.percentiles()
        out[name] = {
            "tokens": tok_full,
            "wall_s": round(t_full, 4),
            "tokens_per_s": round(tok_full / t_full, 1),
            "decode_tokens_per_s": round(decode_tok / decode_t, 1),
            "latency_ms_mean": pct["mean_ms"],
            "latency_ms_p50": pct["p50_ms"],
            "latency_ms_p95": pct["p95_ms"],
            "latency_ms_p99": pct["p99_ms"],
            "latency_ms_max": pct["max_ms"],
        }
        print(name, out[name])

    # paged row: token parity vs the dense oracle + prefix-sharing and
    # memory-budget fields (the timing loops above left the paged
    # engine's page pool cache-hot, so the hit rate reflects the
    # repeated-passage workload, not a cold start)
    out["paged"].update(_paged_extras(engines["paged"],
                                      engines["continuous"],
                                      workload, mcfg))
    print("paged extras:", {k: out["paged"][k] for k in
                            ("token_parity", "prefix_hit_rate",
                             "prefill_tokens_avoided",
                             "slots_per_gib_dense",
                             "slots_per_gib_paged")})
    out["decode_speedup"] = round(
        out["continuous"]["decode_tokens_per_s"]
        / out["padded"]["decode_tokens_per_s"], 2)
    out["e2e_speedup"] = round(
        out["continuous"]["tokens_per_s"]
        / out["padded"]["tokens_per_s"], 2)
    out["latency_mean_speedup"] = round(
        out["padded"]["latency_ms_mean"]
        / out["continuous"]["latency_ms_mean"], 2)
    # sharded-on-1-device-mesh vs single-device executor: the mesh
    # machinery (NamedSharding layouts, out_shardings jits) must not
    # regress decode throughput
    out["sharded_1dev_decode_ratio"] = round(
        out["continuous_sharded"]["decode_tokens_per_s"]
        / out["continuous"]["decode_tokens_per_s"], 2)
    print(f"decode speedup: {out['decode_speedup']}x; "
          f"end-to-end: {out['e2e_speedup']}x; "
          f"mean latency: {out['latency_mean_speedup']}x lower; "
          f"sharded/single decode on 1-dev mesh: "
          f"{out['sharded_1dev_decode_ratio']}x")
    if mesh_probe:
        print(f"# forced-device sharded probe ({mesh_probe}) ...")
        out["sharded_probe"] = _sharded_probe(mesh_probe)
        print("probe:", out["sharded_probe"])
    if mp_probe:
        # the dp×mp tensor-parallel engine row: greedy parity + params
        # verifiably partitioned on the model axis (forced 8 devices)
        print(f"# forced-device tensor-parallel probe ({mp_probe}) ...")
        out["continuous_sharded_mp"] = _sharded_probe(mp_probe)
        print("probe:", out["continuous_sharded_mp"])
    print("# open-loop offered-load sweep ...")
    out["open_loop"] = run_open_loop(model, mcfg, params)
    # the paged engine's open-loop latency at one fixed mid-sweep rate
    # (same seeded trace as the dense sweep's second operating point)
    print("# open-loop fixed-rate paged row ...")
    paged_ol = run_open_loop(
        model, mcfg, params, rates=(OPEN_LOOP_RATES[1],),
        engine_kw={"paged": True, "page_size": PAGE_SIZE})
    out["paged"]["open_loop"] = paged_ol["rows"][0]
    print("# tracer hot-path overhead ...")
    out["tracer_overhead"] = tracer_overhead_row()
    save_artifact("BENCH_serving", out)
    # the repo-root copy is the perf-trajectory entry point
    (Path(__file__).resolve().parents[1] / "BENCH_serving.json").write_text(
        json.dumps(out, indent=1))
    return {"decode_speedup": out["decode_speedup"],
            "sharded_1dev_decode_ratio": out["sharded_1dev_decode_ratio"]}


def quick_main() -> dict:
    """CI paged smoke: dense-vs-paged greedy parity plus prefix-sharing
    stats on the mixed-action workload, no timing repeats or probes.
    Two passes through the same paged engine so the second is
    cache-hot; merges the ``paged`` row into BENCH_serving.json,
    preserving whatever a full run already wrote (the
    ``open_loop_main`` merge pattern)."""
    mcfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                               dtype="float32")
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    workload = build_workload()[:GATEWAY_BATCH]
    dense = ContinuousEngine(
        model, params, num_slots=NUM_SLOTS, max_len=MAX_LEN,
        max_new_cap=MAX_NEW, sync_every=SYNC_EVERY,
        prefill_batch=NUM_SLOTS)
    paged = ContinuousEngine(
        model, params, num_slots=NUM_SLOTS, max_len=MAX_LEN,
        max_new_cap=MAX_NEW, sync_every=SYNC_EVERY,
        prefill_batch=NUM_SLOTS, paged=True, page_size=PAGE_SIZE,
        num_pages=PAGED_POOL_PAGES)
    _paged_extras(paged, dense, workload, mcfg)        # pass 1: cold
    row = _paged_extras(paged, dense, workload, mcfg)  # pass 2: hot
    print("paged-quick:", row)
    assert row["prefix_hit_rate"] > 0, row
    root = Path(__file__).resolve().parents[1]
    out = {}
    target = root / "BENCH_serving.json"
    if target.exists():
        out = json.loads(target.read_text())
    merged = out.get("paged", {})
    merged.update(row)
    out["paged"] = merged
    save_artifact("BENCH_serving", out)
    target.write_text(json.dumps(out, indent=1))
    return row


def open_loop_main() -> dict:
    """Just the open-loop sweep (the CI traffic-harness smoke): merge
    the ``open_loop`` key into BENCH_serving.json, preserving whatever
    engine rows a full run already wrote."""
    mcfg = dataclasses.replace(get_config("qwen1.5-32b", "smoke"),
                               dtype="float32")
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    open_loop = run_open_loop(model, mcfg, params)
    overhead = tracer_overhead_row(repeats=3)
    root = Path(__file__).resolve().parents[1]
    out = {}
    target = root / "BENCH_serving.json"
    if target.exists():
        out = json.loads(target.read_text())
    out["open_loop"] = open_loop
    out["tracer_overhead"] = overhead
    save_artifact("BENCH_serving", out)
    target.write_text(json.dumps(out, indent=1))
    return open_loop


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="dp=8", metavar="dp=N",
                    help="forced-host-device count for the sharded probe "
                         "(empty string skips the probe)")
    ap.add_argument("--mesh-mp", default="dp=4,mp=2", metavar="dp=N,mp=M",
                    help="dp×mp tensor-parallel probe — writes the "
                         "continuous_sharded_mp engine row (empty string "
                         "skips it)")
    ap.add_argument("--open-loop-only", action="store_true",
                    help="run only the open-loop offered-load sweep and "
                         "merge it into BENCH_serving.json (CI smoke)")
    ap.add_argument("--quick", action="store_true",
                    help="paged-vs-dense parity + prefix-hit smoke only; "
                         "merges the paged row into BENCH_serving.json "
                         "(CI bench-smoke)")
    ap.add_argument("--probe", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.probe:
        probe_main(args.probe)
    elif args.open_loop_only:
        open_loop_main()
    elif args.quick:
        quick_main()
    else:
        print(main(mesh_probe=args.mesh, mp_probe=args.mesh_mp))
