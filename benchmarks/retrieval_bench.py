"""Retrieval subsystem benchmark: BM25 vs dense vs hybrid vs sharded.

Throughput rows (batch of queries against the synthetic corpus):

* ``bm25_pallas`` — the blocked BM25 kernel path (full score matrix +
  host top-k, the seed scoring model);
* ``dense_pallas`` — the FUSED dense score+top-k kernel
  (``kernels/dense_topk``): only (Q, k) candidates ever leave the
  kernel, the (Q, D) matrix never materializes;
* ``bm25_host`` / ``dense_host`` — the numpy serving paths
  (``index.topk`` per query, what the simulator pipeline runs);
* ``hybrid_host`` — weighted/RRF fusion of both candidate sets;
* ``cached`` — a second pass over the same query stream through the
  bounded LRU (the serving cache satellite): hit rate + speedup.

Throughput is reported as queries/s and M-scores/s (Q·D dot-rows per
second — "tokens scored" in retrieval terms).  On this CPU container
the Pallas rows run in interpret mode: correctness smokes with relative
numbers, not TPU speedup claims (same convention as serving_bench).

Quality table: hit@k (gold answer string contained in a top-k passage,
answerable questions only) per retriever for k ∈ {2, 5, 10} — the
cost/quality frontier retriever-choice routing exploits.

A forced-8-host-device subprocess probe checks the sharded paths
(``DistributedBM25`` / ``DistributedDenseIndex``: local top-k →
all-gather → merge) stay id-identical to the single-device oracles.

Finally the paper's failure-mode convention, now with retriever choice
in the action set: a compact ``hybrid9`` cheap-profile check — does
Argmax-CE still collapse to refusal, and does the constrained
objective mitigate it?

Writes ``benchmarks/artifacts/BENCH_retrieval.json`` AND repo-root
``BENCH_retrieval.json``.

    PYTHONPATH=src:. python benchmarks/retrieval_bench.py [--quick]
        [--no-probe]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from benchmarks.common import save_artifact
from repro.core.config import RetrievalConfig, RouterConfig, TestbedConfig
from repro.data.synthetic_squad import SyntheticSquad
from repro.retrieval import (BM25Index, DenseIndex, HybridRetriever,
                             IndexRetriever, resolve_retrievers)

RCFG = RetrievalConfig(vocab_hash_dim=1024, dense_embed_dim=256)
KS = (2, 5, 10)
REPEATS = 3


def _best_wall(fn, repeats=REPEATS):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _throughput(n_queries, n_docs, wall):
    return {"wall_s": round(wall, 4),
            "queries_per_s": round(n_queries / wall, 1),
            "mscores_per_s": round(n_queries * n_docs / wall / 1e6, 3)}


def main(n_docs: int = 512, n_queries: int = 32, probe: bool = True) -> dict:
    import jax.numpy as jnp
    from jax import lax

    from repro.kernels import bm25_scores, dense_topk

    data = SyntheticSquad(n_paragraphs=n_docs, n_questions=n_queries,
                          seed=0)
    texts = [p.text for p in data.paragraphs]
    bm25 = BM25Index.build(texts, RCFG)
    dense = DenseIndex.build(texts, RCFG)
    hybrid = HybridRetriever(
        [IndexRetriever("bm25", bm25), IndexRetriever("dense", dense)],
        texts, method=RCFG.hybrid_method)
    queries = [q.text for q in data.questions]
    D = len(texts)

    out = {"n_docs": D, "n_queries": n_queries,
           "vocab_hash_dim": RCFG.vocab_hash_dim,
           "dense_embed_dim": RCFG.dense_embed_dim, "k": max(KS)}

    # ---------------- kernel paths (batched) ----------------
    qv = jnp.asarray(np.stack([bm25.query_vector(q) for q in queries]))
    tf = jnp.asarray(bm25.tf)
    dl = jnp.asarray(bm25.doc_len)
    idf = jnp.asarray(bm25.idf)

    def bm25_kernel():
        s = bm25_scores(qv, tf, dl, idf)         # full (Q, D) matrix...
        return lax.top_k(s, max(KS))[1].block_until_ready()

    qe = jnp.asarray(np.stack([dense.encode(q) for q in queries]))
    emb = jnp.asarray(dense.emb)

    def dense_kernel():
        return dense_topk(qe, emb, k=max(KS))[1].block_until_ready()

    bm25_kernel(), dense_kernel()                # compile warmup
    out["bm25_pallas"] = _throughput(n_queries, D, _best_wall(bm25_kernel))
    out["dense_pallas"] = _throughput(n_queries, D, _best_wall(dense_kernel))

    # ---------------- host serving paths (per query) ----------------
    for name, r in (("bm25_host", IndexRetriever("bm25", bm25)),
                    ("dense_host", IndexRetriever("dense", dense)),
                    ("hybrid_host", hybrid)):
        wall = _best_wall(lambda r=r: [r.topk(q, max(KS)) for q in queries])
        out[name] = _throughput(n_queries, D, wall)

    # ---------------- cache satellite ----------------
    suite, cache = resolve_retrievers(
        {"bm25": IndexRetriever("bm25", bm25), "hybrid": hybrid},
        bm25, cache_size=4 * n_queries)
    cold = time.perf_counter()
    for q in queries:
        suite["hybrid"].passages(q, 5)
    cold = time.perf_counter() - cold
    warm = time.perf_counter()
    for q in queries:
        suite["hybrid"].passages(q, 5)
    warm = time.perf_counter() - warm
    out["cached"] = {
        "hits": cache.hits, "lookups": cache.lookups,
        "hit_rate": round(cache.hits / max(cache.lookups, 1), 3),
        "warm_speedup": round(cold / max(warm, 1e-9), 1)}

    # ---------------- hit@k quality table ----------------
    answerable = [q for q in data.questions if q.answerable and q.gold_answer]
    quality = {}
    for name, r in (("bm25", IndexRetriever("bm25", bm25)),
                    ("dense", IndexRetriever("dense", dense)),
                    ("hybrid", hybrid)):
        row = {}
        for k in KS:
            hits = sum(any(q.gold_answer in p for p in r.passages(q.text, k))
                       for q in answerable)
            row[f"hit@{k}"] = round(hits / max(len(answerable), 1), 3)
        quality[name] = row
    out["hit_at_k"] = quality

    print(f"{'retriever':>14s} {'q/s':>9s} {'Mscores/s':>10s}")
    for name in ("bm25_pallas", "dense_pallas", "bm25_host", "dense_host",
                 "hybrid_host"):
        r = out[name]
        print(f"{name:>14s} {r['queries_per_s']:9.1f} "
              f"{r['mscores_per_s']:10.3f}")
    print("hit@k:", json.dumps(quality))
    print("cache:", json.dumps(out["cached"]))

    # ---------------- sharded probe (forced 8 host devices) ----------------
    if probe:
        print("# forced-8-device sharded retrieval probe ...")
        out["sharded_probe"] = _sharded_probe()
        print("probe:", json.dumps(out["sharded_probe"]))

    # ---------------- hybrid9 refusal-collapse check ----------------
    print("# hybrid9 cheap-profile refusal-collapse check ...")
    out["hybrid9_refusal_collapse"] = _refusal_collapse_check()
    print("collapse:", json.dumps(out["hybrid9_refusal_collapse"]))

    save_artifact("BENCH_retrieval", out)
    (Path(__file__).resolve().parents[1] / "BENCH_retrieval.json"
     ).write_text(json.dumps(out, indent=1))
    return {"dense_pallas_qps": out["dense_pallas"]["queries_per_s"],
            "hybrid_hit@5": quality["hybrid"]["hit@5"],
            "hybrid9_collapsed":
                out["hybrid9_refusal_collapse"]["collapsed"]}


_PROBE_SCRIPT = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from jax.sharding import Mesh

from repro.core.config import RetrievalConfig
from repro.data.synthetic_squad import SyntheticSquad
from repro.retrieval import (BM25Index, DenseIndex, DistributedBM25,
                             DistributedDenseIndex)

cfg = RetrievalConfig(vocab_hash_dim=1024, dense_embed_dim=256)
data = SyntheticSquad(n_paragraphs=256, n_questions=16, seed=3)
texts = [p.text for p in data.paragraphs]
bm25 = BM25Index.build(texts, cfg)
dense = DenseIndex.build(texts, cfg)
mesh = Mesh(np.array(jax.devices()).reshape(8, 1), ("data", "model"))
qv = np.stack([bm25.query_vector(q.text) for q in data.questions])
qe = np.stack([dense.encode(q.text) for q in data.questions])

report = {"devices": len(jax.devices()), "n_docs": len(texts)}
dist_b = DistributedBM25(mesh, bm25.tf, bm25.doc_len, bm25.idf)
dist_d = DistributedDenseIndex(mesh, dense.emb)
for name, dist, q, oracle in (("bm25", dist_b, qv, bm25),
                              ("dense", dist_d, qe, dense)):
    i, s = dist.topk(q, k=10)                       # compile warmup
    t0 = time.perf_counter()
    i, s = dist.topk(q, k=10)
    wall = time.perf_counter() - t0
    # bm25 sums saturate differently across shard reduction orders, so
    # exact ties at the k boundary may reorder: require >=9/10 overlap
    # per query (the test_distributed_retrieval tolerance); dense gets
    # the strict id-identical check below
    ok = all(len(set(i[j].tolist()) &
                 set(oracle.topk(data.questions[j].text, 10)[0].tolist()))
             >= 9 for j in range(len(data.questions)))
    report[name] = {"wall_s": round(wall, 4), "id_parity": bool(ok),
                    "queries_per_s": round(len(q) / wall, 1)}
# dense merge must be id-IDENTICAL (ordered), not just set-equal
exact = all(dist_d.topk(qe, k=10)[0][j].tolist() ==
            dense.topk(data.questions[j].text, 10)[0].tolist()
            for j in range(len(data.questions)))
report["dense"]["id_identical"] = bool(exact)
print("PROBE_JSON:" + json.dumps(report))
"""


def _sharded_probe() -> dict:
    root = Path(__file__).resolve().parents[1]
    # a CPU probe on forced host devices: the parent may hold the
    # accelerator, which one process at a time can use
    env = dict(os.environ, PYTHONPATH=f"{root / 'src'}:{root}",
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _PROBE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    for line in res.stdout.splitlines():
        if line.startswith("PROBE_JSON:"):
            return json.loads(line[len("PROBE_JSON:"):])
    raise RuntimeError("sharded retrieval probe failed:\n"
                       f"{(res.stderr or res.stdout)[-800:]}")


def _refusal_collapse_check(n_train: int = 300, n_eval: int = 100,
                            n_paragraphs: int = 300) -> dict:
    """Compact hybrid9 failure-mode check (paper §6.2 convention):
    cheap-profile Argmax-CE refusal share vs the constrained
    objective's, with retriever choice in the action set."""
    import dataclasses

    from repro.core.actions import SLO_PROFILES
    from repro.core.metrics import evaluate_actions
    from repro.core.offline_log import build_testbed
    from repro.routing import ConstrainedPolicy, MLPPolicy, get_action_space

    space = get_action_space("hybrid9")
    cfg = TestbedConfig(n_train=n_train, n_eval=n_eval,
                        n_paragraphs=n_paragraphs,
                        router=RouterConfig(n_actions=space.n_actions,
                                            n_epochs=15))
    _, _, _, train_log, eval_log = build_testbed(cfg, space)
    profile = SLO_PROFILES["cheap"]
    rewards = train_log.rewards(profile)
    # the Lagrangian caps expected refusal PROBABILITY; with 9 actions
    # the other logits split ~0.6 of the mass 8 ways, so the paper's
    # 0.45 cap never flips the argmax — the cap must push p(refuse)
    # toward ~1/9 before routing changes.  0.2 binds (collapse is
    # HARDER to mitigate as the action set grows — a failure-mode
    # scaling observation the bench records).
    rates = {}
    for name, pol in (
            ("argmax_ce", MLPPolicy.train(train_log, rewards, cfg.router,
                                          objective="argmax_ce")),
            ("constrained", ConstrainedPolicy.train(train_log, rewards,
                                                    cfg.router,
                                                    refusal_cap=0.2))):
        rep = evaluate_actions(eval_log, pol.actions(eval_log.states),
                               profile, name)
        rates[name] = round(rep.refusal_rate, 3)
    return {"slo": "cheap", "n_eval": n_eval, **rates,
            "collapsed": rates["argmax_ce"] > 0.5,
            "mitigated": rates["constrained"] < rates["argmax_ce"]}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (smaller corpus/stream)")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the forced-8-device sharded probe")
    args = ap.parse_args()
    kw = dict(n_docs=256, n_queries=16) if args.quick else {}
    print(main(probe=not args.no_probe, **kw))
