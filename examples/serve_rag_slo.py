"""End-to-end serving driver: batched requests through the full stack.

 request batch -> Gateway (unified routing API)
               -> RoutingPolicy (trained Argmax-CE MLP)
               -> action-bucketed BM25 retrieval at the routed depth
               -> a REAL JAX transformer backend (reduced qwen family)
                  generating answers token-by-token through the KV-cache
                  engine (prefill + decode), one batched call per bucket
               -> per-SLO reward + error-budget accounting.

The generation quality of the tiny local model is irrelevant — the point
is the full serving path: routing, retrieval, batched prefill/decode,
cost accounting, all through the one `repro.routing.Gateway` entry
point (no hand-rolled route→retrieve→generate loop).

    PYTHONPATH=src python examples/serve_rag_slo.py --slo cheap
"""
import argparse
import time

import jax

from repro.configs import get_config
from repro.core.config import TestbedConfig
from repro.core.offline_log import build_testbed
from repro.data.tokenizer import HashTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.routing import (ContinuousEngineBackend, EngineBackend, Gateway,
                           MLPPolicy, Request, get_slo_profile,
                           list_slo_profiles)
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slo", default="quality_first",
                    choices=list_slo_profiles())
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--engine", default="continuous",
                    choices=("continuous", "padded"),
                    help="continuous = slot-based shared decode stream; "
                         "padded = legacy serial per-bucket engine")
    ap.add_argument("--mesh", default=None, metavar="dp=N[,mp=M]",
                    help="shard the continuous engine over a device "
                         "mesh (dp=N slots-on-data, mp=M params "
                         "tensor-parallel on the model axis; pair with "
                         "XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N*M on a CPU host)")
    args = ap.parse_args()
    if args.mesh and args.engine != "continuous":
        ap.error("--mesh requires --engine continuous")
    enable_compile_cache()
    profile = get_slo_profile(args.slo)

    print("# building testbed + routing policy ...")
    cfg = TestbedConfig(n_train=300, n_eval=100, n_paragraphs=200)
    data, index, pipe, train_log, eval_log = build_testbed(cfg)
    policy = MLPPolicy.train(train_log, train_log.rewards(profile),
                             cfg.router, objective="argmax_ce")

    print("# loading local JAX generation backend (reduced qwen family)")
    mcfg = get_config("qwen1.5-32b", "smoke")
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = HashTokenizer(mcfg.vocab_size)
    # slot caches must hold the padded prompt plus the generation
    # budget; the backend pads every prompt to max_prompt_len
    max_prompt_len = 384
    max_len = max_prompt_len + args.max_new_tokens
    if args.engine == "continuous":
        mesh = None
        if args.mesh:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(args.mesh, model_cfg=mcfg)
            print(f"# sharded executor over mesh {args.mesh} "
                  f"({len(jax.devices())} devices; slots on data, "
                  f"params on model)")
        engine = ContinuousEngine(model, params, num_slots=args.batch,
                                  max_len=max_len,
                                  max_new_cap=args.max_new_tokens,
                                  prefill_batch=args.batch, mesh=mesh)
        backend_cls = ContinuousEngineBackend
    else:
        engine = Engine(model, params, max_len=max_len)
        backend_cls = EngineBackend

    def report(req, action, out, rew):
        status = "REFUSED(pre)" if out.refused else out.answer
        print(f"  a{action.idx} (k={action.k:2d},{action.mode:7s}) "
              f"cost={out.cost_tokens:6.0f}  {status:22s} "
              f"q: {req.question.text[:44]}")

    gateway = Gateway(
        policy,
        backend_cls(engine, tok, index, max_prompt_len=max_prompt_len,
                    max_new_tokens=args.max_new_tokens),
        router_cfg=cfg.router, index=index, max_batch=args.batch,
        adaptive_refusal=False, on_outcome=report)

    reqs = [Request(qid=q.qid, question=q, slo=args.slo)
            for q in data.questions[-args.batch:]]
    print(f"# serving {args.batch} requests under SLO={args.slo}\n")
    t0 = time.time()
    stats = gateway.serve(reqs)
    dt = time.time() - t0

    print(f"\nbatch served in {dt:.1f}s; avg SLO reward "
          f"{stats.avg_reward:+.4f}; actions {dict(stats.action_counts)}")


if __name__ == "__main__":
    main()
