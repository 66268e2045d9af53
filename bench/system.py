"""The system under test, built for one cell, and what the benchmark
records of it.

The window drives the entry users call: ``AsyncGateway.submit_stream``
with its serving thread started.  Behind it sit the routing policy,
BM25 retrieval, ``ContinuousEngineBackend``, ``ContinuousEngine`` and
the paged executor with the Pallas paged flash-decode kernel.

The benchmark makes the weights and the corpus; the program makes its
router (trained on its own small testbed) and its BM25 index.  Thin
wrappers on the built objects record what the checks and the per-layer
metrics read: the prompt ids the engine received, the tokens it served,
the retrieval ids, each answer's completion time, and in a traced run
the control syncs and the host spans around each layer's calls.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

import corpus as corpus_mod
import weights


class HostSpans:
    """Host intervals on ``time.perf_counter``, by layer, from the
    benchmark's wrappers (traced runs only)."""

    def __init__(self):
        self.spans: List[tuple] = []      # (name, t0, t1)
        self._lock = threading.Lock()

    def wrap(self, name: str, fn):
        def wrapped(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                with self._lock:
                    self.spans.append((name, t0, time.perf_counter()))
        return wrapped


class RecordingRetriever:
    """Passes retrieval through and keeps the ids of the last lookup."""

    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
        self.last: Optional[List[int]] = None

    def topk(self, query: str, k: int):
        return self.inner.topk(query, k)

    def passages(self, query: str, k: int) -> List[str]:
        ids, _ = self.inner.topk(query, k)
        self.last = [int(i) for i in ids]
        return [self.inner.index.texts[i] for i in ids]


class System:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, devices, family, *,
                 trace: bool):
        import jax
        from repro.configs import get_config
        from repro.core.config import (RetrievalConfig, RouterConfig,
                                       TestbedConfig)
        from repro.core.offline_log import build_testbed
        from repro.data.tokenizer import HashTokenizer
        from repro.models import build_model
        from repro.obs import Tracer
        from repro.retrieval.bm25 import BM25Index
        from repro.retrieval.hybrid import IndexRetriever
        from repro.routing import (ContinuousEngineBackend, MLPPolicy,
                                   get_action_space, get_slo_profile)
        from repro.serving.streaming import AdmissionConfig, AsyncGateway

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.devices = devices
        sv = cfg["serving"]
        self.timings: Dict[str, float] = {}
        t = time.perf_counter()

        # the served corpus (benchmark-made, fixed by the mix)
        self.corpus = corpus_mod.generate(mix["corpus"],
                                          mix["corpus"]["seed"])
        bm = mix["bm25"]
        rcfg = RetrievalConfig(vocab_hash_dim=bm["hash_dim"], k1=bm["k1"],
                               b=bm["b"])
        self.index = BM25Index.build(self.corpus.texts, rcfg)
        self.timings["corpus_index_s"] = time.perf_counter() - t

        # the router: the program's testbed and training, fixed seed
        t = time.perf_counter()
        rt = cfg["router"]
        self.router_cfg = RouterConfig(n_epochs=rt["n_epochs"],
                                       seed=rt["seed"])
        tb = TestbedConfig(n_train=rt["n_train"], n_eval=rt["n_eval"],
                           n_paragraphs=rt["n_paragraphs"], seed=rt["seed"],
                           router=self.router_cfg)
        _, _, _, train_log, _ = build_testbed(tb)
        self.policy = MLPPolicy.train(
            train_log, train_log.rewards(get_slo_profile(mix["slo"])),
            self.router_cfg)
        self.space = get_action_space(mix["action_space"])
        want = [(a["k"], a["mode"]) for a in mix["actions"]]
        if [(a.k, a.mode) for a in self.space] != want:
            raise ValueError(f"action space {mix['action_space']} is not "
                             f"the mix's {want}")
        self.timings["router_s"] = time.perf_counter() - t

        # the model, at the configuration's widths and cut, as its
        # family (``family.py``) maps them onto the program's config
        t = time.perf_counter()
        pcfg = family.program_config(
            cfg, get_config(cfg["program_config"],
                            cfg.get("program_variant", "full")))
        family.check_widths(cfg, pcfg)
        self.model = build_model(pcfg)
        mesh, shardings = None, None
        if sv["mp"] > 1:
            from repro.launch.mesh import make_serving_mesh
            from repro.sharding import shardings_for_schema
            mesh = make_serving_mesh(f"dp=1,mp={sv['mp']}", model_cfg=pcfg,
                                     devices=devices)
            shardings = shardings_for_schema(self.model.schema, mesh,
                                             fsdp=False)
        else:
            shardings = jax.sharding.SingleDeviceSharding(devices[0])
        self.params = weights.make_params(self.model.param_shapes(), seed,
                                          family.LEAVES, shardings)
        jax.block_until_ready(self.params)
        self.timings["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        # the references check BM25 retrieval; another retriever needs
        # its own reference first
        if mix["retriever"] != "bm25":
            raise ValueError(f"unknown retriever {mix['retriever']!r}")
        self.retriever = RecordingRetriever(IndexRetriever("bm25",
                                                           self.index))
        self.backend = ContinuousEngineBackend.create(
            self.model, self.params, HashTokenizer(pcfg.vocab_size),
            self.index, mesh=mesh, num_slots=mix["num_slots"],
            max_prompt_len=sv["max_prompt_len"],
            max_new_tokens=mix["max_new_tokens"],
            sync_every=sv["sync_every"], prefill_batch=sv["prefill_batch"],
            retrievers={"bm25": self.retriever}, paged=bool(sv["paged"]),
            page_size=sv["page_size"],
            prefix_sharing=bool(sv["prefix_sharing"]))
        self.engine = self.backend.engine
        self.tracer = Tracer(time.perf_counter) if trace else None
        self.gateway = AsyncGateway(
            self.policy, self.backend, router_cfg=self.router_cfg,
            index=self.index, action_space=self.space,
            admission=AdmissionConfig(**mix["admission"]),
            deadline_ms=float(mix["deadline_ms"]), adaptive_refusal=False,
            on_outcome=self._on_outcome, tracer=self.tracer)
        self.timings["engine_s"] = time.perf_counter() - t

        # records
        self.prompts: Dict[int, np.ndarray] = {}     # rid -> ids
        self.gens: Dict[int, object] = {}            # rid -> generation
        self.submits: Dict[int, tuple] = {}          # qid -> (rid, action,
        #                                              retrieval ids)
        self.done: Dict[int, tuple] = {}             # qid -> (t, action)
        self.on_done = None                          # closed-loop hook
        self.host = HostSpans() if trace else None
        self.syncs: List[tuple] = []                 # (t, active, gen)
        self.admits: List[tuple] = []                # (t, slots, padded
        #                                              and real lengths)
        self._install_recorders()

    def _install_recorders(self) -> None:
        eng, be = self.engine, self.backend
        submit, poll = eng.submit, eng.poll
        stream_submit = be.stream_submit

        def rec_submit(rid, prompt, *a, **kw):
            self.prompts[rid] = np.asarray(prompt, np.int32)
            return submit(rid, prompt, *a, **kw)

        def rec_poll():
            out = poll()
            self.gens.update(out)
            return out

        def rec_stream_submit(question, action, **kw):
            self.retriever.last = None
            rid, imm = stream_submit(question, action, **kw)
            self.submits[question.qid] = (rid, action.idx,
                                          self.retriever.last)
            return rid, imm

        eng.submit, eng.poll = rec_submit, rec_poll
        be.stream_submit = rec_stream_submit
        ex = eng.executor
        sync, admit = ex.sync_control, ex.admit_paged

        def rec_sync():
            out = sync()
            self.syncs.append((time.perf_counter(), out[0].copy(),
                               out[1].copy()))
            return out

        def rec_admit(tokens, slot_idx, limits, pos0, *a):
            # rows are right-padded with PAD (id 0); real ids are >= 1
            self.admits.append((time.perf_counter(), np.array(slot_idx),
                                np.array(pos0) + tokens.shape[1],
                                np.array(pos0) + np.count_nonzero(
                                    tokens, axis=1)))
            return admit(tokens, slot_idx, limits, pos0, *a)

        ex.sync_control, ex.admit_paged = rec_sync, rec_admit
        if self.host is None:
            return
        hs = self.host
        ex.sync_control = hs.wrap("sync_wait", ex.sync_control)
        ex.admit_paged = hs.wrap("prefill_dispatch", ex.admit_paged)
        ex.decode_chunk = hs.wrap("decode_dispatch", ex.decode_chunk)
        eng.poll = hs.wrap("engine_step", eng.poll)
        gw = self.gateway
        gw.state_fn = hs.wrap("route_features", gw.state_fn)
        self.policy.route = hs.wrap("route_policy", self.policy.route)
        self.retriever.passages = hs.wrap("retrieval",
                                          self.retriever.passages)
        be.stream_submit = hs.wrap("submit", be.stream_submit)

    def generated(self) -> List[tuple]:
        """``(t, tokens generated so far)`` at each control sync, as the
        device reports them: a slot's generation count grows by what it
        gained since the previous sync, or by all of it when the slot
        was admitted afresh in between."""
        out, total, prev, i = [], 0, None, 0
        for t, _, gen in self.syncs:
            fresh = set()
            while i < len(self.admits) and self.admits[i][0] <= t:
                fresh.update(int(s) for s in self.admits[i][1])
                i += 1
            for s, g in enumerate(gen):
                old = 0 if (prev is None or s in fresh) else int(prev[s])
                total += max(int(g) - old, 0)
            prev = gen
            out.append((t, total))
        return out

    def _on_outcome(self, request, action, outcome, reward) -> None:
        self.done[request.qid] = (time.perf_counter(), action.idx)
        if self.on_done is not None:
            self.on_done(request.qid)

    def warm(self) -> None:
        """Shapes the window uses that serving does not compile on its
        own before the window: the router MLP at every micro-batch
        size the gateway routes."""
        dim = self.router_cfg.state_dim
        for b in range(1, self.gateway.route_batch + 1):
            self.policy.route(np.zeros((b, dim), np.float32))

    def free_engine(self) -> None:
        """Free every device buffer of the engine but the weights."""
        import jax
        keep = {id(x) for x in jax.tree_util.tree_leaves(self.params)}
        ex = self.engine.executor
        for leaf in jax.tree_util.tree_leaves(vars(ex)):
            if (isinstance(leaf, jax.Array) and id(leaf) not in keep
                    and not leaf.is_deleted()):
                leaf.delete()
