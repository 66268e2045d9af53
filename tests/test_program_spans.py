"""Host spans (``Tracer.span``) and the request stages they feed.

Unit cases drive the tracer by hand; the served cases run
``AsyncGateway`` over ``ContinuousEngineBackend`` on the numpy fake
executor of ``test_host_scheduler`` (the real routing, retrieval,
tokenizing, scheduler and accounting code, with no device), on a clock
that advances at every read so that every interval has a width.  The
profiler case captures a few pump iterations with ``jax.profiler`` and
finds the same spans on the host line of the trace.
"""
from __future__ import annotations

import glob
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.config import RouterConfig, TestbedConfig
from repro.core.offline_log import build_testbed
from repro.data.tokenizer import HashTokenizer
from repro.obs import NESTED, NULL_TRACER, TOP_LEVEL, Tracer
from repro.obs import trace as trace_mod
from repro.routing import ContinuousEngineBackend, FixedPolicy
from repro.routing.gateway import Request
from repro.serving.continuous import ContinuousEngine
from repro.serving.streaming import AsyncGateway
from test_host_scheduler import FakeExecutor, arith_gen

ZERO_STATE = lambda qs: np.zeros((len(qs), 1))


class TickClock:
    """A virtual clock that moves 1 ms at every read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1e-3
        return self.t


def _scripted(*values):
    it = iter(values)
    return lambda: next(it)


# --- the span primitive -----------------------------------------------------


def test_span_parent_is_innermost_open_span_of_its_thread():
    tr = Tracer(time.perf_counter, annotate=None)
    barrier = threading.Barrier(2)

    def work(tag):
        with tr.span(f"{tag}.outer", tag=tag):
            barrier.wait()           # both threads hold an open span
            with tr.span(f"{tag}.inner"):
                barrier.wait()
            with tr.span(f"{tag}.second"):
                pass

    threads = [threading.Thread(target=work, args=(t,), name=t)
               for t in ("a", "b")]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    by = {sp.name: sp for sp in tr.spans}
    assert len(by) == 6 and len({sp.sid for sp in tr.spans}) == 6
    for tag, th in zip(("a", "b"), threads):
        outer = by[f"{tag}.outer"]
        assert outer.parent == 0 and outer.attrs == {"tag": tag}
        assert outer.thread == th.ident
        for child in ("inner", "second"):
            sp = by[f"{tag}.{child}"]
            assert sp.parent == outer.sid and sp.thread == th.ident
            assert outer.t0 <= sp.t0 <= sp.t1 <= outer.t1
        assert outer.n_children == 2
    assert by["a.outer"].thread != by["b.outer"].thread
    assert tr.problems() == []
    names = {e["args"]["name"] for e in tr.chrome_trace()["traceEvents"]
             if e["name"] == "thread_name"}
    assert names == {"a", "b"}


def test_span_buffer_stays_bounded():
    tr = Tracer(lambda: 0.0, annotate=None)
    n = trace_mod.MAX_SPANS + 100
    for i in range(n):
        with tr.span("engine.step", i=i):
            pass
    assert len(tr.spans) == trace_mod.MAX_SPANS
    assert [sp.attrs["i"] for sp in tr.spans] == list(
        range(100, n))


def test_spans_from_many_threads_keep_their_own_parents():
    """More threads than cores, switching as often as the interpreter
    allows: every span keeps a unique id and the parent of its own
    thread, and none is lost."""
    tr = Tracer(time.perf_counter, annotate=None)
    n_threads, n_iter = 16, 100
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_iter):
                with tr.span("outer"):
                    with tr.span("inner"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    spans = list(tr.spans)
    assert len(spans) == 2 * n_threads * n_iter
    by_sid = {sp.sid: sp for sp in spans}
    assert len(by_sid) == len(spans)
    for sp in spans:
        if sp.name == "inner":
            up = by_sid[sp.parent]
            assert up.name == "outer" and up.thread == sp.thread
        else:
            assert sp.parent == 0 and sp.n_children == 1
    assert tr.problems() == []


def test_dropped_span_is_not_recorded():
    tr = Tracer(lambda: 0.0, annotate=None)
    with tr.span("gateway.pump") as sp:
        sp.drop()
    with tr.span("gateway.pump", n_events=1):
        pass
    assert [sp.attrs for sp in tr.spans] == [{"n_events": 1}]


def test_span_holds_a_profiler_annotation_of_its_name():
    opened = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    tr = Tracer(lambda: 0.0, annotate=Ann)
    with tr.span("gateway.route"):
        with tr.span("gateway.route.policy"):
            pass
    assert opened == [("enter", "gateway.route"),
                      ("enter", "gateway.route.policy"),
                      ("exit", "gateway.route.policy"),
                      ("exit", "gateway.route")]


def test_null_tracer_span_reads_no_clock_and_opens_no_annotation(
        monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("the disabled tracer read a clock")

    monkeypatch.setattr(time, "perf_counter", boom)
    monkeypatch.setattr(time, "monotonic", boom)
    monkeypatch.setattr(trace_mod, "_profiler_annotation", boom)
    sp = NULL_TRACER.span("gateway.pump")
    assert NULL_TRACER.span("engine.step", n=1) is sp   # one shared object
    with sp as inner:
        inner.set(n_events=3)
        inner.drop()
        with NULL_TRACER.span("gateway.route"):
            pass
    assert (sp.t0, sp.t1, sp.n_children) == (0.0, 0.0, 0)
    assert NULL_TRACER.spans == ()


def test_problems_catch_a_child_that_escapes_its_parent():
    # parent opens at 1.0; the child reads 0.5..0.7 (a clock that went
    # backwards), so it lies outside its parent
    tr = Tracer(_scripted(1.0, 0.5, 0.7, 2.0), annotate=None)
    with tr.span("engine.step"):
        with tr.span("engine.sync_wait"):
            pass
    probs = tr.problems()
    assert len(probs) == 1 and "engine.sync_wait" in probs[0]
    assert "escapes its parent engine.step" in probs[0]
    tr = Tracer(_scripted(1.0, 0.5), annotate=None)
    with tr.span("engine.harvest"):
        pass
    assert tr.problems() == ["host span engine.harvest ends before it "
                             "starts"]


def test_problems_catch_a_nested_stage_outside_its_parent():
    tr = Tracer(lambda: 0.0)
    tr.begin_request(1, 0.0)
    tr.mark(1, "queue_wait", 0.0, 0.010)
    tr.mark(1, "lock_wait", 0.0, 0.020)       # longer than its parent
    tr.mark(1, "admission", 0.010, 0.030)
    tr.finish_request(1, "completed", t=0.030)
    assert tr.problems() == ["request 1 stage lock_wait escapes "
                             "queue_wait"]


# --- the served stream ------------------------------------------------------


@pytest.fixture(scope="module")
def index():
    cfg = TestbedConfig(n_train=40, n_eval=16, n_paragraphs=60,
                        router=RouterConfig(n_epochs=1))
    data, index, *_ = build_testbed(cfg)
    return data, index


def _served(index, clock, *, traced=True, n=10, action=1, annotate=None):
    data, idx = index
    fake = FakeExecutor(arith_gen, num_slots=4, max_len=64, max_new_cap=8,
                        sync_every=2, prefill_batch=2)
    engine = ContinuousEngine(executor=fake, clock=clock)
    backend = ContinuousEngineBackend(engine, HashTokenizer(512), idx,
                                      max_prompt_len=48, max_new_tokens=6)
    tracer = Tracer(clock, annotate=annotate) if traced else None
    gw = AsyncGateway(FixedPolicy(action), backend, state_fn=ZERO_STATE,
                      clock=clock, route_batch=3, tracer=tracer)
    handles = []
    for i, q in enumerate(data.questions[:n]):
        handles.append(gw.submit_stream(Request(qid=i, question=q)))
        if i % 3 == 2:
            gw.pump()
    gw.drain_stream()
    return gw, handles


def test_served_stages_nest_and_sum_to_e2e(index):
    gw, handles = _served(index, TickClock())
    tr = gw.tracer
    assert tr.problems() == []
    trees = {t.qid: t for t in tr.sampled_trees}
    assert len(trees) == len(handles)
    for h in handles:
        bd = h.breakdown
        assert bd.kind == "completed"
        assert bd.stage_sum_ms == pytest.approx(bd.e2e_ms, abs=1e-6)
        assert set(bd.stages) == set(TOP_LEVEL) | set(NESTED)
        stage = {sp.name: sp for sp in trees[h.request.qid].spans}
        for child, parent in NESTED.items():
            c, p = stage[child], stage[parent]
            assert p.t0 <= c.t0 <= c.t1 <= p.t1, (child, c, p)
            assert c.t1 > c.t0                  # every clock read ticks
        assert bd.stages["lock_wait"] <= bd.stages["queue_wait"]
        # prefill ends at the first token, which a sync showed after
        # the dispatch
        assert stage["prefill"].t1 == pytest.approx(h.first_token_t)
        assert h.first_token_ms > 0 and h.first_token_t < h.completed_t


def test_served_host_spans_nest_and_join_the_requests(index):
    gw, handles = _served(index, TickClock())
    spans = list(gw.tracer.spans)
    by_sid = {sp.sid: sp for sp in spans}

    def parent(sp):
        return by_sid[sp.parent].name if sp.parent else None

    names = {sp.name for sp in spans}
    assert names == {
        "gateway.pump", "gateway.route", "gateway.route.features",
        "gateway.route.policy", "gateway.submit", "backend.retrieval",
        "backend.tokenize", "engine.step", "engine.harvest",
        "engine.decode_dispatch", "engine.prefill_dispatch",
        "engine.sync_wait", "gateway.account"}
    want_parent = {
        "gateway.pump": None, "gateway.route": "gateway.pump",
        "gateway.route.features": "gateway.route",
        "gateway.route.policy": "gateway.route",
        "gateway.submit": "gateway.pump",
        "backend.retrieval": "gateway.submit",
        "backend.tokenize": "gateway.submit",
        "engine.step": "gateway.pump", "engine.harvest": "engine.step",
        "engine.decode_dispatch": "engine.step",
        "engine.prefill_dispatch": "engine.step",
        "engine.sync_wait": "engine.step", "gateway.account": "gateway.pump"}
    for sp in spans:
        assert parent(sp) == want_parent[sp.name], sp.name
    # every kept pump iteration did something
    for sp in spans:
        if sp.name == "gateway.pump":
            assert sp.attrs["n_events"] > 0 or sp.n_children > 0
    tok = [sp for sp in spans if sp.name == "backend.tokenize"]
    assert all(sp.attrs["padded"] == 48
               and 0 < sp.attrs["unpadded"] <= 48 for sp in tok)
    # a request's admission carries the rid its prefill dispatch names
    pre = [sp for sp in spans if sp.name == "engine.prefill_dispatch"]
    rids = [r for sp in pre for r in sp.attrs["rids"]]
    assert sorted(rids) == list(range(len(handles)))
    assert all(sp.attrs["padded"] == 2 * 48
               and 0 < sp.attrs["unpadded"] <= 2 * 48 for sp in pre)
    for tree in gw.tracer.sampled_trees:
        adm = [s for s in tree.spans if s.name == "admission"][0]
        assert adm.attrs["rid"] in rids


def test_idle_pump_leaves_no_span(index):
    gw, _ = _served(index, TickClock(), n=3)
    before = len(gw.tracer.spans)
    for _ in range(5):
        assert gw.pump() == 0
    assert len(gw.tracer.spans) == before


def test_served_tokens_identical_with_tracing_on_and_off(index):
    gw_t, h_t = _served(index, TickClock(), traced=True)
    gw_n, h_n = _served(index, TickClock(), traced=False)
    assert gw_n.tracer is NULL_TRACER
    for a, b in zip(h_t, h_n):
        assert a.outcome.to_row() == b.outcome.to_row()
    gens_t = gw_t.backend.engine.executor._out
    gens_n = gw_n.backend.engine.executor._out
    assert np.array_equal(gens_t, gens_n)
    assert gw_t.stats.served == gw_n.stats.served == len(h_t)


# --- the profiler's timeline ------------------------------------------------


def test_spans_appear_on_the_profilers_host_timeline(index, tmp_path):
    """A ``jax.profiler`` capture around a served stream holds each
    span as an annotation of its name on the host plane, nested as
    the tracer recorded it and as long within 1 ms."""
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        gw, _ = _served(index, time.perf_counter, n=6,
                        annotate=trace_mod._profiler_annotation())
    finally:
        jax.profiler.stop_trace()
    spans = list(gw.tracer.spans)
    assert spans
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    prof = ProfileData.from_file(path[0])
    names = {sp.name for sp in spans}
    events = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
              for plane in prof.planes if plane.name.startswith("/host")
              for line in plane.lines for ev in line.events
              if ev.name in names]
    assert {n for n, _, _ in events} == names
    # the two clocks differ by one offset: take it from the first span
    first = min(spans, key=lambda sp: sp.t0)
    off = min(s for n, s, _ in events if n == first.name) - first.t0
    match = {}
    for sp in spans:
        s, d = min(((s, d) for n, s, d in events if n == sp.name),
                   key=lambda e: abs(e[0] - off - sp.t0))
        assert abs(s - off - sp.t0) < 1e-3, sp.name
        assert abs(d - (sp.t1 - sp.t0)) < 1e-3
        match[sp.sid] = (s, s + d)
    for sp in spans:
        if sp.parent in match:
            (a, b), (pa, pb) = match[sp.sid], match[sp.parent]
            assert pa <= a and b <= pb, sp.name
