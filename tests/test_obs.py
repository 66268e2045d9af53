"""Unified telemetry plane: metrics registry, span tracer, latency
attribution, and the instrumented serving stack.

Unit layers run against hand-fed instruments; the integration layer
replays a seeded open-loop trace through AsyncGateway + the simulator
backend in virtual time and asserts the PR's acceptance criteria:
every terminal request carries a per-stage breakdown whose top-level
stage sum equals end-to-end latency, the span trees are well-formed,
the Chrome trace and Prometheus exposition parse, and the healthy path
is bit-identical with tracing disabled.
"""
from __future__ import annotations

import json
import math

import numpy as np
import pytest

from repro.core.config import RouterConfig, TestbedConfig
from repro.core.offline_log import build_testbed
from repro.obs import (KINDS, NULL_TRACER, STAGES, TOP_LEVEL, Histogram,
                       MetricsRegistry, NullTracer, RequestBreakdown,
                       StageAttribution, Tracer)
from repro.routing import FixedPolicy, SimulatorBackend
from repro.serving.slo_budget import LatencyReservoir
from repro.serving.streaming import AdmissionConfig, AsyncGateway
from repro.serving.traffic import (LoadGenerator, PoissonProcess,
                                   VirtualClock, build_trace)

ZERO_STATE = lambda qs: np.zeros((len(qs), 1))


@pytest.fixture(scope="module")
def testbed():
    cfg = TestbedConfig(n_train=40, n_eval=16, n_paragraphs=60,
                        router=RouterConfig(n_epochs=1))
    return cfg, build_testbed(cfg)


# --- MetricsRegistry --------------------------------------------------------


def test_registry_exposition_and_snapshot():
    clock = VirtualClock()
    clock.advance(3.5)
    reg = MetricsRegistry(clock.now)
    c = reg.counter("served_total", "requests served")
    g = reg.gauge("queue_depth", "pending")
    h = reg.histogram("latency_ms", "per-request", bounds=(1.0, 10.0))
    c.inc(); c.inc(2.0)
    g.set(4)
    h.observe(0.5); h.observe(5.0); h.observe(99.0)
    text = reg.exposition()
    lines = text.splitlines()
    assert "# HELP repro_served_total requests served" in lines
    assert "# TYPE repro_served_total counter" in lines
    assert "repro_served_total 3" in lines
    assert "repro_queue_depth 4" in lines
    # cumulative buckets + implicit +Inf
    assert 'repro_latency_ms_bucket{le="1"} 1' in lines
    assert 'repro_latency_ms_bucket{le="10"} 2' in lines
    assert 'repro_latency_ms_bucket{le="+Inf"} 3' in lines
    assert "repro_latency_ms_count 3" in lines
    # every non-comment line is `name[{labels}] value`
    for ln in lines:
        if not ln.startswith("#"):
            name, val = ln.rsplit(" ", 1)
            assert name.startswith("repro_") and float(val) >= 0
    snap = json.loads(reg.snapshot_json())
    assert snap["clock_s"] == 3.5                    # injected clock
    assert snap["metrics"]["served_total"]["value"] == 3.0
    assert snap["metrics"]["latency_ms"]["count"] == 3


def test_registry_rejects_duplicates_bad_names_and_clockless():
    reg = MetricsRegistry(lambda: 0.0)
    reg.counter("served_total")
    with pytest.raises(ValueError, match="registered twice"):
        reg.gauge("served_total")
    with pytest.raises(ValueError, match="lowercase_snake"):
        reg.counter("ServedTotal")
    with pytest.raises(TypeError, match="clock"):
        MetricsRegistry()  # type: ignore[call-arg]
    with pytest.raises(TypeError, match="clock"):
        Tracer()  # type: ignore[call-arg]


def test_registry_collector_runs_at_scrape_only():
    reg = MetricsRegistry(lambda: 0.0)
    g = reg.gauge("resident")
    state = {"v": 0, "scrapes": 0}

    def scrape():
        state["scrapes"] += 1
        g.set(state["v"])

    reg.register_collector(scrape)
    state["v"] = 7
    assert state["scrapes"] == 0                     # hot path untouched
    assert "repro_resident 7" in reg.exposition()
    assert state["scrapes"] == 1


def test_histogram_merge_associative_and_commutative():
    bounds = (1.0, 5.0, 25.0)

    def build(vals):
        h = Histogram("m", bounds=bounds)
        for v in vals:
            h.observe(v)
        return h

    a = build([0.5, 3.0])
    b = build([30.0, 4.0, 0.1])
    c = build([7.0])

    def key(h):
        return (h.counts, h.inf_count, h.total, h.count)

    assert key(a.merge(b).merge(c)) == key(a.merge(b.merge(c)))
    assert key(a.merge(b)) == key(b.merge(a))
    # merge returns a NEW histogram; inputs unchanged
    assert a.count == 2 and b.count == 3
    merged = a.merge(b).merge(c)
    assert merged.count == 6 and merged.inf_count == 1
    with pytest.raises(ValueError, match="different bounds"):
        a.merge(Histogram("m", bounds=(1.0, 2.0)))


def test_histogram_quantile_and_empty():
    h = Histogram("m", bounds=(10.0, 20.0))
    assert math.isnan(h.quantile(0.5))
    for v in (1.0, 2.0, 3.0, 15.0):
        h.observe(v)
    assert 0.0 < h.quantile(0.5) <= 10.0
    assert 10.0 < h.quantile(0.99) <= 20.0


# --- LatencyReservoir percentile edges --------------------------------------


def test_latency_reservoir_empty_is_nan():
    r = LatencyReservoir()
    assert math.isnan(r.percentile(50))
    p = r.percentiles()
    assert p["n"] == 0 and math.isnan(p["p99_ms"])


def test_latency_reservoir_single_sample():
    r = LatencyReservoir()
    r.record(42.0)
    for q in (0, 50, 99, 100):
        assert r.percentile(q) == 42.0
    assert r.percentiles()["n"] == 1


def test_latency_reservoir_exact_capacity_boundary():
    r = LatencyReservoir(capacity=8, seed=0)
    r.extend(float(i) for i in range(8))
    # below/at capacity the reservoir is exact — no sampling yet
    assert len(r) == 8 and r.count == 8
    assert r.percentile(0) == 0.0 and r.percentile(100) == 7.0
    r.record(100.0)                     # crosses the boundary
    assert len(r) == 8 and r.count == 9
    # deterministic for a given seed + insert sequence
    r2 = LatencyReservoir(capacity=8, seed=0)
    r2.extend(float(i) for i in range(8))
    r2.record(100.0)
    assert r.percentiles() == r2.percentiles()


# --- Tracer unit ------------------------------------------------------------


def _finish_simple(tr, qid=1, t0=0.0):
    tr.begin_request(qid, t0)
    tr.mark(qid, "queue_wait", t0, t0 + 0.001)
    tr.mark(qid, "admission", t0 + 0.001, t0 + 0.003)
    tr.mark(qid, "retrieval", t0 + 0.0015, t0 + 0.0025)
    tr.mark(qid, "prefill", t0 + 0.003, t0 + 0.004)
    tr.mark(qid, "decode", t0 + 0.004, t0 + 0.009)
    tr.mark(qid, "harvest", t0 + 0.009, t0 + 0.010)
    return tr.finish_request(qid, "completed", t=t0 + 0.010,
                             cost_tokens=17.0)


def test_tracer_breakdown_sums_and_dominant_stage():
    tr = Tracer(lambda: 0.0)
    bd = _finish_simple(tr)
    assert bd.kind == "completed" and bd.cost_tokens == 17.0
    assert bd.e2e_ms == pytest.approx(10.0)
    # top-level chain is contiguous: stage sum == e2e exactly
    assert bd.stage_sum_ms == pytest.approx(bd.e2e_ms)
    # retrieval (1ms) nests inside admission (2ms): no double count,
    # decode (5ms) dominates
    assert bd.dominant_stage == "decode"
    assert tr.n_finished == 1 and tr.n_open == 0
    d = bd.as_dict()
    assert d["dominant_stage"] == "decode"
    assert set(d["stages"]) <= set(TOP_LEVEL) | {"retrieval"}


def test_tracer_rejects_unknown_kind_and_ignores_unknown_qid():
    tr = Tracer(lambda: 0.0)
    tr.begin_request(1, 0.0)
    with pytest.raises(ValueError, match="unknown terminal kind"):
        tr.finish_request(1, "exploded")
    tr.mark(99, "decode", 0.0, 1.0)          # unknown qid: no-op
    assert tr.finish_request(99, "completed") is None
    tr.begin_request(2, 0.0)
    assert tr.finish_request(2, "completed", t=0.5) is not None


def test_tracer_note_adopt_and_discard():
    tr = Tracer(lambda: 0.0)
    tr.begin_request(5, 0.0)
    tr.note("retrieval", 0.001, 0.002, retriever="bm25", k=3)
    tr.adopt(5)
    bd = tr.finish_request(5, "completed", t=0.01)
    assert bd.stages["retrieval"] == pytest.approx(1.0)
    tree = tr.sampled_trees[0]
    retr = [s for s in tree.spans if s.name == "retrieval"][0]
    assert retr.attrs == {"retriever": "bm25", "k": 3}
    # discarded notes never attach
    tr.begin_request(6, 0.0)
    tr.note("retrieval", 0.0, 0.001)
    tr.discard_pending()
    tr.adopt(6)
    assert "retrieval" not in tr.finish_request(6, "completed", t=0.01).stages


def test_tracer_problems_catch_malformed_trees():
    tr = Tracer(lambda: 0.0)
    _finish_simple(tr)
    assert tr.problems() == []
    # open request
    tr.begin_request(2, 0.0)
    assert any("never finished" in p for p in tr.problems())
    tr.finish_request(2, "faulted", t=0.001)
    assert tr.problems() == []
    # span escaping the root interval
    tr.begin_request(3, 1.0)
    tr.mark(3, "decode", 0.5, 2.0)
    tr.finish_request(3, "completed", t=1.5)
    assert any("escapes root" in p for p in tr.problems())


def test_tracer_chrome_trace_export():
    tr = Tracer(lambda: 0.0, annotate=None)
    _finish_simple(tr)
    with tr.span("engine.step"):
        with tr.span("engine.decode_dispatch", steps=4):
            pass
    data = json.loads(tr.chrome_trace_json(indent=1))
    events = data["traceEvents"]
    assert data["displayTimeUnit"] == "ms"
    # the artifact carries its own well-formedness audit
    assert data["otherData"] == {"n_finished": 1, "n_open": 0,
                                 "problems": []}
    x = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    # host + requests processes, one named track per host thread
    assert sorted(e["name"] for e in meta) == [
        "process_name", "process_name", "thread_name"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in x)
    root = [e for e in x if e["name"] == "request[completed]"]
    assert len(root) == 1 and root[0]["pid"] == 1
    # children stay inside the root interval (µs domain)
    for e in x:
        if e["pid"] == 1 and e is not root[0]:
            assert e["ts"] >= root[0]["ts"] - 1e-6
            assert (e["ts"] + e["dur"]
                    <= root[0]["ts"] + root[0]["dur"] + 1e-6)
    host = {e["name"]: e for e in x if e["pid"] == 0}
    assert set(host) == {"engine.step", "engine.decode_dispatch"}
    step, chunk = host["engine.step"], host["engine.decode_dispatch"]
    assert step["args"]["parent"] == 0
    assert chunk["args"]["parent"] == step["args"]["sid"] > 0
    assert chunk["args"]["steps"] == 4 and chunk["tid"] == step["tid"]


def test_tracer_sampling_bounds_memory():
    tr = Tracer(lambda: 0.0, max_trees=16, seed=3)
    for i in range(200):
        tr.begin_request(i, float(i))
        tr.finish_request(i, "completed", t=float(i) + 0.001)
    assert len(tr.sampled_trees) == 16
    assert tr.n_finished == 200
    assert len(tr.breakdowns) == 200        # every request still counted


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.now() == 0.0
    NULL_TRACER.begin_request(1, 0.0)
    NULL_TRACER.mark(1, "decode", 0.0, 1.0)
    NULL_TRACER.note("retrieval", 0.0, 1.0)
    NULL_TRACER.adopt(1)
    with NULL_TRACER.span("engine.prefill_dispatch"):
        pass
    assert NULL_TRACER.finish_request(1, "completed") is None
    assert NULL_TRACER.stage_percentiles() == {}
    assert NULL_TRACER.problems() == []
    assert isinstance(NULL_TRACER, NullTracer)


# --- StageAttribution / budget integration ----------------------------------


def test_stage_attribution_windowed_report():
    att = StageAttribution(window=4)
    for i in range(6):
        att.record(RequestBreakdown(
            qid=i, kind="completed", e2e_ms=10.0,
            stages={"queue_wait": 1.0, "admission": 2.0,
                    "retrieval": 1.5, "decode": 7.0}))
    assert len(att) == 4                    # window bounds the deque
    rep = att.report()
    assert rep["n"] == 4 and rep["dominant_stage"] == "decode"
    # admission share is net of nested retrieval
    assert rep["stage_ms"]["admission"] == pytest.approx(2.0)
    assert rep["stage_share"]["retrieval"] > 0
    shares = sum(rep["stage_share"].values())
    assert shares == pytest.approx(1.0, abs=1e-6)


# --- open-loop integration: the acceptance criteria -------------------------


def _run_traced(data, pipe, *, rate=500.0, n=80, deadline_ms=1000.0,
                backlog=4, traced=True):
    """500 req/s into a ~4-slot service with a tiny backlog cap: the
    queue must overflow, so the run exercises shed AND completed
    terminal kinds (mirrors test_backlog_shedding_engages_under_overload)."""
    clock = VirtualClock()
    backend = SimulatorBackend(pipe, stream_slots=4, service_polls=2,
                               clock=clock.now)
    kw = ({"tracer": Tracer(clock.now),
           "metrics": MetricsRegistry(clock.now)} if traced else {})
    gw = AsyncGateway(FixedPolicy(2), backend, state_fn=ZERO_STATE,
                      clock=clock.now, deadline_ms=deadline_ms,
                      admission=AdmissionConfig(max_backlog=backlog), **kw)
    trace = build_trace(data.questions[:8], PoissonProcess(rate, seed=0),
                        n, deadline_ms=deadline_ms)
    gen = LoadGenerator(gw, trace)
    rep = gen.run_virtual(clock, service_quantum_s=0.01)
    return gw, gen, rep


@pytest.fixture(scope="module")
def traced_run(testbed):
    _, (data, index, pipe, *_rest) = testbed
    return _run_traced(data, pipe)


def test_every_terminal_request_carries_breakdown(traced_run):
    gw, gen, rep = traced_run
    assert rep.offered == 80 and rep.completed == 80
    assert rep.shed > 0                     # overload engaged shedding
    for h in gen.last_handles:
        assert h.done()
        bd = h.breakdown
        assert bd is not None, f"qid {h.request.qid} missing breakdown"
        assert bd.kind in KINDS
        if h.shed:
            assert bd.kind == "shed"
        # top-level stage sum equals end-to-end latency by construction
        assert bd.stage_sum_ms == pytest.approx(bd.e2e_ms, abs=1e-6), \
            (bd.qid, bd.kind, bd.stages, bd.e2e_ms)
    kinds = {h.breakdown.kind for h in gen.last_handles}
    assert "completed" in kinds and "shed" in kinds


def test_traced_run_trees_well_formed_and_export_parses(traced_run):
    gw, gen, rep = traced_run
    tr = gw.tracer
    assert tr.n_open == 0
    assert tr.problems() == []
    data = json.loads(tr.chrome_trace_json())
    assert len([e for e in data["traceEvents"] if e["ph"] == "X"]) > 0
    pct = tr.stage_percentiles()
    assert set(pct) <= set(STAGES) | {"e2e"}
    assert pct["e2e"]["n"] == 80            # every terminal kind counted
    # LoadReport picked the stages table up
    assert rep.stages == pct
    assert "stages" in rep.as_dict()


def test_traced_run_metrics_and_attribution(traced_run):
    gw, gen, rep = traced_run
    text = gw.metrics.exposition()
    served = gw.stats.served
    assert f"repro_gateway_served_total {served}" in text.splitlines()
    assert "repro_gateway_request_latency_ms_bucket" in text
    assert f"repro_gateway_shed_total {gw.stats.shed}" in text.splitlines()
    report = gw.budget.report_dict()
    att = report.get("latency_attribution")
    assert att and att["n"] > 0
    assert att["dominant_stage"] in set(STAGES)


def test_healthy_path_parity_with_tracing_disabled(testbed):
    """Acceptance criterion: the traced run and the NULL_TRACER run are
    token-identical — same outcomes, same latencies, same report."""
    _, (data, index, pipe, *_rest) = testbed
    gw_t, gen_t, rep_t = _run_traced(data, pipe, traced=True)
    gw_n, gen_n, rep_n = _run_traced(data, pipe, traced=False)
    assert gw_n.tracer is NULL_TRACER
    d_t, d_n = rep_t.as_dict(), rep_n.as_dict()
    d_t.pop("stages", None)                  # the only traced-run extra
    assert d_t == d_n
    for ht, hn in zip(gen_t.last_handles, gen_n.last_handles):
        assert ht.request.qid == hn.request.qid
        assert ht.shed == hn.shed
        if ht.outcome is not None:
            assert ht.outcome.answer == hn.outcome.answer
            assert ht.outcome.cost_tokens == hn.outcome.cost_tokens
            assert ht.outcome.to_row() == hn.outcome.to_row()
    assert gw_t.stats.served == gw_n.stats.served
    assert gw_t.stats.avg_reward == gw_n.stats.avg_reward


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
