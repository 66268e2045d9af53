"""The harness end to end on the CPU, at the tiny cell of conftest.py:
a sound run is correct, a broken timed path is not, the float8 control
reads far above the program, and a configuration, mix and metric added
as files are found by name."""
from __future__ import annotations

import json

import numpy as np

import run
from conftest import TINY, TINY_CLOSED


def test_sound_run_is_correct(checkout):
    res = run.execute(checkout, TINY, 2**40 + 7, 3.0, False,
                      require_chip=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert set(res["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_closed_loop_run_is_correct(checkout):
    res = run.execute(checkout, TINY_CLOSED, 9, 4.0, False,
                      require_chip=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["out_tokens_per_s"]["value"] > 0
    assert set(res["metrics"]) == {"out_tokens_per_s", "setup_s"}


def test_altered_token_is_not_correct(checkout):
    """A token altered where it is produced: every generation the
    engine hands back has its last token shifted by one."""
    def tamper(system):
        poll = system.engine.poll

        def bad_poll():
            out = poll()
            for g in out.values():
                if len(g.tokens):
                    g.tokens[-1] = (g.tokens[-1] + 1) % 512
            return out
        system.engine.poll = bad_poll

    res = run.execute(checkout, TINY, 11, 3.0, False, require_chip=False,
                      tamper=tamper)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"][
        "logit_gap"]["limit"]


def test_altered_route_is_not_correct(checkout):
    """Each routing decision moved to the next action of the space."""
    import dataclasses

    def tamper(system):
        route = system.policy.route
        n = len(system.space)

        def bad_route(*a, **kw):
            d = route(*a, **kw)
            return dataclasses.replace(
                d, actions=(np.asarray(d.actions) + 1) % n)
        system.policy.route = bad_route

    res = run.execute(checkout, TINY, 13, 3.0, False, require_chip=False,
                      tamper=tamper)
    assert not res["correct"]
    assert res["checks"]["route_mismatch"]["value"] > 0


def test_altered_retrieval_is_not_correct(checkout):
    """BM25 hands back the worst-scoring passages instead of the best."""
    def tamper(system):
        inner = system.retriever.inner

        class Worst:
            name, index = inner.name, inner.index

            def topk(self, query, k):
                ids, scores = inner.topk(query, len(inner.index.texts))
                return ids[::-1][:k], scores[::-1][:k]
        system.retriever.inner = Worst()

    res = run.execute(checkout, TINY, 17, 3.0, False, require_chip=False,
                      tamper=tamper)
    assert not res["correct"]
    assert res["checks"]["bm25_mismatch"]["value"] > 0


def test_control_reads_far_above_the_program(checkout):
    """The float8 control in the program's place is not correct at the
    committed limit, where the program is."""
    res = run.execute(checkout, TINY, 5, 3.0, False, require_chip=False,
                      control=True)
    prog = max(res["control"]["gaps"])
    ctl = max(res["control"]["control_gaps"])
    assert ctl > 0 and ctl >= 3 * prog, (prog, ctl)
    assert res["correct"], res["checks"]
    assert res["control_correct"] is False


def test_files_found_by_name(checkout):
    """A throwaway metric file and its BENCHMARK.json entry; no code
    edit."""
    (checkout / "bench/metrics/slice_s.rag.py").write_text(
        "def read(ctx):\n    return ctx.window_s\n")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "slice_s.rag", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "scheduler",
        "moves": "latency_p50_ms", "workloads": [TINY]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    # the copy's peak table learns the CPU so the traced run can reduce
    peaks = json.loads((checkout / "bench/peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
    (checkout / "bench/peaks.json").write_text(json.dumps(peaks))
    res = run.execute(checkout, TINY, 3, 3.0, True, require_chip=False)
    assert res["correct"], res["checks"]
    got = res["metrics"]["slice_s.rag"]["value"]
    assert np.isclose(got, res["device"]["window_s"])
