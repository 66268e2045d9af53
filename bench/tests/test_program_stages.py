"""The per-layer metrics that read the program's request stages
(``lock_wait``, ``route`` and the ``prefill`` that ends at the first
token): each reads its stage's 90th percentile from a context whose
breakdowns carry it, and nothing from one whose breakdowns do not, as
from a program that marks no such stage."""
from pathlib import Path

import numpy as np
import pytest

import tracewin

BENCH = Path(__file__).resolve().parents[1]
READS = {"lock_wait_p90_ms.rag": "lock_wait", "route_p90_ms.rag": "route",
         "dispatch_to_token_p90_ms.rag": "prefill"}


class Stages:
    def __init__(self, stages):
        self.stages = stages


def context(breakdowns):
    return tracewin.Context(
        cfg={}, mix={}, family=None, peak={}, chips=1, shape={},
        shape_chip={}, t_a=0.0, t_b=1.0, clock_pc=0.0, trace=None, host=[],
        syncs=[], admits=[], breakdowns=breakdowns)


def stages(i):
    return {"queue_wait": 900.0 + i, "lock_wait": 600.0 + 2 * i,
            "admission": 80.0 + i, "route": 30.0 + 3 * i,
            "retrieval": 10.0, "tokenize": 1.0, "prefill": 300.0 + 5 * i,
            "decode": 4000.0, "harvest": 0.1}


@pytest.mark.parametrize("name", sorted(READS))
def test_metric_reads_its_stage(name):
    rows = [stages(i) for i in range(20)]
    got = tracewin.read_metric(BENCH, name, context([Stages(r)
                                                     for r in rows]))
    want = np.percentile([r[READS[name]] for r in rows], 90)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READS))
def test_metric_reads_nothing_without_the_stage(name):
    # the stages a program marks without the lock_wait, route and
    # first-token stamps (its prefill ends at the dispatch)
    old = {"queue_wait": 900.0, "admission": 80.0, "retrieval": 10.0,
           "prefill": 0.2, "decode": 4300.0, "harvest": 0.1}
    assert tracewin.read_metric(BENCH, name,
                                context([Stages(dict(old))] * 5)) is None
    assert tracewin.read_metric(BENCH, name, context([])) is None
