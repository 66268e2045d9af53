"""The trace-to-metrics reduction on small traces recorded on a TPU v5e
chip (``data/<mix>/``: about a second of a cell's window, with the
control syncs, admissions and host spans the run recorded, and the
values the reduction reads from them)."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

import family
import readers
import tracefile
import tracewin

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


class Stages:
    def __init__(self, stages):
        self.stages = stages


@pytest.fixture(scope="module",
                params=sorted(p.name for p in DATA.iterdir() if p.is_dir()))
def recorded(request):
    where = DATA / request.param
    ctx_json = json.loads((where / "context.json").read_text())
    raw = gzip.decompress((where / "trace.xplane.pb.gz").read_bytes())
    tr = tracefile.load(raw, ctx_json["clock_pc"])
    c = ctx_json
    cell = {"cfg": c["cfg"], "mix": c["mix"],
            "family": family.of(BENCH, c["cfg"])}
    ctx = tracewin.context(
        cell, peak=c["peak"], chips=c["chips"],
        t_a=c["t_a"], t_b=c["t_b"], clock_pc=c["clock_pc"], trace=tr,
        host=[tuple(h) for h in c["host"]],
        syncs=[(t, np.array(a), np.array(g)) for t, a, g in c["syncs"]],
        admits=[(t, np.array(s), np.array(p), np.array(u))
                for t, s, p, u in c["admits"]],
        breakdowns=[Stages(b) for b in c["breakdowns"]])
    return ctx, c


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (6.0, 9.0)]
    assert tracefile.union_length(iv, 0.5, 7.0) == pytest.approx(3.5)
    assert tracefile.gaps(iv, 0.5, 7.0) == [(2.0, 3.0), (4.0, 6.0)]
    assert tracefile.gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_trace_on_the_host_clock(recorded):
    """Device work lands inside the traced slice and the decode-chunk
    programs run after the host dispatched them."""
    ctx, _ = recorded
    ops = ctx.trace.ops[0]
    assert ops and ctx.trace.modules[0]
    inside = [o for o in ops if ctx.t_a <= o[2] < ctx.t_b]
    assert len(inside) > 0.9 * len(ops)
    disp = sorted(a for n, a, b in ctx.host if n == "decode_dispatch")
    for _, a, _ in ctx.modules(0, readers.DECODE):
        assert any(d <= a + 1e-3 for d in disp)


def test_readers_repeat_the_recorded_values(recorded):
    ctx, c = recorded
    for name, want in c["metrics"].items():
        got = tracewin.read_metric(Path(__file__).resolve().parents[1],
                                   name, ctx)
        assert got == pytest.approx(want, rel=1e-9), name
    assert np.mean([ctx.busy(d) for d in range(ctx.chips)]) == \
        pytest.approx(c["busy_s"], rel=1e-9)


def test_shares_are_shares(recorded):
    ctx, _ = recorded
    idle = readers.idle_share(ctx)
    busy = ctx.busy(0)
    assert 0.0 <= idle <= 100.0
    assert busy == pytest.approx(ctx.window_s * (1 - idle / 100))
    roof = readers.paged_roofline(ctx)
    assert 0.0 < roof <= 100.0
    assert 0.0 < readers.mfu(ctx) <= 100.0
