"""The traced run: profile a slice of the window, then reduce.

The profiler runs for ``trace_s`` seconds from ``trace_lead_s`` into
the window (both from the mix file); end-to-end numbers are never
taken from a traced run.  Each per-layer metric of the cell is read by
``bench/metrics/<name>.py``, whose ``read(ctx)`` returns a number, or
``None`` when it finds nothing to read (the metric is then left out).
"""
from __future__ import annotations

import shutil
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import family
import tracefile


def start_hook(state: Dict, cell: Dict):
    """The window's ``on_start``: a thread that starts and stops the
    profiler inside the window."""
    mix = cell["mix"]

    def run(t0: float):
        import jax
        lead, span = float(mix["trace_lead_s"]), float(mix["trace_s"])
        time.sleep(max(0.0, t0 + lead - time.perf_counter()))
        d = tempfile.mkdtemp(prefix="bench_trace_")
        state["dir"] = d
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(tracefile.CLOCK_MARK):
            state["clock_pc"] = time.perf_counter()
        state["t_a"] = time.perf_counter()
        time.sleep(span)
        state["t_b"] = time.perf_counter()
        jax.profiler.stop_trace()

    def on_start(win):
        th = threading.Thread(target=run, args=(win.t0,), daemon=True)
        state["thread"] = th
        th.start()
    return on_start


def finish(state: Dict) -> None:
    th = state.get("thread")
    if th is not None:
        th.join()


@dataclass
class Context:
    """What a metric reader may read."""
    cfg: Dict
    mix: Dict
    family: object                 # the cell's family (``family.py``)
    peak: Dict
    chips: int
    shape: Dict[str, int]          # whole model
    shape_chip: Dict[str, int]     # one chip's share
    t_a: float                     # traced slice, perf_counter clock
    t_b: float
    clock_pc: float                # the trace's clock mark
    trace: tracefile.Trace
    host: List[tuple]              # (name, t0, t1)
    syncs: List[tuple]             # (t, active, gen)
    admits: List[tuple]            # (t, slots, padded plens, plens)
    breakdowns: List[object]       # obs RequestBreakdown per answer

    @property
    def window_s(self) -> float:
        return self.t_b - self.t_a

    def busy(self, dev: int) -> float:
        """Seconds of the slice in which some operation ran on ``dev``."""
        ops = self.trace.ops.get(dev, [])
        return tracefile.union_length([(a, b) for *_, a, b in ops],
                                      self.t_a, self.t_b)

    def modules(self, dev: int, part: str):
        """Program executions on ``dev`` whose name holds ``part`` and
        that start inside the slice."""
        return [m for m in self.trace.modules.get(dev, [])
                if part in m[0] and self.t_a <= m[1] < self.t_b]

    def ops_in(self, dev: int, t0: float, t1: float):
        """Leaf operations (no loops or other containers) on ``dev``
        that start in [t0, t1): ``(name, opcode, start, end)``."""
        return [o for o in self.trace.ops.get(dev, [])
                if t0 <= o[2] < t1 and o[1] not in tracefile.CONTAINERS]


def context(cell: Dict, **fields) -> Context:
    """A cell's context: its configuration, mix and family, and the
    family's shapes of the whole model and of one chip's share."""
    fam, cfg = cell["family"], cell["cfg"]
    return Context(cfg=cfg, mix=cell["mix"], family=fam,
                   shape=fam.shape(cfg),
                   shape_chip=fam.shape(cfg, per_chip=True), **fields)


def load_peaks(bench: Path, kind: str) -> Dict:
    import json
    table = json.loads((bench / "peaks.json").read_text())
    if kind not in table:
        raise ValueError(f"no peaks for device kind {kind!r}; known: "
                         f"{sorted(table)}")
    return table[kind]


def read_metric(bench: Path, name: str, ctx: Context) -> Optional[float]:
    return family.by_name(bench, "metrics", name).read(ctx)


def breakdown(ctx: Context) -> Dict:
    """Device operations that took most time (by stable name: the
    program, then the operation with its instance number dropped) and
    the longest idle gaps of chip 0, each by what the host was doing."""
    import re
    tot: Dict[str, float] = {}
    mods = ctx.trace.modules.get(0, [])
    for name, _, a, b in ctx.ops_in(0, ctx.t_a, ctx.t_b):
        prog = next((m[0] for m in mods if m[1] <= a < m[2]), "none")
        key = _program(prog) + ":" + re.sub(r"[.]\d+$", "", name)
        tot[key] = tot.get(key, 0.0) + (b - a)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
    busy = [(a, b) for *_, a, b in ctx.trace.ops.get(0, [])]
    idle = sorted(tracefile.gaps(busy, ctx.t_a, ctx.t_b),
                  key=lambda g: g[0] - g[1])[:10]
    labelled = []
    for g0, g1 in idle:
        labelled.append([_host_label(ctx.host, g0, g1), g1 - g0])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": labelled}


def _program(module_name: str) -> str:
    for part in ("decode_chunk", "prefill", "commit", "gather",
                 "clear_flags"):
        if part in module_name:
            return part
    return module_name.split("(")[0][:40]


def _host_label(spans, g0: float, g1: float) -> str:
    """The host span that covers most of the gap (the innermost when
    several do), or ``idle`` when none does."""
    best, best_cover, best_len = "idle", 0.0, float("inf")
    for name, a, b in spans:
        cover = min(b, g1) - max(a, g0)
        if cover <= 0:
            continue
        if cover > best_cover + 1e-9 or (abs(cover - best_cover) <= 1e-9
                                          and b - a < best_len):
            best, best_cover, best_len = name, cover, b - a
    return f"host:{best}"


def reduce(state: Dict, win, groups, cell: Dict):
    """Per-layer metrics of the cell, plus the device's busy time and
    the breakdown, from the traced slice."""
    bench: Path = cell["bench"]
    system = win.sys
    paths = list(Path(state["dir"]).rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    tr = tracefile.load(str(paths[0]), state["clock_pc"])
    shutil.rmtree(state["dir"], ignore_errors=True)
    answered = set(groups["answered"])
    bds = [h.breakdown for q, h in win.handles.items()
           if q in answered and h.breakdown is not None]
    dev = win.sys.devices[0]
    ctx = context(cell, peak=load_peaks(bench, dev.device_kind),
                  chips=len(system.devices),
                  t_a=state["t_a"], t_b=state["t_b"],
                  clock_pc=state["clock_pc"], trace=tr,
                  host=list(system.host.spans), syncs=system.syncs,
                  admits=system.admits, breakdowns=bds)
    metrics = {}
    for m in cell["per_layer"]:
        v = read_metric(bench, m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    busy = [ctx.busy(d) for d in range(ctx.chips)]
    extra = {"device": {"busy_s": float(np.mean(busy)),
                        "window_s": ctx.window_s},
             "breakdown": breakdown(ctx)}
    return metrics, extra
