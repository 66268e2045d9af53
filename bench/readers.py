"""Reductions shared by the per-layer metric files in ``metrics/``.

Every function takes the traced run's :class:`tracewin.Context` and
returns a number, or ``None`` when the slice holds nothing to read.
Shares are in percent.  Operations, bytes and the decode kernel's name
are the cell's family's (``ctx.family``, ``family.py``).
"""
from __future__ import annotations

import bisect
from typing import Optional, Tuple

import numpy as np

DECODE = "decode_chunk"
PREFILL = "prefill"


def span_p90(ctx, stage: str) -> Optional[float]:
    v = [b.stages[stage] for b in ctx.breakdowns if stage in b.stages]
    return float(np.percentile(v, 90)) if v else None


def idle_share(ctx) -> Optional[float]:
    """1 - busy/slice of the most idle chip."""
    if ctx.window_s <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * max(1.0 - ctx.busy(d) / ctx.window_s
                       for d in range(ctx.chips))


def program_ms(ctx, part: str, per: int = 1) -> Optional[float]:
    """Mean device time of one execution of a program on chip 0,
    divided by ``per``."""
    mods = ctx.modules(0, part)
    if not mods:
        return None
    return 1e3 * float(np.mean([b - a for _, a, b in mods])) / per


class SlotState:
    """Each slot's state entering a decode chunk, from the control
    syncs and admissions the run recorded."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.sync_t = [s[0] for s in ctx.syncs]

    def at(self, t: float) -> Optional[Tuple[np.ndarray, ...]]:
        i = bisect.bisect_right(self.sync_t, t) - 1
        if i < 0:
            return None
        _, active, gen = self.ctx.syncs[i]
        n = len(active)
        plen = np.zeros(n, np.int64)
        ulen = np.zeros(n, np.int64)
        t_sync = self.sync_t[i]
        for ta, slots, padded, unpadded in self.ctx.admits:
            if ta > t_sync:
                break
            for s, p, u in zip(slots, padded, unpadded):
                if s < n:
                    plen[s], ulen[s] = p, u
        return active, gen, plen, ulen


def _chunks(ctx):
    """(start, end, state) of each decode-chunk execution on chip 0.
    A chunk runs on the state of the last sync before its dispatch; the
    sync that ends it returns after it ends.  Its midpoint picks the
    former whatever the small error between the host's and the
    device's clocks."""
    st = SlotState(ctx)
    for _, a, b in ctx.modules(0, DECODE):
        s = st.at((a + b) / 2)
        if s is not None:
            yield a, b, s


def useful_flops(ctx) -> float:
    """FLOPs of the unpadded prompts prefilled and the tokens decoded
    in the slice, for the whole model."""
    fam, sh = ctx.family, ctx.shape
    k = int(ctx.cfg["serving"]["sync_every"])
    cap = int(ctx.mix["max_new_tokens"])
    total = 0.0
    for t, slots, padded, unpadded in ctx.admits:
        if ctx.t_a <= t < ctx.t_b:
            total += sum(fam.prefill_flops(sh, int(u)) for u in unpadded
                         if u > 0)
    for _, _, (active, gen, plen, ulen) in _chunks(ctx):
        for j in range(k):
            live = active & (gen + j < cap)
            for s in np.flatnonzero(live):
                total += fam.decode_flops(sh, int(ulen[s] + gen[s] + j))
    return total


def mfu(ctx) -> Optional[float]:
    if ctx.window_s <= 0 or not ctx.trace.modules:
        return None
    peak = float(ctx.peak["bf16_flops_per_s"])
    return 100.0 * useful_flops(ctx) / (ctx.window_s * ctx.chips * peak)


def paged_roofline(ctx) -> Optional[float]:
    """Least time at peak HBM bandwidth for the bytes each call of the
    family's decode kernel must move (the live slots' valid pages), over
    the kernel's device time, on chip 0."""
    fam, sh = ctx.family, ctx.shape_chip
    k = int(ctx.cfg["serving"]["sync_every"])
    ps = int(ctx.cfg["serving"]["page_size"])
    cap = int(ctx.mix["max_new_tokens"])
    bw = float(ctx.peak["hbm_bytes_per_s"])
    need = spent = 0.0
    for a, b, (active, gen, plen, _) in _chunks(ctx):
        calls = [o for o in ctx.ops_in(0, a, b)
                 if fam.decode_kernel in o[0]]
        if len(calls) != k * sh["layers"]:
            continue
        for j in range(k):
            live = np.flatnonzero(active & (gen + j < cap))
            lens = [int(plen[s] + gen[s] + j) for s in live]
            need += sh["layers"] * fam.decode_bytes(sh, lens, ps)
        spent += sum(o[3] - o[2] for o in calls)
    if spent <= 0:
        return None
    return 100.0 * need / bw / spent
