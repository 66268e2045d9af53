"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``, whose ``model_type`` names its
architecture's family, ``bench/families/<model_type>.py``) and a
traffic mix (``bench/mixes/<traffic>.json``); its correctness limits
are in ``bench/limits/<cell>.json`` and each per-layer metric is read
by ``bench/metrics/<metric>.py``.  Nothing here names a cell or a
family.

The run: check that JAX sees the cell's chips (a TPU; there is no CPU
fallback), build the system from the seed, warm every shape the window
uses, drive the window (open loop: arrivals on a schedule drawn from
the seed; closed loop: a fixed number of clients, started staggered),
then compare a seed-drawn sample of what the window served with the
plain references (``reference.py``).  With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, read from a profiler trace of part of the window.  The last
line of standard output is the JSON result; the numbers compared, each
with its limit, are the last lines of standard error and the last key
of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
# JAX's persistent compilation cache lives inside the checkout, at a
# fixed path (the path is part of the cache key); the program's
# ``enable_compile_cache`` takes the directory from this variable.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

import repro  # noqa: E402,F401  (no result without the program)

import family  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from system import System  # noqa: E402

DRAIN_S = 60.0           # an answer due in the window may come this late


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from data
# ---------------------------------------------------------------------------

def load_cell(root: Path, workload: str) -> Dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    bench = root / Path(conf["file"]).parent.parent
    mix = json.loads((bench / "mixes" / f"{cell['traffic']}.json")
                     .read_text())
    limits = json.loads((bench / "limits" / f"{workload}.json").read_text())

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return {"name": workload, "cell": cell, "cfg": cfg, "mix": mix,
            "limits": limits, "e2e": e2e, "per_layer": per_layer,
            "bench": bench, "family": family.of(bench, cfg)}


def find_devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"the cell needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} devices, found {len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Window:
    """One measured window: what was due, when each answer came."""

    def __init__(self, system: System, cell: Dict, rng, seconds: float):
        self.sys, self.cell, self.rng = system, cell, rng
        self.mix = cell["mix"]
        self.seconds = float(seconds)
        self.due: Dict[int, float] = {}          # qid -> due time
        self.handles: Dict[int, object] = {}
        self.lateness: List[float] = []
        self.t0 = self.t1 = 0.0
        pool = system.corpus.questions
        self._pool = pool
        self._next = 0

    def _request(self):
        from repro.routing.gateway import Request
        q = self._pool[int(self.rng.integers(0, len(self._pool)))]
        qid = self._next
        self._next += 1
        mine = type(q)(qid, q.text, q.answerable, q.gold_answer, q.gold_pid)
        return qid, Request(qid=qid, question=mine, slo=self.mix["slo"],
                            deadline_ms=float(self.mix["deadline_ms"]))

    def submit(self, due: float):
        qid, req = self._request()
        self.due[qid] = due
        self.handles[qid] = self.sys.gateway.submit_stream(req)
        self.lateness.append(time.perf_counter() - due)
        return qid

    def _wait_on_chip(self, qids: List[int], limit_s: float = 600) -> None:
        """Wait until ``qids`` are routed and two control syncs have
        followed: their prefill and a decode chunk with them in it have
        run on the chip (compiled first if need be)."""
        until = time.perf_counter() + limit_s
        while (not all(q in self.sys.submits for q in qids)
               and time.perf_counter() < until):
            time.sleep(0.01)
        routed = time.perf_counter()
        if not any(self.sys.submits.get(q, (None,))[0] in self.sys.prompts
                   for q in qids):
            return          # all refused: nothing went to the engine
        syncs = self.sys.syncs
        while (not (len(syncs) >= 2 and syncs[-2][0] > routed)
               and time.perf_counter() < until):
            time.sleep(0.01)

    def warm_up(self) -> None:
        """Two prefill groups through the whole path: the first admitted
        to an idle engine, the second while the first decodes.  That
        compiles (or loads) the prefill, commit and decode programs for
        every pairing of inputs the window gives them: an admission
        after another one, and after a decode chunk."""
        n = self.cell["cfg"]["serving"]["prefill_batch"]
        qids = [self.submit(time.perf_counter()) for _ in range(n)]
        self._wait_on_chip(qids)
        qids += [self.submit(time.perf_counter()) for _ in range(n)]
        for q in qids:
            self.handles[q].result(timeout=600)
        for q in qids:
            del self.due[q], self.handles[q]
        self.lateness.clear()

    def run_open(self, on_start=None) -> None:
        offsets = traffic.open_loop_schedule(self.mix["arrivals"],
                                             self.seconds, self.rng)
        self.t0 = time.perf_counter() + 0.05
        self.t1 = self.t0 + self.seconds
        if on_start:
            on_start(self)
        for off in offsets:
            due = self.t0 + off
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.submit(due)
        left = self.t1 - time.perf_counter()
        if left > 0:
            time.sleep(left)
        deadline = time.perf_counter() + DRAIN_S
        for h in self.handles.values():
            try:
                h.result(timeout=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                pass

    def run_closed(self, on_start=None) -> None:
        """``concurrency`` clients, started in prefill-sized groups
        spread over ``ramp_s`` so that answers do not all finish
        together; each client asks again as soon as its answer comes."""
        import queue
        n = int(self.mix["concurrency"])
        ramp = float(self.mix["ramp_s"])
        pb = int(self.cell["cfg"]["serving"]["prefill_batch"])
        n_groups = -(-n // pb)
        freed: "queue.Queue[int]" = queue.Queue()
        self.sys.on_done = freed.put
        start = time.perf_counter()
        for g in range(n_groups):
            due = start + ramp * g / n_groups
            time.sleep(max(0.0, due - time.perf_counter()))
            group = [self.submit(due) for _ in range(min(pb, n - g * pb))]
            # A group whose programs compile on its way to the chip
            # holds the later groups back by as long, so that clients
            # start as spread out as in a run that finds every program
            # in the cache.
            self._wait_on_chip(group)
            start += max(0.0, time.perf_counter()
                         - (start + ramp * (g + 1) / n_groups))
        time.sleep(max(0.0, start + ramp - time.perf_counter()))
        self.t0 = time.perf_counter()
        self.t1 = self.t0 + self.seconds
        if on_start:
            on_start(self)
        while True:
            left = self.t1 - time.perf_counter()
            if left <= 0:
                break
            try:
                freed.get(timeout=left)
            except queue.Empty:
                break
            self.submit(time.perf_counter())
        self.sys.on_done = None


# ---------------------------------------------------------------------------
# result
# ---------------------------------------------------------------------------

def classify(win: Window) -> Dict[str, List[int]]:
    """Requests the window counts, by how they ended."""
    out = {"answered": [], "refused": [], "failed": []}
    sys_ = win.sys
    closed = win.mix["loop"] == "closed"
    for qid, h in win.handles.items():
        t_done = sys_.done.get(qid, (None,))[0]
        if closed and not (t_done is not None and win.t0 <= t_done
                           <= win.t1):
            if not (h.done() and h.shed and win.t0 <= h.completed_t
                    <= win.t1):
                continue
        if not h.done() or h.shed or h.outcome is None:
            out["failed"].append(qid)
            continue
        o = h.outcome
        if (getattr(o, "timed_out", False) or getattr(o, "transient", False)
                or getattr(o, "rejected", False)):
            out["failed"].append(qid)
        elif o.refused:
            out["refused"].append(qid)
        elif qid in sys_.done:
            out["answered"].append(qid)
        else:
            out["failed"].append(qid)
    return out


def e2e_metrics(win: Window, groups, setup_s: float) -> Dict[str, float]:
    s = win.sys
    vals = {"setup_s": setup_s}
    lat = [(s.done[q][0] - win.due[q]) * 1e3 for q in groups["answered"]]
    if lat:
        vals["latency_p50_ms"] = float(np.percentile(lat, 50))
    # Every token the engine generated in the window, as its control
    # syncs report them, over the time from the window's first sync to
    # its last.  (Answers finish a prefill group at a time, so counting
    # only the answers completed in the window would swing by a whole
    # group with where its edges fall.)
    gen = [(t, n) for t, n in s.generated() if win.t0 <= t <= win.t1]
    if len(gen) >= 2 and gen[-1][0] > gen[0][0]:
        vals["out_tokens_per_s"] = ((gen[-1][1] - gen[0][1])
                                    / (gen[-1][0] - gen[0][0]))
    return vals


COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileWatch:
    """Counts JAX tracing, lowering and compiling that happen inside the
    window (there should be none: set-up warms every shape)."""

    def __init__(self, win: "Window"):
        import jax
        self.win, self.n = win, 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if (event in COMPILE_EVENTS
                and self.win.t0 <= time.perf_counter() <= self.win.t1):
            self.n += 1

    def close(self) -> int:
        self._jax.monitoring.unregister_event_duration_listener(self._on)
        return self.n


def checks(win: Window, groups, cell: Dict, rng) -> Dict[str, Dict]:
    """Compare a seed-drawn sample of what the window served with the
    references; returns ``{name: {value, limit, rule}}``."""
    s, mix, cfg = win.sys, cell["mix"], cell["cfg"]
    lim = cell["limits"]
    answered = groups["answered"]
    # routing, retrieval and prompt of every answered request
    bm = mix["bm25"]
    exact = reference.ExactBM25(s.corpus.texts, dim=bm["hash_dim"],
                                k1=bm["k1"], b=bm["b"])
    mlp = {k: np.asarray(v) for k, v in s.policy.params.items()}
    rc = s.router_cfg
    route_bad = bm25_bad = prompt_bad = 0
    for q in answered + groups["refused"]:
        req = win.handles[q].request
        rid, a_idx, ids = s.submits.get(q, (None, s.done[q][1], None))
        lg = reference.route_logits(reference.route_state(
            req.question.text, exact, rc.embed_dim, rc.n_meta_features), mlp)
        route_bad += not reference.route_ok(lg, a_idx)
        act = mix["actions"][a_idx]
        if rid is None or act["mode"] == "refuse":
            continue
        bm25_bad += not exact.topk_ok(req.question.text, ids or [],
                                      act["k"])
        want = reference.prompt_ids(
            act["mode"], req.question.text,
            [s.corpus.texts[i] for i in (ids or [])],
            int(cfg["vocab_size"]), int(cfg["serving"]["max_prompt_len"]))
        prompt_bad += not np.array_equal(s.prompts[rid], want)
    # the model: a sample with the longest answer in it
    n_check = int(mix["check_requests"])
    by_len = sorted(answered, key=lambda q: (
        -s.gens[s.submits[q][0]].n_steps, q))
    sample = by_len[:1] + list(rng.permutation(by_len[1:])[:n_check - 1])
    seqs, n_out = [], []
    for q in sample:
        rid = s.submits[q][0]
        toks = np.asarray(s.gens[rid].tokens, np.int32)
        seqs.append(np.concatenate([s.prompts[rid], toks]))
        n_out.append(len(toks))
    win.sample_seqs, win.sample_n = seqs, n_out
    gaps = reference.logit_gaps(s.params, cfg, cell["family"], seqs, n_out,
                                device=s.devices[0])["gaps"] if seqs else []
    return {
        **model_checks(gaps, lim),
        "route_mismatch": {"value": route_bad, "limit": 0, "rule": "<="},
        "bm25_mismatch": {"value": bm25_bad, "limit": 0, "rule": "<="},
        "prompt_mismatch": {"value": prompt_bad, "limit": 0, "rule": "<="},
    }


def model_checks(gaps: List[float], lim: Dict) -> Dict[str, Dict]:
    """The served tokens' widest logit gap and how many were compared."""
    return {
        "logit_gap": {"value": max(gaps) if gaps else None,
                      "limit": lim["logit_gap"], "rule": "<="},
        "checked_tokens": {"value": len(gaps),
                           "limit": lim["checked_tokens"], "rule": ">="},
    }


def passed(chk: Dict[str, Dict]) -> bool:
    ok = True
    for c in chk.values():
        v = c["value"]
        if v is None:
            ok = False
        elif c["rule"] == "<=":
            ok &= v <= c["limit"]
        else:
            ok &= v >= c["limit"]
    return bool(ok)


def device_info(devices) -> Dict:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, **kw) -> Dict:
    """One run of a cell; returns the result object."""
    return run_cell(root, workload, seed, seconds, trace, **kw)[0]


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True, tamper=None,
             control: bool = False):
    """One run of a cell: ``(result, window, groups)``.
    ``tamper(system)`` may break the timed path (tests); ``control``
    also computes the float8 control's gaps on the same sample and
    whether the control, put in the program's place, would be
    ``correct`` (``bench/control.py``)."""
    cell = load_cell(root, workload)
    devices = find_devices(int(cell["cell"]["chips"]), require_chip)
    cache = enable_cache()
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    system = System(cell["cfg"], cell["mix"], int(seed), devices,
                    cell["family"], trace=trace)
    if tamper is not None:
        tamper(system)
    system.warm()
    win = Window(system, cell, rng, seconds)
    watch = CompileWatch(win)
    system.gateway.start()
    tracing = {}
    try:
        if cell["mix"]["loop"] == "open":
            win.warm_up()
        hook = None
        if trace:
            import tracewin
            hook = tracewin.start_hook(tracing, cell)
        if cell["mix"]["loop"] == "open":
            win.run_open(on_start=hook)
        else:
            win.run_closed(on_start=hook)
        if trace:
            tracewin.finish(tracing)
    finally:
        system.gateway.stop(drain=False)
    compiles = watch.close()
    setup_s = win.t0 - T_START
    groups = classify(win)
    dev = device_info(devices)
    result: Dict = {"correct": False,
                    "attempted": sum(map(len, groups.values())),
                    "failed": len(groups["failed"]), "metrics": {},
                    "device": dev}
    st = system.gateway.stats
    log(f"# cell {workload} seed {seed}: {len(groups['answered'])} answered, "
        f"{len(groups['refused'])} policy refusals, {len(groups['failed'])} "
        f"failed; shed {st.shed}, forced refusals {st.forced_refusals}, "
        f"depth clamped {st.depth_clamped}, timed out {st.timed_out}")
    if win.lateness:
        late = np.asarray(win.lateness) * 1e3
        log(f"# generator lateness p50 {np.percentile(late, 50):.3f} ms "
            f"p95 {np.percentile(late, 95):.3f} ms over {len(late)} sends")
    due = sorted((win.due[q], system.done[q][0] - win.due[q])
                 for q in groups["answered"])
    if len(due) >= 4:
        lat = np.asarray([d for _, d in due]) * 1e3
        half = len(due) // 2
        log(f"# latency from due over {len(lat)} answers: p50 "
            f"{np.percentile(lat, 50):.3f} p90 {np.percentile(lat, 90):.3f}"
            f" p95 {np.percentile(lat, 95):.3f} p99 "
            f"{np.percentile(lat, 99):.3f} ms; p90 of the first half due "
            f"{np.percentile(lat[:half], 90):.3f}, of the second "
            f"{np.percentile(lat[half:], 90):.3f} ms")
    log(f"# set-up {setup_s:.3f} s ({json.dumps(system.timings)}); "
        f"compile cache {cache}; compilations in the window {compiles}; "
        f"peak device memory {dev['memory_peak_bytes']} B")
    if trace:
        import tracewin
        per_layer, extra = tracewin.reduce(tracing, win, groups, cell)
        result["metrics"] = per_layer
        result["device"].update(extra["device"])
        result["breakdown"] = extra["breakdown"]
    else:
        vals = e2e_metrics(win, groups, setup_s)
        result["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["e2e"] if m["name"] in vals}
    system.free_engine()
    chk = checks(win, groups, cell, rng)
    if control:
        # the float8 control in the program's place, judged by the same
        # checks and limits as the program
        ctl = reference.logit_gaps(
            system.params, cell["cfg"], cell["family"], win.sample_seqs,
            win.sample_n, control=True, device=devices[0])
        result["control"] = ctl
        result["control_correct"] = passed(
            {**chk, **model_checks(ctl["control_gaps"], cell["limits"])})
    result["correct"] = passed(chk)
    result["checks"] = chk
    return result, win, groups


def report(result: Dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} {c['rule']} {c['limit']}")
    print(json.dumps(result), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result = execute(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    report(result)


if __name__ == "__main__":
    main()
