"""Find an open-loop cell's knee: the highest fixed rate it sustains.

    python3 bench/sweep.py --workload <cell> --seconds <s> \
        --rates 4,8,12 --seed <n>

Runs the cell once per rate, in this one process (which holds the
chip), with the mix's arrival rate replaced.  For each rate it prints
the latency p50 and p90 from due time, and the p90 of the answers due
in the first and in the second half of the window: a rate is sustained
when the second half's p90 is not well above the first half's (the
queue does not grow across the window).  The benchmark's own runs never
call this; its result is written into the mix file as a number.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    load = run.load_cell
    for rate in [float(r) for r in args.rates.split(",")]:
        def at_rate(root, workload, rate=rate):
            cell = load(root, workload)
            cell["mix"]["arrivals"]["rate"] = rate
            return cell
        run.load_cell = at_rate
        res, win, groups = run.run_cell(run.ROOT, args.workload, args.seed,
                                        args.seconds, False)
        half = win.t0 + win.seconds / 2
        lat = {q: (win.sys.done[q][0] - win.due[q]) * 1e3
               for q in groups["answered"]}
        first = [v for q, v in lat.items() if win.due[q] < half]
        second = [v for q, v in lat.items() if win.due[q] >= half]
        late = np.asarray(win.lateness) * 1e3
        row = {"rate": rate, "answered": len(lat),
               "failed": res["failed"], "correct": res["correct"],
               "p50_ms": float(np.percentile(list(lat.values()), 50)),
               "p90_ms": float(np.percentile(list(lat.values()), 90)),
               "p90_first_half_ms": float(np.percentile(first, 90)),
               "p90_second_half_ms": float(np.percentile(second, 90)),
               "generator_late_p95_ms": float(np.percentile(late, 95))}
        print(json.dumps(row), flush=True)
        del res, win, groups
        gc.collect()


if __name__ == "__main__":
    main()
