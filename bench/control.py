"""Readings that the correctness limits of a cell are set from.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--out control.json]

For each seed, one run of the cell as ``run.py`` makes it (at the
cell's own load, for ``--seconds``), with the float8 control computed
beside the float32 reference on the same sample of served answers.
Per seed it records the largest gap of a served token (the program's
reading, which sets the lower end of the limit) and of the control's
own picks (which sets the upper end), and whether the control, judged
by the run's own checks at the committed limits, comes out correct (it
must not).  The benchmark's own runs never run the control.  All seeds
run in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        res = run.execute(run.ROOT, args.workload, seed, args.seconds,
                          False, control=True)
        ctl = res.pop("control")
        row = {"seed": seed, "correct": res["correct"],
               "control_correct": res.pop("control_correct"),
               "program_gap": max(ctl["gaps"], default=None),
               "control_gap": max(ctl["control_gaps"], default=None),
               "tokens": len(ctl["gaps"]),
               "checks": res["checks"], "metrics": res["metrics"],
               "device": res["device"], "wall_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del res
        gc.collect()
    read = [r for r in rows if r["tokens"]]
    summary = {"workload": args.workload,
               "lower": max((r["program_gap"] for r in read), default=None),
               "upper": min((r["control_gap"] for r in read), default=None),
               "seeds": len(read),
               "control_ever_correct": any(r["control_correct"]
                                           for r in rows)}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary}))


if __name__ == "__main__":
    main()
