#!/usr/bin/env python3
"""Readings for a cell's correctness limits: the program's, and the
precision control's, on several seeds at the cell's own size.

  python3 benchmarks/chip/control.py --workload qwen3-0.6b.chat-prefix \\
      --seeds 11,12,13 --seconds 15

Serve cells: each seed runs a short window at the cell's own load (the
rate ``--rate`` overrides), then compares a sample of what was served with
the float32 reference (the program's readings) and with the same reference
computed in float8 e4m3 and put in the program's place (the control's
readings).  Fold cells: each seed folds one whole job with the program and
one with the float32-accumulating reference in its place, each compared
with ``np.bincount``.  Each seed's readings are judged under the cell's
own limits as ``run.py`` judges a run: ``correct`` for the program's,
``control_correct`` for the control's, which has to read false.  One JSON
line per seed, then the largest program reading and the smallest control
reading of each number.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def serve_seed(cell, seed, seconds, rate, devs) -> dict:
    from chipbench.record import WindowTracer
    from chipbench.serve import ServeCell

    mix = cell.traffic
    if rate is not None:
        mix = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
    sc = ServeCell(cell.config, mix, seed, devs)
    sc.warm_up()
    served, _, _ = sc.run_window(sc.requests(seconds), seconds,
                                 WindowTracer(False, seconds))
    sample = sc.sample(served, int(mix["check"]["sample_requests"]))
    sc.release()
    out = sc.compare(sample, control=True)
    out["failed"] = sum(not r.ok for r in served)
    return out


def fold_seed(cell, seed, devs) -> dict:
    from chipbench.fold import FoldCell

    out = {}
    for control in (False, True):
        fc = FoldCell(cell.config, seed, devs, control=control)
        acc = fc.one_job()
        n = fc.compare([acc], (acc, len(fc.splits)))["count_diff"]
        out["control_count_diff" if control else "count_diff"] = n
        del fc, acc
        gc.collect()
    return out


def verdicts(row: dict, limits: dict) -> dict:
    """The program's and the control's readings judged under ``limits``."""
    from run import judge

    def ok(numbers):
        checks = judge(numbers, limits)
        return bool(checks) and all(c["ok"] for c in checks.values())

    program = {k: v for k, v in row.items() if k in limits}
    control = {k[len("control_"):]: v for k, v in row.items()
               if k.startswith("control_")}
    return {"correct": ok(program), "control_correct": ok(control)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rate", type=float)
    args = ap.parse_args(argv)

    from chipbench import device, spec
    cell = spec.load_cell(args.workload)
    devs = device.tpu_devices(cell.chips)
    device.compile_cache()
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        if cell.traffic["kind"] == "serve":
            row = serve_seed(cell, seed, args.seconds, args.rate, devs)
        else:
            row = fold_seed(cell, seed, devs)
        rows.append(dict(row, **verdicts(row, cell.limits), seed=seed))
        print(json.dumps(rows[-1]), flush=True)
        gc.collect()
    summary = {}
    for key in rows[0]:
        if key in ("seed", "failed", "sampled_requests", "sampled_tokens",
                   "correct", "control_correct"):
            continue
        if key.startswith("control_"):
            summary[key + "_min"] = min(r[key] for r in rows)
        else:
            summary[key + "_max"] = max(r[key] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
