#!/usr/bin/env python3
"""Find a serve cell's knee: the highest offered rate it sustains.

  python3 benchmarks/chip/sweep.py --workload qwen3-0.6b.chat-prefix \\
      --seed 7 --seconds 20 --rates 1,2,3,4,6

One process builds and warms the cell once, then offers the cell's mix at
each rate in turn for ``--seconds`` and serves it to the end.  A rate is
sustained when the backlog does not grow over the window: at most two
requests wait at the close, the median time to first token of the requests
due in the last third of the window is under twice the median at the
lowest rate, and the drain after the close is under three times the lowest
rate's plus ten seconds.  The sweep stops after the first rate that is not
sustained.  One JSON line per rate; the last line names the knee.  The
cell's traffic file then states 0.8 x knee as its rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def third_p50(served, seconds: float, k: int) -> float:
    lo, hi = seconds * k / 3, seconds * (k + 1) / 3
    xs = [r.times[0] - r.due if r.ok else float("inf") for r in served
          if lo <= r.req.offset_s < hi]
    return statistics.median(xs) if xs else float("nan")


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from chipbench import device, spec
    from chipbench.record import WindowTracer
    from chipbench.serve import ServeCell
    cell = spec.load_cell(args.workload)
    devs = device.tpu_devices(cell.chips)
    device.compile_cache()
    sc = ServeCell(cell.config, cell.traffic, args.seed, devs)
    sc.warm_up()
    print(json.dumps({"setup_s": time.perf_counter() - t_start}), flush=True)
    knee, base = None, None
    for rate in [float(r) for r in args.rates.split(",")]:
        sc.mix = dict(cell.traffic, arrivals=dict(cell.traffic["arrivals"],
                                                  rate_per_s=rate))
        reqs = sc.requests(args.seconds)
        served, spans, window = sc.run_window(
            reqs, args.seconds, WindowTracer(False, args.seconds))
        mid, last = (third_p50(served, args.seconds, k) for k in (1, 2))
        e2e = ServeCell.end_to_end(served)
        if base is None:
            base = (e2e["ttft_p50_ms"] / 1e3, max(window["drain_s"], 0.0))
        ok = (window["pending_at_close"] <= 2 and last < 2 * base[0]
              and window["drain_s"] < 3 * base[1] + 10.0)
        row = {"rate_per_s": rate, "requests": len(reqs),
               "failed": sum(not r.ok for r in served),
               "ttft_p50_mid_ms": mid * 1e3, "ttft_p50_last_ms": last * 1e3,
               "pending_at_close": window["pending_at_close"],
               "drain_s": window["drain_s"], "sustained": ok,
               **e2e}
        print(json.dumps(row), flush=True)
        if not ok:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
