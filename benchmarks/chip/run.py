#!/usr/bin/env python3
"""Run one benchmark cell once on the chips it asks for.

  python3 benchmarks/chip/run.py --workload qwen3-0.6b.chat-prefix \\
      --seed 12345 --seconds 30 --trace 0

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its traffic
file's ``kind`` names the driver (``chipbench/<kind>.py``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, last, ``checks``:
each number compared with the reference beside its limit.  The same
numbers are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def log(msg: str) -> None:
    print(msg, flush=True)


def judge(numbers: dict, limits: dict) -> dict:
    """Each number compared beside its limit, in the order of ``numbers``."""
    out = {}
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        out[name] = {"value": value, "limit": limit,
                     "ok": limit is not None and value <= limit}
    return out


def result_line(cell, out: dict, trace: bool, devs) -> dict:
    from chipbench import device, spec
    from metrics.common import trace as trace_mod

    dev = dict(device.describe(devs),
               memory_peak_bytes=out["memory_peak_bytes"])
    if trace:
        metrics = spec.read_metrics(cell.per_layer, out["run"])
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in out["e2e"]:
                raise RuntimeError(f"the run measured no {m['name']}")
            metrics[m["name"]] = {"value": float(out["e2e"][m["name"]]),
                                  "unit": m["unit"]}
    checks = judge(out["numbers"], cell.limits)
    line = {"correct": bool(checks) and all(c["ok"] for c in checks.values()),
            "attempted": int(out["attempted"]), "failed": int(out["failed"]),
            "metrics": metrics, "device": dev}
    t = out["run"].trace
    if trace and t is not None:
        dev["busy_s"] = trace_mod.busy_s(t)
        dev["window_s"] = t.window_s
        line["breakdown"] = {"device_ops": trace_mod.top_ops(t),
                             "idle_gaps": trace_mod.idle_gaps(t)}
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        raise SystemExit("--seed must be >= 0 and --seconds > 0")
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"the repro package is not at {ROOT / 'src'}: run "
                         f"from a checkout of the repository")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]

    from chipbench import device, spec

    cell = spec.load_cell(args.workload)
    devs = device.tpu_devices(cell.chips)
    peaks = device.peaks(devs[0].device_kind)
    log(f"compile cache: {device.compile_cache()}")
    driver = importlib.import_module(f"chipbench.{cell.traffic['kind']}")
    out = driver.run(cell, args.seed, args.seconds, bool(args.trace), devs,
                     peaks, T_START, log)
    line = result_line(cell, out, bool(args.trace), devs)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
