#!/usr/bin/env python3
"""Run one serve cell with the program's span recorder on, and print what
the program's spans and counters say about its layers.

  python3 benchmarks/chip/program_spans.py \\
      --workload qwen3-0.6b.chat-prefix --seed 12345 --seconds 51 --trace 1

Set-up, traffic, window and profiled slice are those of ``run.py``; in
addition the span recorder (``repro.runtime.spans``) records the window,
the engine's, prefix cache's and admission queue's counters are read at
its open and close, and the trace keeps the program's ``repro.*`` host
spans beside the harness's ``bench.*`` ones.  No correctness check is made.

The last line of standard output is one JSON object: the five readings of
``metrics/common/spans.py`` (``queue_wait_ms``, ``admit_host_ms``,
``prefix_copy_mb``, ``decode_host_ms``, ``decode_batch``), the cell's own
per-layer metrics of this run, the window's counters, each span name's
mean length inside an admission, and with ``--trace 1`` the idle gaps
named by the innermost span and the longest traced admission split into
program spans and device programs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from unittest import mock  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench.record import WindowTracer  # noqa: E402
from metrics.common import trace as trace_mod  # noqa: E402


def keep_program_spans():
    """Within it the trace reduction keeps the program's ``repro.*`` host
    spans as well as the harness's ``bench.*`` ones."""
    return mock.patch.object(trace_mod, "SPAN_PREFIX",
                             (trace_mod.SPAN_PREFIX, "repro."))


def engine_counters(engine) -> dict:
    """The engine's counters with the prefix cache's and the queue's."""
    out = dataclasses.asdict(engine.stats)
    out.update(dataclasses.asdict(engine.queue.stats))
    if engine.prefix is not None:
        out.update({"prefix_" + k: v for k, v in
                    dataclasses.asdict(engine.prefix.stats).items()})
    return out


class RecordingTracer(WindowTracer):
    """The window's profiler, which also records the program's spans and
    the engine's counters from the window's open to its close."""

    def __init__(self, enabled: bool, seconds: float, engine):
        super().__init__(enabled, seconds)
        self.seconds, self.engine = seconds, engine
        self.before = None
        self.counters: dict = {}
        self.program = None

    def poll(self, now: float, t0: float) -> None:
        from repro.runtime import spans

        if self.before is None:
            self.before = engine_counters(self.engine)
            spans.start()              # the engine's clock: perf_counter
        elif self.program is None and now - t0 >= self.seconds:
            self.close()
        super().poll(now, t0)

    def close(self) -> None:
        from repro.runtime import spans

        after = engine_counters(self.engine)
        self.counters = {k: after[k] - self.before[k] for k in after}
        self.program = spans.stop()

    def finish(self, now: float):
        if self.program is None:
            self.close()
        with keep_program_spans():
            return super().finish(now)


def run(cell, seed: int, seconds: float, trace: bool, devs,
        peaks) -> dict:
    from chipbench import spec
    from chipbench.record import Run
    from chipbench.serve import ServeCell
    from metrics.common import spans as readings

    sc = ServeCell(cell.config, cell.traffic, seed, devs)
    sc.warm_up()
    reqs = sc.requests(seconds)
    tracer = RecordingTracer(trace, seconds, sc.engine)
    setup_s = time.perf_counter() - T_START
    served, bench_spans, window = sc.run_window(reqs, seconds, tracer)
    t = tracer.finish(time.perf_counter())
    prog, traced, counters = tracer.program, tracer.host, tracer.counters
    out = {
        "workload": cell.name, "seed": seed, "setup_s": setup_s,
        "readings": {
            "queue_wait_ms": readings.queue_wait_ms(prog, traced),
            "admit_host_ms": readings.admit_host_ms(prog, traced),
            "prefix_copy_mb": readings.prefix_copy_mb(counters),
            "decode_host_ms": readings.decode_host_ms(prog, traced),
            "decode_batch": readings.decode_batch(counters)},
        "per_layer": {k: v["value"] for k, v in spec.read_metrics(
            cell.per_layer, Run(config=cell.config, peaks=peaks,
                                chips=len(devs), spans=bench_spans,
                                counters=window, trace=t,
                                traced=traced)).items()},
        "end_to_end": sc.end_to_end(served),
        "counters": counters,
        "spans": len(prog),
        "admission_ms": readings.admission_split(prog, traced),
    }
    if t is not None:
        out["idle_gaps"] = trace_mod.idle_gaps(t, 20)
        out["traced_admission"] = readings.traced_admission(t)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    from chipbench import device, spec

    cell = spec.load_cell(args.workload)
    devs = device.tpu_devices(cell.chips)
    peaks = device.peaks(devs[0].device_kind)
    device.compile_cache()
    out = run(cell, args.seed, args.seconds, bool(args.trace), devs, peaks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
