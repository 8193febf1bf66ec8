"""The trace reduction on a small trace with known answers."""
import json

import pytest

from conftest import CHIP
from metrics.common import trace

# window [100, 1100) ns; device 0 runs a fold program with one collective,
# device 1 one long op; the harness admitted over [150, 500)
PLANES = [
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 100, 1000], ["bench.admit_step", 150, 350],
        ["bench.decode_step", 500, 200]]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["fusion.1", 120, 100], ["all-reduce.3", 200, 100],
            ["fusion.2", 600, 50], ["fusion.1", 1050, 100]]},
        {"name": "XLA Modules", "events": [
            ["jit_bench_fold_next(1)", 110, 200], ["jit_other", 590, 70]]}]},
    {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.9", 100, 500]]}]},
]


@pytest.fixture
def t():
    return trace.from_planes(PLANES)


def test_busy_and_idle(t):
    # device 0: [120, 300) + [600, 650) + [1050, 1100) = 280 ns;
    # device 1: [100, 600) = 500 ns; mean 390 ns of a 1000 ns window
    assert t.window_s == pytest.approx(1e-6)
    assert trace.busy_s(t) == pytest.approx(390e-9)
    assert trace.idle_share(t) == pytest.approx(0.61)


def test_collective_exposure(t):
    # the all-reduce [200, 300) overlaps fusion.1 until 220: 80 ns exposed
    # on device 0, none on device 1
    assert trace.collective_exposed_share(t) == pytest.approx(0.04)


def test_per_op_and_module_time(t):
    assert trace.top_ops(t) == [
        ["fusion.9", pytest.approx(250e-9)], ["fusion.1", pytest.approx(75e-9)],
        ["all-reduce.3", pytest.approx(50e-9)], ["fusion.2", pytest.approx(25e-9)]]
    d0 = t.devices[0]
    runs = trace.module_runs(d0, "bench_fold", *t.window)
    assert [r[2] for r in runs] == ["jit_bench_fold_next(1)"]
    assert trace.op_seconds_in(d0, runs, collectives=False) == pytest.approx(100e-9)
    assert trace.op_seconds_in(d0, runs, collectives=True) == pytest.approx(100e-9)


def test_idle_gaps_named_by_the_covering_span(t):
    assert trace.idle_gaps(t) == [
        ["bench.window", pytest.approx(400e-9)],
        ["bench.admit_step", pytest.approx(300e-9)],
        ["bench.window", pytest.approx(20e-9)]]


def test_interval_helpers():
    a = trace.union([(0, 10, "a"), (5, 20, "b"), (30, 40, "c")], 2, 35)
    assert a == [(2, 20), (30, 35)]
    assert trace.subtract(a, [(0, 3), (10, 31)]) == [(3, 10), (31, 35)]
    assert trace.is_collective("all-gather-start.2")
    assert trace.is_collective("reduce-scatter.1")
    assert not trace.is_collective("fusion.12")


def test_a_trace_needs_one_window_and_a_device():
    with pytest.raises(ValueError, match="bench.window"):
        trace.from_planes(PLANES[1:])
    with pytest.raises(ValueError, match="no TPU device"):
        trace.from_planes(PLANES[:1])


def test_planes_of_reads_the_harness_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.decode_step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.planes_of(trace.find_xplane(str(tmp_path)))
    names = {e[0] for p in planes for ln in p["lines"] for e in ln["events"]}
    assert {"bench.window", "bench.decode_step"} <= names


def test_recorded_tpu_excerpt_reduces_consistently():
    """An excerpt of a trace recorded on a TPU v5e serving qwen3-0.6b: busy
    time agrees with an independent sweep over start/end events."""
    planes = json.loads((CHIP / "tests" / "fixtures" /
                         "tpu_trace_excerpt.json").read_text())
    t = trace.from_planes(planes)
    lo, hi = t.window
    edges = []
    for s, e, _ in t.devices[0].ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for x, step in sorted(edges, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += x - last
        depth += step
        last = x
    assert trace.busy_s(t) == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0.0 < trace.idle_share(t) < 1.0
    assert all(" " not in name for name, _ in trace.top_ops(t))
    assert all(g[0] == "bench.admit_step" for g in trace.idle_gaps(t)[:3])
