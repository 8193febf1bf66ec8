"""The readings of the program's spans and counters
(``metrics/common/spans.py``) on hand-built spans with known answers, the
trace reduction that keeps the program's host spans, and one smoke-size
window of ``program_spans.py`` on the CPU."""
import json
from types import SimpleNamespace

import jax
import pytest

from conftest import CHIP
import program_spans
from chipbench import spec
from metrics.common import spans as readings
from metrics.common import trace

FIXTURES = CHIP / "tests" / "fixtures"


def sp(name, start, end, parent=None, wait=False, **attrs):
    return SimpleNamespace(name=name, start=start, end=end, parent=parent,
                           wait=wait, attrs=attrs)


# two engine steps, the first admitting, the second only decoding, and one
# decode-only step inside the profiled slice [20, 30)
SPANS = [
    sp("engine.queued", -4.0, 1.0, uid=0),                        # 0
    sp("engine.step", 0.0, 10.0),                                 # 1
    sp("engine.queued", -1.0, 1.0, uid=1),                        # 2
    sp("engine.admit", 1.0, 8.0, 1, uids=[0, 1]),                 # 3
    sp("prefix.lookup", 1.0, 1.5, 3, uid=0),                      # 4
    sp("engine.prefill_chunk", 2.0, 7.0, 3, k=2),                 # 5
    sp("engine.assemble_prefix", 2.0, 3.0, 5),                    # 6
    sp("engine.wait_gather", 3.0, 5.0, 5, wait=True),             # 7
    sp("engine.wait_first_tokens", 4.5, 6.0, 5, wait=True),       # 8
    sp("prefix.insert", 6.0, 7.0, 5, uid=0),                      # 9
    sp("engine.decode", 8.0, 9.5, 1, slots=2),                    # 10
    sp("engine.wait_tokens", 8.5, 9.0, 10, wait=True),            # 11
    sp("engine.step", 10.0, 12.0),                                # 12
    sp("engine.decode", 10.0, 12.0, 12, slots=2),                 # 13
    sp("engine.wait_tokens", 10.5, 11.0, 13, wait=True),          # 14
    sp("engine.step", 20.0, 22.0),                                # 15
    sp("engine.queued", 19.0, 21.0, uid=2),                       # 16
    sp("engine.decode", 20.0, 22.0, 15, slots=3),                 # 17
    sp("engine.wait_tokens", 20.0, 21.9, 17, wait=True),          # 18
]
TRACED = (20.0, 30.0)


def test_self_and_host_time():
    assert readings.descendants(SPANS, 1) == list(range(3, 12))
    assert readings.descendants(SPANS, 3) == list(range(4, 10))
    # children of the admission: lookup 0.5 + chunk 5
    assert readings.self_time(SPANS, 3) == pytest.approx(7.0 - 5.5)
    # the chunk's children cover [2, 7) but for nothing: 0
    assert readings.self_time(SPANS, 5) == pytest.approx(0.0)
    # waits [3, 6) overlap once: 3 s of the admission's 7
    assert readings.host_time(SPANS, 3) == pytest.approx(4.0)
    assert readings.host_time(SPANS, 1) == pytest.approx(10.0 - 3.5)


def test_readings_leave_out_the_profiled_slice():
    # queued: 5 s and 2 s; the third overlaps the slice
    assert readings.queue_wait_ms(SPANS, TRACED) == pytest.approx(3500.0)
    assert readings.queue_wait_ms(SPANS, None) == pytest.approx(3000.0)
    assert readings.admit_host_ms(SPANS, TRACED) == pytest.approx(4000.0)
    # only the second step's decode: the first admitted, the third is traced
    assert readings.decode_host_ms(SPANS, TRACED) == pytest.approx(1500.0)
    assert readings.decode_host_ms(SPANS, None) == pytest.approx(
        (1500.0 + 100.0) / 2)
    assert readings.admission_split(SPANS, TRACED) == pytest.approx({
        "engine.admit": 1500.0, "prefix.lookup": 500.0,
        "engine.prefill_chunk": 5000.0, "engine.assemble_prefix": 1000.0,
        "engine.wait_gather": 2000.0, "engine.wait_first_tokens": 1500.0,
        "prefix.insert": 1000.0})
    assert readings.queue_wait_ms([], TRACED) is None
    assert readings.admit_host_ms(SPANS[:3], None) is None


def test_counter_readings():
    c = {"admitted": 4, "prefix_upload_bytes": 4 * 1008 * 114688,
         "prefix_download_bytes": 4 * 1008 * 114688, "steps": 10,
         "slot_steps": 47}
    assert readings.prefix_copy_mb(c) == pytest.approx(231.211008)
    assert readings.decode_batch(c) == pytest.approx(4.7)
    assert readings.prefix_copy_mb({"admitted": 0}) is None
    assert readings.prefix_copy_mb({"admitted": 3}) is None   # no counter
    assert readings.decode_batch({"steps": 3}) is None


# the harness's window [100, 1100) ns, one engine step with an admission
# whose slab upload leaves the device idle over [300, 500)
PLANES = [
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 100, 1000], ["bench.admit_step", 150, 700],
        ["repro.engine.step", 160, 680], ["repro.engine.admit", 170, 600],
        ["repro.engine.prefix_upload", 300, 200],
        ["repro.engine.wait_gather", 520, 200],
        ["other.span", 300, 200]]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["fusion.1", 100, 200], ["while.3", 500, 300],
            ["fusion.2", 900, 100]]},
        {"name": "XLA Modules", "events": [
            ["jit_prefill_impl", 500, 250], ["jit_write_impl", 750, 50],
            ["jit_step_impl", 900, 100]]}]},
]


def test_trace_keeps_program_spans():
    plain = trace.from_planes(PLANES)
    with program_spans.keep_program_spans():
        t = trace.from_planes(PLANES)
    assert trace.SPAN_PREFIX == "bench."
    assert [s[2] for s in plain.spans] == ["bench.window",
                                           "bench.admit_step"]
    assert sorted(s[2] for s in t.spans) == sorted(
        ["bench.window", "bench.admit_step", "repro.engine.step",
         "repro.engine.admit", "repro.engine.prefix_upload",
         "repro.engine.wait_gather"])
    # the longest gap [300, 500) is named by the innermost span over it
    assert trace.idle_gaps(plain)[0] == ["bench.admit_step",
                                         pytest.approx(200e-9)]
    assert trace.idle_gaps(t)[0] == ["repro.engine.prefix_upload",
                                     pytest.approx(200e-9)]
    assert trace.idle_share(t) == trace.idle_share(plain) \
        == pytest.approx(0.4)


def test_traced_admission():
    with program_spans.keep_program_spans():
        t = trace.from_planes(PLANES)
    got = readings.traced_admission(t)
    assert got["ms"] == pytest.approx(600e-6)
    assert got["host"] == [
        ["repro.engine.admit", 1, pytest.approx(600e-6)],
        ["repro.engine.prefix_upload", 1, pytest.approx(200e-6)],
        ["repro.engine.wait_gather", 1, pytest.approx(200e-6)]]
    assert got["device"] == [["jit_prefill_impl", 1, pytest.approx(250e-6)],
                             ["jit_write_impl", 1, pytest.approx(20e-6)]]
    assert readings.traced_admission(trace.from_planes(PLANES)) is None


def test_smoke_window_on_the_cpu():
    cfg = json.loads((FIXTURES / "qwen3-smoke.json").read_text())
    mix = json.loads((FIXTURES / "chat-smoke.json").read_text())
    cell = spec.Cell(workload={"name": "smoke", "chips": 1}, config=cfg,
                     traffic=mix, limits={}, end_to_end=[], per_layer=[])
    out = program_spans.run(cell, 2**31 + 5, 1.0, False,
                            jax.devices()[:1], {})
    got, c = out["readings"], out["counters"]
    assert c["admitted"] > 0 and c["taken"] == c["admitted"]
    assert all(v is not None for v in got.values()), got
    assert 1 <= got["decode_batch"] <= mix["server"]["num_slots"]
    # every admission sends its slab and fetches it back, whatever its hit
    slab = (max(mix["server"]["prefill_buckets"]) - 1) \
        // mix["server"]["prefix_block"] * mix["server"]["prefix_block"]
    row = 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * 2
    assert got["prefix_copy_mb"] == pytest.approx(2 * slab * row / 1e6,
                                                  rel=1e-12)
    assert out["admission_ms"]["engine.prefill_chunk"] > 0
