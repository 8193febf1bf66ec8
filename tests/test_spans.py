"""The serving engine's span recorder and the counters at the same
boundaries (toy backend, CPU).

* off by default: a whole ``engine.run()`` records nothing and builds no
  profiler annotation;
* on: spans nest under the right parents, inside their parents' times;
* a request's ``engine.queued`` span plus its admission up to the first
  token is its ``ttft_s``, on the engine's clock;
* the prefix KV byte counters, ``slot_steps`` and the queue-wait counter
  agree with what the run did.
"""
import itertools
import time

import numpy as np
import pytest

import jax

from repro.runtime import RequestBatcher, spans
from repro.serving import ContinuousEngine, ServeConfig
from test_serving import drain, toy_backend, toy_init_cache

PROMPTS = [[1, 2, 3, 4, 5], [1, 2, 3, 4, 9, 9], [7], [1, 2, 3, 4, 5, 6, 7],
           [2, 2], [1, 2, 3, 4], [5, 6, 7, 8, 1, 2], [3]]

# the span every engine span must sit in (None: top level)
PARENTS = {
    "engine.step": {None},
    "engine.queued": {None},
    "engine.admit": {"engine.step"},
    "prefix.lookup": {"engine.admit"},
    "engine.prefill_chunk": {"engine.admit"},
    "engine.assemble_prefix": {"engine.prefill_chunk"},
    "engine.prefix_upload": {"engine.prefill_chunk"},
    "engine.prefill": {"engine.prefill_chunk"},
    "engine.wait_gather": {"engine.prefill_chunk"},
    "engine.slot_write": {"engine.prefill_chunk"},
    "engine.wait_first_tokens": {"engine.prefill_chunk"},
    "prefix.insert": {"engine.prefill_chunk"},
    "prefix.evict": {"prefix.insert"},
    "prefix.wait_scores": {"prefix.evict"},
    "prefix.flush_stats": {"engine.step"},
    "engine.decode": {"engine.step"},
    "engine.decode_inputs": {"engine.decode"},
    "engine.decode_dispatch": {"engine.decode"},
    "engine.wait_tokens": {"engine.decode"},
    "engine.retire": {"engine.step", "engine.decode"},
    "engine.wait_table": {"engine.retire"},
    "engine.dispatch_events": {"engine.step"},
}


def tick_clock(step=1e-3):
    """Each reading is ``step`` seconds after the last one."""
    counter = itertools.count(1)
    return lambda: next(counter) * step


def serve(clock=time.perf_counter, record=True, **overrides):
    """Run PROMPTS through a toy engine; (engine, events, spans)."""
    kw = dict(num_slots=3, prefill_buckets=(4, 8), prefill_batch=2,
              max_new_tokens=4, eos_id=-7, prefix_block=2, prefix_capacity=6)
    kw.update(overrides)
    eng = ContinuousEngine(toy_backend(), ServeConfig(**kw), clock=clock)
    if record:
        spans.start(clock)
    try:
        for i, p in enumerate(PROMPTS):
            eng.submit(p, max_new_tokens=1 + i % 4)
        events = drain(eng)
    finally:
        got = spans.stop() if record else []
    return eng, events, got


@pytest.fixture(autouse=True)
def recorder_left_off():
    yield
    if spans.recording():            # a failed test left it on
        spans._stack.clear()
        spans.stop()


def test_off_records_nothing(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a profiler annotation was built while off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not spans.recording()
    assert spans.span("a", x=1) is spans.span("b")
    eng, events, _ = serve(record=False)
    assert eng.stats.completed == len(PROMPTS)
    spans.start()
    assert spans.stop() == []


def test_spans_nest_under_their_parents():
    eng, _, got = serve(clock=tick_clock())
    names = {sp.name for sp in got}
    assert names == set(PARENTS)          # every boundary is reached
    for sp in got:
        parent = None if sp.parent is None else got[sp.parent]
        assert (parent and parent.name) in PARENTS[sp.name], sp.name
        assert sp.wait == (".wait_" in sp.name), sp.name
        assert sp.end >= sp.start
        if parent is not None:
            assert parent.start <= sp.start and sp.end <= parent.end
    by = lambda name: [sp for sp in got if sp.name == name]   # noqa: E731
    assert len(by("engine.queued")) == eng.stats.admitted == len(PROMPTS)
    assert sorted(sp.attrs["uid"] for sp in by("engine.queued")) \
        == sorted(u for sp in by("engine.admit") for u in sp.attrs["uids"])
    for sp in by("engine.prefill_chunk"):
        assert sp.attrs["k"] == len(sp.attrs["uids"])
    assert len(by("engine.decode")) == eng.stats.steps
    assert len(by("prefix.evict")) == eng.prefix.stats.evictions > 0


@pytest.mark.parametrize("clock", ["tick", "perf_counter"])
def test_queue_and_admission_spans_add_up_to_ttft(clock):
    clock = tick_clock() if clock == "tick" else time.perf_counter
    eng, events, got = serve(clock=clock)
    queued = {sp.attrs["uid"]: sp for sp in got
              if sp.name == "engine.queued"}
    admit = {u: sp for sp in got if sp.name == "engine.admit"
             for u in sp.attrs["uids"]}
    firsts = [ev for ev in events if ev.kind == "token" and ev.index == 0]
    assert len(firsts) == len(PROMPTS)
    assert any(queued[ev.uid].seconds > 0 for ev in firsts)
    for ev in firsts:
        assert admit[ev.uid].end == ev.time_s
        assert queued[ev.uid].seconds + admit[ev.uid].seconds \
            == pytest.approx(ev.ttft_s, abs=1e-6)
    assert eng.queue.stats.taken == len(PROMPTS)
    assert eng.queue.stats.queue_wait_s == pytest.approx(
        sum(sp.seconds for sp in queued.values()), abs=1e-9)


def test_prefix_bytes_are_the_slabs_moved_per_chunk():
    eng, _, got = serve()
    kv = {k: v for k, v in toy_init_cache(1, True).items() if k != "pos"}
    row_bytes = sum(v.dtype.itemsize * v.size // (v.shape[0] * v.shape[1])
                    for v in kv.values())
    slab = eng.config.prefix_slab
    chunks = [sp.attrs["k"] for sp in got if sp.name == "engine.prefill_chunk"]
    assert sum(chunks) == eng.stats.admitted
    want = sum(k * slab * row_bytes for k in chunks)
    assert eng.stats.prefix_upload_bytes == want > 0
    assert eng.stats.prefix_download_bytes == want

    cold, _, _ = serve(record=False, prefix_cache=False)
    assert cold.stats.prefix_upload_bytes == 0
    assert cold.stats.prefix_download_bytes == 0


def test_slot_steps_sum_the_occupied_slots():
    eng, events, got = serve()
    decoded = [ev for ev in events if ev.kind == "token" and ev.index > 0]
    assert eng.stats.slot_steps == len(decoded) > eng.stats.steps
    assert eng.stats.slot_steps == sum(sp.attrs["slots"] for sp in got
                                       if sp.name == "engine.decode")


def test_take_counts_queue_wait():
    clock = iter([1.0, 2.0, 4.0]).__next__
    b = RequestBatcher(max_batch_size=4, clock=clock)
    b.submit([1])                                  # arrives at 1.0
    b.submit([2])                                  # arrives at 2.0
    assert len(b.take(1, now=5.0)) == 1
    assert (b.stats.taken, b.stats.queue_wait_s) == (1, 4.0)
    b.take(3)                                      # reads 4.0
    assert (b.stats.taken, b.stats.queue_wait_s) == (2, 6.0)
    assert (b.stats.flushed_batches, b.stats.flushed_requests) == (0, 0)


def test_recorder_brackets():
    spans.start()
    with pytest.raises(RuntimeError):
        spans.start()
    outer = spans.span("outer")
    outer.__enter__()
    with pytest.raises(RuntimeError):
        spans.stop()
    outer.__exit__(None, None, None)
    with spans.span("x", start=-1.0, uid=3, wait=True) as sp:
        assert sp.end_at(7.0) == 7.0
    got = spans.stop()
    assert [(s.name, s.parent, s.attrs, s.wait) for s in got] \
        == [("outer", None, {}, False), ("x", None, {"uid": 3}, True)]
    assert (got[1].start, got[1].end) == (-1.0, 7.0)
    assert np.isfinite(got[0].seconds)
