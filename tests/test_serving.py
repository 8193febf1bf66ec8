"""Continuous-batching serve engine: rolling slots, bucketed compilation,
streaming decode (repro.serving facade).

The load-bearing properties:

* slot-reuse exactness — a request admitted into a freed slot mid-decode
  produces tokens/metrics bit-identical to decoding it alone (per-slot
  cache positions make a reused slot indistinguishable from a fresh one);
* bounded compilation — the number of distinct jitted shapes over any
  arrival trace is bounded by the declared prefill-bucket ladder;
* the admission queue is FIFO, streaming is per-request ordered, and the
  deprecated batch-to-completion shim reports the same metrics.

Most tests drive a tiny deterministic toy backend (no model) so the slot
machinery is exercised in milliseconds; one class runs the real smoke
model end-to-end.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401
from repro.models.attention import cache_row_update, decode_positions
from repro.runtime import RequestBatcher
from repro.serving import ContinuousEngine, EngineBackend, Request, ServeConfig

VOCAB = 13
TOY_SEQ = 32


def toy_decode(params, cache, cur):
    """Deterministic position- and history-dependent toy LM.

    Row-independent by construction (everything is per-row), uses the cache
    position exactly like real attention does: reads only history at
    positions <= its own pos, so stale KV from a prior occupant is
    unreadable iff the engine's slot handoff is sound.
    """
    batch = cur.shape[0]
    pos = cache["pos"]
    posb = decode_positions(pos, batch)[:, 0]
    hist = cache_row_update(cache["hist"], cur, pos)
    valid = jnp.arange(hist.shape[1])[None, :] <= posb[:, None]
    s = jnp.sum(hist * valid, axis=1)
    tgt = (s * params["a"] + posb * params["b"]) % VOCAB
    logits = 5.0 * jax.nn.one_hot(tgt, VOCAB) + 0.01 * jnp.arange(VOCAB)
    return logits.astype(jnp.float32), {"pos": pos + 1, "hist": hist}


def toy_init_cache(batch, pos_per_slot):
    pos0 = jnp.zeros((batch,) if pos_per_slot else (), jnp.int32)
    return {"pos": pos0, "hist": jnp.zeros((batch, TOY_SEQ), jnp.int32)}


def toy_backend():
    return EngineBackend(decode=toy_decode, init_cache=toy_init_cache,
                         params={"a": jnp.int32(3), "b": jnp.int32(7)},
                         vocab_size=VOCAB)


def toy_engine(**overrides):
    kw = dict(num_slots=2, prefill_buckets=(4, 8), max_new_tokens=6,
              eos_id=-7)        # eos unreachable: retirement is budget-driven
    kw.update(overrides)
    return ContinuousEngine(toy_backend(), ServeConfig(**kw))


def drain(engine, max_steps=200):
    return list(engine.run(max_steps=max_steps))


def solo_result(prompt, uid_seed, **overrides):
    """The same request decoded alone in a fresh single-slot engine."""
    overrides = dict(overrides)
    max_new = overrides.pop("max_new", None)
    eng = toy_engine(num_slots=1, **overrides)
    uid = eng.submit(prompt, max_new_tokens=max_new, seed=uid_seed)
    drain(eng)
    return eng.result(uid)


# ---------------------------------------------------------------------------
# config + admission queue
# ---------------------------------------------------------------------------

class TestServeConfig:
    def test_bucket_ladder_validation(self):
        with pytest.raises(ValueError):
            ServeConfig(prefill_buckets=())
        with pytest.raises(ValueError):
            ServeConfig(prefill_buckets=(16, 8))
        with pytest.raises(ValueError):
            ServeConfig(prefill_buckets=(8, 8))
        with pytest.raises(ValueError):
            ServeConfig(num_slots=0)

    def test_bucket_for_and_max_seq(self):
        cfg = ServeConfig(prefill_buckets=(4, 8, 16), max_new_tokens=5)
        assert cfg.bucket_for(1) == 4
        assert cfg.bucket_for(4) == 4
        assert cfg.bucket_for(5) == 8
        assert cfg.bucket_for(16) == 16
        with pytest.raises(ValueError):
            cfg.bucket_for(17)
        assert cfg.max_seq == 16 + 5
        assert cfg.max_prompt == 16

    def test_submit_validation(self):
        eng = toy_engine()
        with pytest.raises(ValueError):
            eng.submit([])
        with pytest.raises(ValueError):
            eng.submit(list(range(9)))          # > largest bucket (8)
        with pytest.raises(ValueError):
            eng.submit([1], max_new_tokens=0)
        with pytest.raises(ValueError):
            eng.submit([1], max_new_tokens=99)  # > config ceiling


class TestBatcherTake:
    def test_take_is_fifo_and_immediate(self):
        b = RequestBatcher(max_batch_size=4, max_wait_s=100.0)
        uids = [b.submit([i]) for i in range(5)]
        got = b.take(2)
        assert [r.uid for r in got] == uids[:2]   # oldest first, no waiting
        assert len(b) == 3
        assert [r.uid for r in b.take(10)] == uids[2:]
        assert b.take(3) == ()
        with pytest.raises(ValueError):
            b.take(-1)


# ---------------------------------------------------------------------------
# the rolling engine (toy backend)
# ---------------------------------------------------------------------------

class TestContinuousEngine:
    def test_slot_reused_mid_decode(self):
        """The tentpole: a freed slot is handed to a waiting request while
        another request is still decoding, and both come out exact."""
        eng = toy_engine(num_slots=2)
        a = eng.submit([1, 2, 3], max_new_tokens=6)
        b = eng.submit([4, 5], max_new_tokens=2)
        c = eng.submit([6, 7, 8], max_new_tokens=2)   # queued: no free slot
        events = drain(eng)

        done = {e.uid: e for e in events if e.kind == "done"}
        admit = {e.uid: e for e in events
                 if e.kind == "token" and e.index == 0}
        # c inherits b's slot, is admitted after b retires and BEFORE a
        # finishes — continuous batching, not batch-to-completion
        assert admit[c].slot == done[b].result.slot
        assert done[b].step <= admit[c].step < done[a].step
        assert eng.stats.slot_reuses >= 1
        # and every request matches its solo decode exactly
        for uid, prompt, max_new in [(a, [1, 2, 3], 6), (b, [4, 5], 2),
                                     (c, [6, 7, 8], 2)]:
            ref = solo_result(prompt, uid, max_new=max_new)
            got = eng.result(uid)
            assert got.tokens == ref.tokens
            assert got.logprob_sum == ref.logprob_sum   # bitwise
            assert got.stopped == ref.stopped

    def test_admission_is_fifo(self):
        eng = toy_engine(num_slots=1, max_new_tokens=1)
        uids = [eng.submit([i + 1]) for i in range(4)]
        events = drain(eng)
        done_order = [e.uid for e in events if e.kind == "done"]
        assert done_order == uids

    def test_streaming_order_and_ttft(self):
        eng = toy_engine()
        uids = [eng.submit([1, 2]), eng.submit([3, 4, 5, 6, 7])]
        events = drain(eng)
        for uid in uids:
            toks = [e for e in events if e.kind == "token" and e.uid == uid]
            assert [e.index for e in toks] == list(range(len(toks)))
            assert toks[0].ttft_s is not None and toks[0].ttft_s >= 0
            assert all(e.ttft_s is None for e in toks[1:])
            done = [e for e in events if e.kind == "done" and e.uid == uid]
            assert len(done) == 1
            res = done[0].result
            assert res.tokens == [e.token for e in toks]
            assert res.ttft_s == toks[0].ttft_s
            assert eng.result(uid) is res

    def test_eos_stops_and_frees_slot(self):
        # find a token the toy model actually generates, make it the eos
        probe = toy_engine(num_slots=1)
        u = probe.submit([1, 2, 3])
        drain(probe)
        eos = probe.result(u).tokens[-1]
        eng = toy_engine(num_slots=1, eos_id=eos)
        u2 = eng.submit([1, 2, 3])
        drain(eng)
        res = eng.result(u2)
        assert res.stopped
        assert res.tokens[-1] == eos
        assert len(res.tokens) <= len(probe.result(u).tokens)
        assert eng.num_active == 0

    def test_metrics_table_matches_results(self):
        eng = toy_engine()
        uids = [eng.submit([1, 2, 3, 4]), eng.submit([5, 6])]
        drain(eng)
        for uid in uids:
            res = eng.result(uid)
            # logprob_sum is the fold of per-token log-softmax picks
            assert np.isfinite(res.logprob_sum)
            assert len(res.tokens) >= 1
            assert res.latency_s >= res.ttft_s >= 0

    def test_temperature_sampling_is_request_keyed(self):
        """temperature>0: per-(seed, token-index) PRNG streams make a
        request's samples independent of slot assignment and neighbours."""
        prompts = [[1, 2], [3, 4, 5], [6]]
        eng = toy_engine(temperature=1.0, num_slots=2)
        uids = [eng.submit(p, seed=100 + i) for i, p in enumerate(prompts)]
        drain(eng)
        for i, (p, uid) in enumerate(zip(prompts, uids)):
            ref = solo_result(p, 100 + i, temperature=1.0)
            assert eng.result(uid).tokens == ref.tokens

    def test_recompile_count_bounded_by_bucket_ladder(self):
        """Zero recompilation beyond the declared ladder: one step program,
        one slot-write/prefix-gather program per admission size k, one
        prefill program per (k, bucket) — over a churny trace of mixed
        lengths, budgets, and slot handoffs."""
        eng = toy_engine(num_slots=3, prefill_buckets=(2, 4, 8))
        rng = np.random.default_rng(0)
        for i in range(12):
            plen = int(rng.integers(1, 9))
            eng.submit(rng.integers(1, VOCAB, plen).tolist(),
                       max_new_tokens=int(rng.integers(1, 7)))
        drain(eng, max_steps=500)
        counts = eng.compile_counts()
        assert eng.stats.slot_reuses > 0
        assert counts["step"] == 1
        for key, n in counts.items():
            assert n <= 1, (key, n)
        assert sum(counts.values()) <= eng.compile_bound()

    def test_fixed_trace_matches_solo(self):
        """Deterministic fallback for the hypothesis property below."""
        trace = [([1, 2, 3, 4, 5, 6], 4), ([7, 8], 6), ([9], 1),
                 ([10, 11, 12], 3), ([1, 3, 5, 7], 6), ([2, 4], 2)]
        eng = toy_engine(num_slots=2)
        uids = [eng.submit(p, max_new_tokens=m) for p, m in trace]
        drain(eng)
        for uid, (p, m) in zip(uids, trace):
            ref = solo_result(p, uid, max_new=m)
            got = eng.result(uid)
            assert (got.tokens, got.logprob_sum, got.stopped) == \
                (ref.tokens, ref.logprob_sum, ref.stopped)


@settings(max_examples=15, deadline=None)
@given(st.lists(
    st.tuples(st.lists(st.integers(1, VOCAB - 1), min_size=1, max_size=8),
              st.integers(1, 6)),
    min_size=1, max_size=8))
def test_arrival_trace_bit_identical_to_solo(trace):
    """Property: ANY arrival trace through the rolling engine yields
    per-request (tokens, logprob sum, stop) bit-identical to decoding each
    request alone — slot reuse is unobservable in the results."""
    eng = toy_engine(num_slots=2)
    uids = [eng.submit(p, max_new_tokens=m) for p, m in trace]
    drain(eng, max_steps=1000)
    for uid, (p, m) in zip(uids, trace):
        ref = solo_result(p, uid, max_new=m)
        got = eng.result(uid)
        assert got.tokens == ref.tokens
        assert got.logprob_sum == ref.logprob_sum
        assert got.stop_step >= 0 and ref.stop_step >= 0
        assert got.stopped == ref.stopped


# ---------------------------------------------------------------------------
# real model substrate (smoke config) + deprecated shim
# ---------------------------------------------------------------------------

# logprob sums of one request decoded in batches of different shapes: a few
# float32 ulps of a sum of a handful of per-token logprobs
LOGPROB_RTOL = 1e-5


class TestRealModelServing:
    @pytest.fixture(scope="class")
    def engine_factory(self):
        from repro.serving import build_engine

        def make(**overrides):
            kw = dict(arch="qwen3-0.6b", num_slots=2, prefill_buckets=(8,),
                      max_new_tokens=4)
            kw.update(overrides)
            return build_engine(ServeConfig(**kw))

        return make

    @pytest.fixture(scope="class")
    def solo_engine(self, engine_factory):
        # ONE single-slot reference engine, reused across prompts (its slot
        # hands off between them — solo decode is itself slot reuse)
        return engine_factory(num_slots=1)

    def solo(self, solo_engine, prompt):
        uid = solo_engine.submit(prompt)
        drain(solo_engine)
        return solo_engine.result(uid)

    def test_engine_matches_solo_decode(self, engine_factory, solo_engine):
        prompts = [[5, 9, 2, 7], [11, 3], [6, 6, 6], [8, 1, 4, 4, 2]]
        eng = engine_factory()
        uids = [eng.submit(p) for p in prompts]
        drain(eng)
        assert eng.stats.slot_reuses >= 1
        counts = eng.compile_counts()
        assert counts["step"] == 1 and counts["write_k1"] == 1
        assert counts["prefill_k1_b8"] == 1
        assert sum(counts.values()) <= eng.compile_bound()
        for p, uid in zip(prompts, uids):
            ref, got = self.solo(solo_engine, p), eng.result(uid)
            assert got.tokens == ref.tokens
            # a 2-slot and a 1-slot step are different programs: XLA may
            # order the float reductions by batch shape, so the sums agree
            # to float32 rounding, not bit for bit
            assert got.logprob_sum == pytest.approx(ref.logprob_sum,
                                                    rel=LOGPROB_RTOL)
            assert got.stopped == ref.stopped

    def test_prefix_hit_bit_identical_to_cold(self, engine_factory):
        """A real-model admission served from the prefix cache decodes
        bit-identically to a cold prefill of the same prompt: RoPE keys KV
        rows to absolute positions, so cached rows ARE recomputed rows."""
        prompt = [5, 9, 2, 7, 11, 3]
        warm = engine_factory(num_slots=1, prefill_buckets=(4, 8),
                              prefix_block=2)
        cold = engine_factory(num_slots=1, prefill_buckets=(4, 8),
                              prefix_cache=False)
        u1 = warm.submit(prompt)
        drain(warm)
        u2 = warm.submit(prompt)
        drain(warm)
        uc = cold.submit(prompt)
        drain(cold)
        assert warm.prefix is not None and warm.prefix.stats.hits == 1
        ref = cold.result(uc)
        for uid in (u1, u2):
            got = warm.result(uid)
            assert got.tokens == ref.tokens
            assert got.logprob_sum == ref.logprob_sum   # bitwise
        assert warm.result(u2).bucket == 4              # suffix bucket

    def test_run_batched_decode_shim(self, engine_factory, solo_engine):
        from repro.runtime import DecodeBatch
        from repro.serving import run_batched_decode

        prompts = [[5, 9, 2, 7], [11, 3, 8]]
        reqs = tuple(Request(uid=i, prompt=tuple(p), max_new_tokens=4)
                     for i, p in enumerate(prompts))
        batch = DecodeBatch(requests=reqs, num_slots=2)
        eng = engine_factory()
        with pytest.warns(DeprecationWarning):
            res = run_batched_decode(eng, batch)
        assert res.tokens.shape == (2, 4)
        # shim metrics identical to each request decoded alone
        for i, p in enumerate(prompts):
            ref = self.solo(solo_engine, p)
            assert res.tokens[i, : len(ref.tokens)].tolist() == ref.tokens
            # different batch shapes: equal to float32 rounding (see above)
            assert res.metrics["logprob_sum"][i] == pytest.approx(
                ref.logprob_sum, rel=LOGPROB_RTOL)
            assert res.metrics["tokens"][i] == len(ref.tokens)
            assert res.metrics["stopped"][i] == ref.stopped
        assert res.decode_steps == eng.stats.steps
        assert res.prefill_s > 0 and res.decode_s > 0
