"""Radix prefix KV cache + prefix-aware chunked prefill (PR 10).

The load-bearing properties:

* trie exactness — lookup returns EXACTLY the longest cached block-aligned
  strict prefix, payloads intact (differential oracle vs a pure-Python LCP
  reference, deterministic + hypothesis);
* monoid bookkeeping — the folded stats table's bytes column always sums to
  the host byte mirror, and eviction order follows the decayed-LRU score
  (recency can beat frequency at short half-lives);
* serving exactness — a prefix-hit admission decodes bit-identically to a
  cold one (cached KV rows ARE the recomputed rows for position-indexed
  caches), batched same-bucket admissions share ONE prefill program, and
  the compile count stays within the declared bound.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401
from repro.core import monoids
from repro.data.windows import WindowedMetrics
from repro.models.attention import cache_layer, cache_write
from repro.serving import (ContinuousEngine, PrefixCache, PrefixCacheConfig,
                           ServeConfig)
from test_serving import drain, toy_backend, toy_engine

BLOCK = 2


def token_payload(prompt, block=BLOCK):
    """Payload generator whose block i IS the token block (one leaf)."""
    return lambda i: [np.asarray(prompt[i * block:(i + 1) * block],
                                 np.int64)]


def oracle_hit_blocks(inserted, prompt, block=BLOCK):
    """Pure-Python reference: longest cached strict block prefix of
    ``prompt`` given the full-block prefixes of every inserted prompt."""
    limit = max(len(prompt) - 1, 0) // block
    best = 0
    for p in inserted:
        lcp = 0
        while lcp < min(len(p), len(prompt)) and p[lcp] == prompt[lcp]:
            lcp += 1
        best = max(best, min(lcp // block, len(p) // block, limit))
    return best


class ManualClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# the trie: differential oracle
# ---------------------------------------------------------------------------

class TestTrieOracle:
    def test_lookup_is_longest_strict_block_prefix(self):
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=64))
        inserted = [[1, 2, 3, 4, 5], [1, 2, 3, 9], [7, 8]]
        for p in inserted:
            c.insert(p, token_payload(p))
        for prompt in ([1, 2, 3, 4, 5, 6], [1, 2, 3, 9, 9], [1, 2], [1, 3],
                       [7, 8, 1], [9], [1, 2, 3, 4], [1, 2, 3]):
            hit = c.lookup(prompt)
            want = oracle_hit_blocks(inserted, prompt)
            assert hit.length == want * BLOCK, prompt
            assert len(hit.blocks) == len(hit.node_ids) == want
            for i, blk in enumerate(hit.blocks):
                np.testing.assert_array_equal(
                    blk[0], prompt[i * BLOCK:(i + 1) * BLOCK])

    def test_shared_prefixes_share_nodes(self):
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=64))
        assert c.insert([1, 2, 3, 4], token_payload([1, 2, 3, 4])) == 2
        # the [1,2] node already exists: only the divergent block is new
        assert c.insert([1, 2, 9, 9], token_payload([1, 2, 9, 9])) == 1
        assert c.node_count == 3

    def test_max_blocks_caps_insert_depth(self):
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=64))
        p = [1, 2, 3, 4, 5, 6]
        c.insert(p, token_payload(p), max_blocks=2)
        assert c.lookup(p + [7]).length == 2 * BLOCK

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.lists(st.integers(0, 2), min_size=1, max_size=9),
                    min_size=1, max_size=10))
    def test_trie_matches_oracle_on_random_traces(self, prompts):
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=256))
        inserted = []
        for p in prompts:
            hit = c.lookup(p)
            assert hit.length == oracle_hit_blocks(inserted, p) * BLOCK
            for i, blk in enumerate(hit.blocks):
                np.testing.assert_array_equal(
                    blk[0], p[i * BLOCK:(i + 1) * BLOCK])
            c.insert(p, token_payload(p))
            inserted.append(p)
        assert c.accounted_bytes() == c.total_bytes


# ---------------------------------------------------------------------------
# monoid bookkeeping: byte accounting + decayed-LRU eviction order
# ---------------------------------------------------------------------------

class TestStatsFold:
    def test_bytes_column_tracks_host_mirror(self):
        clock = ManualClock()
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=4),
                        clock=clock)
        for p in ([1, 2], [3, 4], [5, 6, 7, 8]):
            c.insert(p, token_payload(p))
        assert c.total_bytes > 0
        assert c.accounted_bytes() == c.total_bytes
        c.evict(2)
        assert c.accounted_bytes() == c.total_bytes
        assert c.stats.evictions == 2

    def test_eviction_order_follows_decayed_score(self):
        clock = ManualClock()
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=8,
                                          half_life_s=1e6), clock=clock)
        # three leaves inserted together; touch B once and C three times
        for p in ([1, 1], [2, 2], [3, 3]):
            c.insert(p, token_payload(p))
        clock.t = 10.0
        c.lookup([2, 2, 0])
        for _ in range(3):
            c.lookup([3, 3, 0])
        # near-infinite half life: score == touch count; A < B < C
        assert c.evict(1) == 1 and c.lookup([1, 1, 0]).length == 0
        assert c.evict(1) == 1 and c.lookup([2, 2, 0]).length == 0
        assert c.lookup([3, 3, 0]).length == BLOCK

    def test_recency_beats_frequency_at_short_half_life(self):
        clock = ManualClock()
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=8,
                                          half_life_s=10.0), clock=clock)
        c.insert([1, 1], token_payload([1, 1]))
        for _ in range(5):
            c.lookup([1, 1, 0])          # 6 touches at t=0
        clock.t = 100.0
        c.insert([2, 2], token_payload([2, 2]))   # 1 touch at t=100
        # 6 * 2^-10 << 1: the stale-but-popular node goes first
        c.evict(1)
        assert c.lookup([1, 1, 0]).length == 0
        assert c.lookup([2, 2, 0]).length == BLOCK

    def test_capacity_eviction_protects_insert_path(self):
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=2))
        c.insert([1, 2, 3, 4], token_payload([1, 2, 3, 4]))
        assert c.node_count == 2
        # full: inserting a 2-block chain must evict, but never its own
        # freshly-created parent — the chain lands intact
        c.insert([5, 6, 7, 8], token_payload([5, 6, 7, 8]))
        assert c.lookup([5, 6, 7, 8, 9]).length == 2 * BLOCK
        assert c.node_count == 2
        assert c.accounted_bytes() == c.total_bytes

    def test_max_bytes_budget(self):
        p = [1, 2, 3, 4]
        one_block = int(np.asarray(p[:BLOCK], np.int64).nbytes)
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=16,
                                          max_bytes=2 * one_block))
        c.insert(p, token_payload(p))
        assert c.total_bytes == 2 * one_block
        c.insert([9, 9], token_payload([9, 9]))      # evicts to fit
        assert c.total_bytes <= 2 * one_block
        assert c.accounted_bytes() == c.total_bytes

    def test_one_fold_per_flush_and_compile_counts(self):
        c = PrefixCache(PrefixCacheConfig(block=BLOCK, capacity=8,
                                          events_per_fold=4))
        for p in ([1, 2], [3, 4], [5, 6]):
            c.insert(p, token_payload(p))
        assert c.flush_stats() == 1                  # 3 events, one chunk
        for p in ([1, 2, 9], [3, 4, 9], [5, 6, 9], [1, 2, 8], [3, 4, 8]):
            c.lookup(p)
        assert c.flush_stats() == 2                  # 5 events, two chunks
        counts = c.compile_counts()
        assert counts["prefix_stats_fold"] == 1      # fixed-shape: ONE program
        assert c.flush_stats() == 0

    def test_cache_stats_monoid_registered(self):
        assert monoids.missing_law_samples() == []
        m = monoids.cache_stats(32.0)
        assert m.name in monoids.REGISTRY


# ---------------------------------------------------------------------------
# engine integration: exactness, batching, compile bound
# ---------------------------------------------------------------------------

class TestEnginePrefix:
    def test_warm_hit_bit_identical_to_cold(self):
        p = [1, 2, 3, 4, 5, 6, 7]
        warm = toy_engine(num_slots=1, prefix_block=2)
        cold = toy_engine(num_slots=1, prefix_cache=False)
        u1 = warm.submit(p, seed=5)
        drain(warm)
        u2 = warm.submit(p, seed=5)          # second pass hits the trie
        drain(warm)
        uc = cold.submit(p, seed=5)
        drain(cold)
        assert warm.prefix.stats.hits == 1
        assert warm.result(u2).bucket < cold.result(uc).bucket  # suffix bucket
        for uid in (u1, u2):
            got, ref = warm.result(uid), cold.result(uc)
            assert got.tokens == ref.tokens
            assert got.logprob_sum == ref.logprob_sum            # bitwise
            assert got.stopped == ref.stopped

    def test_partial_hit_and_divergent_suffix(self):
        warm = toy_engine(num_slots=1, prefix_block=2)
        cold = toy_engine(num_slots=1, prefix_cache=False)
        a, b = [1, 2, 3, 4, 5, 6], [1, 2, 3, 4, 9, 9, 9]
        warm.submit(a, seed=1)
        drain(warm)
        u = warm.submit(b, seed=2)           # hits the shared 4-token prefix
        drain(warm)
        uc = cold.submit(b, seed=2)
        drain(cold)
        assert warm.prefix.stats.hit_tokens == 4
        assert warm.result(u).tokens == cold.result(uc).tokens
        assert warm.result(u).logprob_sum == cold.result(uc).logprob_sum

    def test_batched_admission_one_prefill_program(self):
        eng = toy_engine(num_slots=4, prefill_batch=4)
        prompts = [[1, 2, 3], [4, 5, 6], [7, 8, 9], [10, 11, 1]]
        uids = [eng.submit(p, seed=20 + i) for i, p in enumerate(prompts)]
        evs = eng.step()
        assert eng.stats.prefill_calls == 1              # ONE (4, bucket) call
        assert eng.stats.batched_admissions == 4
        assert len([e for e in evs
                    if e.kind == "token" and e.index == 0]) == 4
        drain(eng)
        from test_serving import solo_result
        for i, (p, uid) in enumerate(zip(prompts, uids)):
            ref = solo_result(p, 20 + i)
            got = eng.result(uid)
            assert got.tokens == ref.tokens
            assert got.logprob_sum == ref.logprob_sum
        counts = eng.compile_counts()
        assert counts["prefill_k4_b4"] == 1

    def test_mixed_buckets_group_separately(self):
        eng = toy_engine(num_slots=4, prefill_batch=4, prefix_cache=False)
        for p in ([1, 2], [3, 4], [1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]):
            eng.submit(p)
        eng.step()
        assert eng.stats.prefill_calls == 2              # one per bucket
        assert eng.stats.batched_admissions == 4

    def test_cache_events_feed_windowed_metrics(self):
        metrics = WindowedMetrics(window=8, tumble_s=0.5)
        eng = toy_engine(num_slots=2, prefix_block=2)
        eng.subscribe(metrics.observe)
        p = [1, 2, 3, 4, 5]
        eng.submit(p)
        drain(eng)
        eng.submit(p)
        drain(eng)
        fp = metrics.fleet_prefix()
        assert fp["prompt_tokens"] == 2 * len(p)
        assert fp["hit_tokens"] == 4                     # second admission
        assert fp["bytes_saved"] > 0
        assert fp["hit_rate"] == pytest.approx(4 / 10)

    def test_compile_bound_over_churny_warm_trace(self):
        eng = toy_engine(num_slots=3, prefill_buckets=(2, 4, 8),
                         prefill_batch=2, prefix_block=2)
        rng = np.random.default_rng(7)
        shared = rng.integers(1, 12, 6).tolist()
        for i in range(14):
            if rng.random() < 0.6:
                extra = rng.integers(1, 12, int(rng.integers(1, 3))).tolist()
                p = shared + extra
            else:
                p = rng.integers(1, 12, int(rng.integers(1, 9))).tolist()
            eng.submit(p, max_new_tokens=int(rng.integers(1, 6)))
        drain(eng, max_steps=500)
        assert eng.prefix.stats.hits > 0
        counts = eng.compile_counts()
        for key, n in counts.items():
            assert n <= 1, (key, n)
        assert sum(counts.values()) <= eng.compile_bound()

    def test_prefix_disabled_on_non_positional_backend(self):
        backend = toy_backend()
        backend.prefix_sharing = False
        eng = ContinuousEngine(backend, ServeConfig(
            num_slots=2, prefill_buckets=(4, 8), max_new_tokens=4,
            eos_id=-7))
        assert eng.prefix is None
        u = eng.submit([1, 2, 3])
        drain(eng)
        assert len(eng.result(u).tokens) == 4

    def test_accounting_stays_exact_under_engine_churn(self):
        eng = toy_engine(num_slots=2, prefix_block=2, prefix_capacity=4)
        rng = np.random.default_rng(3)
        for _ in range(10):
            eng.submit(rng.integers(1, 12,
                                    int(rng.integers(2, 8))).tolist())
        drain(eng, max_steps=500)
        assert eng.prefix.stats.evictions > 0            # capacity 4 churns
        assert eng.prefix.accounted_bytes() == eng.prefix.total_bytes


# ---------------------------------------------------------------------------
# the row writer: the step's new rows, scattered where the cache lies
# ---------------------------------------------------------------------------

def _rows_written(cache, new, pos, layer=None):
    """Loop reference of :func:`cache_write`: row j of batch row b at
    ``pos[b] + j``, rows at or past S dropped."""
    want = np.array(cache)
    view = want if layer is None else want[layer]
    B, L = new.shape[:2]
    S = view.shape[1]
    for b in range(B):
        start = int(pos[b]) if np.ndim(pos) else int(pos)
        for j in range(L):
            if start + j < S:
                view[b, start + j] = new[b, j]
    return want


class TestCacheSpanUpdate:
    def test_matches_loop_reference_vector_pos(self):
        rng = np.random.default_rng(0)
        cache = rng.normal(size=(3, 10, 4)).astype(np.float32)
        new = rng.normal(size=(3, 5, 4)).astype(np.float32)
        pos = np.array([0, 2, 5], np.int32)
        got = np.asarray(cache_write(jnp.asarray(cache), jnp.asarray(new),
                                     jnp.asarray(pos)))
        want = cache.copy()
        for b in range(3):
            want[b, pos[b]:pos[b] + 5] = new[b]
        np.testing.assert_array_equal(got, want)

    def test_scalar_pos_and_stacked_axis(self):
        rng = np.random.default_rng(1)
        cache = rng.normal(size=(2, 3, 10, 4)).astype(np.float32)  # (n,B,S,H)
        new = rng.normal(size=(3, 4, 4)).astype(np.float32)
        got = np.asarray(cache_write(jnp.asarray(cache), jnp.asarray(new),
                                     jnp.int32(3), jnp.int32(1)))
        want = cache.copy()
        want[1, :, 3:7] = new
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            np.asarray(cache_layer(jnp.asarray(got), jnp.int32(1))), want[1])

    @pytest.mark.parametrize("per_row", [False, True],
                             ids=["scalar", "per_row"])
    def test_span_past_the_end_is_dropped_not_shifted(self, per_row):
        rng = np.random.default_rng(2)
        cache = rng.normal(size=(2, 10, 3)).astype(np.float32)
        new = rng.normal(size=(2, 4, 3)).astype(np.float32)
        pos = np.array([1, 8], np.int32) if per_row else np.int32(8)
        got = np.asarray(cache_write(jnp.asarray(cache), jnp.asarray(new),
                                     jnp.asarray(pos)))
        want = cache.copy()
        for b in range(2):
            start = int(pos[b]) if per_row else int(pos)
            n = min(4, 10 - start)
            want[b, start:start + n] = new[b, :n]
        np.testing.assert_array_equal(got, want)

    def test_single_row_delegates_to_row_update(self):
        """L = 1, the decode step's write: one row per slot, each at its
        own position."""
        cache = jnp.zeros((2, 6), jnp.int32)
        out = cache_write(cache, jnp.asarray([[5], [7]], jnp.int32),
                          jnp.asarray([1, 4], jnp.int32))
        np.testing.assert_array_equal(np.asarray(out),
                                      [[0, 5, 0, 0, 0, 0],
                                       [0, 0, 0, 0, 7, 0]])

    @pytest.mark.parametrize("stacked", [False, True],
                             ids=["layer", "stack"])
    @pytest.mark.parametrize("L", [1, 5])
    @pytest.mark.parametrize("pos", [7, 9, (0, 9, 3), (11, 6, 2)],
                             ids=["scalar", "scalar_last", "per_row",
                                  "per_row_past_end"])
    def test_writes_only_the_new_rows(self, pos, L, stacked):
        """Scalar and per-row ``pos``, one row or a span, into a layer or a
        stack: the rows that fit are written, those at or past S dropped
        (a row starting at S writes nothing), and every other row of the
        cache, other layers' included, is bit-identical to before."""
        rng = np.random.default_rng(L)
        n, B, S = 3, 3, 10
        shape = (n, B, S, 2, 4) if stacked else (B, S, 2, 4)
        cache = rng.normal(size=shape).astype(np.float32)
        new = rng.normal(size=(B, L, 2, 4)).astype(np.float32)
        pos = np.asarray(pos, np.int32)
        layer = 2 if stacked else None
        got = np.asarray(cache_write(
            jnp.asarray(cache), jnp.asarray(new), jnp.asarray(pos),
            None if layer is None else jnp.int32(layer)))
        want = _rows_written(cache, new, pos, layer)
        np.testing.assert_array_equal(got, want)
        written = np.zeros(shape[:-2], bool)
        view = written if layer is None else written[layer]
        for b in range(B):
            start = int(pos[b]) if pos.ndim else int(pos)
            view[b, start:start + L] = True
        np.testing.assert_array_equal(got[~written], cache[~written])
