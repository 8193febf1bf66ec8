"""Continuous-batching serve engine: rolling request slots, bucketed
compilation, streaming decode.

The paper's point is that monoid aggregation states can be merged
incrementally, anywhere, at any time — which is precisely what a
continuously-batched decode loop needs.  A fixed batch decoded to
completion (PR 3's ``run_batched_decode``) wastes every slot whose request
finished early; here a freed slot (= segment id, the planner's keyed-fold
key) is handed to the next waiting request *mid-decode*, and the
per-request metrics keep folding through the SAME keyed masked fold
(:func:`decode_metrics_step`) over the rolling slot population — the fold
never needs to know a slot changed hands, because the running table is just
a monoid value re-bracketed across admissions (``init=`` carries it).

Compilation is bucketed so slot churn never recompiles anything:

* ONE decode-step program at ``(num_slots, 1)`` — model forward + per-row
  sampling + the keyed masked metrics fold, jitted together.
* ONE prefill program per ``(k, bucket)`` pair — up to k same-bucket
  admissions ``lax.scan`` the decode step together over their prompts
  padded to the bucket, against a fresh k-row cache whose first ``slab``
  rows were scattered from the radix prefix cache
  (``runtime/prefix_cache.py``) so only the uncached SUFFIX is computed
  (buckets are chosen on suffix length).
* ONE slot-write program per k — scatter the prefilled k-row cache into
  the rolling cache at the freed slots (resetting their metrics rows) —
  and, with the prefix cache on, ONE gather program per k that slices the
  first ``slab`` KV rows back out for the trie.

So the number of distinct jitted shapes is bounded by
:meth:`ContinuousEngine.compile_bound` for the whole engine lifetime (the
recompile-count test in tests/test_serving.py asserts this).  Padding to
the nearest bucket trades bounded extra prefill FLOPs for zero recompiles —
the external-memory cost-model trade (Greiner & Jacob, PAPERS.md): pay
predictable padding, never pay compilation.

Slot independence is guaranteed by the model layer's per-slot cache
positions (``init_cache(pos_per_slot=True)``): each row writes and masks
its KV at its own position, so a reused slot's computation is bit-identical
to the same request decoded alone.

The engine is model-agnostic: it drives an :class:`EngineBackend` (a
traceable decode function + cache constructor), so the whole slot/admission
machinery is testable without a model.  ``repro.launch.serve.build_engine``
wires the real model substrate; the stable import surface is
``repro.serving``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import monoids
from ..core.plan import Plan, execute_fold, plan_fold
from ..models.attention import cache_span_update
from .batcher import Request, RequestBatcher
from .prefix_cache import PrefixCache, PrefixCacheConfig, PrefixHit

# ---------------------------------------------------------------------------
# the per-request metrics fold (request slot == segment id)
# ---------------------------------------------------------------------------

# columns of the per-request metrics table — ONE additive fold carries all
# three: sum of sampled-token logprobs, count of generated tokens, and the
# stop condition as a summed indicator (eos_hits > 0 <=> OR of eos hits)
METRIC_COLS = ("logprob_sum", "tokens", "eos_hits")


def decode_metrics_init(num_slots: int) -> jnp.ndarray:
    """The identity table: (num_slots, len(METRIC_COLS)) float32 zeros."""
    return jnp.zeros((num_slots, len(METRIC_COLS)), jnp.float32)


def decode_metrics_plan(batch_rows: int, num_slots: int) -> Plan:
    """The plan of ONE decode step's per-request aggregation (no FLOPs).

    This is the contract the serving path is built on: B concurrent
    requests aggregate through a single keyed, masked fold — inspect the
    plan to see one local tier, not B of them.
    """
    return plan_fold(
        monoids.sum_,
        jax.ShapeDtypeStruct((batch_rows, len(METRIC_COLS)), jnp.float32),
        segment_ids=jax.ShapeDtypeStruct((batch_rows,), jnp.int32),
        num_segments=num_slots,
        valid_mask=jax.ShapeDtypeStruct((batch_rows,), jnp.bool_))


def metric_rows(logits: jnp.ndarray, sampled: jnp.ndarray,
                eos_id: int) -> jnp.ndarray:
    """(B, V) logits + (B,) sampled ids -> (B, 3) metric rows to fold."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tok_logp = jnp.take_along_axis(logp, sampled[:, None], axis=-1)[:, 0]
    return jnp.stack(
        [tok_logp, jnp.ones_like(tok_logp),
         (sampled == eos_id).astype(jnp.float32)], axis=-1)


def fold_decode_metrics(table: jnp.ndarray, rows: jnp.ndarray,
                        slot_ids: jnp.ndarray, active: jnp.ndarray,
                        num_slots: int) -> jnp.ndarray:
    """ONE planner-lowered keyed masked fold of metric rows into the table."""
    return execute_fold(monoids.sum_, rows, segment_ids=slot_ids,
                        num_segments=num_slots, valid_mask=active, init=table)


@functools.partial(jax.jit, static_argnames=("num_slots", "eos_id"))
def decode_metrics_step(table: jnp.ndarray, logits: jnp.ndarray,
                        sampled: jnp.ndarray, slot_ids: jnp.ndarray,
                        active: jnp.ndarray, *, num_slots: int,
                        eos_id: int) -> jnp.ndarray:
    """Fold one decode step's per-request aggregates into the running table.

    logits: (B, V) last-position logits; sampled: (B,) sampled token ids;
    slot_ids: (B,) request slot per row (segment ids); active: (B,) bool —
    rows still generating this step.  The whole batch reduces in ONE
    planner-lowered keyed fold; inactive/empty slots are masked to the
    identity, and the running table rides in as ``init`` (the fold across
    steps is the same monoid, re-bracketed — the paper's point).
    """
    rows = metric_rows(logits, sampled, eos_id)
    return fold_decode_metrics(table, rows, slot_ids, active, num_slots)


def extract_metrics(table: jnp.ndarray) -> Dict[str, np.ndarray]:
    """Read the metrics table out into per-slot host arrays."""
    t = np.asarray(table)
    return {
        "logprob_sum": t[:, 0],
        "tokens": t[:, 1].astype(np.int64),
        "stopped": t[:, 2] > 0,       # summed eos indicator == OR
    }


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """One config object for the whole serving stack.

    Shared by :class:`ContinuousEngine`, ``repro.launch.serve`` (model
    wiring + CLI) and ``benchmarks/bench_serve.py`` — replaces the loose
    ``(arch, max_batch, max_seq, ...)`` keywords the PR-3 API threaded
    around.
    """

    arch: str = "qwen3-0.6b"
    num_slots: int = 4                       # rolling request slots (segment ids)
    prefill_buckets: Tuple[int, ...] = (16, 32)   # prompt-length ladder, ascending
    max_new_tokens: int = 16                 # per-request generation ceiling
    eos_id: int = 0
    pad_id: int = 0
    temperature: float = 0.0                 # 0 = greedy
    seed: int = 0                            # sampling PRNG seed
    model_parallel: int = 1
    full: bool = False                       # full-size config (default: smoke)
    # batched same-bucket admission: up to this many waiting requests with
    # the same suffix bucket prefill in ONE (k, bucket) program; the power-
    # of-two k-ladder keeps the compile bound declared
    prefill_batch: int = 1
    # radix prefix KV cache (runtime/prefix_cache.py): admissions look up
    # the longest cached block-aligned prefix, scatter its KV rows into the
    # slot cache, and prefill only the remaining suffix
    prefix_cache: bool = True
    prefix_block: int = 4                    # tokens per trie node
    prefix_capacity: int = 256               # trie nodes == stats-table rows
    prefix_max_bytes: Optional[int] = None   # resident-KV budget (None = off)
    prefix_half_life_s: float = 60.0         # decayed-LRU eviction half life

    def __post_init__(self):
        buckets = tuple(int(b) for b in self.prefill_buckets)
        if not buckets or any(b < 1 for b in buckets) or \
                list(buckets) != sorted(set(buckets)):
            raise ValueError(
                f"prefill_buckets must be distinct ascending positive ints, "
                f"got {self.prefill_buckets}")
        object.__setattr__(self, "prefill_buckets", buckets)
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if self.prefill_batch < 1:
            raise ValueError("prefill_batch must be >= 1")
        if self.prefix_block < 1:
            raise ValueError("prefix_block must be >= 1")
        if self.prefix_capacity < 1:
            raise ValueError("prefix_capacity must be >= 1")

    @property
    def max_prompt(self) -> int:
        return self.prefill_buckets[-1]

    @property
    def prefill_k_ladder(self) -> Tuple[int, ...]:
        """Powers of two up to min(prefill_batch, num_slots) — the declared
        admission batch sizes (each is one compiled (k, bucket) program)."""
        ks, k = [], 1
        while k <= min(self.prefill_batch, self.num_slots):
            ks.append(k)
            k *= 2
        return tuple(ks)

    @property
    def prefix_slab(self) -> int:
        """Per-request prefix rows every prefill program accepts: the
        largest block multiple strictly below the biggest bucket (a hit
        must leave >= 1 suffix token to produce the first logits)."""
        return ((self.max_prompt - 1) // self.prefix_block) \
            * self.prefix_block

    @property
    def max_seq(self) -> int:
        """Cache length: the largest bucket plus the generation ceiling."""
        return self.prefill_buckets[-1] + self.max_new_tokens

    def bucket_for(self, prompt_len: int) -> int:
        """Smallest ladder bucket that fits the prompt."""
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds the largest prefill "
            f"bucket ({self.prefill_buckets[-1]})")


# ---------------------------------------------------------------------------
# streaming API
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RequestResult:
    """Final per-request record, built from the slot's metrics-table row."""

    uid: int
    slot: int
    prompt_len: int
    bucket: int
    user: int
    tokens: List[int]
    logprob_sum: float
    stopped: bool                 # hit eos (vs exhausted max_new_tokens)
    stop_step: int                # engine step count at retirement
    ttft_s: float                 # submit -> first streamed token
    latency_s: float              # submit -> retirement


@dataclasses.dataclass(frozen=True)
class StreamEvent:
    """One streamed serving event.

    kind == "token": ``token``/``index`` are set; ``ttft_s`` on index 0.
    kind == "done":  ``result`` carries the full :class:`RequestResult`.
    kind == "cache": emitted at admission when the prefix cache is on —
      ``hit_tokens``/``prompt_tokens``/``bytes_saved`` feed the fleet
      prefix-hit-rate windows (``data.windows.WindowedMetrics``).
    """

    uid: int
    kind: str                     # "token" | "done" | "cache"
    slot: int
    step: int                     # engine step counter at emission
    time_s: float
    user: int = 0
    token: Optional[int] = None
    index: Optional[int] = None   # position in the generated sequence
    ttft_s: Optional[float] = None
    result: Optional[RequestResult] = None
    hit_tokens: Optional[int] = None      # prompt tokens served from cache
    prompt_tokens: Optional[int] = None   # total prompt tokens
    bytes_saved: Optional[int] = None     # KV bytes not re-prefilled


# ---------------------------------------------------------------------------
# backend contract
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineBackend:
    """What the engine needs from a model substrate.

    ``decode(params, cache, cur)`` must be *traceable* (the engine jits it,
    fused with sampling and the metrics fold) and row-independent: row b of
    the outputs depends only on row b of ``cache``/``cur``.  ``cur`` is
    ``(B, 1)`` int32; it returns ``((B, V) float32 logits, new cache)``.

    ``init_cache(batch, pos_per_slot)`` builds a fresh cache pytree whose
    leaves carry the batch dim at axis 0 (axis 1 under the ``stacked_key``
    subtree) plus a ``pos`` leaf — scalar, or ``(batch,)`` when
    ``pos_per_slot`` (the rolling cache).
    """

    decode: Callable[[Any, Any, jnp.ndarray], Tuple[jnp.ndarray, Any]]
    init_cache: Callable[[int, bool], Any]
    params: Any
    vocab_size: int
    stacked_key: str = "layers"   # cache subtree with a leading stack dim
    # True iff every non-``pos`` cache leaf is indexed by absolute sequence
    # position (see models.transformer.positional_cache) — the property
    # prefix KV sharing needs; recurrent-state substrates set this False
    # and the engine keeps cold prefills only
    prefix_sharing: bool = True
    # placement for the engine's initial device state (rolling cache +
    # metrics table).  Mesh-aware backends should commit with the SAME
    # sharding their jitted outputs carry — otherwise the first write_slot
    # call sees differently-placed args and compiles a second (identical)
    # executable for the same shape.
    place: Optional[Callable[[Any], Any]] = None


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineStats:
    submitted: int = 0
    admitted: int = 0
    completed: int = 0
    steps: int = 0                # decode steps over the rolling population
    slot_reuses: int = 0          # admissions into a previously-used slot
    generated_tokens: int = 0
    prefill_calls: int = 0        # prefill program invocations (k >= 1 each)
    batched_admissions: int = 0   # admissions that shared a k > 1 prefill


@dataclasses.dataclass
class _SlotState:
    uid: int
    user: int
    seed: int
    prompt_len: int
    bucket: int
    max_new: int
    arrival_s: float
    ttft_s: float
    tokens: List[int]
    cur: int                      # last sampled token (next step's input)

    @property
    def n_gen(self) -> int:
        return len(self.tokens)


@dataclasses.dataclass
class _AdmitJob:
    """One admission in flight: request + slot + prefix-cache hit."""

    req: Request
    slot: int
    plen: int
    bucket: int                   # SUFFIX bucket (prompt minus cached prefix)
    seed: int
    hit: Optional[PrefixHit]
    first: int = 0                # first sampled token, set by _admit_chunk


class ContinuousEngine:
    """Admit and retire requests *mid-decode* over rolling request slots.

    Lifecycle per request: ``submit`` enqueues it on the FIFO admission
    queue (a :class:`~repro.runtime.batcher.RequestBatcher`); when slots
    free, ``_admit`` looks up each prompt's longest cached prefix in the
    radix trie, groups same-suffix-bucket requests into one compiled
    ``(k, bucket)`` prefill over a fresh k-row cache seeded with the cached
    prefix KV rows, scatters the result into the rolling cache (resetting
    each slot's cache position and metrics row), feeds the new KV blocks
    back into the trie, and streams each first token (TTFT).  Every
    ``step()`` then
    advances ALL occupied slots one token — model forward, per-row
    sampling, and ONE planner-lowered keyed masked fold of the per-request
    metrics — and retires slots that hit ``eos_id`` or their token budget,
    which immediately frees them for the next waiting request.
    """

    def __init__(self, backend: EngineBackend, config: ServeConfig, *,
                 clock: Callable[[], float] = time.perf_counter,
                 consumers: Sequence[Callable[[StreamEvent], None]] = ()):
        self.backend = backend
        self.config = config
        self._clock = clock
        # stream-event consumers (e.g. data.windows.WindowedMetrics.observe):
        # every event a step produces — admissions, tokens, retirements —
        # is dispatched to each consumer at the end of that step()
        self._consumers: List[Callable[[StreamEvent], None]] = list(consumers)
        # the batcher's FIFO is the admission queue: arrival order in,
        # arrival order into freed slots (take(), not flush()).
        self.queue = RequestBatcher(max_batch_size=config.num_slots,
                                    max_wait_s=0.0, clock=clock)
        self.stats = EngineStats()
        self.results: Dict[int, RequestResult] = {}
        self._slots: List[Optional[_SlotState]] = [None] * config.num_slots
        self._used_before = [False] * config.num_slots
        self._seeds: Dict[int, int] = {}
        self._step_count = 0
        place = backend.place if backend.place is not None else (lambda x: x)
        self._cache = place(backend.init_cache(config.num_slots, True))
        self._table = place(decode_metrics_init(config.num_slots))
        # -- radix prefix KV cache (runtime/prefix_cache.py) ----------------
        self.prefix: Optional[PrefixCache] = None
        self._slab = 0
        if config.prefix_cache and backend.prefix_sharing \
                and config.prefix_slab >= config.prefix_block:
            self.prefix = PrefixCache(
                PrefixCacheConfig(block=config.prefix_block,
                                  capacity=config.prefix_capacity,
                                  max_bytes=config.prefix_max_bytes,
                                  half_life_s=config.prefix_half_life_s),
                clock=clock)
            self._slab = config.prefix_slab
            # flattened view of the cache WITHOUT ``pos``: leaf order, batch
            # and sequence axes per leaf — the host-side (dis)assembly spec
            # for prefix slabs (the trie stores opaque per-leaf numpy blocks)
            tmpl = jax.eval_shape(lambda: backend.init_cache(1, True))
            kv_tmpl = {k: v for k, v in tmpl.items() if k != "pos"}
            leaves, treedef = jax.tree_util.tree_flatten_with_path(kv_tmpl)
            self._kv_treedef = treedef
            self._kv_shapes = [tuple(leaf.shape) for _, leaf in leaves]
            self._kv_dtypes = [leaf.dtype for _, leaf in leaves]
            self._kv_batch_axes = []
            for path, _ in leaves:
                keys = [getattr(e, "key", None) for e in path]
                self._kv_batch_axes.append(
                    1 if backend.stacked_key in keys else 0)
        self._build_compiled()

    # -- compiled programs (the whole shape ladder) -------------------------

    def _build_compiled(self) -> None:
        cfg = self.config
        S, V = cfg.num_slots, self.backend.vocab_size
        eos, temp = cfg.eos_id, cfg.temperature
        decode = self.backend.decode
        stacked = self.backend.stacked_key
        base_seed = cfg.seed

        def sample_rows(logits, seeds, tok_idx):
            if temp <= 0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            base = jax.random.PRNGKey(base_seed)

            def one(s, i, l):
                k = jax.random.fold_in(jax.random.fold_in(base, s), i)
                return jax.random.categorical(k, l / temp)

            # per-request key streams (seed, token index): sampling is
            # independent of slot assignment and neighbours, so a request
            # decodes identically alone or in a rolling batch
            return jax.vmap(one)(seeds, tok_idx, logits).astype(jnp.int32)

        def step_impl(params, cache, cur, active, seeds, tok_idx, table):
            logits, cache = decode(params, cache, cur)
            sampled = sample_rows(logits, seeds, tok_idx)
            rows = metric_rows(logits, sampled, eos)
            table = fold_decode_metrics(
                table, rows, jnp.arange(S, dtype=jnp.int32), active, S)
            return cache, sampled, table

        self._step_fn = jax.jit(step_impl, donate_argnums=(1,))

        slab = self._slab
        kv_treedef = getattr(self, "_kv_treedef", None)

        def load_prefix(cachek, prefix_leaves, prefix_len):
            """Scatter cached prefix KV rows into a fresh k-row prefill
            cache (rows beyond each request's prefix are zeros over zeros)
            and start each row's position at its prefix length."""
            kv = {key: val for key, val in cachek.items() if key != "pos"}
            slabs = jax.tree_util.tree_unflatten(kv_treedef, prefix_leaves)

            def put(path, big, small):
                keys = [getattr(e, "key", None) for e in path]
                axis = 2 if stacked in keys else 1
                return cache_span_update(big, small.astype(big.dtype),
                                         jnp.int32(0), seq_axis=axis)

            kv = jax.tree_util.tree_map_with_path(put, kv, slabs)
            kv["pos"] = jnp.asarray(prefix_len, cachek["pos"].dtype)
            return kv

        def make_prefill(k: int, bucket: int):
            def scan_suffix(params, cachek, toks, lengths, seeds):
                def body(carry, x):
                    cache, last = carry
                    tok, i = x
                    logits, cache = decode(params, cache, tok[:, None])
                    last = jnp.where((i == lengths - 1)[:, None], logits,
                                     last)
                    return (cache, last), None

                xs = (toks.T, jnp.arange(bucket))
                (cachek, last), _ = jax.lax.scan(
                    body, (cachek, jnp.zeros((k, V), jnp.float32)), xs)
                sampled = sample_rows(last, seeds,
                                      jnp.zeros((k,), jnp.int32))
                return cachek, sampled, metric_rows(last, sampled, eos)

            if slab:
                def prefill_impl(params, cachek, toks, lengths, seeds,
                                 prefix_leaves, prefix_len):
                    cachek = load_prefix(cachek, prefix_leaves, prefix_len)
                    return scan_suffix(params, cachek, toks, lengths, seeds)
            else:
                def prefill_impl(params, cachek, toks, lengths, seeds):
                    return scan_suffix(params, cachek, toks, lengths, seeds)

            return jax.jit(prefill_impl, donate_argnums=(1,))

        self._prefill_fns = {(k, b): make_prefill(k, b)
                             for k in cfg.prefill_k_ladder
                             for b in cfg.prefill_buckets}

        def make_write(k: int):
            def write_impl(cache, cachek, slots, lengths, table, rows):
                def put(path, big, small):
                    keys = [getattr(e, "key", None) for e in path]
                    if keys and keys[0] == "pos":
                        # each slot restarts at its full prompt length
                        # (positions are per-slot)
                        return big.at[slots].set(lengths.astype(big.dtype))
                    axis = 1 if stacked in keys else 0
                    out = big
                    for r in range(k):
                        piece = jax.lax.dynamic_slice_in_dim(
                            small, r, 1, axis=axis)
                        out = jax.lax.dynamic_update_slice_in_dim(
                            out, piece.astype(out.dtype), slots[r],
                            axis=axis)
                    return out

                new = jax.tree_util.tree_map_with_path(put, cache, cachek)
                # reset + first tokens in one write: each row IS its slot's
                # first metrics fold
                return new, table.at[slots].set(rows)

            return jax.jit(write_impl, donate_argnums=(0, 1, 4))

        self._write_fns = {k: make_write(k) for k in cfg.prefill_k_ladder}

        def make_gather(k: int):
            def gather_impl(cachek):
                kv = {key: val for key, val in cachek.items()
                      if key != "pos"}

                def take(path, leaf):
                    keys = [getattr(e, "key", None) for e in path]
                    axis = 2 if stacked in keys else 1
                    return jax.lax.slice_in_dim(leaf, 0, slab, axis=axis)

                return jax.tree_util.tree_map_with_path(take, kv)

            return jax.jit(gather_impl)

        self._gather_fns = {} if not slab else \
            {k: make_gather(k) for k in cfg.prefill_k_ladder}

    def compile_counts(self) -> Dict[str, int]:
        """Distinct compiled shapes per engine program.  The declared bound
        (:meth:`compile_bound`): one step program, one write + one prefix
        gather per admission batch size k, one prefill per (k, bucket), and
        the prefix cache's stats fold + row reset."""
        def n(f):
            return int(f._cache_size())

        counts = {"step": n(self._step_fn)}
        for (k, b), f in self._prefill_fns.items():
            counts[f"prefill_k{k}_b{b}"] = n(f)
        for k, f in self._write_fns.items():
            counts[f"write_k{k}"] = n(f)
        for k, f in self._gather_fns.items():
            counts[f"gather_k{k}"] = n(f)
        if self.prefix is not None:
            counts.update(self.prefix.compile_counts())
        return counts

    def compile_bound(self) -> int:
        """The declared ceiling on distinct compiled shapes over ANY trace:
        ``1 step + |k| x |buckets| prefills + |k| writes`` plus, with the
        prefix cache on, ``|k| gathers + stats fold + row reset``."""
        cfg = self.config
        kk = len(cfg.prefill_k_ladder)
        n = 1 + kk * len(cfg.prefill_buckets) + kk
        if self.prefix is not None:
            n += kk + 2
        return n

    # -- request lifecycle --------------------------------------------------

    @property
    def pending(self) -> int:
        """Requests waiting in the admission queue."""
        return len(self.queue)

    @property
    def num_active(self) -> int:
        """Slots currently occupied by a generating request."""
        return sum(s is not None for s in self._slots)

    @property
    def active_uids(self) -> List[int]:
        return [s.uid for s in self._slots if s is not None]

    def subscribe(self, consumer: Callable[[StreamEvent], None]) -> None:
        """Add a stream-event consumer (called once per event, in event
        order, at the end of each :meth:`step`)."""
        self._consumers.append(consumer)

    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: Optional[int] = None,
               seed: Optional[int] = None, user: int = 0) -> int:
        """Enqueue a request; returns its uid.  Admission happens on the
        next :meth:`step` as soon as a slot is free."""
        cfg = self.config
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("prompt must be non-empty")
        cfg.bucket_for(len(prompt))      # raises if it exceeds the ladder
        max_new = cfg.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        if not (1 <= max_new <= cfg.max_new_tokens):
            raise ValueError(
                f"max_new_tokens must be in [1, {cfg.max_new_tokens}], "
                f"got {max_new}")
        uid = self.queue.submit(prompt, max_new_tokens=max_new,
                                user=int(user))
        self._seeds[uid] = uid if seed is None else int(seed)
        self.stats.submitted += 1
        return uid

    def result(self, uid: int) -> RequestResult:
        return self.results[uid]

    def _admit(self, events: List[StreamEvent]) -> None:
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return
        reqs = self.queue.take(len(free))
        if not reqs:
            return
        cfg = self.config
        jobs: List[_AdmitJob] = []
        for req, slot in zip(reqs, free):
            plen = len(req.prompt)
            # the trie walk: requests prefill only their uncached suffix,
            # and the prefill bucket is chosen on SUFFIX length
            hit = self.prefix.lookup(req.prompt) \
                if self.prefix is not None else None
            hit_len = hit.length if hit is not None else 0
            jobs.append(_AdmitJob(
                req=req, slot=slot, plen=plen, hit=hit,
                seed=self._seeds.pop(req.uid, req.uid),
                bucket=cfg.bucket_for(plen - hit_len)))

        # group same-bucket admissions into shared (k, bucket) prefill
        # programs, k drawn from the declared power-of-two ladder
        groups: Dict[int, List[_AdmitJob]] = {}
        order: List[int] = []
        for job in jobs:
            if job.bucket not in groups:
                groups[job.bucket] = []
                order.append(job.bucket)
            groups[job.bucket].append(job)
        ladder = cfg.prefill_k_ladder
        for b in order:
            group = groups[b]
            while group:
                k = max(x for x in ladder if x <= len(group))
                self._admit_chunk(group[:k], b)
                group = group[k:]

        # stream in arrival order regardless of chunk grouping: the
        # admission accounting ("cache") event, then the first token
        now = self._clock()
        retire: List[int] = []
        for job in jobs:
            st = _SlotState(uid=job.req.uid, user=job.req.user,
                            seed=job.seed, prompt_len=job.plen,
                            bucket=job.bucket,
                            max_new=job.req.max_new_tokens,
                            arrival_s=job.req.arrival_s,
                            ttft_s=now - job.req.arrival_s,
                            tokens=[job.first], cur=job.first)
            self._slots[job.slot] = st
            self.stats.admitted += 1
            self.stats.generated_tokens += 1
            if self._used_before[job.slot]:
                self.stats.slot_reuses += 1
            self._used_before[job.slot] = True
            if job.hit is not None:
                events.append(StreamEvent(
                    uid=st.uid, kind="cache", slot=job.slot,
                    step=self._step_count, time_s=now, user=st.user,
                    hit_tokens=job.hit.length, prompt_tokens=job.plen,
                    bytes_saved=job.hit.nbytes))
            events.append(StreamEvent(uid=st.uid, kind="token",
                                      slot=job.slot, step=self._step_count,
                                      time_s=now, user=st.user,
                                      token=job.first, index=0,
                                      ttft_s=st.ttft_s))
            if job.first == cfg.eos_id or st.max_new <= 1:
                retire.append(job.slot)
        if retire:
            self._retire(retire, events, now)

    def _admit_chunk(self, jobs: List[_AdmitJob], bucket: int) -> None:
        """Prefill up to k same-bucket requests in ONE compiled program,
        scatter their (prefix-loaded) caches into the rolling cache, and
        feed each request's first-slab KV back into the trie."""
        cfg = self.config
        k = len(jobs)
        toks = np.full((k, bucket), cfg.pad_id, np.int32)
        suffix_lens = np.zeros((k,), np.int32)
        plens = np.zeros((k,), np.int32)
        seeds = np.zeros((k,), np.int32)
        prefix_lens = np.zeros((k,), np.int32)
        for r, job in enumerate(jobs):
            hit_len = job.hit.length if job.hit is not None else 0
            suffix = job.req.prompt[hit_len:]
            toks[r, :len(suffix)] = suffix
            suffix_lens[r] = len(suffix)
            plens[r] = job.plen
            seeds[r] = job.seed
            prefix_lens[r] = hit_len
        cachek = self.backend.init_cache(k, True)
        fn = self._prefill_fns[(k, bucket)]
        if self._slab:
            leaves = [jnp.asarray(a) for a in self._assemble_prefix(jobs, k)]
            cachek, sampled, rows = fn(
                self.backend.params, cachek, jnp.asarray(toks),
                jnp.asarray(suffix_lens), jnp.asarray(seeds), leaves,
                jnp.asarray(prefix_lens))
        else:
            cachek, sampled, rows = fn(
                self.backend.params, cachek, jnp.asarray(toks),
                jnp.asarray(suffix_lens), jnp.asarray(seeds))
        # gather BEFORE the (donating) slot write: the first `slab` KV rows
        # of every admitted request, host-side, become trie payloads
        gathered = None
        if self.prefix is not None:
            gathered = jax.device_get(self._gather_fns[k](cachek))
        slots = np.asarray([j.slot for j in jobs], np.int32)
        self._cache, self._table = self._write_fns[k](
            self._cache, cachek, jnp.asarray(slots), jnp.asarray(plens),
            self._table, rows)
        sampled_np = np.asarray(jax.device_get(sampled))
        for r, job in enumerate(jobs):
            job.first = int(sampled_np[r])
        if gathered is not None:
            g_leaves = jax.tree_util.tree_leaves(gathered)
            max_blocks = self._slab // cfg.prefix_block
            for r, job in enumerate(jobs):
                self.prefix.insert(
                    job.req.prompt,
                    lambda i, r=r: self._slice_block(g_leaves, r, i),
                    max_blocks=max_blocks)
        self.stats.prefill_calls += 1
        if k > 1:
            self.stats.batched_admissions += k

    def _assemble_prefix(self, jobs: List[_AdmitJob],
                         k: int) -> List[np.ndarray]:
        """Pack each job's cached prefix blocks into fixed (k, slab) KV
        slabs (one per cache leaf; rows past a job's prefix stay zero)."""
        B = self.config.prefix_block
        out = []
        for shape, dtype, bax in zip(self._kv_shapes, self._kv_dtypes,
                                     self._kv_batch_axes):
            s = list(shape)
            s[bax] = k
            s[bax + 1] = self._slab
            out.append(np.zeros(s, dtype))
        for r, job in enumerate(jobs):
            if job.hit is None:
                continue
            for i, blk in enumerate(job.hit.blocks):
                for j, arr in enumerate(blk):
                    bax = self._kv_batch_axes[j]
                    idx = [slice(None)] * out[j].ndim
                    idx[bax] = slice(r, r + 1)
                    idx[bax + 1] = slice(i * B, (i + 1) * B)
                    out[j][tuple(idx)] = arr
        return out

    def _slice_block(self, leaves: List[np.ndarray], r: int,
                     i: int) -> List[np.ndarray]:
        """Trie payload for request row r, block i: one `block`-row slice
        per gathered cache leaf (batch dim kept at size 1)."""
        B = self.config.prefix_block
        out = []
        for j, arr in enumerate(leaves):
            bax = self._kv_batch_axes[j]
            idx = [slice(None)] * arr.ndim
            idx[bax] = slice(r, r + 1)
            idx[bax + 1] = slice(i * B, (i + 1) * B)
            out.append(np.ascontiguousarray(arr[tuple(idx)]))
        return out

    def _retire(self, slots: List[int], events: List[StreamEvent],
                now: float) -> None:
        table = np.asarray(jax.device_get(self._table))
        for slot in slots:
            st = self._slots[slot]
            res = RequestResult(
                uid=st.uid, slot=slot, prompt_len=st.prompt_len,
                bucket=st.bucket, user=st.user, tokens=list(st.tokens),
                logprob_sum=float(table[slot, 0]),
                stopped=bool(table[slot, 2] > 0),
                stop_step=self._step_count,
                ttft_s=st.ttft_s, latency_s=now - st.arrival_s)
            self.results[st.uid] = res
            self._slots[slot] = None
            self.stats.completed += 1
            events.append(StreamEvent(uid=st.uid, kind="done", slot=slot,
                                      step=self._step_count, time_s=now,
                                      user=st.user, result=res))

    # -- the rolling decode step --------------------------------------------

    def step(self) -> List[StreamEvent]:
        """Admit waiting requests into free slots, then advance the whole
        rolling population one token.  Returns the streamed events."""
        events: List[StreamEvent] = []
        self._admit(events)
        if self.prefix is not None:
            # ONE keyed stats fold per engine step carries every cache
            # event this step produced (hits + inserts)
            self.prefix.flush_stats()
        S = self.config.num_slots
        occupied = [i for i, s in enumerate(self._slots) if s is not None]
        if not occupied:
            return self._dispatch(events)

        cur = np.zeros((S, 1), np.int32)
        active = np.zeros((S,), bool)
        seeds = np.zeros((S,), np.int32)
        tok_idx = np.zeros((S,), np.int32)
        for i in occupied:
            st = self._slots[i]
            cur[i, 0] = st.cur
            active[i] = True
            seeds[i] = st.seed
            tok_idx[i] = st.n_gen
        self._cache, sampled, self._table = self._step_fn(
            self.backend.params, self._cache, jnp.asarray(cur),
            jnp.asarray(active), jnp.asarray(seeds), jnp.asarray(tok_idx),
            self._table)
        self._step_count += 1
        self.stats.steps += 1

        sampled_np = np.asarray(jax.device_get(sampled))
        now = self._clock()
        retired = []
        for i in occupied:
            st = self._slots[i]
            tok = int(sampled_np[i])
            index = st.n_gen
            st.tokens.append(tok)
            st.cur = tok
            self.stats.generated_tokens += 1
            events.append(StreamEvent(uid=st.uid, kind="token", slot=i,
                                      step=self._step_count, time_s=now,
                                      user=st.user, token=tok, index=index))
            if tok == self.config.eos_id or st.n_gen >= st.max_new:
                retired.append(i)
        if retired:
            self._retire(retired, events, now)
        return self._dispatch(events)

    def _dispatch(self, events: List[StreamEvent]) -> List[StreamEvent]:
        for ev in events:
            for consumer in self._consumers:
                consumer(ev)
        return events

    def run(self, *, max_steps: Optional[int] = None) -> Iterator[StreamEvent]:
        """Stream events until the queue and every slot drain."""
        steps = 0
        while self.pending or self.num_active:
            yield from self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps "
                    f"({self.pending} pending, {self.num_active} active)")
