"""Radix prefix KV cache with monoid-fold bookkeeping.

Concurrent requests share prompt prefixes — system prompts, few-shot
templates — yet a cold admission re-prefills the whole prompt.  This module
caches the KV rows of previously-prefilled prompts in a *block-quantized
radix trie* over token ids: each trie node owns exactly ``block`` tokens'
worth of KV rows (host-side numpy, one array per cache leaf), so the
longest cached prefix of a new prompt is a trie walk, and admission only
prefills the remaining suffix (``runtime/engine.py`` buckets on *suffix*
length, so TTFT drops proportionally).

The paper's angle is the bookkeeping.  Hit counting, byte-level memory
accounting, and the eviction score are all columns of ONE per-node monoid
state — :func:`repro.core.monoids.cache_stats`, a :func:`product` of two
additive columns and a :func:`decayed_lru` score — and the stats table
(keyed by trie-node id) updates with a single planner-lowered keyed fold
(:func:`repro.core.plan.execute_fold`, ``node id == segment id``) per
engine step, exactly the shape of the engine's per-request metrics fold.
Host code appends event rows (hit, insert) as they happen;
:meth:`PrefixCache.flush_stats` folds them in fixed-width chunks so the
fold compiles once.  Eviction reads the table back, re-anchors the decayed
scores to now (:func:`repro.core.monoids.decayed_value`), and removes the
lowest-scoring childless node — decayed-LRU with smooth aging, no
timestamps stored host-side.

The trie is payload-agnostic: the engine hands it opaque lists of numpy
arrays per block (one per KV cache leaf), so the same cache serves the toy
test backend and the real model substrate.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import monoids
from ..core.plan import execute_fold
from . import spans


@dataclasses.dataclass(frozen=True)
class PrefixCacheConfig:
    """Sizing/behaviour of the prefix cache.

    block: tokens per trie node (prefix hits are quantized to multiples).
    capacity: max trie nodes == rows of the stats table (segment-id space).
    max_bytes: resident-KV byte budget (None = bounded by capacity only).
    half_life_s: decayed-LRU half life of the eviction score.
    events_per_fold: fixed row count of one stats fold (events are padded
      to this width with masked identity rows, so the fold compiles once).
    """

    block: int = 4
    capacity: int = 256
    max_bytes: Optional[int] = None
    half_life_s: float = 60.0
    events_per_fold: int = 64

    def __post_init__(self):
        if self.block < 1:
            raise ValueError(f"block must be >= 1, got {self.block}")
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")
        if self.max_bytes is not None and self.max_bytes < 0:
            raise ValueError(f"max_bytes must be >= 0, got {self.max_bytes}")
        if self.half_life_s <= 0:
            raise ValueError(
                f"half_life_s must be positive, got {self.half_life_s}")
        if self.events_per_fold < 1:
            raise ValueError(
                f"events_per_fold must be >= 1, got {self.events_per_fold}")


@dataclasses.dataclass
class PrefixHit:
    """Longest cached block-aligned prefix of a prompt.

    length: tokens covered (multiple of ``block``; 0 = miss).
    blocks: per-block KV payloads, each a list of numpy arrays in the
      engine's cache-leaf order.
    node_ids: stats-table row per block (hit events were recorded).
    nbytes: resident bytes of the reused payloads (the bytes NOT re-prefilled).
    """

    length: int
    blocks: List[List[np.ndarray]]
    node_ids: List[int]
    nbytes: int


@dataclasses.dataclass
class PrefixCacheStats:
    lookups: int = 0
    hits: int = 0                 # lookups that matched >= 1 block
    hit_tokens: int = 0           # prompt tokens served from the cache
    prompt_tokens: int = 0        # all prompt tokens seen by lookup()
    bytes_saved: int = 0          # KV bytes not re-prefilled
    inserted_nodes: int = 0
    evictions: int = 0
    folds: int = 0                # planner folds executed
    fold_rows: int = 0            # event rows folded (excl. padding)

    def hit_rate(self) -> float:
        """Fraction of prompt tokens served from the cache."""
        return self.hit_tokens / max(self.prompt_tokens, 1)


class _Node:
    __slots__ = ("key", "node_id", "payload", "nbytes", "parent", "children")

    def __init__(self, key, node_id, payload, nbytes, parent):
        self.key = key                  # tuple of `block` token ids
        self.node_id = node_id          # row in the stats table
        self.payload = payload          # list of np arrays (KV rows)
        self.nbytes = nbytes
        self.parent = parent
        self.children: Dict[Tuple[int, ...], _Node] = {}


class PrefixCache:
    """Block-quantized radix trie over tokenized prompts; KV rows per node;
    all bookkeeping through one keyed monoid fold (see module docstring)."""

    def __init__(self, config: PrefixCacheConfig = PrefixCacheConfig(), *,
                 clock: Callable[[], float] = time.monotonic):
        self.config = config
        self.monoid = monoids.cache_stats(config.half_life_s)
        self._clock = clock
        self._root = _Node(key=None, node_id=-1, payload=None, nbytes=0,
                           parent=None)
        self._nodes: Dict[int, _Node] = {}
        self._free = list(range(config.capacity - 1, -1, -1))   # pop() -> 0 first
        self._bytes = 0      # host mirror of the table's bytes column
        self.stats = PrefixCacheStats()
        # pending event rows: (node_id, hits, bytes, score_weight, score_t)
        self._pending: List[Tuple[int, float, float, float, float]] = []
        C = config.capacity
        self._table = {
            "bytes": jnp.zeros((C,), jnp.float32),
            "hits": jnp.zeros((C,), jnp.float32),
            "score": (jnp.zeros((C,), jnp.float32),
                      jnp.full((C,), -jnp.inf, jnp.float32)),
        }
        m = self.monoid

        def fold_impl(table, ids, hits, nbytes, sw, st, valid):
            rows = {"bytes": nbytes, "hits": hits, "score": (sw, st)}
            return execute_fold(m, rows, segment_ids=ids, num_segments=C,
                                valid_mask=valid, init=table)

        self._fold_fn = jax.jit(fold_impl)

        def clear_impl(table, nid):
            # reset one row to the identity: the monoid-consistent way to
            # retire a node id — the bytes column drops by the node's bytes,
            # so sum(bytes) keeps equalling resident bytes
            return {
                "bytes": table["bytes"].at[nid].set(0.0),
                "hits": table["hits"].at[nid].set(0.0),
                "score": (table["score"][0].at[nid].set(0.0),
                          table["score"][1].at[nid].set(-jnp.inf)),
            }

        self._clear_fn = jax.jit(clear_impl)

    # -- sizes --------------------------------------------------------------

    @property
    def node_count(self) -> int:
        return len(self._nodes)

    @property
    def total_bytes(self) -> int:
        """Resident KV bytes (host mirror; equals the table's bytes sum)."""
        return self._bytes

    # -- the keyed stats fold ----------------------------------------------

    def _event(self, nid: int, hits: float, nbytes: float, weight: float,
               t: float) -> None:
        self._pending.append((nid, hits, nbytes, weight, t))

    def flush_stats(self) -> int:
        """Fold pending event rows into the stats table.

        Called once per engine step: all of a step's cache events reduce in
        ONE fixed-shape keyed fold (more only if a step produced more than
        ``events_per_fold`` rows).  Padding rows are masked to the identity
        via ``valid_mask``.  Returns the number of folds run.
        """
        E = self.config.events_per_fold
        n = 0
        while self._pending:
            chunk = self._pending[:E]
            del self._pending[:E]
            ids = np.zeros((E,), np.int32)
            hits = np.zeros((E,), np.float32)
            nb = np.zeros((E,), np.float32)
            sw = np.zeros((E,), np.float32)
            st = np.full((E,), -np.inf, np.float32)   # identity anchor
            valid = np.zeros((E,), bool)
            for i, (nid, h, b, w, t) in enumerate(chunk):
                ids[i], hits[i], nb[i], sw[i], st[i] = nid, h, b, w, t
                valid[i] = True
            self._table = self._fold_fn(
                self._table, jnp.asarray(ids), jnp.asarray(hits),
                jnp.asarray(nb), jnp.asarray(sw), jnp.asarray(st),
                jnp.asarray(valid))
            n += 1
            self.stats.folds += 1
            self.stats.fold_rows += len(chunk)
        return n

    def table(self) -> Dict:
        """The folded stats table (host), pending events flushed."""
        self.flush_stats()
        return jax.device_get(self._table)

    def accounted_bytes(self) -> int:
        """Resident bytes as accounted by the fold (sum of the bytes
        column) — bit-equal to :attr:`total_bytes` by construction."""
        return int(round(float(np.sum(np.asarray(self.table()["bytes"])))))

    def scores(self, now: float) -> np.ndarray:
        """(capacity,) decayed eviction scores re-anchored to ``now``."""
        self.flush_stats()
        val = monoids.decayed_value(self._table["score"], now,
                                    self.config.half_life_s)
        return np.asarray(jax.device_get(val))

    def compile_counts(self) -> Dict[str, int]:
        def n(f):
            return int(f._cache_size())

        return {"prefix_stats_fold": n(self._fold_fn),
                "prefix_row_reset": n(self._clear_fn)}

    # -- lookup / insert / evict -------------------------------------------

    def lookup(self, prompt: Sequence[int]) -> PrefixHit:
        """Longest cached block-aligned prefix STRICTLY shorter than the
        prompt (at least one token must remain to prefill: the suffix
        decode produces the first sampled token's logits)."""
        B = self.config.block
        self.stats.lookups += 1
        self.stats.prompt_tokens += len(prompt)
        limit = max(len(prompt) - 1, 0) // B
        node = self._root
        blocks: List[List[np.ndarray]] = []
        ids: List[int] = []
        nbytes = 0
        for i in range(limit):
            child = node.children.get(
                tuple(int(t) for t in prompt[i * B:(i + 1) * B]))
            if child is None:
                break
            node = child
            blocks.append(child.payload)
            ids.append(child.node_id)
            nbytes += child.nbytes
        t = float(self._clock())
        for nid in ids:
            self._event(nid, 1.0, 0.0, 1.0, t)
        length = len(blocks) * B
        if length:
            self.stats.hits += 1
            self.stats.hit_tokens += length
            self.stats.bytes_saved += nbytes
        return PrefixHit(length=length, blocks=blocks, node_ids=ids,
                         nbytes=nbytes)

    def insert(self, prompt: Sequence[int],
               payload: Callable[[int], List[np.ndarray]], *,
               max_blocks: Optional[int] = None) -> int:
        """Insert the full-block prefixes of ``prompt`` into the trie.

        ``payload(i)`` materializes block i's KV rows (list of np arrays) —
        called only for blocks not already cached.  Returns the number of
        new nodes.  Evicts (childless, lowest decayed score first) when the
        node capacity or byte budget would overflow; nodes on the path
        being inserted are protected.
        """
        B = self.config.block
        n = len(prompt) // B
        if max_blocks is not None:
            n = min(n, max_blocks)
        node = self._root
        t = float(self._clock())
        protect = set()
        created = 0
        for i in range(n):
            key = tuple(int(x) for x in prompt[i * B:(i + 1) * B])
            child = node.children.get(key)
            if child is None:
                child = self._new_node(node, key, payload(i), t, protect)
                if child is None:
                    break          # budget exhausted, nothing evictable
                created += 1
            protect.add(child.node_id)
            node = child
        return created

    def _new_node(self, parent: _Node, key, arrays: List[np.ndarray],
                  t: float, protect) -> Optional[_Node]:
        nbytes = int(sum(int(a.nbytes) for a in arrays))
        mb = self.config.max_bytes
        if mb is not None and nbytes > mb:
            return None
        if not self._free and not self._evict_one(protect):
            return None
        while mb is not None and self._bytes + nbytes > mb:
            if not self._evict_one(protect):
                return None
        nid = self._free.pop()
        node = _Node(key=key, node_id=nid, payload=list(arrays),
                     nbytes=nbytes, parent=parent)
        parent.children[key] = node
        self._nodes[nid] = node
        self._bytes += nbytes
        self.stats.inserted_nodes += 1
        # insertion event: bytes land in the accounting column, the score
        # anchors at now (a fresh node is as warm as a fresh hit)
        self._event(nid, 0.0, float(nbytes), 1.0, t)
        return node

    def evict(self, n: int = 1) -> int:
        """Evict up to ``n`` nodes (childless, lowest decayed score first).
        Returns how many were evicted."""
        done = 0
        while done < n and self._evict_one(frozenset()):
            done += 1
        return done

    def _evict_one(self, protect) -> bool:
        with spans.span("prefix.evict"):
            # pending hit events move scores: fold them BEFORE choosing a
            # victim (also: no pending row may reference the id we are
            # about to free)
            self.flush_stats()
            candidates = [nd for nd in self._nodes.values()
                          if not nd.children and nd.node_id not in protect]
            if not candidates:
                return False
            with spans.span("prefix.wait_scores", wait=True):
                scores = self.scores(float(self._clock()))
            victim = min(candidates,
                         key=lambda nd: (float(scores[nd.node_id]),
                                         nd.node_id))
            del victim.parent.children[victim.key]
            del self._nodes[victim.node_id]
            self._bytes -= victim.nbytes
            self._table = self._clear_fn(self._table,
                                         jnp.int32(victim.node_id))
            self._free.append(victim.node_id)
            self.stats.evictions += 1
            return True
