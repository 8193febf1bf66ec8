"""Attention in all the flavours the assigned pool needs.

* GQA with optional qk-norm (qwen3/gemma3), QKV bias (qwen2.5), RoPE.
* Sliding-window ('local') attention (gemma3's 5:1 local:global).
* Cross-attention (whisper decoder, llama-3.2-vision image layers).
* MLA — DeepSeek-V2 multi-head latent attention, with the weight-absorbed
  decode form (attention runs in the 576-dim latent space; the KV cache is
  ``kv_lora_rank + qk_rope_dim`` per token, shared across all 128 heads).
* Chunked attention + flash-decoding built on the ``attn_state`` monoid
  (repro.core.monoids): the running (m, l, o) softmax state is associative,
  so KV chunking / KV sharding across devices are legal re-bracketings —
  the paper's principle applied to softmax (DESIGN.md §2).

Shapes: x (B, S, D); q (B, S, H, hd); k,v (B, S, KV, hd).
Masks are built from positions so the same code serves train (S queries)
and decode (1 query against a cache).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import common
from .common import (ModelConfig, ParamBuilder, attn_softmax_scale, dense,
                     rms_norm, rotary_embed)
from ..dist import sharding as shd
from ..core import monoids
from ..core.aggregation import monoid_allreduce

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def init_attn(pb: ParamBuilder, cfg: ModelConfig) -> None:
    """Standard GQA projection weights into ``pb`` (one layer)."""
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pb.param("wq", (D, H, hd), ("embed", "heads", "head_dim"), scale=D)
    pb.param("wk", (D, KV, hd), ("embed", "kv_heads", "head_dim"), scale=D)
    pb.param("wv", (D, KV, hd), ("embed", "kv_heads", "head_dim"), scale=D)
    pb.param("wo", (H, hd, D), ("heads", "head_dim", "embed"), scale=H * hd)
    if cfg.qkv_bias:
        pb.param("bq", (H, hd), ("heads", "head_dim"), init="zeros")
        pb.param("bk", (KV, hd), ("kv_heads", "head_dim"), init="zeros")
        pb.param("bv", (KV, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        pb.param("q_norm", (hd,), ("head_dim",), init="ones")
        pb.param("k_norm", (hd,), ("head_dim",), init="ones")


def init_cross_attn(pb: ParamBuilder, cfg: ModelConfig) -> None:
    """Cross-attention: q from x, k/v from a context sequence."""
    init_attn(pb, cfg)
    pb.param("gate", (), (), init="zeros")   # llama-vision gated cross-attn


def init_mla(pb: ParamBuilder, cfg: ModelConfig) -> None:
    D, H = cfg.d_model, cfg.num_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    dr, dn, dv = cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    if ql > 0:
        pb.param("wq_a", (D, ql), ("embed", "q_lora"), scale=D)
        pb.param("q_a_norm", (ql,), ("q_lora",), init="ones")
        pb.param("wq_b", (ql, H, dn + dr), ("q_lora", "heads", "head_dim"), scale=ql)
    else:
        pb.param("wq", (D, H, dn + dr), ("embed", "heads", "head_dim"), scale=D)
    pb.param("wkv_a", (D, kvl + dr), ("embed", "kv_lora"), scale=D)
    pb.param("kv_a_norm", (kvl,), ("kv_lora",), init="ones")
    pb.param("wk_b", (kvl, H, dn), ("kv_lora", "heads", "head_dim"), scale=kvl)
    pb.param("wv_b", (kvl, H, dv), ("kv_lora", "heads", "head_dim"), scale=kvl)
    pb.param("wo", (H, dv, D), ("heads", "head_dim", "embed"), scale=H * dv)


# ---------------------------------------------------------------------------
# q/k/v projection
# ---------------------------------------------------------------------------

def _project_qkv(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                 positions: jnp.ndarray, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = rotary_embed(q, positions, cfg.rope_theta)
        k = rotary_embed(k, positions, cfg.rope_theta)
    q = shd.act(q, ("batch", "seq", "heads", None))
    k = shd.act(k, ("batch", "seq", "kv_heads", None))
    v = shd.act(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def _gqa_scores(q: jnp.ndarray, k: jnp.ndarray, scale: float) -> jnp.ndarray:
    """(B,Sq,H,hd) x (B,Sk,KV,hd) -> (B, H, Sq, Sk) with GQA head grouping."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    return s.reshape(B, KV * G, Sq, k.shape[1])


def _gqa_values(w: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """(B,H,Sq,Sk) x (B,Sk,KV,hd) -> (B,Sq,H,hd)."""
    B, H, Sq, Sk = w.shape
    KV = v.shape[2]
    G = H // KV
    wg = w.reshape(B, KV, G, Sq, Sk)
    o = jnp.einsum("bkgqs,bskh->bqkgh", wg, v)
    return o.reshape(B, Sq, H, v.shape[3])


def _causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                 window: Optional[int] = None) -> jnp.ndarray:
    """(…, Sq, Sk) boolean keep-mask: causal, optionally sliding-window."""
    keep = k_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        keep &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return keep


def _causal_bias(seq_len: int, window: Optional[int] = None) -> jnp.ndarray:
    """(S, S) additive f32 mask bias, shared across batch and heads.

    §Perf iteration 3: `where(keep, scores, -inf)` is a 3-operand select over
    the (B, H, S, S) scores — ~3 full passes of S^2 traffic per use, and the
    -inf broadcast materializes at (B,1,S,S). Adding a SHARED (S,S) bias
    reads S^2 * 4 bytes once (64MB at 4k) and turns masking into the cheap
    epilogue of the scores matmul. Valid whenever positions are the uniform
    arange (the whole training/prefill path)."""
    pos = jnp.arange(seq_len, dtype=jnp.int32)
    keep = _causal_mask(pos, pos, window)
    return jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# training / prefill attention (full sequence)
# ---------------------------------------------------------------------------

def attention(p: Dict, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray,
              *, window: Optional[int] = None,
              chunk_size: Optional[int] = None) -> jnp.ndarray:
    """Causal self-attention over the full sequence.

    chunk_size: if set, use the attn_state-monoid chunked form over the KV
    axis (memory O(S*chunk) instead of O(S^2) live scores).
    """
    q, k, v = _project_qkv(p, cfg, x, positions)
    if chunk_size is None:
        scale = 1.0 / math.sqrt(cfg.head_dim)
        scores = _gqa_scores(q, k, scale)                       # (B,H,Sq,Sk) fp32
        if common._F32_CHAINS:   # baseline program: 3-operand select masking
            keep = _causal_mask(positions, positions, window)[:, None]
            scores = jnp.where(keep, scores, NEG_INF)
        else:                    # §Perf iter 3: shared (S,S) additive bias
            scores = scores + _causal_bias(x.shape[1], window)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o = _gqa_values(w, v)
    else:
        o = _chunked_attention(cfg, q, k, v, positions, positions,
                               window=window, chunk_size=chunk_size)
    o = shd.act(o, ("batch", "seq", "heads", None))
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shd.act(out, ("batch", "seq", "embed"))


def attention_bidir(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                    positions: jnp.ndarray) -> jnp.ndarray:
    """Bidirectional (encoder) self-attention — no mask, no RoPE (whisper
    encoder uses sinusoidal absolute positions added to the features)."""
    q, k, v = _project_qkv(p, cfg, x, positions, rope=False)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = _gqa_scores(q, k, scale)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_values(w, v)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shd.act(out, ("batch", "seq", "embed"))


def _attn_chunk_state(cfg, q, k, v, q_pos, k_pos, window):
    """Partial attn_state (m, l, o) of q against one KV chunk."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = _gqa_scores(q, k, scale)                           # (B,H,Sq,Ck) fp32
    keep = _causal_mask(q_pos, k_pos, window)[:, None]
    scores = jnp.where(keep, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)                                # (B,H,Sq)
    msafe = jnp.where(jnp.isneginf(m), 0.0, m)
    e = jnp.where(jnp.isneginf(scores), 0.0, jnp.exp(scores - msafe[..., None]))
    l = jnp.sum(e, axis=-1)
    o = _gqa_values(e.astype(v.dtype), v)                       # (B,Sq,H,hd)
    o = jnp.moveaxis(o, 1, 2).astype(jnp.float32)               # (B,H,Sq,hd)
    return (m, l, o)


def _chunked_attention(cfg, q, k, v, q_pos, k_pos, *, window, chunk_size):
    """Fold attn_state over KV chunks with lax.scan (in-mapper combining)."""
    B, Sk = k.shape[0], k.shape[1]
    assert Sk % chunk_size == 0, (Sk, chunk_size)
    n_chunks = Sk // chunk_size

    def chunks(t):
        return t.reshape(B, n_chunks, chunk_size, *t.shape[2:]).swapaxes(0, 1)

    kc, vc = chunks(k), chunks(v)
    kp = k_pos.reshape(B, n_chunks, chunk_size).swapaxes(0, 1) \
        if k_pos.ndim == 2 else k_pos.reshape(n_chunks, chunk_size)

    H, Sq, hd = q.shape[2], q.shape[1], v.shape[-1]
    init = (jnp.full((B, H, Sq), -jnp.inf),
            jnp.zeros((B, H, Sq)),
            jnp.zeros((B, H, Sq, hd)))

    def step(acc, chunk):
        kci, vci, kpi = chunk
        state = _attn_chunk_state(cfg, q, kci, vci, q_pos, kpi, window)
        return monoids.attn_state.combine(acc, state), None

    acc, _ = jax.lax.scan(step, init, (kc, vc, kp))
    o = monoids.attn_state.extract(acc)                         # (B,H,Sq,hd)
    return jnp.moveaxis(o, 1, 2).astype(q.dtype)                # (B,Sq,H,hd)


# ---------------------------------------------------------------------------
# decode attention (L new tokens against a cache)
# ---------------------------------------------------------------------------

def decode_positions(pos: jnp.ndarray, batch: int,
                     length: int = 1) -> jnp.ndarray:
    """(B, L) query positions ``pos + arange(L)`` from a scalar OR per-row
    ``pos``.

    Scalar ``pos`` is the classic aligned-batch decode (every row at the
    same position).  A ``(B,)`` ``pos`` is the continuous-batching case:
    each request slot carries its own position, so a freed slot can restart
    at 0 mid-decode while its neighbours keep generating.  ``length > 1``
    is an extend: L consecutive tokens per row, as a prefill writes them.
    """
    if jnp.ndim(pos) == 1:
        first = pos[:, None].astype(jnp.int32)
    else:
        first = jnp.full((batch, 1), pos, jnp.int32)
    if length == 1:
        return first
    return first + jnp.arange(length, dtype=jnp.int32)


def cache_write(cache: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray,
                layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Write the step's new rows into a position-indexed cache.

    cache: (B, S, ...), or with ``layer`` a stack (n, B, S, ...) of which
    layer ``layer`` is written; new: (B, L, ...); pos: () or (B,) start
    positions.  Row j of batch row b lands at position ``pos[b] + j``
    (:func:`decode_positions`); rows that would land at or past S are
    DROPPED, never shifted onto real ones.  One scatter of the B x L rows
    and nothing else: every other row of the cache, every other layer's
    included, is left as it was, so a donated cache or a loop-carried stack
    is updated in place.
    """
    B, L = new.shape[:2]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    cols = decode_positions(pos, B, L)
    index = (rows, cols) if layer is None else (layer, rows, cols)
    return cache.at[index].set(new, mode="drop", unique_indices=True)


def cache_layer(cache: jnp.ndarray,
                layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Layer ``layer`` of a stacked (n, ...) cache leaf; the leaf itself
    when ``layer`` is None."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def attention_decode(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                     cache: Tuple[jnp.ndarray, jnp.ndarray], pos: jnp.ndarray,
                     *, window: Optional[int] = None,
                     layer: Optional[jnp.ndarray] = None,
                     kv_shards: int = 1) -> Tuple[jnp.ndarray, Tuple]:
    """One decode step. x: (B, L, D); cache: (k, v) each (B, S, KV, hd), or
    each a stack (n, B, S, KV, hd) of which this is layer ``layer`` (see
    :func:`cache_write`); pos: () current position (tokens 0..pos-1 are
    valid in the cache), or (B,) per-slot positions for a continuously-
    batched cache — each row writes and masks at its own position, so rows
    stay independent and a reused slot's computation is identical to a
    fresh batch's.  L > 1 is an extend: the L tokens at pos..pos+L-1 are
    written and attend causally over the cache in one pass (scores (B, H,
    L, S)).  Returns the cache as it was given, with the new rows written.

    kv_shards > 1 requests flash-decoding: the KV cache's sequence axis is
    sharded over the 'model' mesh axis and partial attn_states are merged
    with the monoid (sequence-parallel decode for long_500k).
    """
    kstack, vstack = cache
    B = x.shape[0]
    positions = decode_positions(pos, B, x.shape[1])
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)
    kstack = cache_write(kstack, k_new, pos, layer)
    vstack = cache_write(vstack, v_new, pos, layer)
    kcache = shd.act(cache_layer(kstack, layer),
                     ("batch", "kv_seq", "kv_heads", None))
    vcache = shd.act(cache_layer(vstack, layer),
                     ("batch", "kv_seq", "kv_heads", None))
    S = kcache.shape[1]

    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = _gqa_scores(q, kcache, scale)                      # (B,H,L,S)
    keep = _causal_mask(positions, k_pos, window)[:, None]
    scores = jnp.where(keep, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_values(w, vcache)                                  # (B,L,H,hd)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shd.act(out, ("batch", None, "embed")), (kstack, vstack)


def flash_decode_shardmap(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                          cache: Tuple[jnp.ndarray, jnp.ndarray],
                          pos: jnp.ndarray, mesh, *, axis_name: str = "model",
                          window: Optional[int] = None):
    """Flash-decoding over a sequence-sharded KV cache (explicit shard_map).

    Each device holds a contiguous S/P slice of the KV cache, computes the
    partial (m, l, o) attn_state for its slice, and the states are merged
    with one monoid_allreduce — the distributed combiner of DESIGN.md §2.
    Used by the long_500k serving path. The new token's (k, v) is written by
    the owning shard only.
    """
    if jnp.ndim(pos) == 1:
        raise NotImplementedError(
            "flash decode requires a scalar cache position; per-slot (B,) "
            "positions (continuous batching) run the dense decode path")
    P = mesh.shape[axis_name]
    B, S = cache[0].shape[0], cache[0].shape[1]
    S_local = S // P
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def body(kc, vc):
        idx = jax.lax.axis_index(axis_name)
        start = idx * S_local
        # write the new token's kv if it falls in our slice
        local_off = jnp.clip(pos - start, 0, S_local - 1)
        in_range = (pos >= start) & (pos < start + S_local)
        upd_k = jnp.where(in_range, k_new, jax.lax.dynamic_slice_in_dim(kc, local_off, 1, 1))
        upd_v = jnp.where(in_range, v_new, jax.lax.dynamic_slice_in_dim(vc, local_off, 1, 1))
        kc = jax.lax.dynamic_update_slice_in_dim(kc, upd_k, local_off, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, upd_v, local_off, axis=1)

        k_pos = start + jnp.arange(S_local, dtype=jnp.int32)
        k_pos = jnp.broadcast_to(k_pos, (B, S_local))
        scores = _gqa_scores(q, kc, scale)
        keep = _causal_mask(positions, k_pos, window)[:, None]
        scores = jnp.where(keep, scores, -jnp.inf)
        m = jnp.max(scores, axis=-1)
        msafe = jnp.where(jnp.isneginf(m), 0.0, m)
        e = jnp.where(jnp.isneginf(scores), 0.0, jnp.exp(scores - msafe[..., None]))
        l = jnp.sum(e, axis=-1)
        o = jnp.moveaxis(_gqa_values(e.astype(vc.dtype), vc), 1, 2).astype(jnp.float32)
        state = monoid_allreduce(monoids.attn_state, (m, l, o), axis_name)
        out = monoids.attn_state.extract(state)                 # (B,H,1,hd)
        return jnp.moveaxis(out, 1, 2).astype(x.dtype), kc, vc

    pspec = jax.sharding.PartitionSpec
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec(None, axis_name), pspec(None, axis_name)),
        out_specs=(pspec(), pspec(None, axis_name), pspec(None, axis_name)),
        check_vma=False)
    o, kcache, vcache = fn(cache[0], cache[1])
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shd.act(out, ("batch", None, "embed")), (kcache, vcache)


def ring_attention_shardmap(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            mesh, *, axis_name: str = "model",
                            causal: bool = True, head_dim_scale: Optional[float] = None):
    """Ring attention: seq-sharded Q/K/V; K/V blocks rotate around the ring
    via collective_permute while each device folds partial AttnStates.

    The third re-bracketing of the softmax monoid (after chunked attention
    and flash-decoding): legal because the (m, l, o) combine is associative,
    so the order in which KV blocks arrive is irrelevant — each hop is one
    combiner application (DESIGN.md §2). q, k, v: (B, S, H/KV, hd) with the
    S axis sharded over ``axis_name``. GQA should be pre-broadcast
    (KV == H) or use equal heads; returns (B, S, H, hd) seq-sharded.
    """
    P = mesh.shape[axis_name]
    B, S, H, hd = q.shape
    S_local = S // P
    scale = head_dim_scale or (1.0 / math.sqrt(hd))
    perm = [(j, (j + 1) % P) for j in range(P)]

    def body(qc, kc, vc):
        idx = jax.lax.axis_index(axis_name)
        q_pos = (idx * S_local + jnp.arange(S_local, dtype=jnp.int32))
        q_pos = jnp.broadcast_to(q_pos, (B, S_local))
        init_acc = (jnp.full((B, H, S_local), -jnp.inf),
                    jnp.zeros((B, H, S_local)),
                    jnp.zeros((B, H, S_local, hd)))

        def hop(i, carry):
            kc, vc, acc = carry
            src = (idx - i) % P                  # who produced this block
            k_pos = src * S_local + jnp.arange(S_local, dtype=jnp.int32)
            k_pos = jnp.broadcast_to(k_pos, (B, S_local))
            scores = _gqa_scores(qc, kc, scale)
            if causal:
                keep = _causal_mask(q_pos, k_pos)[:, None]
                scores = jnp.where(keep, scores, -jnp.inf)
            m = jnp.max(scores, axis=-1)
            msafe = jnp.where(jnp.isneginf(m), 0.0, m)
            e = jnp.where(jnp.isneginf(scores), 0.0,
                          jnp.exp(scores - msafe[..., None]))
            l = jnp.sum(e, axis=-1)
            o = jnp.moveaxis(_gqa_values(e.astype(vc.dtype), vc), 1, 2)
            state = (m, l, o.astype(jnp.float32))
            acc = monoids.attn_state.combine(acc, state)
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
            return (kc, vc, acc)

        _, _, acc = jax.lax.fori_loop(0, P, hop, (kc, vc, init_acc))
        out = monoids.attn_state.extract(acc)    # (B,H,S_local,hd)
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)

    pspec = jax.sharding.PartitionSpec
    spec = pspec(None, axis_name)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder / llama-vision image layers)
# ---------------------------------------------------------------------------

def cross_attention(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                    context_kv: Tuple[jnp.ndarray, jnp.ndarray],
                    *, gated: bool = False) -> jnp.ndarray:
    """Attend from x to a precomputed context (k, v) — no mask, no RoPE.

    context_kv is computed once per sequence by :func:`cross_kv` (for decode
    this is the paper's in-mapper combining of the static vision/audio
    context: computed once, reused every step).
    """
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k, v = context_kv
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = _gqa_scores(q, k, scale)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_values(w, v)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    if gated:
        out = jnp.tanh(p["gate"].astype(out.dtype)) * out
    return shd.act(out, ("batch", "seq", "embed"))


def cross_kv(p: Dict, cfg: ModelConfig, context: jnp.ndarray):
    """Project a context sequence to (k, v) once (cached across decode steps)."""
    k = jnp.einsum("bsd,dhk->bshk", context, p["wk"].astype(context.dtype))
    v = jnp.einsum("bsd,dhk->bshk", context, p["wv"].astype(context.dtype))
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    k = shd.act(k, ("batch", "seq", "kv_heads", None))
    v = shd.act(v, ("batch", "seq", "kv_heads", None))
    return (k, v)


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------

def _mla_q(p: Dict, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    dr, dn = cfg.qk_rope_dim, cfg.qk_nope_dim
    if cfg.q_lora_rank > 0:
        cq = dense(x, p["wq_a"])
        cq = rms_norm(cq, p["q_a_norm"], cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", cq, p["wq_b"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rotary_embed(q_rope, positions, cfg.rope_theta, cfg)
    return q_nope, q_rope


def _mla_latent(p: Dict, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    """x -> (c_kv normalized, k_rope rotated): exactly what the MLA cache holds."""
    kvl, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    ckv = dense(x, p["wkv_a"])                                  # (B,S,kvl+dr)
    c, k_rope = ckv[..., :kvl], ckv[..., kvl:]
    c = rms_norm(c, p["kv_a_norm"], cfg.norm_eps)
    k_rope = rotary_embed(k_rope[:, :, None, :], positions, cfg.rope_theta,
                          cfg)[:, :, 0]
    return c, k_rope


@jax.named_scope("mla")
def mla_attention(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray) -> jnp.ndarray:
    """Training/prefill MLA: up-project the latent, standard MHA."""
    dn, dv = cfg.qk_nope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c, k_rope = _mla_latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsl,lhk->bshk", c, p["wk_b"].astype(x.dtype))
    v = jnp.einsum("bsl,lhk->bshk", c, p["wv_b"].astype(x.dtype))
    scale = attn_softmax_scale(cfg, dn + cfg.qk_rope_dim)
    s_nope = jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope,
                        preferred_element_type=jnp.float32)
    scores = (s_nope + s_rope) * scale
    if common._F32_CHAINS:
        keep = _causal_mask(positions, positions)[:, None]
        scores = jnp.where(keep, scores, NEG_INF)
    else:
        scores = scores + _causal_bias(x.shape[1])
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqs,bshk->bqhk", w, v)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shd.act(out, ("batch", "seq", "embed"))


@jax.named_scope("mla")
def mla_decode(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
               cache: Tuple[jnp.ndarray, jnp.ndarray],
               pos: jnp.ndarray,
               layer: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, Tuple]:
    """Weight-absorbed MLA decode: attention runs in the latent space.

    cache = (c (B,S,kvl), k_rope (B,S,dr)), or stacks of them of which this
    is layer ``layer``, written as :func:`attention_decode` writes. Per step:
      q_nope' = q_nope @ wk_b^T  (absorb the k up-projection into q)
      scores  = q_nope' . c  +  q_rope . k_rope
      o_latent = softmax(scores) @ c          (B,1,H,kvl)
      o        = o_latent @ wv_b  then wo     (absorb the v up-projection)

    The cache is (kvl + dr) floats/token shared across ALL heads — the MLA
    memory-term win reported in the roofline table.  x may hold L > 1
    tokens (an extend), masked causally as in :func:`attention_decode`.
    """
    c_stack, r_stack = cache
    B = x.shape[0]
    positions = decode_positions(pos, B, x.shape[1])
    q_nope, q_rope = _mla_q(p, cfg, x, positions)               # (B,L,H,*)
    c_new, r_new = _mla_latent(p, cfg, x, positions)
    c_stack = cache_write(c_stack, c_new, pos, layer)
    r_stack = cache_write(r_stack, r_new, pos, layer)
    c_cache = shd.act(cache_layer(c_stack, layer), ("batch", "kv_seq", None))
    r_cache = shd.act(cache_layer(r_stack, layer), ("batch", "kv_seq", None))
    S = c_cache.shape[1]

    # absorb wk_b into q: (B,L,H,dn) x (kvl,H,dn) -> (B,L,H,kvl)
    q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, p["wk_b"].astype(x.dtype))
    s_lat = jnp.einsum("bqhl,bsl->bhqs", q_lat, c_cache,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhk,bsk->bhqs", q_rope, r_cache,
                        preferred_element_type=jnp.float32)
    scale = attn_softmax_scale(cfg, cfg.qk_nope_dim + cfg.qk_rope_dim)
    scores = (s_lat + s_rope) * scale
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    keep = _causal_mask(positions, k_pos)[:, None]
    scores = jnp.where(keep, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhqs,bsl->bqhl", w, c_cache)            # (B,L,H,kvl)
    o = jnp.einsum("bqhl,lhv->bqhv", o_lat, p["wv_b"].astype(x.dtype))
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shd.act(out, ("batch", None, "embed")), (c_stack, r_stack)
