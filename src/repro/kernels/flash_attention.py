"""Pallas TPU kernel: flash attention forward = the AttnState monoid in VMEM.

The (m, l, o) online-softmax state (repro.core.monoids.attn_state) is held in
VMEM and folded over KV blocks — in-mapper combining (paper Algorithm 4)
inside the kernel: nothing S^2-sized ever reaches HBM. HBM traffic drops from
O(S^2) score materialization to Q + K + V + O reads/writes, which is the
memory-term reduction claimed in EXPERIMENTS.md §Perf (napkin math there).

Grid: (B*H, Sq/BQ, Sk/BK) — the KV dim is innermost, and the out/m/l blocks'
index_maps are constant in ki, so Pallas keeps them VMEM-resident across the
KV sweep and flushes once per (head, q-block). GQA reads the kv head via
index_map arithmetic (no materialized head repeat). Causality is handled by
masking inside the block; fully-masked blocks contribute the monoid identity.

Block sizes default to (BQ, BK) = (128, 128): q/k/v blocks (128 x d x 4B) +
the f32 (128,128) score tile ~= 260KB at d=128, far under VMEM; MXU dims are
128-aligned.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                     # (BQ, d)
    k = k_ref[0].astype(jnp.float32)                     # (BK, d)
    v = v_ref[0].astype(jnp.float32)                     # (BK, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    # fold this block's partial state into (m, l, o) — the attn_state monoid
    m_prev = m_ref[0]                                    # (BQ,)
    l_prev = l_ref[0]
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_blk)
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_safe[:, None]))
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_ref[0] = l_prev * alpha + p.sum(axis=-1)
    o_ref[0] = o_ref[0] * alpha[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[0] = m_new


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None
                    ) -> jnp.ndarray:
    """q: (B, H, Sq, d); k, v: (B, KV, Sk, d) with H % KV == 0.

    Returns (B, H, Sq, d) in q's dtype. Forward only (serving / frozen-eval;
    the training path uses the XLA-fused chunked AttnState form, which
    autodiffs — models/attention.py).
    """
    if interpret is None:
        from .ops import _default_interpret
        interpret = _default_interpret()
    B, H, Sq, d = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0
    G = H // KV
    scale = 1.0 / math.sqrt(d)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    qf = q.reshape(B * H, Sq, d)
    kf = k.reshape(B * KV, Sk, d)
    vf = v.reshape(B * KV, Sk, d)
    grid = (B * H, Sq // bq, Sk // bk)

    def kv_index(bh, qi, ki):
        return ((bh // H) * KV + (bh % H) // G, ki, 0)

    o, m, l = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=bq, block_k=bk,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq), lambda bh, qi, ki: (bh, qi)),
            pl.BlockSpec((1, bq), lambda bh, qi, ki: (bh, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, d), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Sq), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Sq), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = o / jnp.maximum(l, 1e-30)[..., None]           # the extract()
    return out.reshape(B, H, Sq, d).astype(q.dtype)
