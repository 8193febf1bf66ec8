"""Pallas TPU kernel: Algorithm 5 ("stripes") co-occurrence accumulation.

The full (V, V) stripe table is the VMEM-resident accumulator; each grid
step takes a block of center tokens plus their pre-gathered window of
neighbors and accumulates one-hot OUTER PRODUCTS on the MXU:

    table += onehot(center)^T @ onehot(neighbor_j)      for each offset j

which is exactly "H{u} += 1 for u in Neighbors(w)" (paper Algorithm 5),
batched into a systolic matmul. The wrapper builds the (N, window) neighbor
matrix so blocks need no halo exchange; vocab is hash-bucketed to V_bucket
(the paper's answer to open key spaces — sketch the tail, §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _stripes_kernel(tok_ref, neigh_ref, mask_ref, out_ref, *, vocab: int,
                    window: int, block_n: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    toks = tok_ref[...]                                   # (BN,)
    center = (toks[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block_n, vocab), 1)).astype(jnp.float32)
    acc = jnp.zeros((vocab, vocab), jnp.float32)
    for j in range(window):
        nb = neigh_ref[..., j]                            # (BN,)
        valid = mask_ref[..., j].astype(jnp.float32)      # (BN,)
        onehot_nb = (nb[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (block_n, vocab), 1)).astype(jnp.float32)
        onehot_nb = onehot_nb * valid[:, None]
        # (V, BN) @ (BN, V): all BN pair-updates in one MXU pass
        acc += jax.lax.dot(center.T, onehot_nb,
                           preferred_element_type=jnp.float32)
    out_ref[...] += acc + acc.T                           # symmetric relation


def stripes_pallas(tokens: jnp.ndarray, vocab: int, window: int, *,
                   block_n: int = 512, interpret: bool | None = None
                   ) -> jnp.ndarray:
    """tokens: (N,) int -> (vocab, vocab) symmetric co-occurrence counts
    (pairs within distance <= window, both directions)."""
    if interpret is None:
        from .ops import _default_interpret
        interpret = _default_interpret()
    N = tokens.shape[0]
    # pre-gather forward neighbors: neigh[i, j] = tokens[i + j + 1]
    idx = jnp.arange(N)[:, None] + jnp.arange(1, window + 1)[None, :]
    mask = (idx < N).astype(jnp.int32)
    neigh = tokens[jnp.clip(idx, 0, N - 1)].astype(jnp.int32)
    pad = (-N) % block_n
    toks = tokens.astype(jnp.int32)
    if pad:
        toks = jnp.concatenate([toks, jnp.full((pad,), -1, jnp.int32)])
        neigh = jnp.concatenate(
            [neigh, jnp.full((pad, window), -1, jnp.int32)], axis=0)
        mask = jnp.concatenate([mask, jnp.zeros((pad, window), jnp.int32)], axis=0)
    grid = ((N + pad) // block_n,)
    return pl.pallas_call(
        functools.partial(_stripes_kernel, vocab=vocab, window=window,
                          block_n=block_n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,)),
                  pl.BlockSpec((block_n, window), lambda i: (i, 0)),
                  pl.BlockSpec((block_n, window), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((vocab, vocab), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((vocab, vocab), jnp.float32),
        interpret=interpret,
    )(toks, neigh, mask)
