"""Pallas TPU kernel: count-min sketch batch update (paper §3 sketches).

The (depth, width) sketch is the VMEM-resident accumulator (constant
out-block index_map); each grid step hashes a token block with `depth`
universal hashes and scatter-adds via one-hot matmuls on the MXU. The sketch
monoid combine (elementwise +) across devices is one psum — the kernel is
the in-mapper-combining stage of the paper's word-count-with-sketches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.monoids import _HASH_PRIMES


def _uhash_u32(x, seed: int):
    x = x.astype(jnp.uint32)
    a = jnp.uint32(_HASH_PRIMES[seed % len(_HASH_PRIMES)])
    b = jnp.uint32(_HASH_PRIMES[(seed + 3) % len(_HASH_PRIMES)])
    h = (x ^ (x >> 16)) * a
    h = (h ^ (h >> 13)) * b
    return h ^ (h >> 16)


def _cms_kernel(tok_ref, mask_ref, out_ref, *, depth: int, width: int,
                block_n: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    toks = tok_ref[...]
    mask = mask_ref[...].astype(jnp.float32)             # (BN,) 1 for real rows
    rows = []
    for d in range(depth):
        idx = (_uhash_u32(toks, d) % jnp.uint32(width)).astype(jnp.int32)
        onehot = (idx[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (block_n, width), 1)).astype(jnp.float32)
        # (1, BN) @ (BN, W) on the MXU -> counts for this hash row
        rows.append(jax.lax.dot(mask[None, :], onehot,
                                preferred_element_type=jnp.float32))
    out_ref[...] += jnp.concatenate(rows, axis=0)


def cms_update_pallas(tokens: jnp.ndarray, depth: int, width: int, *,
                      block_n: int = 1024, interpret: bool | None = None
                      ) -> jnp.ndarray:
    """tokens: (N,) int -> (depth, width) float32 counts."""
    if interpret is None:
        from .ops import _default_interpret
        interpret = _default_interpret()
    N = tokens.shape[0]
    pad = (-N) % block_n
    mask = jnp.ones((N,), jnp.int32)
    if pad:
        tokens = jnp.concatenate([tokens, jnp.zeros((pad,), tokens.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros((pad,), jnp.int32)])
    grid = ((N + pad) // block_n,)
    return pl.pallas_call(
        functools.partial(_cms_kernel, depth=depth, width=width,
                          block_n=block_n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,)),
                  pl.BlockSpec((block_n,), lambda i: (i,))],
        out_specs=pl.BlockSpec((depth, width), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((depth, width), jnp.float32),
        interpret=interpret,
    )(tokens.astype(jnp.int32), mask)
