"""jit'd public wrappers for the Pallas kernels.

Kernels run compiled on a TPU backend and in interpret mode everywhere else
(the tests run on the CPU and check them against the ref.py oracles).
``REPRO_INTERPRET=0`` forces compiled mode; ``REPRO_INTERPRET=1`` forces
interpret mode off the TPU and is refused on it, so a chip run never
measures the interpreter.  The setting is read when the wrapper is called
(i.e. at trace time for jit'd callers).
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from .segment_fold import segment_fold_pallas
from .cms import cms_update_pallas
from .stripes import stripes_pallas
from .flash_attention import flash_attention

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def _default_interpret() -> bool:
    """Interpret mode off the TPU, compiled kernels on it; REPRO_INTERPRET
    overrides, except that interpret mode is refused on a TPU backend."""
    env = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    on_tpu = jax.default_backend() == "tpu"
    if env in _TRUTHY:
        if on_tpu:
            raise RuntimeError(
                "REPRO_INTERPRET=1 asks for interpret-mode kernels on a TPU "
                "backend; the chip runs compiled kernels only")
        return True
    if env in _FALSY:
        return False
    return not on_tpu


@partial(jax.jit, static_argnames=("num_segments", "with_count", "block_n",
                                   "semiring", "interpret"))
def _segment_fold_jit(values, seg_ids, num_segments, with_count, block_n,
                      semiring, interpret):
    return segment_fold_pallas(values, seg_ids, num_segments,
                               with_count=with_count, block_n=block_n,
                               semiring=semiring, interpret=interpret)


def segment_fold(values: jnp.ndarray, seg_ids: jnp.ndarray, num_segments: int,
                 *, with_count: bool = False, block_n: int = 512,
                 semiring: str = "sum", interpret: bool | None = None):
    """MXU-tiled key-grouped semiring fold: the paper's combiner.

    semiring='sum' (default) is the additive family; 'max'/'min' serve the
    max-plus monoids.  Exact integer inputs round-trip to their dtype.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _segment_fold_jit(values, seg_ids, num_segments, with_count,
                             block_n, semiring, interpret)


@partial(jax.jit, static_argnames=("num_segments", "block_n", "interpret"))
def _mean_by_key_jit(values, seg_ids, num_segments, block_n, interpret):
    sums, counts = segment_fold_pallas(values, seg_ids, num_segments,
                                       with_count=True, block_n=block_n,
                                       interpret=interpret)
    return sums / jnp.maximum(counts, 1.0)[:, None]


def mean_by_key(values: jnp.ndarray, seg_ids: jnp.ndarray, num_segments: int,
                *, block_n: int = 512,
                interpret: bool | None = None) -> jnp.ndarray:
    """The paper's running example, kernel edition: extract(sum/count)."""
    if interpret is None:
        interpret = _default_interpret()
    return _mean_by_key_jit(values, seg_ids, num_segments, block_n, interpret)


@partial(jax.jit, static_argnames=("depth", "width", "block_n"))
def cms_update(tokens: jnp.ndarray, depth: int = 4, width: int = 2048,
               *, block_n: int = 1024) -> jnp.ndarray:
    return cms_update_pallas(tokens, depth, width, block_n=block_n,
                             interpret=_default_interpret())


@partial(jax.jit, static_argnames=("vocab", "window", "block_n"))
def stripes(tokens: jnp.ndarray, vocab: int, window: int,
            *, block_n: int = 512) -> jnp.ndarray:
    return stripes_pallas(tokens, vocab, window, block_n=block_n,
                          interpret=_default_interpret())


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attn(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
               causal: bool = True, block_q: int = 128,
               block_k: int = 128) -> jnp.ndarray:
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=_default_interpret())
