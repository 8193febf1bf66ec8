"""Pallas TPU kernel: key-grouped semiring fold (the paper's combiner).

Hadoop's combiner sorts intermediate pairs and streams them; the TPU
adaptation (DESIGN.md §5) instead:

* tiles the record axis N into VMEM-sized blocks (grid dim 0),
* holds the per-key accumulator table (S, D) RESIDENT IN VMEM across grid
  steps — in-mapper combining *inside the kernel* (the output block's
  index_map is constant, so Pallas keeps one live copy),
* turns the scatter into a one-hot (S, BN) x (BN, D) matmul so the combine
  runs on the MXU systolic array (a serialized scatter would be VPU-bound —
  napkin math: BN=512, S=512, D=512 => 1.3e8 MACs/block vs 2.6e5 serial VPU
  adds; the MXU path is ~500x denser).

The kernel is parameterized by semiring, so one lowering path serves the
whole additive/max-plus monoid family the planner (core/plan.py) registers:

* ``'sum'``  — the additive monoids (sum / count / stripes / mean's
  (sum, count) pair): one-hot matmul on the MXU.  ``with_count=True``
  appends a ones column so mean's two components ride one matmul.
* ``'max'`` / ``'min'`` — the max-plus family (max, min, and 0/1-bitmap
  bitwise_or): the one-hot mask selects block rows per segment and the VPU
  takes the running max/min, eight segments at a time, so the select is an
  (8, BN, D) block whatever the key count.

Segment ids enter as a (1, N) row on the additive path (a (1, BN) block
matches XLA's lane tiling at any N; a 1-D (BN,) block is refused once N
exceeds it) and as an (N, 1) column on the max/min path, where the mask
must broadcast along the value lanes.  The planner
(``core/plan.py:_kernel_vmem_bytes``) keeps the mask, the select and the
resident (S, D) output within the chip's scoped fast memory.

Exact integer monoids round-trip: integer inputs are accumulated in float32
(exact for |values| < 2**24) and cast back to the input dtype, with empty
max/min segments mapped to the dtype's min/max (the integer identity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SEMIRINGS = ("sum", "max", "min")

_IDENTITY = {"sum": 0.0, "max": -jnp.inf, "min": jnp.inf}

# segments per max/min select (one sublane tile)
_MAX_ROWS = 8


def _segment_fold_kernel(seg_ref, val_ref, out_ref, *, num_segments: int,
                         block_n: int, semiring: str):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _IDENTITY[semiring])

    vals = val_ref[...].astype(jnp.float32)    # (D, BN) sum; (BN, D) max/min
    # one-hot scatter mask: padded rows carry seg id == num_segments (out of
    # range), so they match no row and contribute the semiring identity.
    if semiring == "sum":
        seg = seg_ref[...]                               # (1, BN), ids on lanes
        mask = seg == jax.lax.broadcasted_iota(
            jnp.int32, (num_segments, block_n), 0)       # (S, BN)
        # (S, BN) x (D, BN)^T on the MXU: records stay on the lanes
        out_ref[...] += jax.lax.dot_general(
            mask.astype(jnp.float32), vals, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return
    # max/min: ids on sublanes, so the (rows, BN, 1) mask broadcasts along
    # the lanes of (BN, D) without a relayout; _MAX_ROWS segments at a time
    # keep the (rows, BN, D) select at a fixed size whatever num_segments is
    seg = seg_ref[...]                                   # (BN, 1)
    reduce = jnp.max if semiring == "max" else jnp.min
    combine = jnp.maximum if semiring == "max" else jnp.minimum

    def chunk(c, carry):
        lo = pl.multiple_of(c * _MAX_ROWS, _MAX_ROWS)
        rows = lo + jax.lax.broadcasted_iota(
            jnp.int32, (_MAX_ROWS, block_n, 1), 0)
        picked = jnp.where(seg[None, :, :] == rows, vals[None, :, :],
                           _IDENTITY[semiring])          # (rows, BN, D)
        cur = out_ref[pl.ds(lo, _MAX_ROWS), :]
        out_ref[pl.ds(lo, _MAX_ROWS), :] = combine(cur, reduce(picked, axis=1))
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // _MAX_ROWS, chunk, 0)


def _finish_dtype(out: jnp.ndarray, dtype, semiring: str) -> jnp.ndarray:
    """Cast the float32 accumulator back for exact integer monoids.

    Floating inputs keep the float32 accumulator (the historical contract);
    integer inputs round-trip, with ±inf (empty max/min segments) mapped to
    the integer identity iinfo.min/max — matching jax.ops.segment_max/min.
    """
    if not jnp.issubdtype(dtype, jnp.integer):
        return out
    info = jnp.iinfo(dtype)
    if semiring == "max":
        out = jnp.where(jnp.isneginf(out), float(info.min), out)
    elif semiring == "min":
        out = jnp.where(jnp.isposinf(out), float(info.max), out)
    return out.astype(dtype)


def segment_fold_pallas(values: jnp.ndarray, seg_ids: jnp.ndarray,
                        num_segments: int, *, block_n: int = 512,
                        semiring: str = "sum", with_count: bool = False,
                        valid_mask: jnp.ndarray | None = None,
                        interpret: bool | None = None):
    """values: (N, D); seg_ids: (N,) int32 in [0, num_segments).

    Returns the (S, D) semiring fold — or ((S, D) sums, (S,) counts) with
    ``with_count`` (additive semiring only).  N is padded to a block multiple
    with the out-of-range segment id ``num_segments``, which folds into no
    real segment — the semiring identity contributes nothing.

    ``valid_mask`` (N,) bool makes the fold ragged: invalid rows are routed
    to the same out-of-range segment id the padding uses, so they contribute
    the semiring identity — no rectangular batch required, and the kernel
    body is untouched (one mask per grid step, zero extra FLOPs on the MXU).

    ``interpret=None`` resolves via :func:`repro.kernels.ops._default_interpret`
    (compiled on a TPU backend, interpret mode elsewhere).
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}; one of {SEMIRINGS}")
    if valid_mask is not None:
        seg_ids = jnp.where(jnp.asarray(valid_mask, jnp.bool_),
                            seg_ids, num_segments)
    if interpret is None:
        from .ops import _default_interpret
        interpret = _default_interpret()
    orig_dtype = values.dtype
    N, D = values.shape
    if with_count:
        if semiring != "sum":
            raise ValueError("with_count requires the additive semiring")
        values = jnp.concatenate(
            [values.astype(jnp.float32), jnp.ones((N, 1), jnp.float32)], axis=1)
        D += 1
    pad = (-N) % block_n
    if pad:
        values = jnp.concatenate(
            [values, jnp.zeros((pad, D), values.dtype)], axis=0)
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((pad,), num_segments, seg_ids.dtype)], axis=0)
    grid = ((N + pad) // block_n,)
    seg_ids = seg_ids.astype(jnp.int32)
    if semiring == "sum":
        # records on the lanes: (1, N) ids and (D, N) values, whose
        # block_n-wide blocks match XLA's tiling at any N (a 1-D (block_n,)
        # id block does not) and pad D to 8 sublanes, not 128 lanes
        seg_ids, seg_spec = seg_ids[None, :], pl.BlockSpec(
            (1, block_n), lambda i: (0, i))
        values, val_spec = values.T, pl.BlockSpec(
            (D, block_n), lambda i: (0, i))
        rows = num_segments
    else:
        seg_ids, seg_spec = seg_ids[:, None], pl.BlockSpec(
            (block_n, 1), lambda i: (i, 0))
        val_spec = pl.BlockSpec((block_n, D), lambda i: (i, 0))
        rows = -(-num_segments // _MAX_ROWS) * _MAX_ROWS
    out = pl.pallas_call(
        functools.partial(_segment_fold_kernel, num_segments=num_segments,
                          block_n=block_n, semiring=semiring),
        grid=grid,
        in_specs=[seg_spec, val_spec],
        out_specs=pl.BlockSpec((rows, D), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        interpret=interpret,
    )(seg_ids, values)[:num_segments]
    if with_count:
        return _finish_dtype(out[:, :-1], orig_dtype, semiring), out[:, -1]
    return _finish_dtype(out, orig_dtype, semiring)
