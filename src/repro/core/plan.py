"""The unified monoid execution planner — ONE lowering path for every fold.

The paper's point is that once an intermediate value is a monoid, the
*framework* — not the caller — may re-bracket and relocate the reduction
(combiner, in-mapper combining, hierarchical aggregation).  This module is
that freedom given a single entry point: :func:`execute_fold` lowers any
fold — flat or keyed, local or cross-mesh — to a tiered plan:

  tier 1  kernel      a registered Pallas lowering (kernels/segment_fold.py's
                      semiring kernel) when the monoid has one,
  tier 2  segment-ops ``jax.ops.segment_*`` for the monoids XLA reduces
                      natively, or the generic serial scan / tree fold that
                      works for ANY monoid,
  tier 3  collective  hierarchical ICI-first-then-DCN mesh combine via
                      ``dist/collectives.py`` (the rack-aware combiner tree).

:func:`plan_fold` is the pure cost model behind it: it reports the chosen
tier per stage, the predicted shuffle/collective bytes, AND the predicted
wall time per tier from the calibrated coefficients of
:mod:`repro.core.calibration` — so ``layout='auto'`` is an argmin over
predicted microseconds (backend detection is only the feasibility filter),
the reduce-scatter-vs-allreduce shuffle choice is made here rather than in
callers, and ``mapreduce.ShuffleStats`` is derived from the plan rather
than ad-hoc accounting.  Planning works on concrete arrays or
ShapeDtypeStructs alike.

Kernel lowerings are registered on :class:`~repro.core.monoid.Monoid` by
name (see ``register_kernel_lowering``); the additive and max-plus zoo
monoids get leaf-wise semiring lowerings below.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .monoid import (KernelLowering, Monoid, Pytree, register_kernel_lowering,
                     scan_fold, tree_fold)
from .aggregation import _PMAX_LIKE, _PMIN_LIKE, _PSUM_LIKE, tree_bytes
from .calibration import Calibration, get_calibration, pipeline_exposed_us

LAYOUTS = ("auto", "kernel", "segment", "scan", "tree", "async")

# records per grid step of the Pallas kernel tier
BLOCK_N = 512

# layout spelling (user-facing) -> calibration tier kind (TierPlan.kind).
# 'async' is absent on purpose: it is a whole-plan shape (fused local +
# pipelined crossings), not a local tier the per-record model prices.
_LAYOUT_TIER_KIND = {"kernel": "kernel", "segment": "segment_ops",
                     "scan": "scan", "tree": "tree"}

# TierPlan.kind values that are collective (shuffle) stages, not local folds
_COLLECTIVE_KINDS = ("gather_pairs", "allreduce", "reduce_scatter")

# monoids XLA reduces natively with a segment primitive (tier 2, fast path)
_SEGMENT_OPS: Mapping[str, Callable] = {
    "sum": jax.ops.segment_sum,
    "count": jax.ops.segment_sum,
    "mean": jax.ops.segment_sum,   # applied leaf-wise to (sum, count)
    "max": jax.ops.segment_max,
    "min": jax.ops.segment_min,
    "bitwise_or": jax.ops.segment_max,   # 0/1 bitmaps: OR == max
}


# ---------------------------------------------------------------------------
# kernel lowerings for the zoo — leaf-wise semiring application
# ---------------------------------------------------------------------------

def _semiring_lowering(semiring: str) -> KernelLowering:
    """Leaf-wise lowering onto the semiring-parameterized Pallas kernel.

    Each leaf (N, ...) is flattened to (N, D), folded by key on the MXU/VPU,
    and reshaped to (num_segments, ...).  Exact integer leaves round-trip to
    their dtype (kernels/segment_fold.py handles the cast-back).
    """

    def lower(values: Pytree, seg_ids: jnp.ndarray, num_segments: int, *,
              block_n: int = BLOCK_N, valid_mask: Optional[jnp.ndarray] = None,
              interpret: Optional[bool] = None) -> Pytree:
        from ..kernels.segment_fold import segment_fold_pallas

        def per_leaf(v):
            v = jnp.asarray(v)
            flat = v.reshape((v.shape[0], -1))
            out = segment_fold_pallas(flat, seg_ids, num_segments,
                                      semiring=semiring, block_n=block_n,
                                      valid_mask=valid_mask,
                                      interpret=interpret)
            return out.reshape((num_segments,) + v.shape[1:])

        return jax.tree_util.tree_map(per_leaf, values)

    return KernelLowering(semiring=semiring, fn=lower)


def _mean_pair_lowering() -> KernelLowering:
    """Fused lowering for mean's (sum, count) pair: the count column rides
    the same one-hot matmul as the sums (ONE kernel launch, the paper's
    running example), falling back to leaf-wise for pytree-valued sums."""
    leafwise = _semiring_lowering("sum").fn

    def lower(values: Pytree, seg_ids: jnp.ndarray, num_segments: int, *,
              block_n: int = BLOCK_N, valid_mask: Optional[jnp.ndarray] = None,
              interpret: Optional[bool] = None) -> Pytree:
        from ..kernels.segment_fold import segment_fold_pallas

        s, c = values
        s_leaves = jax.tree_util.tree_leaves(s)
        if len(s_leaves) != 1 or jnp.ndim(c) != 1:
            return leafwise(values, seg_ids, num_segments, block_n=block_n,
                            valid_mask=valid_mask, interpret=interpret)
        (sv,) = s_leaves
        sv = jnp.asarray(sv)
        flat = jnp.concatenate(
            [sv.reshape((sv.shape[0], -1)).astype(jnp.float32),
             jnp.asarray(c).reshape((-1, 1)).astype(jnp.float32)], axis=1)
        out = segment_fold_pallas(flat, seg_ids, num_segments,
                                  semiring="sum", block_n=block_n,
                                  valid_mask=valid_mask,
                                  interpret=interpret)
        sums = out[:, :-1].reshape((num_segments,) + sv.shape[1:])
        if jnp.issubdtype(sv.dtype, jnp.integer):
            sums = sums.astype(sv.dtype)
        counts = out[:, -1]
        if jnp.issubdtype(jnp.asarray(c).dtype, jnp.integer):
            counts = counts.astype(jnp.asarray(c).dtype)
        treedef = jax.tree_util.tree_structure(s)
        return (jax.tree_util.tree_unflatten(treedef, [sums]), counts)

    return KernelLowering(semiring="sum", fn=lower)


# The additive family rides the MXU one-hot matmul; the max-plus family the
# VPU masked reduce.  bitwise_or qualifies because the sketch monoids keep
# 0/1 uint8 bitmaps, where OR == max (see aggregation.monoid_allreduce).
# (monoids.stripes is an alias of sum_ — Monoid.name 'sum' — so the stripes
# fold rides the 'sum' registration; no separate entry needed.)
for _name in ("sum", "count"):
    register_kernel_lowering(_name, _semiring_lowering("sum"))
register_kernel_lowering("mean", _mean_pair_lowering())
register_kernel_lowering("max", _semiring_lowering("max"))
register_kernel_lowering("bitwise_or", _semiring_lowering("max"))
register_kernel_lowering("min", _semiring_lowering("min"))


# ---------------------------------------------------------------------------
# the plan — tiers + predicted bytes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TierPlan:
    """One stage of a lowered fold.

    kind: 'kernel' | 'segment_ops' | 'scan' | 'tree' | 'gather_pairs' |
          'allreduce' | 'reduce_scatter'.
    wire_bytes: predicted bytes this stage puts on the wire, summed over the
      participants of one reduction group (0 for on-device stages).
    predicted_us: modeled wall time of this stage under the active
      calibration (0 when the model has nothing to say, e.g. unknown axis
      size).
    candidate_us: the (candidate, predicted_us) table the planner chose
      from — layout names for the local tier, shuffle algorithms for a
      collective tier.  Empty for stages with no choice.
    """

    kind: str
    detail: str
    out_bytes: int
    wire_bytes: int = 0
    predicted_us: float = 0.0
    candidate_us: Tuple[Tuple[str, float], ...] = ()


@dataclasses.dataclass(frozen=True)
class Plan:
    """A lowered fold: local tier(s) followed by collective tier(s).

    ``num_valid`` is the statically-known count of rows a ``valid_mask``
    keeps (None when no mask was given or the mask is abstract/traced) —
    ragged folds shuffle only valid rows, and the byte model reflects that.
    """

    monoid: Monoid
    tiers: Tuple[TierPlan, ...]
    num_records: int
    num_segments: Optional[int]
    value_bytes: int          # bytes of ONE lifted monoid value
    out_bytes: int            # bytes of the final local result (table/value)
    num_valid: Optional[int] = None
    # -- overlap / compression annotations (flat mesh folds) ----------------
    lossy: Optional[str] = None        # LossySpec.describe() when annotated
    overlap_modeled: float = 0.0       # modeled hidden fraction of DCN time
    dense_wire_bytes: int = 0          # per-device DCN bytes of a dense sync
                                       #   crossing (0: no DCN axis planned)
    lossy_wire_bytes: int = 0          # per-device DCN bytes actually planned
                                       #   (== dense_wire_bytes when not lossy)
    plan_candidate_us: Tuple[Tuple[str, float], ...] = ()
                                       # whole-plan (sync vs async) argmin
                                       #   table when both shapes were priced
    kernel_infeasible: Optional[str] = None
                                       # why the kernel tier was left out on
                                       #   a TPU backend (fast memory, dtype)

    @property
    def local_tier(self) -> TierPlan:
        return next(t for t in self.tiers
                    if t.kind not in _COLLECTIVE_KINDS)

    @property
    def collective_wire_bytes(self) -> int:
        return sum(t.wire_bytes for t in self.tiers)

    @property
    def predicted_us(self) -> float:
        """Modeled wall time of the whole plan (local + collectives)."""
        return float(sum(t.predicted_us for t in self.tiers))

    @property
    def candidate_us(self) -> Mapping[str, float]:
        """Predicted microseconds per feasible local-tier layout — the table
        ``layout='auto'`` argmins over."""
        return dict(self.local_tier.candidate_us)

    @property
    def shuffle_algorithm(self) -> Optional[str]:
        """'reduce_scatter' | 'allreduce' for the first collective stage
        (None when the plan has no collective stage) — what
        ``mapreduce.run_sharded`` executes instead of choosing itself."""
        for t in self.tiers:
            if t.kind in ("allreduce", "reduce_scatter"):
                return t.kind
        return None

    @property
    def shuffle_candidate_us(self) -> Mapping[str, float]:
        """Predicted microseconds per shuffle algorithm on the first
        collective axis (empty when there is none or its size is unknown)."""
        for t in self.tiers:
            if t.kind in ("allreduce", "reduce_scatter"):
                return dict(t.candidate_us)
        return {}

    def describe(self) -> str:
        parts = []
        for t in self.tiers:
            us = f" ~{t.predicted_us:.1f}us" if t.predicted_us > 0 else ""
            parts.append(f"{t.kind}[{t.detail}{us}]")
        s = " -> ".join(parts)
        if self.lossy:
            s += (f" [lossy={self.lossy}: dcn {self.lossy_wire_bytes}B"
                  f" vs {self.dense_wire_bytes}B dense]")
        if self.overlap_modeled > 0.0:
            s += f" [overlap modeled {self.overlap_modeled:.0%}]"
        if self.kernel_infeasible:
            s += f" [kernel infeasible: {self.kernel_infeasible}]"
        return s


def collective_algorithm(m: Monoid) -> str:
    """'ring' when the monoid lowers to a psum/pmax/pmin-family collective
    (see aggregation.monoid_allreduce), 'gather' for the generic fallback."""
    name = m.name
    if (name in _PSUM_LIKE or name in _PMAX_LIKE or name in _PMIN_LIKE
            or name in ("mean", "logsumexp", "attn_state")
            or name.startswith("hll") or name.startswith("cms")):
        return "ring"
    return "gather"


def collective_wire_bytes(nbytes: int, axis_size: int, algorithm: str) -> int:
    """Total wire bytes across one reduction group of ``axis_size`` devices."""
    if axis_size <= 1:
        return 0
    if algorithm in ("ring", "reduce_scatter"):
        # ring allreduce decomposes into the same two phases the explicit
        # reduce-scatter + all-gather spells out: 2(P-1)/P x nbytes each
        return int(2 * nbytes * (axis_size - 1))
    if algorithm == "gather":     # every device replicates its value P-1 times
        return int(nbytes * (axis_size - 1) * axis_size)
    raise ValueError(algorithm)


def _per_device_shuffle_bytes(nbytes: int, axis_size: int, shuffle_kind: str,
                              allreduce_algo: str) -> float:
    """Wire bytes ONE device moves for a table shuffle — the quantity the
    link-time model prices.  reduce_scatter scatters then gathers 1/P shards
    (2(P-1)/P x nbytes) for any monoid; allreduce matches that for the
    psum/pmax-family ('ring') but degrades to a full (P-1) x nbytes gather
    for generic monoids."""
    if axis_size <= 1:
        return 0.0
    if shuffle_kind == "reduce_scatter" or allreduce_algo == "ring":
        return 2.0 * nbytes * (axis_size - 1) / axis_size
    return float(nbytes) * (axis_size - 1)


def _split_ici_dcn(mesh_axes: Sequence[Any]) -> Tuple[Tuple, Tuple]:
    # delegate to dist: planning order must match execution order exactly
    from ..dist.collectives import split_axis_names
    return split_axis_names(mesh_axes)


def _leading_dim(values: Pytree) -> int:
    return jax.tree_util.tree_leaves(values)[0].shape[0]


def _one_slice(values: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype), values)


def _lifted_value_shape(m: Monoid, values: Pytree, lifted: bool,
                        map_fn: Optional[Callable]) -> Pytree:
    """Shape/dtype pytree of ONE lifted monoid value (no FLOPs spent)."""
    one = _one_slice(values)
    if map_fn is not None:
        return jax.eval_shape(lambda x: m.lift(map_fn(x)), one)
    if not lifted:
        return jax.eval_shape(m.lift, one)
    return one


def _static_valid_count(valid_mask) -> Optional[int]:
    """Number of True rows when the mask is concrete; None when abstract
    (ShapeDtypeStruct at plan time, or a tracer inside jit)."""
    if valid_mask is None or isinstance(valid_mask, jax.ShapeDtypeStruct):
        return None
    try:
        return int(jnp.sum(jnp.asarray(valid_mask, jnp.bool_)))
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError, TypeError):
        return None


def _check_valid_mask(valid_mask, n: int) -> None:
    shape = getattr(valid_mask, "shape", None)
    if shape is not None and tuple(shape) != (n,):
        raise ValueError(
            f"valid_mask must be one flag per record, shape ({n},); got "
            f"shape {tuple(shape)}")


def _mask_rows_to_identity(m: Monoid, values: Pytree,
                           valid_mask: jnp.ndarray) -> Pytree:
    """Replace invalid rows of a LIFTED batch with the monoid identity, so
    they are no-ops under combine — the generic ragged lowering that works
    for ANY monoid (scan/tree tiers)."""
    mask = jnp.asarray(valid_mask, jnp.bool_)
    one = m.identity_like(jax.tree_util.tree_map(lambda v: v[0], values))
    return jax.tree_util.tree_map(
        lambda v, i: jnp.where(
            mask.reshape(mask.shape + (1,) * (jnp.ndim(v) - 1)), v,
            jnp.asarray(i, jnp.asarray(v).dtype)),
        values, one)


def _mask_segment_ids(segment_ids: jnp.ndarray, valid_mask,
                      num_segments: int) -> jnp.ndarray:
    """Route invalid rows to the out-of-range id ``num_segments`` — dropped
    by XLA scatters (jax.ops.segment_*) and by the Pallas kernel's one-hot,
    exactly like its block padding."""
    if valid_mask is None:
        return segment_ids
    return jnp.where(jnp.asarray(valid_mask, jnp.bool_), segment_ids,
                     num_segments)


# Mosaic's default scoped fast-memory (VMEM) limit on TPU v5e, the smallest
# of the TPU generations: a kernel whose live blocks exceed it is refused by
# the compiler (RESOURCE_EXHAUSTED), so the planner refuses it first.
_KERNEL_VMEM_BYTES = 16 * 2**20


def _kernel_vmem_bytes(semiring: str, num_segments: int, width: int,
                       block_n: int) -> int:
    """Fast memory the segment-fold kernel keeps live for one leaf of
    ``width`` columns (f32; tiles of 8 sublanes x 128 lanes): the one-hot
    (S, BN) mask of the additive path or the (8, BN, D) select of max/min,
    the resident (S, D) output block, and the double-buffered input blocks
    (records on the lanes for sum, on the sublanes for max/min)."""
    lanes = -(-width // 128) * 128
    if semiring == "sum":
        work, rows = num_segments * block_n, num_segments
        inputs = (-(-width // 8) * 8 + 8) * block_n
    else:
        work, rows = 8 * block_n * lanes, -(-num_segments // 8) * 8
        inputs = block_n * (lanes + 128)
    return 4 * (work + rows * lanes + 2 * inputs)


def _kernel_infeasible_reason(m: Monoid, value_shape: Pytree,
                              num_segments: int,
                              block_n: int) -> Optional[str]:
    """Why the kernel tier cannot lower this fold — None when it can.

    The returned text names the offending leaf (tree path) and its dtype,
    or the fast memory the kernel would need, so a forced
    ``layout='kernel'`` fails at PLAN time with an actionable message
    instead of deep inside the Pallas lowering or the TPU compiler."""
    low = m.kernel_lowering()
    if low is None:
        return (f"monoid {m.name!r} has no registered Pallas kernel lowering "
                "(see register_kernel_lowering)")
    leaves, _ = jax.tree_util.tree_flatten_with_path(value_shape)
    for path, leaf in leaves:
        if not (jnp.issubdtype(leaf.dtype, jnp.floating)
                or jnp.issubdtype(leaf.dtype, jnp.integer)):
            where = jax.tree_util.keystr(path) or "<value>"
            return (f"value leaf {where!r} has dtype "
                    f"{jnp.dtype(leaf.dtype).name}, which the Pallas "
                    "segment-fold kernel cannot lower (float/int leaves only)")
    # mean's count column rides its sums' kernel call
    width = max(math.prod(leaf.shape) for _, leaf in leaves) + (
        m.name == "mean")
    need = _kernel_vmem_bytes(low.semiring, num_segments, width, block_n)
    if need > _KERNEL_VMEM_BYTES:
        return (f"{num_segments} keys x {width} columns at block_n={block_n} "
                f"need {need / 2**20:.1f} MiB of kernel fast memory, over "
                f"the {_KERNEL_VMEM_BYTES / 2**20:.0f} MiB limit")
    return None


def _kernel_exact(value_shape: Pytree, num_records: int,
                  unit: bool = False) -> bool:
    """Whether the kernel's float32 accumulator is exact for these inputs.

    Integer leaves are accumulated in float32; that is exact only while the
    per-key running total stays below 2**24.  We cannot see magnitudes at
    plan time, so ``layout='auto'`` only keeps integer inputs on the kernel
    tier when even the worst case (every record at the dtype's extreme, all
    landing in one key) fits — narrow dtypes (8/16-bit bitmaps and counts)
    pass for reasonable batches; 32-bit-and-wider integers down-tier to the
    exact segment-ops path.  ``unit`` says every record is known to be 1
    (a count's lift), so the bound is the record count itself.  Forcing
    ``layout='kernel'`` bypasses this — the caller asserts their
    magnitudes fit.
    """
    for leaf in jax.tree_util.tree_leaves(value_shape):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            info = jnp.iinfo(leaf.dtype)
            # extreme magnitude: unsigned dtypes have info.min == 0, so the
            # bound must come from info.max there
            extreme = 1 if unit else max(abs(int(info.min)), int(info.max))
            if extreme * max(num_records, 1) >= 2 ** 24:
                return False
    return True


def _link_domain(ax: Any) -> str:
    """'dcn' for axes wired over DCN (dist.collectives.DCN_AXIS_NAMES),
    'ici' otherwise — the calibration's two link classes."""
    _, dcn = _split_ici_dcn((ax,))
    return "dcn" if dcn else "ici"


def _plan_collective_tier(calib: Calibration, label: str, ax: Any,
                          P: Optional[int], nbytes: int,
                          num_segments: Optional[int],
                          allreduce_algo: str) -> TierPlan:
    """One collective stage, its shuffle algorithm chosen by predicted cost.

    Candidates: 'reduce_scatter' (keyed tables whose key count divides the
    axis size — each device reduces one key shard, then all-gathers: the
    MapReduce shuffle proper) and 'allreduce' (ring for the psum/pmax
    family, gather + on-device fold for generic monoids).  Argmin over the
    calibrated link model; a predicted tie prefers reduce_scatter because
    it distributes the per-key reduce work across the group.  An unknown
    or trivial axis size plans a 0-cost allreduce (today's behavior).
    """
    candidates = ["allreduce"]
    if num_segments is not None and P and P > 1 and num_segments % P == 0:
        candidates.insert(0, "reduce_scatter")   # ties prefer reduce_scatter
    if not P or P <= 1:
        wire = collective_wire_bytes(nbytes, P, allreduce_algo) if P else 0
        return TierPlan("allreduce",
                        f"{label}:{ax} {allreduce_algo}"
                        + ("" if P else " (size unknown)"),
                        nbytes, wire)
    cand_us = tuple(
        (kind, calib.predict_link_us(
            label, _per_device_shuffle_bytes(nbytes, P, kind, allreduce_algo)))
        for kind in candidates)
    costs = dict(cand_us)
    kind = min(candidates, key=costs.get)
    if kind == "reduce_scatter":
        return TierPlan("reduce_scatter",
                        f"{label}:{ax} reduce_scatter+all_gather",
                        nbytes, collective_wire_bytes(nbytes, P, kind),
                        predicted_us=costs[kind], candidate_us=cand_us)
    return TierPlan("allreduce", f"{label}:{ax} {allreduce_algo}",
                    nbytes, collective_wire_bytes(nbytes, P, allreduce_algo),
                    predicted_us=costs[kind], candidate_us=cand_us)


def _plan_lossy_dcn_tier(calib: Calibration, ax: Any, P: Optional[int],
                         comp_bytes: int, dense_bytes: int,
                         spec) -> TierPlan:
    """The DCN crossing of a ``lossy=`` fold: compressed messages gathered
    and combined on-device (concat + scatter-add / dequant-sum — the lossy
    monoid's exact regime), priced at the COMPRESSED bytes."""
    detail = f"dcn:{ax} lossy[{spec.describe()}] {comp_bytes}B/{dense_bytes}B"
    if not P or P <= 1:
        return TierPlan("allreduce", detail + ("" if P else " (size unknown)"),
                        comp_bytes, 0)
    per_dev = float(comp_bytes) * (P - 1)    # gather: each message replicated
    return TierPlan("allreduce", detail, comp_bytes,
                    int(comp_bytes * (P - 1) * P),
                    predicted_us=calib.predict_link_us("dcn", per_dev))


def _plan_async_tier(calib: Calibration, *, n: int, value_bytes: int,
                     out_bytes: int, local_us_total: float,
                     ici: Sequence[Any], dcn: Sequence[Any],
                     sizes: Mapping[Any, int], spec, comp_bytes: int,
                     algo: str) -> Tuple[TierPlan, float, float]:
    """Price the double-buffered shape: n ICI-combined partials, the DCN
    crossing of partial i pipelined against the compute of partial i+1.

    Per-microbatch ICI combines and the compute slot cannot hide anything
    (they ARE the foreground work); of the n DCN crossings, n-1 are
    pipelined and the epilogue is structurally exposed.  How much of the
    pipelined in-flight time is actually hidden is the platform's measured
    ``overlap_frac`` (0 where the compiler serializes collectives against
    compute — CPU — so 'auto' correctly declines the shape there).

    Returns (tier, total_us, modeled hidden fraction of DCN time).
    """
    ici_us, ici_wire = 0.0, 0
    for ax in ici:
        P = sizes.get(ax)
        if P and P > 1:
            ici_us += calib.predict_link_us(
                "ici", _per_device_shuffle_bytes(value_bytes, P,
                                                 "allreduce", algo))
            ici_wire += collective_wire_bytes(value_bytes, P, algo)
    cross_us, dcn_wire = 0.0, 0
    for ax in dcn:
        P = sizes.get(ax)
        if P and P > 1:
            if spec is not None:
                cross_us += calib.predict_link_us(
                    "dcn", float(comp_bytes) * (P - 1))
                dcn_wire += comp_bytes * (P - 1) * P
            else:
                cross_us += calib.predict_link_us(
                    "dcn", _per_device_shuffle_bytes(value_bytes, P,
                                                     "allreduce", algo))
                dcn_wire += collective_wire_bytes(value_bytes, P, algo)
    slot_us = local_us_total / n + ici_us
    exposed, hideable = pipeline_exposed_us(
        num_crossings=n, slot_us=slot_us, cross_us=cross_us)
    ofrac = min(max(calib.link_coeff("dcn").overlap_frac, 0.0), 1.0)
    hidden = hideable * ofrac
    total_cross = n * cross_us
    total = local_us_total + n * ici_us + total_cross - hidden
    modeled = hidden / total_cross if total_cross > 0.0 else 0.0
    detail = (f"double-buffered x{n} microbatch crossings"
              + (f" lossy[{spec.describe()}]" if spec is not None else "")
              + f", modeled overlap {modeled:.0%}")
    tier = TierPlan("async", detail, out_bytes,
                    n * (ici_wire + dcn_wire), predicted_us=total)
    return tier, total, modeled


def plan_fold(m: Monoid, values: Pytree, *, segment_ids=None,
              num_segments: Optional[int] = None,
              valid_mask=None,
              mesh_axes: Optional[Sequence[Any]] = None,
              layout: str = "auto", lifted: bool = True,
              map_fn: Optional[Callable] = None,
              mesh: Optional[jax.sharding.Mesh] = None,
              axis_sizes: Optional[Mapping[Any, int]] = None,
              pre_combine: bool = True, block_n: int = BLOCK_N,
              lossy=None,
              calibration: Optional[Calibration] = None) -> Plan:
    """Lower a fold to a tiered :class:`Plan` without executing it.

    ``values`` may be concrete arrays or ShapeDtypeStructs — planning costs
    no FLOPs.  ``pre_combine=False`` models the paper's Algorithm 1 (no
    combiner: raw pairs cross the wire, receivers fold) purely for byte
    accounting; :func:`execute_fold` refuses to run such plans.

    ``layout='auto'`` is an argmin over predicted microseconds from the
    active :class:`~repro.core.calibration.Calibration` (override with
    ``calibration=``): backend detection and dtype checks only decide which
    tiers are FEASIBLE; the calibrated time model decides which feasible
    tier wins.  The same model chooses reduce-scatter vs allreduce per
    collective axis (``Plan.shuffle_algorithm``).  A forced ``layout=``
    that is infeasible for the inputs raises at plan time with the
    offending leaf dtype named.

    ``valid_mask`` (one bool per record) makes the fold ragged: invalid rows
    contribute the monoid identity on every tier, and — when the mask is
    concrete — only valid rows count toward the shuffle byte model
    (``Plan.num_valid``).  This is how padded batches and packed sequences
    fold without materializing a rectangle of real records.

    ``layout='async'`` plans the double-buffered shape of
    :func:`repro.dist.collectives.async_microbatch_fold` — the DCN crossing
    of record *i*'s ICI-combined partial pipelined against record *i+1*'s
    compute.  It is a flat-fold layout and needs ``mesh_axes=``; under
    ``layout='auto'`` the shape participates in the argmin (priced with the
    calibrated ``overlap_frac`` of the DCN link), with a predicted tie going
    to the sync shape.

    ``lossy=`` (a :class:`repro.optim.compress.LossySpec` or its string
    spelling, e.g. ``"topk:0.01"``) annotates a flat additive fold: the DCN
    crossing moves the compressed representation instead of dense floats,
    and the byte/time model prices the compressed bytes
    (``Plan.lossy_wire_bytes`` vs ``Plan.dense_wire_bytes``).

    Axis sizes for collective byte prediction come from ``mesh`` or
    ``axis_sizes``; unknown sizes predict 0 wire bytes.
    """
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}")
    keyed = segment_ids is not None
    if keyed and num_segments is None:
        raise ValueError(
            "segment_ids= was passed without num_segments=: a keyed fold "
            "returns a static (num_segments, ...) table, so pass the key-"
            "space size as num_segments=")
    spec = None
    if lossy is not None:
        from ..optim.compress import LossySpec  # lazy: optim imports core
        spec = LossySpec.parse(lossy)
        if keyed:
            raise ValueError(
                "lossy= compression applies to flat (gradient) folds; keyed "
                "tables cross the wire dense")
        if m.name != "sum":
            raise ValueError(
                f"lossy= compression needs an additive fold; got monoid "
                f"{m.name!r}")
    if layout == "async":
        if keyed:
            raise ValueError(
                "layout='async' overlaps the DCN crossing of a flat "
                "microbatch fold; keyed folds use kernel/segment/scan")
        if not mesh_axes:
            raise ValueError(
                "layout='async' needs mesh_axes= — without a mesh there is "
                "no crossing to overlap")

    n = _leading_dim(values)
    kernel_note = None
    if valid_mask is not None:
        _check_valid_mask(valid_mask, n)
    num_valid = _static_valid_count(valid_mask)
    n_model = n if num_valid is None else num_valid   # rows in the byte model
    value_shape = _lifted_value_shape(m, values, lifted, map_fn)
    vbytes = tree_bytes(value_shape)
    out_bytes = (num_segments * vbytes) if keyed else vbytes
    masked = " +mask" if valid_mask is not None else ""

    calib = calibration if calibration is not None else get_calibration()
    leaves = jax.tree_util.tree_leaves(value_shape)
    dtype_key = jnp.dtype(leaves[0].dtype).name if leaves else "*"

    def local_us(layout_name: str) -> float:
        # every tier touches all n rows (masked rows still flow through the
        # kernel/scatter/scan); only the SHUFFLE byte model is ragged-aware
        return calib.predict_local_us(
            _LAYOUT_TIER_KIND[layout_name], monoid=m.name, dtype=dtype_key,
            num_records=n, record_bytes=vbytes)

    # -- local tier: feasibility filter, then argmin over predicted cost ----
    if keyed:
        if layout == "tree":
            raise ValueError("layout='tree' is a flat-fold layout; keyed "
                             "folds use kernel/segment/scan")
        kernel_reason = _kernel_infeasible_reason(m, value_shape,
                                                  num_segments, block_n)
        # count lifts every raw record to 1: its per-key total is <= n
        unit = m.name == "count" and (not lifted or map_fn is not None)
        candidates = []
        # feasibility only: the kernel tier needs a registered lowering with
        # compatible dtypes that fits fast memory, an exact accumulator, and
        # the TPU backend — WHICH feasible tier runs is the cost model's
        # call below
        if (kernel_reason is None and _kernel_exact(value_shape, n_model, unit)
                and jax.default_backend() == "tpu"):
            candidates.append("kernel")
        if m.name in _SEGMENT_OPS:
            candidates.append("segment")
        candidates.append("scan")
        shown = candidates + ([layout] if layout not in ("auto", *candidates)
                              else [])
        candidate_us = tuple((c, local_us(c)) for c in shown)
        costs = dict(candidate_us)
        kind = (min(candidates, key=costs.get) if layout == "auto"
                else layout)
        if (kernel_reason is not None and m.kernel_lowering() is not None
                and jax.default_backend() == "tpu"):
            kernel_note = kernel_reason
        if kind == "kernel":
            if kernel_reason is not None:
                raise ValueError(
                    f"layout='kernel' was requested but is infeasible: "
                    f"{kernel_reason}. Use layout='segment' or "
                    "layout='scan', or leave layout='auto' to let the cost "
                    "model pick among feasible tiers.")
            low = m.kernel_lowering()
            local = TierPlan("kernel",
                             f"pallas segment_fold[{low.semiring}] "
                             f"block_n={block_n}{masked}", out_bytes,
                             predicted_us=costs["kernel"],
                             candidate_us=candidate_us)
        elif kind == "segment":
            op = _SEGMENT_OPS.get(m.name)
            if op is None:
                raise ValueError(
                    f"layout='segment' was requested but monoid {m.name!r} "
                    "has no XLA segment primitive (jax.ops.segment_*); use "
                    "layout='scan', or leave layout='auto' to let the cost "
                    "model pick among feasible tiers.")
            local = TierPlan("segment_ops", f"jax.ops.{op.__name__}{masked}",
                             out_bytes, predicted_us=costs["segment"],
                             candidate_us=candidate_us)
        else:
            local = TierPlan("scan",
                             f"serial scan (any monoid, Alg 4){masked}",
                             out_bytes, predicted_us=costs["scan"],
                             candidate_us=candidate_us)
    else:
        if layout in ("kernel", "segment"):
            raise ValueError(
                f"layout={layout!r} lowers a KEYED fold but no segment_ids= "
                "were given: pass segment_ids= (one key per record) and "
                "num_segments=, or use layout='tree'/'scan' for a flat fold")
        # with map_fn the point is O(1) live values — materializing for the
        # tree tier would defeat it, so auto considers the fused scan only
        candidates = ["scan"] if map_fn is not None else ["tree", "scan"]
        # 'async' fuses an in-mapper scan with pipelined crossings: its
        # local work is the scan tier's, chosen here; the whole-plan shape
        # is decided after the sync collectives are priced below
        eff_layout = "auto" if layout == "async" else layout
        shown = candidates + ([eff_layout]
                              if eff_layout not in ("auto", *candidates)
                              else [])
        candidate_us = tuple((c, local_us(c)) for c in shown)
        costs = dict(candidate_us)
        kind = (min(candidates, key=costs.get) if eff_layout == "auto"
                else eff_layout)
        if kind == "tree":
            local = TierPlan("tree",
                             f"log-depth tree fold (Alg 3 combiner){masked}",
                             out_bytes, predicted_us=costs["tree"],
                             candidate_us=candidate_us)
        else:
            local = TierPlan("scan",
                             f"in-mapper scan (Alg 4, O(1) live){masked}",
                             out_bytes, predicted_us=costs["scan"],
                             candidate_us=candidate_us)

    # -- collective tiers: ICI first, then DCN ------------------------------
    sizes = dict(axis_sizes or {})
    if mesh is not None:
        for ax, sz in mesh.shape.items():
            sizes.setdefault(ax, sz)
    algo = collective_algorithm(m)
    tiers = []
    if not pre_combine:
        # Algorithm 1: every VALID lifted pair crosses the wire un-combined.
        pair_bytes = n_model * vbytes
        wire = sum(collective_wire_bytes(pair_bytes, sizes.get(ax, 1),
                                         "gather") for ax in (mesh_axes or ()))
        pred = sum(
            calib.predict_link_us(_link_domain(ax),
                                  float(pair_bytes) * (sizes[ax] - 1))
            for ax in (mesh_axes or ())
            if sizes.get(ax) and sizes[ax] > 1)
        tiers.append(TierPlan("gather_pairs",
                              "no combiner: all pairs shuffled (Alg 1)",
                              pair_bytes, wire, predicted_us=float(pred)))
        tiers.append(local)
    else:
        tiers.append(local)
        if mesh_axes:
            ici, dcn = _split_ici_dcn(mesh_axes)
            comp_bytes = spec.wire_bytes(value_shape) if spec else 0
            for ax in ici:
                tiers.append(_plan_collective_tier(
                    calib, "ici", ax, sizes.get(ax), out_bytes,
                    num_segments if keyed else None, algo))
            for ax in dcn:
                if spec is not None and not keyed:
                    tiers.append(_plan_lossy_dcn_tier(
                        calib, ax, sizes.get(ax), comp_bytes, out_bytes,
                        spec))
                else:
                    tiers.append(_plan_collective_tier(
                        calib, "dcn", ax, sizes.get(ax), out_bytes,
                        num_segments if keyed else None, algo))

    # -- overlap / compression annotations + the sync-vs-async argmin --------
    overlap_modeled = 0.0
    dense_wire = lossy_wire = 0
    plan_cand: Tuple[Tuple[str, float], ...] = ()
    if not keyed and mesh_axes and pre_combine:
        ici, dcn = _split_ici_dcn(mesh_axes)
        comp_bytes = spec.wire_bytes(value_shape) if spec else 0
        for ax in dcn:
            P = sizes.get(ax)
            if P and P > 1:
                dense_wire += int(_per_device_shuffle_bytes(
                    out_bytes, P, "allreduce", algo))
                lossy_wire += (comp_bytes * (P - 1) if spec is not None
                               else int(_per_device_shuffle_bytes(
                                   out_bytes, P, "allreduce", algo)))
        if n > 1 and layout in ("auto", "async"):
            async_tier, async_total, modeled = _plan_async_tier(
                calib, n=n, value_bytes=vbytes, out_bytes=out_bytes,
                local_us_total=local.predicted_us, ici=ici, dcn=dcn,
                sizes=sizes, spec=spec, comp_bytes=comp_bytes, algo=algo)
            sync_total = float(sum(t.predicted_us for t in tiers))
            plan_cand = (("sync", sync_total), ("async", async_total))
            # a predicted tie goes to sync: one crossing beats n crossings
            # whenever the model cannot prove the extra n-1 are hidden
            if layout == "async" or async_total < sync_total:
                async_tier = dataclasses.replace(async_tier,
                                                 candidate_us=plan_cand)
                tiers = [async_tier]
                overlap_modeled = modeled
                lossy_wire = (comp_bytes * sum(
                    sizes[ax] - 1 for ax in dcn
                    if sizes.get(ax) and sizes[ax] > 1) * n
                    if spec is not None else dense_wire * n)
                dense_wire *= n
    return Plan(monoid=m, tiers=tuple(tiers), num_records=n,
                num_segments=num_segments, value_bytes=vbytes,
                out_bytes=out_bytes, num_valid=num_valid,
                lossy=spec.describe() if spec else None,
                overlap_modeled=overlap_modeled,
                dense_wire_bytes=dense_wire, lossy_wire_bytes=lossy_wire,
                plan_candidate_us=plan_cand,
                kernel_infeasible=kernel_note)


# ---------------------------------------------------------------------------
# tier implementations
# ---------------------------------------------------------------------------

def _seg_add_init(m: Monoid, folded: Pytree, init: Optional[Pytree]) -> Pytree:
    if init is None:
        return folded
    return jax.vmap(m.combine)(init, folded)


def _segment_fold_generic(m: Monoid, values: Pytree, segment_ids: jnp.ndarray,
                          num_segments: int, init: Optional[Pytree] = None, *,
                          lifted: bool = True,
                          map_fn: Optional[Callable] = None,
                          valid_mask: Optional[jnp.ndarray] = None) -> Pytree:
    """O(N) serial scan — works for ANY monoid (the associative array of
    Alg 4).  With ``lifted=False``/``map_fn`` the lift runs inside the scan
    step, so per-record values are never materialized (true in-mapper
    combining).  Rows where ``valid_mask`` is False contribute the monoid
    identity — combine with it is a no-op, so the ragged fold equals the
    fold over only the valid rows for ANY monoid."""
    def prep(x):
        if map_fn is not None:
            return m.lift(map_fn(x))
        return x if lifted else m.lift(x)

    first = jax.tree_util.tree_map(lambda v: v[0], values)
    one = m.identity_like(prep(first))
    if init is None:
        init = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (num_segments,) + l.shape), one)

    mask = (None if valid_mask is None
            else jnp.asarray(valid_mask, jnp.bool_))

    def step(acc, kv):
        if mask is None:
            k, x = kv
            v = prep(x)
        else:
            k, valid, x = kv
            v = prep(x)
            v = jax.tree_util.tree_map(
                lambda l, i: jnp.where(valid, l,
                                       jnp.asarray(i, jnp.asarray(l).dtype)),
                v, one)
        cur = jax.tree_util.tree_map(lambda a: a[k], acc)
        new = m.combine(cur, v)
        acc = jax.tree_util.tree_map(lambda a, n_: a.at[k].set(n_), acc, new)
        return acc, None

    xs = (segment_ids, values) if mask is None else (segment_ids, mask, values)
    acc, _ = jax.lax.scan(step, init, xs)
    return acc


def _materialize_lifted(m: Monoid, values: Pytree, lifted: bool,
                        map_fn: Optional[Callable]) -> Pytree:
    if map_fn is not None:
        return jax.vmap(lambda x: m.lift(map_fn(x)))(values)
    if not lifted:
        return jax.vmap(m.lift)(values)
    return values


def _scan_fold_map(m: Monoid, values: Pytree, map_fn: Callable,
                   axis: int,
                   valid_mask: Optional[jnp.ndarray] = None) -> Pytree:
    """Flat in-mapper fold: lift(map_fn(x)) folded in a lax.scan carry.
    Invalid rows fold the identity (a combine no-op)."""
    def move(x):
        return jnp.moveaxis(x, axis, 0) if axis != 0 else x

    values = jax.tree_util.tree_map(move, values)
    one = _one_slice(values)
    out_shape = jax.eval_shape(lambda x: m.lift(map_fn(x)), one)
    init = m.identity_like(out_shape)

    if valid_mask is None:
        def step(acc, x):
            return m.combine(acc, m.lift(map_fn(x))), None

        acc, _ = jax.lax.scan(step, init, values)
        return acc

    def step_masked(acc, vx):
        valid, x = vx
        v = m.lift(map_fn(x))
        v = jax.tree_util.tree_map(
            lambda l, i: jnp.where(valid, l,
                                   jnp.asarray(i, jnp.asarray(l).dtype)),
            v, init)
        return m.combine(acc, v), None

    acc, _ = jax.lax.scan(step_masked, init,
                          (jnp.asarray(valid_mask, jnp.bool_), values))
    return acc


# ---------------------------------------------------------------------------
# the single entry point
# ---------------------------------------------------------------------------

def execute_fold(m: Monoid, values: Pytree, *, segment_ids=None,
                 num_segments: Optional[int] = None,
                 valid_mask=None,
                 mesh_axes: Optional[Sequence[Any]] = None,
                 layout: str = "auto", lifted: bool = True,
                 map_fn: Optional[Callable] = None,
                 init: Optional[Pytree] = None, axis: int = 0,
                 block_n: int = BLOCK_N, interpret: Optional[bool] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 axis_sizes: Optional[Mapping[Any, int]] = None,
                 lossy=None, ef: Optional[Pytree] = None,
                 calibration: Optional[Calibration] = None,
                 with_plan: bool = False) -> Pytree:
    """Fold monoid values through the planner-chosen tiers.

    values: pytree with leading (or ``axis``) batch dim.  With
    ``segment_ids`` (and ``num_segments``) the fold is keyed — a MapReduce
    'reduce by key' returning a (num_segments, ...) table.  With
    ``mesh_axes`` the local result is additionally combined across the named
    mesh axes (must run inside shard_map), fast ICI axes before the slow DCN
    ``pod`` axis.

    ``valid_mask`` (one bool per record) makes the fold ragged: invalid rows
    contribute the monoid identity on every tier — the kernel and
    segment-ops tiers route them to the out-of-range segment id (dropped by
    the one-hot / the XLA scatter), the generic tiers fold the identity.
    The result equals the fold over only the valid rows.

    layout: 'auto' argmins the calibrated cost model over the feasible
    tiers (see :func:`plan_fold`); 'kernel' / 'segment' / 'scan' / 'tree'
    force a tier.  The plan also carries the shuffle-algorithm choice per
    collective axis, and the keyed mesh combine executes exactly what the
    plan says (reduce-scatter + all-gather or allreduce).  ``map_fn`` maps
    raw inputs (then ``m.lift``) without materializing them on scan tiers —
    the in-mapper combining of Algorithm 4.  ``lifted=False`` applies
    ``m.lift`` to each element first.

    ``lossy=`` (flat additive folds with ``mesh_axes=``) crosses the DCN
    axis compressed, with error feedback: the return value becomes the pair
    ``(folded, new_ef)`` where ``new_ef`` is the residual fold state to pass
    back as ``ef=`` on the next step (``None`` starts from zeros).
    ``layout='async'`` executes the double-buffered microbatch fold of
    :func:`repro.dist.collectives.async_microbatch_fold`; the surrounding
    ``shard_map`` needs ``check_rep=False`` (the scan carry's replication
    defeats the static checker).

    Returns the folded value — or ``(value, plan)`` with ``with_plan=True``.
    """
    plan_mask = valid_mask
    if valid_mask is not None and not isinstance(valid_mask,
                                                 jax.ShapeDtypeStruct):
        # plan from the mask's SHAPE only: counting a concrete device mask
        # would block dispatch just for byte bookkeeping, and tier choice
        # falls back to the conservative all-rows count.  Call plan_fold
        # directly for the counted byte model.
        plan_mask = jax.ShapeDtypeStruct(jnp.shape(valid_mask), jnp.bool_)
    plan = plan_fold(m, values, segment_ids=segment_ids,
                     num_segments=num_segments, valid_mask=plan_mask,
                     mesh_axes=mesh_axes,
                     layout=layout, lifted=lifted, map_fn=map_fn, mesh=mesh,
                     axis_sizes=axis_sizes, block_n=block_n, lossy=lossy,
                     calibration=calibration)
    kind = plan.local_tier.kind
    keyed = segment_ids is not None
    if valid_mask is not None and axis != 0:
        raise ValueError("valid_mask requires the batch axis at 0")

    spec = None
    if lossy is not None:
        from ..optim.compress import LossySpec
        spec = LossySpec.parse(lossy)

    if kind == "async":
        if valid_mask is not None:
            raise ValueError("layout='async' does not support valid_mask; "
                             "mask rows to the identity before the fold")
        if axis != 0:
            raise ValueError("async folds require the batch axis at 0")
        if init is not None:
            raise ValueError("init is only supported for keyed folds")
        from ..dist.collectives import async_microbatch_fold
        if spec is not None and ef is None:
            one = _lifted_value_shape(m, values, lifted, map_fn)
            ef = jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape, jnp.float32), one)
        out, new_ef = async_microbatch_fold(m, values, mesh_axes,
                                            map_fn=map_fn, lifted=lifted,
                                            lossy=spec, ef=ef)
        result = (out, new_ef) if spec is not None else out
        return (result, plan) if with_plan else result

    if keyed:
        if axis != 0:
            raise ValueError("keyed folds require the batch axis at 0")
        if kind == "kernel":
            mat = _materialize_lifted(m, values, lifted, map_fn)
            folded = m.kernel_lowering().fn(mat, segment_ids, num_segments,
                                            block_n=block_n,
                                            valid_mask=valid_mask,
                                            interpret=interpret)
            out = _seg_add_init(m, folded, init)
        elif kind == "segment_ops":
            mat = _materialize_lifted(m, values, lifted, map_fn)
            seg = _mask_segment_ids(segment_ids, valid_mask, num_segments)
            op = _SEGMENT_OPS[m.name]
            folded = jax.tree_util.tree_map(
                lambda v: op(v, seg, num_segments=num_segments), mat)
            out = _seg_add_init(m, folded, init)
        else:
            out = _segment_fold_generic(m, values, segment_ids, num_segments,
                                        init, lifted=lifted, map_fn=map_fn,
                                        valid_mask=valid_mask)
    else:
        if init is not None:
            raise ValueError("init is only supported for keyed folds")
        if kind == "tree":
            mat = _materialize_lifted(m, values, lifted, map_fn)
            if valid_mask is not None:
                mat = _mask_rows_to_identity(m, mat, valid_mask)
            out = tree_fold(m, mat, axis=axis)
        elif map_fn is not None:
            out = _scan_fold_map(m, values, map_fn, axis,
                                 valid_mask=valid_mask)
        else:
            mat = _materialize_lifted(m, values, lifted, map_fn)
            if valid_mask is not None:
                mat = _mask_rows_to_identity(m, mat, valid_mask)
            out = scan_fold(m, mat, axis=axis)

    if mesh_axes:
        from ..dist.collectives import (combine_keyed_table,
                                        cross_axes_allreduce,
                                        lossy_cross_axes,
                                        split_axis_names)
        if spec is not None:
            if ef is None:
                ef = jax.tree_util.tree_map(
                    lambda l: jnp.zeros(jnp.shape(l), jnp.float32), out)
            out, ef = lossy_cross_axes(spec, out, mesh_axes, ef=ef)
        else:
            coll = [t for t in plan.tiers
                    if t.kind in ("allreduce", "reduce_scatter")]
            if keyed and any(t.kind == "reduce_scatter" for t in coll):
                # execute the plan's per-axis shuffle choice: axis order here
                # (ICI then DCN) matches the planner's tier order by
                # construction
                ici, dcn = split_axis_names(mesh_axes)
                for ax, tier in zip(tuple(ici) + tuple(dcn), coll):
                    out = combine_keyed_table(m, out, ax, algorithm=tier.kind)
            else:
                out = cross_axes_allreduce(m, out, mesh_axes)
    if spec is not None:
        if ef is None:   # lossy annotation but no mesh: residual stays zero
            ef = jax.tree_util.tree_map(
                lambda l: jnp.zeros(jnp.shape(l), jnp.float32), out)
        result = (out, ef)
        return (result, plan) if with_plan else result
    return (out, plan) if with_plan else out


# ---------------------------------------------------------------------------
# keyed-fold compatibility wrapper (the pre-planner public API)
# ---------------------------------------------------------------------------

def segment_fold(m: Monoid, values: Pytree, segment_ids: jnp.ndarray,
                 num_segments: int, *, init: Optional[Pytree] = None,
                 impl: str = "auto") -> Pytree:
    """Key-grouped monoid fold: MapReduce 'reduce by key', shapes static.

    Thin wrapper over :func:`execute_fold` kept for callers that predate the
    planner.  impl: 'auto' — segment primitive when the monoid admits one,
    else the generic scan; 'onehot' — the one-hot matmul strategy (additive
    monoids only): the Pallas kernel tier when it applies (TPU backend,
    kernel-compatible dtypes), the historical pure-XLA ``jax.nn.one_hot``
    matmul otherwise; either way results are cast back to each input leaf's
    dtype, the pre-planner onehot contract; 'scan' — force the generic path.
    """
    if impl == "onehot":
        if m.name not in ("sum", "mean", "count"):
            raise ValueError("onehot impl is only meaningful for additive monoids")
        if (jax.default_backend() == "tpu"
                and _kernel_infeasible_reason(m, _one_slice(values),
                                              num_segments, BLOCK_N) is None):
            out = execute_fold(m, values, segment_ids=segment_ids,
                               num_segments=num_segments, init=init,
                               layout="kernel")
            return jax.tree_util.tree_map(
                lambda o, v: o.astype(jnp.asarray(v).dtype), out, values)
        # Pure-XLA one-hot matmul, the pre-planner implementation: off TPU
        # the Pallas kernel only runs in interpret mode, and it also rejects
        # leaves (e.g. bool) the matmul's f32 cast handles fine.  Explicit
        # layout='kernel' through execute_fold stays the always-Pallas path.
        def onehot_sum(v):
            v2 = jnp.asarray(v)
            flat = v2.reshape((v2.shape[0], -1)).astype(jnp.float32)
            oh = jax.nn.one_hot(segment_ids, num_segments,
                                dtype=jnp.float32, axis=0)
            out = oh @ flat  # (S, V) on the MXU
            return out.reshape((num_segments,) + v2.shape[1:]).astype(v2.dtype)
        folded = jax.tree_util.tree_map(onehot_sum, values)
        return _seg_add_init(m, folded, init)
    elif impl == "scan":
        layout = "scan"
    elif impl == "auto":
        layout = "segment" if m.name in _SEGMENT_OPS else "scan"
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return execute_fold(m, values, segment_ids=segment_ids,
                        num_segments=num_segments, init=init, layout=layout)
