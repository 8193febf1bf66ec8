"""The paper's MapReduce algorithms (1-5), executable on a TPU mesh.

A :class:`MapReduceJob` is the paper's program model made static-shaped for
XLA:

    mapper  : record -> (key, raw_value)      key in [0, num_keys)
    monoid  : lift/combine/identity/extract over the intermediate value
    reducer : the monoid combine + extract (never user-written — that is
              exactly the paper's point)

Three executable strategies mirror the paper's algorithms:

* ``naive``     — Algorithm 1: mappers emit every lifted pair; ALL pairs cross
                  the wire; reducers fold.
* ``combiner``  — Algorithm 3: lifted pairs are materialized on-device, a
                  combiner segment-folds them into a dense per-key table
                  before the shuffle; only ``num_keys`` values cross the wire.
* ``in_mapper`` — Algorithm 4: the per-key table is the scan carry; lifted
                  pairs are never materialized (O(num_keys) live values).

Algorithm 2 (the combiner that changes the value type) is rejected by
:func:`validate_combiner` — the machine-checked MapReduce contract.

Hardware adaptation (DESIGN.md §2): Hadoop's disk shuffle becomes an
``all_to_all``/``psum_scatter`` key re-partition; Hadoop's dynamic keys become
a static key space (hash-bucketed when open — the paper's own sketches are the
unbounded-key answer). Byte accounting reports both the MapReduce-equivalent
shuffle bytes (pairs x bytes, the paper's cost model) and the XLA-actual
collective bytes on this mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .monoid import Monoid, MonoidTypeError, Pytree, tree_fold
from .plan import Plan, _static_valid_count, execute_fold, plan_fold

STRATEGIES = ("naive", "combiner", "in_mapper")


@dataclasses.dataclass(frozen=True)
class ShuffleStats:
    """The paper's efficiency story in numbers (per strategy, whole job).

    intermediate_values: monoid values materialized map-side (Alg 1/3: one per
      record; Alg 4: only the table).
    shuffle_values: monoid values that cross the wire (the sort/shuffle cost).
    shuffle_bytes_mapreduce: shuffle_values x bytes(value) — the paper's model.
    shuffle_bytes_xla: bytes the XLA collective actually moves on this mesh
      (ring reduce-scatter for the dense table; all_gather for naive pairs).
    shuffle_algorithm: the planner's cost-model shuffle choice
      ('reduce_scatter' | 'allreduce'; '' when the job has no mesh combine).
    predicted_us: the plan's modeled wall time (local tier + collectives)
      under the active calibration.
    measured_us: an observed wall time set by the caller via
      :meth:`with_measured`, so modeled-vs-measured rides one record.
    overlap_modeled: the plan's modeled hidden fraction of DCN crossing time
      (0 for sync plans: nothing is pipelined, nothing can hide).
    overlap_measured: the observed hidden fraction, set via
      :meth:`with_measured` — a healthy step keeps it near the model; a
      straggling host shows up here as collapsing overlap before it shows
      up as a timeout (see runtime/fault_tolerance.py).
    dense_wire_bytes / lossy_wire_bytes: per-device DCN bytes of the dense
      sync crossing vs what the plan actually moves (equal unless ``lossy``).
    lossy: the compression annotation (``LossySpec.describe()``; '' = dense).
    """

    strategy: str
    num_records: int
    num_keys: int
    value_bytes: int
    intermediate_values: int
    shuffle_values: int
    shuffle_bytes_mapreduce: int
    shuffle_bytes_xla: int
    plan: str = ""               # the planner's tier chain (plan.describe())
    shuffle_algorithm: str = ""
    predicted_us: float = 0.0
    measured_us: Optional[float] = None
    overlap_modeled: float = 0.0
    overlap_measured: Optional[float] = None
    dense_wire_bytes: int = 0
    lossy_wire_bytes: int = 0
    lossy: str = ""

    def reduction_vs_naive(self) -> float:
        naive = self.num_records * self.value_bytes
        return naive / max(self.shuffle_bytes_mapreduce, 1)

    def with_measured(self, us: float, *,
                      overlap: Optional[float] = None) -> "ShuffleStats":
        """Attach an observed wall time (microseconds) — and optionally the
        observed hidden-overlap fraction — to compare against the model;
        benchmarks report the model error from this."""
        return dataclasses.replace(
            self, measured_us=float(us),
            overlap_measured=(self.overlap_measured if overlap is None
                              else float(overlap)))

    def model_error(self) -> Optional[float]:
        """measured/predicted ratio (None until both sides exist)."""
        if self.measured_us is None or self.predicted_us <= 0:
            return None
        return self.measured_us / self.predicted_us

    def compression_ratio(self) -> float:
        """dense/actual DCN bytes (1.0 when the crossing is dense)."""
        if self.lossy_wire_bytes <= 0:
            return 1.0
        return self.dense_wire_bytes / self.lossy_wire_bytes

    def overlap_collapse(self) -> Optional[float]:
        """modeled − measured overlap fraction: how much of the promised
        hiding did NOT happen (None until a measurement is attached; only
        meaningful for async plans, where overlap_modeled > 0)."""
        if self.overlap_measured is None:
            return None
        return self.overlap_modeled - self.overlap_measured


def fold_stats(plan: Plan, *, strategy: str = "fold") -> ShuffleStats:
    """:class:`ShuffleStats` for a planner-lowered FLAT fold (a gradient
    fold, a metrics fold) — every figure read off the :class:`Plan`,
    including the overlap and compression annotations.  This is the
    per-step record the serving/training loops hand to
    ``runtime.fault_tolerance`` and the benchmarks emit."""
    crossings = (plan.num_records if plan.local_tier.kind == "async" else 1)
    return ShuffleStats(
        strategy=strategy, num_records=plan.num_records,
        num_keys=plan.num_segments or 0, value_bytes=plan.value_bytes,
        intermediate_values=plan.num_records, shuffle_values=crossings,
        shuffle_bytes_mapreduce=crossings * plan.value_bytes,
        shuffle_bytes_xla=plan.collective_wire_bytes,
        plan=plan.describe(), shuffle_algorithm=plan.shuffle_algorithm or "",
        predicted_us=plan.predicted_us,
        overlap_modeled=plan.overlap_modeled,
        dense_wire_bytes=plan.dense_wire_bytes,
        lossy_wire_bytes=plan.lossy_wire_bytes,
        lossy=plan.lossy or "")


def validate_combiner(monoid: Monoid, example_value: Pytree,
                      combiner_fn: Optional[Callable[[Pytree, Pytree], Pytree]] = None) -> None:
    """The MapReduce combiner contract: combine must map M x M -> M.

    The paper's Algorithm 2 fails this check (its combiner turns an ``int``
    into a ``(sum, count)`` pair). We verify with ``eval_shape`` so no FLOPs
    are spent; raises :class:`MonoidTypeError` on violation.
    """
    fn = combiner_fn if combiner_fn is not None else monoid.combine
    out = jax.eval_shape(fn, example_value, example_value)
    s_in = jax.tree_util.tree_structure(example_value)
    s_out = jax.tree_util.tree_structure(out)
    if s_in != s_out:
        raise MonoidTypeError(
            f"combiner output structure {s_out} != input value structure {s_in}: "
            "a combiner may run zero, one, or many times, so its output type "
            "must equal its input type (paper, Algorithm 2)."
        )
    for li, lo in zip(jax.tree_util.tree_leaves(example_value), jax.tree_util.tree_leaves(out)):
        if jnp.shape(li) != lo.shape or jnp.result_type(li) != lo.dtype:
            raise MonoidTypeError(
                f"combiner changed leaf {jnp.shape(li)}/{jnp.result_type(li)} -> "
                f"{lo.shape}/{lo.dtype} (paper, Algorithm 2)."
            )


@dataclasses.dataclass(frozen=True)
class MapReduceJob:
    """A static-shaped MapReduce job over a fixed key space.

    mapper: record -> (key, raw_value); vmapped over the record axis.
    monoid: the intermediate-value monoid (lift applied to raw mapper output).
    num_keys: size of the key space (hash-bucket open key spaces).
    """

    mapper: Callable[[Pytree], Tuple[jnp.ndarray, Pytree]]
    monoid: Monoid
    num_keys: int

    # -- map side -------------------------------------------------------------
    def _map_records(self, records: Pytree) -> Tuple[jnp.ndarray, Pytree]:
        keys, raws = jax.vmap(self.mapper)(records)
        return keys.astype(jnp.int32), raws

    def _local_table_combiner(self, records: Pytree) -> Pytree:
        """Algorithm 3: materialize lifted pairs, then combiner-fold by key.

        The planner picks the tier (Pallas kernel / segment-ops / scan)."""
        keys, raws = self._map_records(records)
        return execute_fold(self.monoid, raws, segment_ids=keys,
                            num_segments=self.num_keys, lifted=False)

    def _local_table_in_mapper(self, records: Pytree) -> Pytree:
        """Algorithm 4: fold each record straight into the per-key table —
        the planner's scan tier with the lift fused into the scan step, so
        lifted pairs are never materialized."""
        keys, raws = self._map_records(records)
        return execute_fold(self.monoid, raws, segment_ids=keys,
                            num_segments=self.num_keys, layout="scan",
                            lifted=False)

    def _fold_pairs_into_table(self, keys: jnp.ndarray, lifted: Pytree) -> Pytree:
        return execute_fold(self.monoid, lifted, segment_ids=keys,
                            num_segments=self.num_keys)

    # -- single-host reference execution ---------------------------------------
    def run_local(self, records: Pytree, *, strategy: str = "in_mapper",
                  num_shards: int = 1, extract: bool = True) -> Pytree:
        """Reference execution with ``num_shards`` simulated mappers.

        Identical numerics to :meth:`run_sharded`; used by tests/benchmarks on
        one device. Records' leading axis must divide by num_shards.
        """
        if strategy not in STRATEGIES:
            raise ValueError(f"strategy must be one of {STRATEGIES}")
        n = jax.tree_util.tree_leaves(records)[0].shape[0]
        assert n % num_shards == 0, (n, num_shards)
        sharded = jax.tree_util.tree_map(
            lambda x: x.reshape((num_shards, n // num_shards) + x.shape[1:]), records)

        if strategy == "naive":
            # every lifted pair survives to the "reduce" side
            keys, raws = jax.vmap(self._map_records)(sharded)
            lifted = jax.vmap(jax.vmap(self.monoid.lift))(raws)
            flat_keys = keys.reshape((n,))
            flat = jax.tree_util.tree_map(
                lambda x: x.reshape((n,) + x.shape[2:]), lifted)
            table = self._fold_pairs_into_table(flat_keys, flat)
        else:
            local = self._local_table_combiner if strategy == "combiner" \
                else self._local_table_in_mapper
            tables = jax.vmap(local)(sharded)              # (shards, K, ...)
            table = tree_fold(self.monoid, tables, axis=0)
        return self._finish(table, extract)

    # -- mesh execution ---------------------------------------------------------
    def run_sharded(self, records: Pytree, mesh: jax.sharding.Mesh, *,
                    axis_name: str = "data", strategy: str = "in_mapper",
                    extract: bool = True) -> Pytree:
        """shard_map execution: local phase on each device, monoid shuffle.

        records: globally-batched pytree, leading axis divisible by the axis
        size; each device runs the map+combine phase on its shard, then the
        dense key table is combined across devices with whatever shuffle the
        PLAN chose (``Plan.shuffle_algorithm`` — reduce-scatter + all-gather
        when the cost model prefers it, allreduce otherwise; this method
        makes no selection of its own):

          naive     -> all pairs cross the wire (all_gather), receivers fold
          combiner / in_mapper -> the plan's shuffle of the dense table

        The result is the full (num_keys, ...) extracted table, replicated.
        """
        from ..dist.collectives import combine_keyed_table

        if strategy not in STRATEGIES:
            raise ValueError(f"strategy must be one of {STRATEGIES}")
        P = mesh.shape[axis_name]
        spec = jax.sharding.PartitionSpec(axis_name)
        nospec = jax.sharding.PartitionSpec()
        plan = self.plan(records, strategy=strategy, num_shards=P,
                         axis_name=axis_name)
        shuffle = plan.shuffle_algorithm or "allreduce"

        def shard_body(recs):
            if strategy == "naive":
                keys, raws = self._map_records(recs)
                lifted = jax.vmap(self.monoid.lift)(raws)
                all_keys = jax.lax.all_gather(keys, axis_name, axis=0, tiled=True)
                all_vals = jax.tree_util.tree_map(
                    lambda v: jax.lax.all_gather(v, axis_name, axis=0, tiled=True),
                    lifted)
                table = self._fold_pairs_into_table(all_keys, all_vals)
            else:
                local = self._local_table_combiner if strategy == "combiner" \
                    else self._local_table_in_mapper
                table = combine_keyed_table(self.monoid, local(recs),
                                            axis_name, algorithm=shuffle)
            return table

        in_specs = (jax.tree_util.tree_map(lambda _: spec, records),)
        fn = jax.shard_map(shard_body, mesh=mesh,
                           in_specs=in_specs, out_specs=nospec,
                           check_vma=False)
        table = fn(records)
        return self._finish(table, extract)

    def _finish(self, table: Pytree, extract: bool) -> Pytree:
        if not extract:
            return table
        return jax.vmap(self.monoid.extract)(table)

    # -- accounting --------------------------------------------------------------
    def plan(self, records: Pytree, *, strategy: str,
             num_shards: int, valid_mask=None,
             axis_name: str = "shard") -> Plan:
        """The execution plan for this job's per-shard fold + shuffle.

        The plan is built from ShapeDtypeStructs (no FLOPs): one shard's
        raw mapper values (lifted inside the fold, as the combiner and
        in-mapper strategies run them), keyed by ``num_keys``, combined
        across the
        ``axis_name`` mesh axis of size ``num_shards`` (pass the real axis
        name so :meth:`run_sharded` executes exactly this plan).
        strategy='naive' models Algorithm 1 (``pre_combine=False``: raw
        pairs cross the wire un-combined); 'combiner'/'in_mapper' differ
        only in the local tier.

        ``valid_mask`` (one bool per record, whole job) marks padding rows
        that never become pairs; the per-shard plan uses shard 0's slice as
        representative for the masked byte model.
        """
        if strategy not in STRATEGIES:
            raise ValueError(f"strategy must be one of {STRATEGIES}")
        n = jax.tree_util.tree_leaves(records)[0].shape[0]
        local_n = max(1, n // num_shards)
        _, raw_shape = jax.eval_shape(
            lambda r: self.mapper(jax.tree_util.tree_map(lambda x: x[0], r)),
            records)
        pairs = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((local_n,) + s.shape, s.dtype),
            raw_shape)
        seg = jax.ShapeDtypeStruct((local_n,), jnp.int32)
        shard_mask = None
        if valid_mask is not None:
            if isinstance(valid_mask, jax.ShapeDtypeStruct):
                # shape-only planning: the mask stays abstract per shard
                shard_mask = jax.ShapeDtypeStruct((local_n,), jnp.bool_)
            else:
                shard_mask = jnp.asarray(valid_mask, jnp.bool_)[:local_n]
        return plan_fold(
            self.monoid, pairs, segment_ids=seg, num_segments=self.num_keys,
            valid_mask=shard_mask,
            mesh_axes=(axis_name,), axis_sizes={axis_name: num_shards},
            layout="scan" if strategy == "in_mapper" else "auto",
            lifted=False, pre_combine=strategy != "naive")

    def stats(self, records: Pytree, *, strategy: str, num_shards: int,
              valid_mask=None) -> ShuffleStats:
        """The paper's cost model for this job on ``num_shards`` mappers —
        every byte figure is read off the execution plan.  With a ragged
        ``valid_mask`` only valid rows become pairs, so only they are
        counted as intermediate/shuffled values."""
        n = jax.tree_util.tree_leaves(records)[0].shape[0]
        plan = self.plan(records, strategy=strategy, num_shards=num_shards,
                         valid_mask=valid_mask)
        n_valid = _static_valid_count(valid_mask)
        if n_valid is None:       # no mask, or abstract: count every row
            n_valid = n
        vbytes = plan.value_bytes
        table_values = self.num_keys * num_shards

        if strategy == "naive":
            inter, shuffled = n_valid, n_valid
        elif strategy == "combiner":
            inter, shuffled = n_valid + table_values, table_values
        else:  # in_mapper: only the table is ever live
            inter, shuffled = table_values, table_values
        return ShuffleStats(
            strategy=strategy, num_records=n, num_keys=self.num_keys,
            value_bytes=vbytes, intermediate_values=inter,
            shuffle_values=shuffled,
            shuffle_bytes_mapreduce=shuffled * vbytes,
            shuffle_bytes_xla=plan.collective_wire_bytes,
            plan=plan.describe(),
            shuffle_algorithm=plan.shuffle_algorithm or "",
            predicted_us=plan.predicted_us,
        )


# ---------------------------------------------------------------------------
# The paper's running example: average of values by key (Algorithms 1/3/4)
# ---------------------------------------------------------------------------

def average_by_key_job(num_keys: int) -> MapReduceJob:
    """Mean-by-key: the paper's running example with the (sum, count) monoid."""
    from . import monoids

    def mapper(record):
        return record["key"], record["value"]

    return MapReduceJob(mapper=mapper, monoid=monoids.mean, num_keys=num_keys)


def algorithm2_combiner(t_and_r, _ignored):
    """The paper's ILLEGAL Algorithm 2 combiner: int values -> (sum, count).

    Provided so the test/benchmark can show the engine rejecting it.
    """
    return (t_and_r, jnp.ones((), jnp.int32))


def word_count_job(vocab: int) -> MapReduceJob:
    """The canonical MapReduce hello-world as a monoid job: the count
    monoid lifts every token to 1, so the planner knows each key's total is
    bounded by the token count (exact on the kernel tier below 2**24)."""
    from . import monoids

    def mapper(token):
        return token, token

    return MapReduceJob(mapper=mapper, monoid=monoids.count, num_keys=vocab)


def cooccurrence_stripes_job(vocab: int, window: int) -> MapReduceJob:
    """Algorithm 5 (stripes): records are token windows; key = center word,
    value = the stripe (dense count vector over the vocab)."""
    from . import monoids

    def mapper(win):
        center = window  # records are (2*window+1,) token windows
        w = win[center]
        neigh_idx = jnp.concatenate([jnp.arange(window), jnp.arange(window + 1, 2 * window + 1)])
        stripe = jnp.zeros((vocab,), jnp.int32).at[win[neigh_idx]].add(1)
        return w, stripe

    return MapReduceJob(mapper=mapper, monoid=monoids.stripes, num_keys=vocab)
