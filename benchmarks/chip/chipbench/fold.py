"""Fold cells: the paper's word count over resident splits, back to back.

Set-up draws every chip's mapper splits on the chips in one jitted call
from the seed: ids that follow a Zipf law over the key space, with the
ranks permuted by the seed.  The window then folds one split per chip per
call through ``MapReduceJob.run_sharded`` (local combine on each chip, then
the planner's shuffle), cycling through the splits; a job's count table
accumulates over its ``splits_per_chip`` calls and the next job starts
afresh.  The check compares the tables of finished jobs, drawn from the
seed, and the last partial one, on every chip, with ``np.bincount`` of the
same splits.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from chipbench.device import CompileCounter, memory_peak_bytes
from chipbench.record import Run, Span, WindowTracer
from reference import wordcount
from reference.seeds import seed_key

AXIS = "data"
# finished jobs compared besides the last, partial one
JOBS_COMPARED = 4


def zipf_cdf(num_keys: int, exponent: float) -> np.ndarray:
    w = 1.0 / np.arange(1, num_keys + 1, dtype=np.float64) ** exponent
    return np.cumsum(w / w.sum()).astype(np.float32)


def make_splits(seed: int, config: dict, mesh) -> List[jax.Array]:
    """``splits_per_chip`` arrays of (chips * split_ids,) int32 ids, each
    chip holding its own ``split_ids``, drawn on the chips."""
    K = int(config["num_keys"])
    chips = mesh.shape[AXIS]
    n = int(config["split_ids"]) * chips
    count = int(config["splits_per_chip"])
    cdf = jnp.asarray(zipf_cdf(K, float(config["zipf_exponent"])))
    sharded = NamedSharding(mesh, PartitionSpec(AXIS))

    @functools.partial(jax.jit, out_shardings=(sharded,) * count)
    def draw(key, cdf):
        perm = jax.random.permutation(jax.random.fold_in(key, count), K)
        out = []
        for j in range(count):
            u = jax.random.uniform(jax.random.fold_in(key, j), (n,))
            rank = jnp.minimum(jnp.searchsorted(cdf, u), K - 1)
            out.append(perm[rank].astype(jnp.int32))
        return tuple(out)

    return list(draw(seed_key(seed), cdf))


def fold_programs(config: dict, mesh, *, fault: Optional[str] = None,
                  control: bool = False):
    """(first, next): a job's first fold, and one more fold added to its
    table.  ``fault`` plants a fault under them (tests only); ``control``
    puts the float32 reference in the program's place."""
    from repro.core import word_count_job

    K = int(config["num_keys"])
    job = word_count_job(K)
    strategy = config["strategy"]
    spec = PartitionSpec(AXIS)

    def fold(ids):
        if control:
            def local(r):
                t = jax.ops.segment_sum(jnp.ones(r.shape, jnp.float32), r, K)
                return jax.lax.psum(t, AXIS)
            return jax.shard_map(local, mesh=mesh, in_specs=(spec,),
                                 out_specs=PartitionSpec())(ids)
        if fault == "exchange_left_out":
            return jax.shard_map(
                lambda r: job.run_local(r, strategy=strategy), mesh=mesh,
                in_specs=(spec,), out_specs=PartitionSpec(),
                check_vma=False)(ids)
        if fault == "half_batch":
            chips = mesh.shape[AXIS]
            half = ids.reshape(chips, -1)[:, ::2].reshape(-1)
            return 2 * job.run_sharded(half, mesh, axis_name=AXIS,
                                       strategy=strategy)
        table = job.run_sharded(ids, mesh, axis_name=AXIS, strategy=strategy)
        if fault == "answer_altered":
            table = table.at[0].add(1)
        return table

    def bench_fold_first(ids):
        return fold(ids)

    def bench_fold_next(acc, ids):
        if fault == "state_unchanged":
            return acc
        return acc + fold(ids)

    return (jax.jit(bench_fold_first),
            jax.jit(bench_fold_next, donate_argnums=(0,)))


class FoldCell:
    def __init__(self, config: dict, seed: int, devs, *,
                 fault: Optional[str] = None, control: bool = False):
        from repro.launch.mesh import make_mesh

        self.config, self.seed, self.devs = config, seed, devs
        self.mesh = make_mesh((len(devs),), (AXIS,), devs)
        self.splits = make_splits(seed, config, self.mesh)
        self.first, self.next = fold_programs(config, self.mesh, fault=fault,
                                              control=control)

    def describe(self) -> str:
        from repro.core import word_count_job

        job = word_count_job(int(self.config["num_keys"]))
        plan = job.plan(self.splits[0], strategy=self.config["strategy"],
                        num_shards=len(self.devs), axis_name=AXIS)
        return plan.describe()

    def warm_up(self) -> None:
        acc = self.first(self.splits[0])
        self.next(acc, self.splits[1]).block_until_ready()

    def one_job(self):
        acc = self.first(self.splits[0])
        for s in self.splits[1:]:
            acc = self.next(acc, s)
        return acc

    def run_window(self, seconds: float, tracer: WindowTracer):
        """Fold back to back until ``seconds`` have passed.  Returns
        (finished job tables, (last table, splits in it), calls, elapsed,
        spans)."""
        per_job = len(self.splits)
        clock = time.perf_counter
        finished, spans = [], []
        acc, calls = None, 0
        t0 = clock()
        now = t0
        while now - t0 < seconds:
            tracer.poll(now, t0)
            j = calls % per_job
            s = clock()
            with jax.profiler.TraceAnnotation("bench.fold_step"):
                acc = self.first(self.splits[0]) if j == 0 \
                    else self.next(acc, self.splits[j])
                acc.block_until_ready()
            now = clock()
            spans.append(Span("fold_step", s, now))
            calls += 1
            if j == per_job - 1:
                finished.append(acc)
        tracer.poll(now, t0)
        return finished, (acc, (calls - 1) % per_job + 1), calls, \
            now - t0, spans

    def compare(self, finished, last) -> Dict[str, float]:
        """The largest difference of any count, over the compared tables on
        every chip, from ``np.bincount`` of the same splits."""
        K = int(self.config["num_keys"])
        per_split = [wordcount.counts([np.asarray(s)], K)
                     for s in self.splits]
        whole = np.sum(per_split, axis=0)
        rng = np.random.default_rng([self.seed, 3])
        pick = rng.permutation(len(finished))[:JOBS_COMPARED]
        tables = [(finished[i], whole) for i in sorted(pick)]
        table, n = last
        tables.append((table, np.sum(per_split[:n], axis=0)))
        diff = 0
        for t, want in tables:
            for shard in t.addressable_shards:
                got = np.asarray(shard.data).astype(np.int64)
                diff = max(diff, int(np.abs(got - want).max()))
        return {"count_diff": float(diff), "tables_compared": len(tables)}


def run(cell, seed: int, seconds: float, trace: bool, devs, peaks,
        t_start: float, log) -> dict:
    compiles = CompileCounter()
    fc = FoldCell(cell.config, seed, devs)
    log(f"fold: plan {fc.describe()}")
    fc.warm_up()
    tracer = WindowTracer(trace, seconds)
    setup_s = time.perf_counter() - t_start
    in_setup = compiles.count
    finished, last, calls, elapsed, spans = fc.run_window(seconds, tracer)
    ids = int(cell.config["split_ids"]) * len(devs)
    log(f"fold: {calls} folds of {ids} ids in {elapsed:.6f} s, "
        f"{len(finished)} finished jobs; compiles in set-up {in_setup}, in "
        f"the window {compiles.count - in_setup}")
    trace_obj = tracer.finish(time.perf_counter())
    mem = memory_peak_bytes(devs)
    runrec = Run(config=cell.config, peaks=peaks, chips=len(devs),
                 spans=spans, counters={},
                 trace=trace_obj, traced=tracer.host)
    numbers = fc.compare(finished, last)
    log(f"fold: compared {numbers.pop('tables_compared')} count tables on "
        f"every chip with np.bincount")
    return {"attempted": calls, "failed": 0,
            "e2e": {"records_per_s": calls * ids / elapsed,
                    "setup_s": setup_s},
            "run": runrec, "numbers": numbers, "memory_peak_bytes": mem}
