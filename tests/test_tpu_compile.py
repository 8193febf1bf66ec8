"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing here runs on a chip: each test lowers a jitted program for a
described, unattached ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what interpret mode accepts (blocks not aligned to
the tiling, more fast memory than a kernel may hold, layouts Mosaic cannot
infer).  Every compile must contain the kernel (``tpu_custom_call``).  The
planner asks ``jax.default_backend()``, which is the CPU here, so each test
steers it to ``"tpu"`` to get the plan the chip would run.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import execute_fold, monoids, plan_fold, word_count_job
from repro.kernels import ops
from repro.runtime.engine import decode_metrics_plan, fold_decode_metrics


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("num_slots", [4, 8])
def test_decode_metrics_fold(on_tpu, one_chip, num_slots):
    """The serve step's per-request fold: (slots, 3) rows, masked."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert "pallas segment_fold[sum]" in \
        decode_metrics_plan(num_slots, num_slots).describe()
    _compile(lambda t, r, s, a: fold_decode_metrics(t, r, s, a, num_slots),
             sds((num_slots, 3), jnp.float32), sds((num_slots, 3), jnp.float32),
             sds((num_slots,), jnp.int32), sds((num_slots,), jnp.bool_))


def test_word_count_many_records(on_tpu, one_chip):
    """The combiner's fold of 2**22 records (far more than block_n) into
    4096 keys: the segment ids' block must match XLA's tiling."""
    n, keys = 1 << 22, 4096
    job = word_count_job(keys)
    tokens = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    assert job.plan(tokens, strategy="combiner",
                    num_shards=1).local_tier.kind == "kernel"
    _compile(lambda t: job.run_local(t, strategy="combiner"), tokens)


def test_mean_with_count(on_tpu, one_chip):
    """mean-by-key: the count column rides the sums' kernel call."""
    n, d, keys = 1 << 16, 8, 1024
    _compile(lambda v, s: ops.mean_by_key(v, s, keys),
             jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("monoid", [monoids.max_, monoids.min_],
                         ids=lambda m: m.name)
def test_max_min_fold(on_tpu, one_chip, monoid):
    """The max-plus path: a masked select per eight keys on the VPU."""
    n, d, keys = 1 << 16, 4, 1000
    vals = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    segs = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    assert plan_fold(monoid, vals, segment_ids=segs,
                     num_segments=keys).local_tier.kind == "kernel"
    _compile(lambda v, s: execute_fold(monoid, v, segment_ids=s,
                                       num_segments=keys), vals, segs)
