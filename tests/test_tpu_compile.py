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


@pytest.mark.parametrize("arch", [
    "qwen3-0.6b",          # dense GQA decoder
    "deepseek-v2-236b",    # MLA latent cache, MoE layers, a dense prelude
    "gemma3-1b",           # sliding-window local layers
    "jamba-v0.1-52b",      # mamba state beside attention and MoE
])
def test_decode_step_writes_the_donated_stack_in_place(on_tpu, one_chip,
                                                       arch):
    """The serving step, its cache donated, updates the stacked cache where
    it lies: its temporaries stay under one layer's cache, and no copy
    makes a buffer of a stacked leaf's shape (a second stack).

    Smoke depth and widths, but the cache rows at their published widths
    (a 128-wide head, MLA's 512 + 64 latent), so the compiler tiles them as
    it tiles the served caches, and two periods, so the stack has layers
    to slice.  MLA's 64-wide rope rows are the exception: the chip lays a
    leaf that narrow out sequence-minor, and the row scatter relays that
    stack out once into the step and once out of it."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import decode_step, init_cache, init_params

    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(
        cfg, num_layers=cfg.prelude_dense_layers + 2 * cfg.period,
        **(dict(kv_lora_rank=512, qk_rope_dim=64,
                head_dim=cfg.qk_nope_dim + 64) if cfg.use_mla
           else dict(head_dim=128)))
    batch, seq = 32, 2048
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))[0])
    cache = jax.eval_shape(lambda p: init_cache(p, cfg, batch, seq,
                                                pos_per_slot=True), params)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def step(p, c, t):
        logits, c = decode_step(p, cfg, c, t)
        return jnp.argmax(logits[:, -1], -1), c

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    ).compile()
    stack = jax.tree_util.tree_leaves_with_path(cache["layers"])
    n = stack[0][1].shape[0]
    layer_bytes = sum(a.size * a.dtype.itemsize for _, a in stack) // n
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    text = compiled.as_text()
    for path, a in stack:
        if path[-1].key == "lat_r":
            continue
        dims = ",".join(map(str, a.shape))
        copy = re.compile(r"= \w+\[" + dims + r"\]\{[^}]*\} copy\(")
        assert not copy.search(text), (arch, jax.tree_util.keystr(path))
