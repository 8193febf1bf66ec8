"""Planner/oracle equivalence: execute_fold must agree with the generic
monoid folds for every zoo monoid across all tiers, and registered kernel
lowerings must preserve the monoid laws (associativity / identity) — the
invariant that licenses the planner to re-bracket and relocate folds."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _hyp import given, settings, st  # hypothesis, or skip-stub when absent

import repro.optim  # noqa: F401  (registers the lossy compression monoids)
from repro.core import execute_fold, local_fold, monoids, plan_fold
from repro.core.monoid import _KERNEL_LOWERINGS
from repro.core.plan import (_segment_fold_generic, collective_algorithm,
                             segment_fold)

KEYED_LAYOUTS = ("kernel", "segment", "scan")


def _keyed_samples(name, n, d, rng):
    """(monoid, lifted values pytree) for a keyed fold of n records."""
    vals = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    if name == "sum":
        return monoids.sum_, vals
    if name == "max":
        return monoids.max_, vals
    if name == "min":
        return monoids.min_, vals
    if name == "count":
        return monoids.count, jnp.ones((n,), jnp.int32)
    if name == "mean":
        return monoids.mean, (vals, jnp.ones((n,), jnp.int32))
    if name == "bitwise_or":
        bits = jnp.asarray(rng.integers(0, 2, size=(n, d)).astype(np.uint8))
        return monoids.bitwise_or, bits
    raise ValueError(name)


def _assert_tree_close(m, got, want, rtol=1e-4, atol=1e-4):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float64),
                                   np.asarray(w, np.float64),
                                   rtol=rtol, atol=atol, err_msg=m.name)


@settings(max_examples=8, deadline=None)
@given(name=st.sampled_from(["sum", "max", "min", "count", "mean",
                             "bitwise_or"]),
       n=st.integers(5, 120), d=st.integers(1, 9), s=st.integers(2, 10),
       layout=st.sampled_from(KEYED_LAYOUTS))
def test_keyed_tiers_match_generic_oracle(name, n, d, s, layout):
    """Every tier == the generic serial-scan oracle, for every keyed zoo
    monoid (the planner may choose any tier without changing the answer)."""
    rng = np.random.default_rng(n * d + s)
    m, values = _keyed_samples(name, n, d, rng)
    segs = jnp.asarray(rng.integers(0, s, n).astype(np.int32))
    got = execute_fold(m, values, segment_ids=segs, num_segments=s,
                       layout=layout, block_n=64)
    want = _segment_fold_generic(m, values, segs, s)
    _assert_tree_close(m, got, want)


def _ragged_oracle(m, values, segs, s, mask):
    """Fold over ONLY the valid rows (dense oracle for valid_mask)."""
    keep = np.asarray(mask)
    if not keep.any():
        one = jax.tree_util.tree_map(lambda v: v[0], values)
        ident = m.identity_like(one)
        return jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (s,) + l.shape), ident)
    kept = jax.tree_util.tree_map(lambda v: jnp.asarray(np.asarray(v)[keep]),
                                  values)
    return _segment_fold_generic(m, kept, jnp.asarray(np.asarray(segs)[keep]),
                                 s)


@settings(max_examples=8, deadline=None)
@given(name=st.sampled_from(["sum", "max", "min", "count", "mean",
                             "bitwise_or"]),
       n=st.integers(5, 120), d=st.integers(1, 9), s=st.integers(2, 10),
       frac=st.floats(0.0, 1.0),
       layout=st.sampled_from(KEYED_LAYOUTS))
def test_ragged_keyed_fold_matches_dense_over_valid(name, n, d, s, frac,
                                                    layout):
    """The ragged contract on every tier: a keyed fold with valid_mask ==
    the fold over only the valid rows, for the whole keyed zoo — including
    all-False masks (every key holds the identity)."""
    rng = np.random.default_rng(n * d + s + int(frac * 100))
    m, values = _keyed_samples(name, n, d, rng)
    segs = jnp.asarray(rng.integers(0, s, n).astype(np.int32))
    mask = rng.random(n) < frac
    got = execute_fold(m, values, segment_ids=segs, num_segments=s,
                       layout=layout, valid_mask=jnp.asarray(mask),
                       block_n=64)
    want = _ragged_oracle(m, values, segs, s, mask)
    _assert_tree_close(m, got, want)


@pytest.mark.parametrize("layout", KEYED_LAYOUTS)
def test_ragged_keyed_fold_deterministic(layout):
    """Non-hypothesis coverage of the mask path on all tiers (the skip-stub
    container runs this even without hypothesis installed)."""
    rng = np.random.default_rng(9)
    n, d, s = 53, 4, 6
    vals = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, s, n).astype(np.int32))
    for mask in (rng.random(n) < 0.6, np.zeros(n, bool), np.ones(n, bool)):
        got = execute_fold(monoids.sum_, vals, segment_ids=segs,
                           num_segments=s, layout=layout,
                           valid_mask=jnp.asarray(mask), block_n=16)
        want = _ragged_oracle(monoids.sum_, vals, segs, s, mask)
        _assert_tree_close(monoids.sum_, got, want)


def test_ragged_flat_fold_matches_dense_over_valid():
    """valid_mask on FLAT folds: tree/scan tiers and the fused map_fn scan
    all equal the fold over only the valid rows."""
    rng = np.random.default_rng(21)
    n = 19
    vals = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    mask = rng.random(n) < 0.5
    want = np.asarray(vals)[mask].sum(0)
    for layout in ("tree", "scan"):
        got = execute_fold(monoids.sum_, vals, valid_mask=jnp.asarray(mask),
                           layout=layout)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)
    xs = vals[:, 0]
    fused = execute_fold(monoids.mean, xs, map_fn=lambda x: x * 3,
                         valid_mask=jnp.asarray(mask), layout="scan")
    np.testing.assert_allclose(float(monoids.mean.extract(fused)),
                               float(np.asarray(xs)[mask].mean() * 3),
                               rtol=1e-5)


def test_ragged_fold_with_init_and_jit():
    """valid_mask composes with init (the serve loop's running table) and
    with jit (the mask is a tracer — num_valid just falls back to None)."""
    rng = np.random.default_rng(4)
    n, s = 40, 5
    vals = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, s, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.7)
    init = jnp.asarray(rng.normal(size=(s, 2)).astype(np.float32))

    @jax.jit
    def step(t, v, sg, mk):
        return execute_fold(monoids.sum_, v, segment_ids=sg, num_segments=s,
                            valid_mask=mk, init=t)

    got = step(init, vals, segs, mask)
    want = np.asarray(init) + np.asarray(
        _ragged_oracle(monoids.sum_, vals, segs, s, np.asarray(mask)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_plan_byte_model_counts_only_valid_rows():
    """A concrete mask shows up in the plan: num_valid is static, the local
    tier is marked masked, and Algorithm-1 pair bytes count valid rows."""
    rng = np.random.default_rng(6)
    n = 64
    vals = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    mask = np.zeros(n, bool)
    mask[:10] = True
    kw = dict(segment_ids=segs, num_segments=4, mesh_axes=("shard",),
              axis_sizes={"shard": 4})
    p = plan_fold(monoids.sum_, vals, valid_mask=jnp.asarray(mask), **kw)
    assert p.num_valid == 10
    assert "+mask" in p.local_tier.detail
    naive = plan_fold(monoids.sum_, vals, valid_mask=jnp.asarray(mask),
                      pre_combine=False, **kw)
    pair_bytes = 10 * p.value_bytes       # only valid rows become pairs
    assert naive.tiers[0].out_bytes == pair_bytes
    # abstract mask (plan-time ShapeDtypeStruct): count unknown, still masked
    p2 = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=4,
                   valid_mask=jax.ShapeDtypeStruct((n,), jnp.bool_))
    assert p2.num_valid is None and "+mask" in p2.local_tier.detail
    with pytest.raises(ValueError, match="valid_mask"):
        plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=4,
                  valid_mask=jnp.ones((n + 1,), jnp.bool_))


def test_shuffle_stats_count_only_valid_rows():
    """ShuffleStats' byte prediction over a ragged job counts only valid
    records as shuffled pairs (the serve batch's padding is free)."""
    from repro.core import average_by_key_job

    n = 32
    rng = np.random.default_rng(3)
    records = {"key": jnp.asarray(rng.integers(0, 4, n).astype(np.int32)),
               "value": jnp.asarray(rng.normal(size=(n,)).astype(np.float32))}
    mask = np.zeros(n, bool)
    mask[:12] = True
    job = average_by_key_job(num_keys=4)
    dense = job.stats(records, strategy="naive", num_shards=1)
    ragged = job.stats(records, strategy="naive", num_shards=1,
                       valid_mask=jnp.asarray(mask))
    assert dense.shuffle_values == n
    assert ragged.shuffle_values == 12
    assert ragged.shuffle_bytes_mapreduce == 12 * ragged.value_bytes
    assert ragged.num_records == n
    # shape-only planning (abstract mask) keeps the no-FLOPs contract and
    # falls back to counting every row
    abstract = job.stats(records, strategy="naive", num_shards=2,
                         valid_mask=jax.ShapeDtypeStruct((n,), jnp.bool_))
    assert abstract.shuffle_values == n
    p = job.plan(records, strategy="combiner", num_shards=2,
                 valid_mask=jax.ShapeDtypeStruct((n,), jnp.bool_))
    assert p.num_valid is None and "+mask" in p.local_tier.detail


def test_keyed_fold_missing_num_segments_error_is_actionable():
    """The keyed error path names the MISSING kwarg (num_segments), not the
    one that was already passed."""
    vals = jnp.ones((8, 2), jnp.float32)
    segs = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match="num_segments="):
        plan_fold(monoids.sum_, vals, segment_ids=segs)
    with pytest.raises(ValueError, match="num_segments="):
        execute_fold(monoids.sum_, vals, segment_ids=segs)


@pytest.mark.parametrize("layout", ["kernel", "segment"])
def test_unkeyed_kernel_layout_error_is_actionable(layout):
    """layout='kernel'/'segment' without segment_ids must say what to pass
    (segment_ids= AND num_segments=) and name the flat-fold alternatives."""
    vals = jnp.ones((8, 2), jnp.float32)
    for fn in (plan_fold, execute_fold):
        with pytest.raises(ValueError) as ei:
            fn(monoids.sum_, vals, layout=layout)
        msg = str(ei.value)
        assert "segment_ids=" in msg and "num_segments=" in msg
        assert "tree" in msg and "scan" in msg


@pytest.mark.parametrize("layout", ["tree", "scan"])
@pytest.mark.parametrize("name", sorted(monoids.REGISTRY))
def test_flat_tiers_match_local_fold(name, layout):
    """Flat execute_fold == local_fold for EVERY registry monoid (incl. the
    non-commutative and pytree-state ones)."""
    m = monoids.REGISTRY[name]
    rng = np.random.default_rng(hash(name) % 2**32)
    n, d = 9, 4
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    if name in ("sum", "prod", "max", "min"):
        values = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    elif name == "bitwise_or":
        values = jnp.asarray(rng.integers(0, 2, size=(n, d)).astype(np.uint8))
    elif name in ("mean", "count", "welford", "logsumexp"):
        values = jax.vmap(m.lift)(x)
    elif name == "attn_state":
        values = (x, jnp.abs(x) + 0.5,
                  jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)))
    elif name == "affine_scan":
        values = (jnp.asarray(rng.uniform(0.5, 1.0, n).astype(np.float32)), x)
    else:
        # everything else (sketches, top-k, the lossy compression states):
        # stack the monoid's own registered law samples and compare under
        # its own equality — requantizing monoids are only associative up
        # to their approx_equal, not elementwise
        provider = monoids.law_samples_for(name)
        if provider is None:
            pytest.skip(f"no sample builder for {name}")
        samples = provider()
        values = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *samples)
        got = execute_fold(m, values, layout=layout)
        want = local_fold(m, values, strategy="tree")
        assert m.equal(got, want), (name, layout, got, want)
        return
    got = execute_fold(m, values, layout=layout)
    want = local_fold(m, values, strategy="tree")
    _assert_tree_close(m, got, want)


@pytest.mark.parametrize("name", sorted(_KERNEL_LOWERINGS))
def test_kernel_lowering_preserves_associativity_and_identity(name):
    """Law check for every registered lowering: re-bracketing the keyed fold
    across an arbitrary split == one fold (associativity), and keys that
    receive no records hold the monoid identity."""
    rng = np.random.default_rng(7)
    n, d, s = 90, 3, 6
    m, values = _keyed_samples(name, n, d, rng)
    # route every record to keys [1, s-1): key 0 and key s-1 stay empty
    segs = jnp.asarray(rng.integers(1, s - 1, n).astype(np.int32))
    lower = _KERNEL_LOWERINGS[name].fn

    full = lower(values, segs, s, block_n=32)
    cut = 41   # deliberately not a block multiple
    head = jax.tree_util.tree_map(lambda v: v[:cut], values)
    tail = jax.tree_util.tree_map(lambda v: v[cut:], values)
    rebracketed = jax.vmap(m.combine)(lower(head, segs[:cut], s, block_n=32),
                                      lower(tail, segs[cut:], s, block_n=32))
    _assert_tree_close(m, rebracketed, full)

    one = jax.tree_util.tree_map(lambda v: v[0], values)
    identity = m.identity_like(one)
    for empty_key in (0, s - 1):
        got = jax.tree_util.tree_map(lambda v: v[empty_key], full)
        _assert_tree_close(m, got, identity)


def test_integer_monoids_round_trip_dtype():
    """Exact integer monoids keep their dtype through the kernel tier."""
    rng = np.random.default_rng(11)
    segs = jnp.asarray(rng.integers(0, 5, 64).astype(np.int32))

    ivals = jnp.asarray(rng.integers(-100, 100, size=(64, 3)).astype(np.int32))
    got = execute_fold(monoids.sum_, ivals, segment_ids=segs, num_segments=5,
                       layout="kernel", block_n=32)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jax.ops.segment_sum(ivals, segs,
                                                        num_segments=5)))

    imax = execute_fold(monoids.max_, ivals, segment_ids=segs, num_segments=5,
                        layout="kernel", block_n=32)
    assert imax.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(imax), np.asarray(jax.ops.segment_max(ivals, segs,
                                                         num_segments=5)))

    counts = execute_fold(monoids.count, jnp.ones((64,), jnp.int32),
                          segment_ids=segs, num_segments=5, layout="kernel",
                          block_n=32)
    assert counts.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.bincount(np.asarray(segs), minlength=5))


def test_empty_int_max_segment_gets_dtype_identity():
    """An empty segment under integer max == iinfo.min (segment_max's own
    convention), not a leaked -inf cast."""
    vals = jnp.asarray([[3], [7]], jnp.int32)
    segs = jnp.asarray([0, 0], jnp.int32)
    out = execute_fold(monoids.max_, vals, segment_ids=segs, num_segments=3,
                       layout="kernel", block_n=32)
    assert int(out[1, 0]) == jnp.iinfo(jnp.int32).min
    assert int(out[0, 0]) == 7


def test_auto_layout_down_tiers_wide_integers(monkeypatch):
    """layout='auto' must keep the f32-accumulator kernel tier away from
    integer inputs whose worst-case per-key total can exceed 2**24 — for
    UNSIGNED dtypes the bound comes from iinfo.max (iinfo.min is 0)."""
    from repro.core import plan as plan_mod

    monkeypatch.setattr(plan_mod.jax, "default_backend", lambda: "tpu")
    segs = jax.ShapeDtypeStruct((128,), jnp.int32)

    for dt in (jnp.uint32, jnp.uint64, jnp.int32):
        vals = jax.ShapeDtypeStruct((128, 4), dt)
        p = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=8)
        assert p.local_tier.kind == "segment_ops", dt

    # narrow unsigned stays exact for small batches ...
    small = jax.ShapeDtypeStruct((128, 4), jnp.uint8)
    p = plan_fold(monoids.sum_, small, segment_ids=segs, num_segments=8)
    assert p.local_tier.kind == "kernel"
    # ... but not once 255 * N can reach 2**24 (~65.8k records on one key)
    big = jax.ShapeDtypeStruct((70_000, 4), jnp.uint8)
    segs_big = jax.ShapeDtypeStruct((70_000,), jnp.int32)
    p = plan_fold(monoids.sum_, big, segment_ids=segs_big, num_segments=8)
    assert p.local_tier.kind == "segment_ops"


def test_segment_fold_onehot_bool_leaves_any_backend(monkeypatch):
    """The pre-planner onehot contract covers dtypes the Pallas kernel tier
    rejects (bool): the wrapper must fall back to the XLA matmul rather than
    raise, even when the backend reports TPU."""
    from repro.core import plan as plan_mod

    vals = jnp.asarray([[True], [False], [True], [True]])
    segs = jnp.asarray([0, 0, 1, 1], jnp.int32)
    want = np.asarray([[1], [2]])
    for backend in (jax.default_backend(), "tpu"):
        monkeypatch.setattr(plan_mod.jax, "default_backend", lambda b=backend: b)
        got = segment_fold(monoids.sum_, vals, segs, 2, impl="onehot")
        assert got.dtype == jnp.bool_
        np.testing.assert_array_equal(np.asarray(got), want.astype(bool))


def test_segment_fold_onehot_keeps_float_dtype():
    """impl='onehot' keeps the pre-planner contract: results come back in the
    input leaf's dtype (bf16 in, bf16 out), on and off TPU."""
    rng = np.random.default_rng(5)
    vals = jnp.asarray(rng.normal(size=(32, 2)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, 4, 32).astype(np.int32))
    for dt in (jnp.float32, jnp.bfloat16):
        got = segment_fold(monoids.sum_, vals.astype(dt), segs, 4,
                           impl="onehot")
        assert got.dtype == dt
    want = jax.ops.segment_sum(vals, segs, num_segments=4)
    got = segment_fold(monoids.sum_, vals, segs, 4, impl="onehot")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_default_interpret_env_override(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert ops._default_interpret() is False
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    assert ops._default_interpret() is True
    monkeypatch.delenv("REPRO_INTERPRET")
    assert ops._default_interpret() == (jax.default_backend() != "tpu")


def test_default_interpret_refused_on_tpu(monkeypatch):
    """On a TPU backend kernels run compiled: asking for interpret mode is
    an error, never a silent switch to the interpreter."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    assert ops._default_interpret() is False
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="interpret"):
        ops._default_interpret()


@pytest.mark.parametrize("keys,feasible", [(4096, True), (32768, False)])
def test_kernel_tier_fast_memory_bound(monkeypatch, keys, feasible):
    """On TPU the kernel tier is feasible only while its one-hot mask and
    resident output block fit the chip's scoped fast memory: a key space
    that does not fit goes to segment-ops at PLAN time, with the reason in
    describe(), and a forced layout='kernel' raises."""
    from repro.core import plan as plan_mod

    monkeypatch.setattr(plan_mod.jax, "default_backend", lambda: "tpu")
    n = 1 << 22
    vals = jax.ShapeDtypeStruct((n, 1), jnp.float32)
    segs = jax.ShapeDtypeStruct((n,), jnp.int32)
    p = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=keys)
    if feasible:
        assert p.local_tier.kind == "kernel"
        assert p.kernel_infeasible is None
        assert "kernel infeasible" not in p.describe()
        return
    assert p.local_tier.kind == "segment_ops"
    assert "fast memory" in p.kernel_infeasible
    assert f"kernel infeasible: {keys} keys" in p.describe()
    with pytest.raises(ValueError, match="fast memory"):
        plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=keys,
                  layout="kernel")


def test_plan_reports_tiers_and_collective_bytes():
    """plan_fold is a pure cost model: ShapeDtypeStructs in, tier chain and
    predicted wire bytes out — ICI axes reduced before the DCN pod axis."""
    pairs = jax.ShapeDtypeStruct((128, 4), jnp.float32)
    segs = jax.ShapeDtypeStruct((128,), jnp.int32)
    p = plan_fold(monoids.sum_, pairs, segment_ids=segs, num_segments=16,
                  mesh_axes=("pod", "data"),
                  axis_sizes={"data": 8, "pod": 2})
    kinds = [t.kind for t in p.tiers]
    assert kinds[0] in ("kernel", "segment_ops")
    # 16 keys divide both axis sizes: the cost model picks the key-sharded
    # reduce-scatter shuffle on both axes (same wire bytes as the ring,
    # ties prefer distributing the per-key reduce)
    assert kinds[1:] == ["reduce_scatter", "reduce_scatter"]
    assert "ici:data" in p.tiers[1].detail          # fast axis first...
    assert "dcn:pod" in p.tiers[2].detail           # ...slow pod axis last
    table_bytes = 16 * 4 * 4
    assert p.out_bytes == table_bytes
    assert p.tiers[1].wire_bytes == 2 * table_bytes * (8 - 1)   # ring-equal
    assert p.tiers[2].wire_bytes == 2 * table_bytes * (2 - 1)
    assert p.shuffle_algorithm == "reduce_scatter"
    assert p.predicted_us > 0
    # 13 keys don't divide either axis: allreduce is the only candidate
    p13 = plan_fold(monoids.sum_, pairs, segment_ids=segs, num_segments=13,
                    mesh_axes=("pod", "data"),
                    axis_sizes={"data": 8, "pod": 2})
    assert [t.kind for t in p13.tiers][1:] == ["allreduce", "allreduce"]

    # generic monoids can't ring-reduce: the planner predicts gather bytes
    assert collective_algorithm(monoids.sum_) == "ring"
    assert collective_algorithm(monoids.top_k(4)) == "gather"


def test_naive_plan_costs_more_than_combined_plan():
    """Algorithm 1 (pre_combine=False) vs 3/4, straight off the planner."""
    pairs = jax.ShapeDtypeStruct((1024, 1), jnp.float32)
    segs = jax.ShapeDtypeStruct((1024,), jnp.int32)
    kw = dict(segment_ids=segs, num_segments=8, mesh_axes=("shard",),
              axis_sizes={"shard": 8})
    naive = plan_fold(monoids.sum_, pairs, pre_combine=False, **kw)
    combined = plan_fold(monoids.sum_, pairs, **kw)
    assert naive.tiers[0].kind == "gather_pairs"
    assert naive.collective_wire_bytes > combined.collective_wire_bytes


def test_segment_fold_wrapper_back_compat():
    """The pre-planner keyed-fold API still dispatches correctly."""
    rng = np.random.default_rng(2)
    vals = jnp.asarray(rng.normal(size=(40, 2)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, 4, 40).astype(np.int32))
    want = jax.ops.segment_sum(vals, segs, num_segments=4)
    for impl in ("auto", "onehot", "scan"):
        got = segment_fold(monoids.sum_, vals, segs, 4, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError):
        segment_fold(monoids.max_, vals, segs, 4, impl="onehot")


def test_execute_fold_keyed_init():
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.normal(size=(30, 2)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, 4, 30).astype(np.int32))
    init = jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32))
    for layout in KEYED_LAYOUTS:
        got = execute_fold(monoids.sum_, vals, segment_ids=segs,
                           num_segments=4, layout=layout, init=init,
                           block_n=32)
        want = init + jax.ops.segment_sum(vals, segs, num_segments=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_in_mapper_map_fn_fuses_lift():
    """map_fn + scan layout == materialize-then-fold (Alg 4 == Alg 3)."""
    xs = jnp.arange(24, dtype=jnp.float32)
    fused = execute_fold(monoids.mean, xs, map_fn=lambda x: x * 2 + 1,
                         layout="scan")
    materialized = execute_fold(
        monoids.mean, jax.vmap(lambda x: monoids.mean.lift(x * 2 + 1))(xs),
        layout="tree")
    np.testing.assert_allclose(float(monoids.mean.extract(fused)),
                               float(monoids.mean.extract(materialized)),
                               rtol=1e-6)
    np.testing.assert_allclose(float(monoids.mean.extract(fused)),
                               float(jnp.mean(xs * 2 + 1)), rtol=1e-6)


def test_mesh_tier_single_device():
    """The collective tier runs inside shard_map (1-device smoke; the
    8-device path is exercised in test_distributed.py)."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    vals = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)

    def body(v):
        return execute_fold(monoids.sum_, v, mesh_axes=("data",))

    out = jax.shard_map(body, mesh=mesh,
                        in_specs=jax.sharding.PartitionSpec("data"),
                        out_specs=jax.sharding.PartitionSpec(),
                        check_vma=False)(vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(vals.sum(0)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# the calibrated cost model: auto == argmin, shuffle choice, forced errors
# ---------------------------------------------------------------------------

def _winner_calibration(winner_layout):
    """A synthetic table making exactly one layout's tier cheap."""
    from repro.core.calibration import CALIB_VERSION, Calibration, TierCoeff
    from repro.core.plan import _LAYOUT_TIER_KIND

    cheap = TierCoeff(t0_us=0.01, us_per_byte=1e-9, us_per_record=1e-9)
    dear = TierCoeff(t0_us=1e4, us_per_byte=1.0, us_per_record=1.0)
    win = _LAYOUT_TIER_KIND[winner_layout]
    return Calibration(
        version=CALIB_VERSION, backend="test", source="measured",
        tiers={kind: {"*": cheap if kind == win else dear}
               for kind in ("kernel", "segment_ops", "scan", "tree")},
        collectives={"ici": TierCoeff(1.0, 1e-5),
                     "dcn": TierCoeff(10.0, 1e-3)})


@settings(max_examples=24, deadline=None)
@given(name=st.sampled_from(["sum", "max", "min", "count", "mean",
                             "bitwise_or"]),
       winner=st.sampled_from(KEYED_LAYOUTS),
       on_tpu=st.booleans())
def test_auto_is_argmin_of_predicted_cost(name, winner, on_tpu):
    """layout='auto' == argmin over the plan's own candidate_us table for
    every keyed zoo monoid, under ANY injected calibration — backend/dtype
    checks only filter feasibility, the cost model decides the winner."""
    from unittest import mock

    from repro.core import plan as plan_mod
    from repro.core.plan import _LAYOUT_TIER_KIND

    rng = np.random.default_rng(7)
    m, values = _keyed_samples(name, 32, 3, rng)
    segs = jnp.asarray(rng.integers(0, 4, 32).astype(np.int32))
    calib = _winner_calibration(winner)
    backend = "tpu" if on_tpu else "cpu"
    with mock.patch.object(plan_mod.jax, "default_backend",
                           return_value=backend):
        p = plan_fold(m, values, segment_ids=segs, num_segments=4,
                      calibration=calib)
    cand = p.candidate_us
    assert cand, "auto plans must report their candidate table"
    best = min(cand, key=cand.get)
    assert p.local_tier.kind == _LAYOUT_TIER_KIND[best]
    assert p.local_tier.predicted_us == pytest.approx(cand[best])
    # kernel may only ever appear as a candidate on the TPU backend
    if not on_tpu:
        assert "kernel" not in cand


def test_auto_follows_injected_calibration_not_heuristics(monkeypatch):
    """Flip the table and the choice flips: scan-cheap beats segment-ops
    even for a monoid with a native segment primitive."""
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
    segs = jnp.asarray(rng.integers(0, 8, 64).astype(np.int32))
    p_scan = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=8,
                       calibration=_winner_calibration("scan"))
    assert p_scan.local_tier.kind == "scan"
    p_seg = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=8,
                      calibration=_winner_calibration("segment"))
    assert p_seg.local_tier.kind == "segment_ops"


def test_shuffle_choice_reduce_scatter_when_keys_divide():
    """Divisible key count -> the cost model picks the key-sharded
    reduce-scatter shuffle (ties break toward it; for gather-fallback
    monoids it is strictly cheaper). Non-divisible -> allreduce only."""
    vals = jax.ShapeDtypeStruct((128, 4), jnp.float32)
    segs = jax.ShapeDtypeStruct((128,), jnp.int32)
    kw = dict(mesh_axes=("data",), axis_sizes={"data": 8})
    p = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=16, **kw)
    assert p.shuffle_algorithm == "reduce_scatter"
    assert set(p.shuffle_candidate_us) == {"reduce_scatter", "allreduce"}
    p13 = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=13,
                    **kw)
    assert p13.shuffle_algorithm == "allreduce"
    assert set(p13.shuffle_candidate_us) == {"allreduce"}
    # generic (gather-allreduce) monoid: reduce_scatter is strictly cheaper
    lifted = jax.ShapeDtypeStruct((128, 4), jnp.float32)
    topk = plan_fold(monoids.top_k(4), lifted, segment_ids=segs,
                     num_segments=16, layout="scan", **kw)
    assert collective_algorithm(monoids.top_k(4)) == "gather"
    assert topk.shuffle_algorithm == "reduce_scatter"
    c = topk.shuffle_candidate_us
    assert c["reduce_scatter"] < c["allreduce"]


def test_shuffle_trivial_or_unknown_axis_is_allreduce():
    vals = jax.ShapeDtypeStruct((32, 2), jnp.float32)
    segs = jax.ShapeDtypeStruct((32,), jnp.int32)
    p1 = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=8,
                   mesh_axes=("data",), axis_sizes={"data": 1})
    assert p1.shuffle_algorithm == "allreduce"
    assert p1.tiers[1].wire_bytes == 0
    p_unknown = plan_fold(monoids.sum_, vals, segment_ids=segs,
                          num_segments=8, mesh_axes=("data",))
    assert p_unknown.shuffle_algorithm == "allreduce"
    assert "size unknown" in p_unknown.tiers[1].detail


def test_forced_infeasible_layout_errors_name_the_leaf(monkeypatch):
    """A forced layout the inputs cannot take fails at PLAN time with the
    offending leaf dtype in the message, not deep inside lowering."""
    segs = jnp.zeros((8,), jnp.int32)
    # kernel on a complex leaf: the error names the dtype and suggests a way out
    with pytest.raises(ValueError, match="complex64"):
        plan_fold(monoids.sum_, jnp.ones((8,), jnp.complex64),
                  segment_ids=segs, num_segments=2, layout="kernel")
    with pytest.raises(ValueError, match="layout='kernel'"):
        plan_fold(monoids.sum_, jnp.ones((8,), jnp.complex64),
                  segment_ids=segs, num_segments=2, layout="kernel")
    # kernel on a monoid with no registered lowering
    with pytest.raises(ValueError, match="no registered Pallas kernel"):
        plan_fold(monoids.top_k(4), jnp.ones((8, 4), jnp.float32),
                  segment_ids=segs, num_segments=2, layout="kernel")
    # segment on a monoid with no XLA segment primitive
    with pytest.raises(ValueError, match="no XLA segment primitive"):
        plan_fold(monoids.top_k(4), jnp.ones((8, 4), jnp.float32),
                  segment_ids=segs, num_segments=2, layout="segment")
    # a pytree leaf path is named when the offender is nested
    with pytest.raises(ValueError, match="count"):
        plan_fold(monoids.product(s=monoids.sum_, count=monoids.sum_),
                  {"s": jnp.ones((8,), jnp.float32),
                   "count": jnp.ones((8,), jnp.complex64)},
                  segment_ids=segs, num_segments=2, layout="kernel")


def test_describe_prints_predicted_microseconds():
    vals = jnp.ones((64, 4), jnp.float32)
    segs = jnp.zeros((64,), jnp.int32)
    p = plan_fold(monoids.sum_, vals, segment_ids=segs, num_segments=16,
                  mesh_axes=("data", "pod"),
                  axis_sizes={"data": 8, "pod": 2})
    desc = p.describe()
    assert "us]" in desc
    assert p.predicted_us == pytest.approx(
        sum(t.predicted_us for t in p.tiers))


@pytest.mark.parametrize("name", ["sum", "max", "min", "count", "mean",
                                  "bitwise_or"])
@pytest.mark.parametrize("winner", KEYED_LAYOUTS)
def test_auto_argmin_deterministic_zoo(name, winner, monkeypatch):
    """Non-hypothesis coverage of the argmin contract across the whole keyed
    zoo x every winner table x both backends (runs even without hypothesis
    installed)."""
    from repro.core import plan as plan_mod
    from repro.core.plan import _LAYOUT_TIER_KIND

    rng = np.random.default_rng(11)
    m, values = _keyed_samples(name, 32, 3, rng)
    segs = jnp.asarray(rng.integers(0, 4, 32).astype(np.int32))
    calib = _winner_calibration(winner)
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(plan_mod.jax, "default_backend",
                            lambda b=backend: b)
        p = plan_fold(m, values, segment_ids=segs, num_segments=4,
                      calibration=calib)
        cand = p.candidate_us
        best = min(cand, key=cand.get)
        assert p.local_tier.kind == _LAYOUT_TIER_KIND[best], (
            name, winner, backend, cand)
        if backend == "cpu":
            assert "kernel" not in cand


# ---------------------------------------------------------------------------
# the async tier and the lossy annotation (planning; execution at mesh scale
# lives in test_distributed.py)
# ---------------------------------------------------------------------------

_ASYNC_SIZES = {"x": 4, "pod": 2}


def _flat_mb_shape(n_mb=4, d=256):
    return jax.ShapeDtypeStruct((n_mb, d), jnp.float32)


def test_forced_async_plan_shape():
    p = plan_fold(monoids.sum_, _flat_mb_shape(), mesh_axes=("x", "pod"),
                  layout="async", axis_sizes=_ASYNC_SIZES)
    assert p.local_tier.kind == "async"
    assert len(p.tiers) == 1                 # the whole plan IS the pipeline
    assert p.overlap_modeled > 0.0
    assert dict(p.plan_candidate_us).keys() == {"sync", "async"}
    assert "overlap modeled" in p.describe()


def test_auto_declines_async_for_pure_grad_fold():
    """Per-microbatch crossings replicate the summed bytes n times and the
    epilogue crossing can never hide — so for a pure grad fold the honest
    model keeps choosing sync, with the async price on the record."""
    p = plan_fold(monoids.sum_, _flat_mb_shape(), mesh_axes=("x", "pod"),
                  layout="auto", axis_sizes=_ASYNC_SIZES)
    assert p.local_tier.kind != "async"
    cand = dict(p.plan_candidate_us)
    assert cand["sync"] <= cand["async"]


def test_async_layout_errors_are_actionable():
    vals = jnp.zeros((4, 8), jnp.float32)
    with pytest.raises(ValueError, match="mesh_axes"):
        plan_fold(monoids.sum_, vals, layout="async")
    with pytest.raises(ValueError, match="keyed"):
        plan_fold(monoids.sum_, vals, layout="async",
                  mesh_axes=("x",), axis_sizes={"x": 4},
                  segment_ids=jnp.zeros((4,), jnp.int32), num_segments=2)


def test_lossy_plan_prices_compressed_crossing():
    dense = plan_fold(monoids.sum_, _flat_mb_shape(), mesh_axes=("x", "pod"),
                      layout="scan", axis_sizes=_ASYNC_SIZES)
    lossy = plan_fold(monoids.sum_, _flat_mb_shape(), mesh_axes=("x", "pod"),
                      layout="scan", axis_sizes=_ASYNC_SIZES, lossy="topk:0.01")
    assert lossy.lossy == "topk:0.01"
    assert 0 < lossy.lossy_wire_bytes < lossy.dense_wire_bytes
    assert lossy.dense_wire_bytes == dense.dense_wire_bytes
    assert dense.lossy_wire_bytes == dense.dense_wire_bytes   # dense == dense
    assert "lossy" in lossy.describe()
    # only the DCN tier moves compressed bytes; the ICI combine stays dense
    dcn = [t for t in lossy.tiers if t.kind == "allreduce" and
           t.detail.startswith("dcn:")]
    assert dcn and "lossy" in dcn[0].detail


def test_lossy_annotation_errors_are_actionable():
    vals = jnp.zeros((4, 8), jnp.float32)
    with pytest.raises(ValueError, match="keyed"):
        plan_fold(monoids.sum_, vals, mesh_axes=("x",), axis_sizes={"x": 4},
                  segment_ids=jnp.zeros((4,), jnp.int32), num_segments=2,
                  lossy="topk:0.01")
    with pytest.raises(ValueError, match="additive"):
        plan_fold(monoids.max_, vals, mesh_axes=("x",), axis_sizes={"x": 4},
                  lossy="topk:0.01")


def test_async_and_lossy_execute_single_device():
    """1-device smoke of both execution paths (the real 8-device equality
    checks live in test_distributed.py): the async pipeline and the lossy
    sync crossing both run inside shard_map and return the exact / the
    EF-consistent sum."""
    mesh = jax.make_mesh((1, 1), ("x", "pod"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    vals = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    want = np.asarray(vals.sum(0))
    spec = jax.sharding.PartitionSpec(("x", "pod"))

    def run(fn):
        return jax.shard_map(
            lambda v: fn(v), mesh=mesh, in_specs=spec,
            out_specs=jax.sharding.PartitionSpec(), check_vma=False)(vals)

    out = run(lambda v: execute_fold(monoids.sum_, v,
                                     mesh_axes=("x", "pod"), layout="async"))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)

    def lossy_body(v):
        out, ef = execute_fold(monoids.sum_, v, mesh_axes=("x", "pod"),
                               layout="scan", lossy="topk:0.5")
        return out + ef          # EF invariant: applied + residual == truth

    np.testing.assert_allclose(np.asarray(run(lossy_body)), want, rtol=1e-5)
