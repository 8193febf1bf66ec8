"""Multi-device distribution tests, run in subprocesses with 8 fake host
devices (the main test process must keep seeing 1 device)."""
import os
import subprocess
import sys


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_distributed(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 8, jax.devices()
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,)*2)
"""


def test_sharded_mapreduce_strategies():
    run_distributed(PRELUDE + """
from repro.core import average_by_key_job
rng = np.random.default_rng(0)
keys = rng.integers(0, 16, 128); vals = rng.normal(size=128).astype(np.float32)
records = {"key": jnp.asarray(keys), "value": jnp.asarray(vals)}
job = average_by_key_job(16)
oracle = np.array([vals[keys==k].mean() if (keys==k).any() else 0.0 for k in range(16)])
for strat in ("naive", "combiner", "in_mapper"):
    out = np.asarray(job.run_sharded(records, mesh, strategy=strat))
    assert np.allclose(out, oracle, atol=1e-5), (strat, out, oracle)
print("ok")
""")


def test_sharded_mapreduce_executes_plan_shuffle():
    """run_sharded consumes the PLAN's shuffle choice (no selection of its
    own): divisible key counts still lower to reduce-scatter (the pre-plan
    special case, now a cost-model decision), non-divisible to allreduce —
    and both give the oracle answer on a real 8-device mesh."""
    run_distributed(PRELUDE + """
from repro.core import average_by_key_job
rng = np.random.default_rng(4)
for num_keys in (16, 13):    # 16 % 8 == 0 -> reduce_scatter; 13 -> allreduce
    keys = rng.integers(0, num_keys, 128)
    vals = rng.normal(size=128).astype(np.float32)
    records = {"key": jnp.asarray(keys), "value": jnp.asarray(vals)}
    job = average_by_key_job(num_keys)
    plan = job.plan(records, strategy="combiner", num_shards=8,
                    axis_name="data")
    want_algo = "reduce_scatter" if num_keys % 8 == 0 else "allreduce"
    assert plan.shuffle_algorithm == want_algo, (num_keys, plan.describe())
    stats = job.stats(records, strategy="combiner", num_shards=8)
    assert stats.shuffle_algorithm == want_algo
    assert stats.predicted_us > 0
    oracle = np.array([vals[keys==k].mean() if (keys==k).any() else 0.0
                       for k in range(num_keys)])
    for strat in ("combiner", "in_mapper"):
        out = np.asarray(job.run_sharded(records, mesh, strategy=strat))
        assert np.allclose(out, oracle, atol=1e-5), (num_keys, strat)
print("ok")
""")


def test_combine_keyed_table_both_algorithms():
    """combine_keyed_table('reduce_scatter') == combine_keyed_table(
    'allreduce') == the replicated sum, inside a real shard_map."""
    run_distributed(PRELUDE + """
from repro.core import monoids
from repro.dist.collectives import combine_keyed_table
rng = np.random.default_rng(5)
table = jnp.asarray(rng.normal(size=(8, 16, 3)).astype(np.float32))
want = np.asarray(table).sum(0)
spec = jax.sharding.PartitionSpec("data")
for algo in ("allreduce", "reduce_scatter"):
    fn = jax.shard_map(
        lambda t, algo=algo: combine_keyed_table(monoids.sum_, t[0], "data",
                                                 algorithm=algo),
        mesh=mesh, in_specs=(spec,),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False)
    out = np.asarray(fn(table))       # per-device slice (1, 16, 3) -> t[0]
    assert np.allclose(out, want, atol=1e-5), algo
print("ok")
""")


def test_hierarchical_psum_equals_flat():
    run_distributed(PRELUDE + """
from repro.core.aggregation import hierarchical_psum
from functools import partial
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
spec = jax.sharding.PartitionSpec("data")

def flat(v):
    return jax.lax.psum(v, ("data", "model"))

def hier(v):
    return hierarchical_psum(v, ici_axis="model", dcn_axis="data")

f1 = jax.shard_map(flat, mesh=mesh2, in_specs=spec, out_specs=spec, check_vma=False)
f2 = jax.shard_map(hier, mesh=mesh2, in_specs=spec, out_specs=spec, check_vma=False)
np.testing.assert_allclose(np.asarray(f1(x)), np.asarray(f2(x)), rtol=1e-6)
print("ok")
""")


def test_monoid_allreduce_attn_state():
    """Distributed flash-decoding merge == single-device softmax."""
    run_distributed(PRELUDE + """
from repro.core import monoids
from repro.core.aggregation import monoid_allreduce
rng = np.random.default_rng(1)
S, d = 64, 4                      # KV length sharded 8 ways
logits = jnp.asarray(rng.normal(size=(S,)).astype(np.float32))
v = jnp.asarray(rng.normal(size=(S, d)).astype(np.float32))
w = jax.nn.softmax(logits)
want = w @ v

def shard_fn(lg, vv):
    m = jnp.max(lg)
    e = jnp.exp(lg - m)
    state = (m, e.sum(), e @ vv)
    state = monoid_allreduce(monoids.attn_state, state, "data")
    return monoids.attn_state.extract(state)

spec = jax.sharding.PartitionSpec("data")
out = jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec, spec),
                    out_specs=jax.sharding.PartitionSpec(), check_vma=False)(logits, v)
np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
print("ok")
""")


def test_execute_fold_mesh_tier_hierarchical():
    """The planner's collective tier on an 8-device (data x pod) mesh: a
    keyed fold per shard, then ICI-first-then-DCN combine — one entry point,
    same answer as the global fold."""
    run_distributed(PRELUDE + """
from repro.core import execute_fold, monoids
mesh_pod = jax.make_mesh((4, 2), ("data", "pod"),
                         axis_types=(jax.sharding.AxisType.Auto,)*2)
rng = np.random.default_rng(5)
n, keys = 128, 8
vals = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
segs = jnp.asarray(rng.integers(0, keys, n).astype(np.int32))
want = jax.ops.segment_sum(vals, segs, num_segments=keys)

def body(v, k):
    return execute_fold(monoids.sum_, v, segment_ids=k, num_segments=keys,
                        mesh_axes=("pod", "data"))

spec = jax.sharding.PartitionSpec(("data", "pod"))
out = jax.shard_map(body, mesh=mesh_pod, in_specs=(spec, spec),
                    out_specs=jax.sharding.PartitionSpec(),
                    check_vma=False)(vals, segs)
np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                           atol=1e-4)
print("ok")
""")


def test_execute_fold_mesh_tier_ragged_valid_mask():
    """The serving case at mesh scale: a RAGGED keyed fold (per-shard
    valid_mask) through the planner's collective tier == the dense fold
    over only the valid rows — padding never crosses the wire combined in."""
    run_distributed(PRELUDE + """
from repro.core import execute_fold, monoids
mesh_pod = jax.make_mesh((4, 2), ("data", "pod"),
                         axis_types=(jax.sharding.AxisType.Auto,)*2)
rng = np.random.default_rng(11)
n, keys = 128, 8
vals = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
segs = jnp.asarray(rng.integers(0, keys, n).astype(np.int32))
mask = jnp.asarray(rng.random(n) < 0.6)
want = jax.ops.segment_sum(vals[mask], segs[mask], num_segments=keys)

def body(v, k, mk):
    return execute_fold(monoids.sum_, v, segment_ids=k, num_segments=keys,
                        valid_mask=mk, mesh_axes=("pod", "data"))

spec = jax.sharding.PartitionSpec(("data", "pod"))
out = jax.shard_map(body, mesh=mesh_pod, in_specs=(spec, spec, spec),
                    out_specs=jax.sharding.PartitionSpec(),
                    check_vma=False)(vals, segs, mask)
np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                           atol=1e-4)
print("ok")
""")


def test_moe_replicated_matches_local():
    run_distributed(PRELUDE + """
import dataclasses
from repro.configs import get_config
from repro.models import init_params
from repro.models import moe as M
cfg = dataclasses.replace(get_config("qwen2-moe-a2.7b", smoke=True), dtype=jnp.float32)
params, _ = init_params(cfg, jax.random.PRNGKey(0))
ffn = jax.tree_util.tree_map(lambda p: p[0], params["layers"])["slot_0"]["ffn"]
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
ref, stats_ref = M.moe_ffn_local(ffn, cfg, x)
out, stats = M.moe_ffn_replicated(ffn, cfg, x, mesh2, axis_name="model",
                                  batch_axes=("data",))
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
np.testing.assert_array_equal(np.asarray(stats["expert_load"]),
                              np.asarray(stats_ref["expert_load"]))
print("ok")
""")


def test_moe_a2a_matches_local():
    run_distributed(PRELUDE + """
import dataclasses
from repro.configs import get_config
from repro.models import init_params
from repro.models import moe as M
cfg = dataclasses.replace(get_config("qwen2-moe-a2.7b", smoke=True),
                          dtype=jnp.float32, moe_capacity_factor=8.0)
params, _ = init_params(cfg, jax.random.PRNGKey(0))
ffn = jax.tree_util.tree_map(lambda p: p[0], params["layers"])["slot_0"]["ffn"]
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
ref, _ = M.moe_ffn_local(ffn, cfg, x)
out, stats = M.moe_ffn_a2a(ffn, cfg, x, mesh2, axis_name="model",
                            batch_axes=("data",))
assert int(stats["dropped"]) == 0, int(stats["dropped"])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
print("ok")
""")


def test_flash_decode_shardmap_matches_dense():
    run_distributed(PRELUDE + """
import dataclasses
from repro.configs import get_config
from repro.models import init_params, ParamBuilder
from repro.models import attention as A
cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True), dtype=jnp.float32)
pb = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
A.init_attn(pb, cfg)
p = pb.params
B, S = 2, 64
x = jax.random.normal(jax.random.PRNGKey(2), (B, 1, cfg.d_model), jnp.float32)
k = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
v = jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
pos = jnp.int32(40)
want, (k1, v1) = A.attention_decode(p, cfg, x, (k, v), pos)
got, (k2, v2) = A.flash_decode_shardmap(p, cfg, x, (k, v), pos, mesh,
                                        axis_name="data")
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)
np.testing.assert_allclose(np.asarray(k2), np.asarray(k1), rtol=1e-5)
print("ok")
""")


def test_ring_attention_matches_dense():
    """Ring attention (collective_permute hops folding AttnState) == dense
    causal softmax attention, on an 8-device ring."""
    run_distributed(PRELUDE + """
from repro.models.attention import ring_attention_shardmap
from repro.kernels import ref
rng = np.random.default_rng(3)
B, S, H, d = 2, 64, 4, 16
q = jnp.asarray(rng.normal(size=(B, S, H, d)).astype(np.float32))
k = jnp.asarray(rng.normal(size=(B, S, H, d)).astype(np.float32))
v = jnp.asarray(rng.normal(size=(B, S, H, d)).astype(np.float32))
got = ring_attention_shardmap(q, k, v, mesh, axis_name="data")
want = ref.flash_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3))
np.testing.assert_allclose(np.asarray(got), np.asarray(want.transpose(0, 2, 1, 3)),
                           rtol=2e-4, atol=2e-4)
print("ok")
""")


def test_sharding_rules_1_device():
    """trim_rules / spec_for / param_shardings / act on a 1-device mesh (the
    main test process): everything degrades to replication, and act() is a
    no-op outside any use_rules scope."""
    import jax
    from jax.sharding import PartitionSpec as P
    import jax.numpy as jnp
    from repro.dist import sharding as shd

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = shd.trim_rules(shd.TRAIN_RULES, mesh)
    assert rules["batch"] == "data"          # 'pod' dropped: not in this mesh
    assert rules["mlp"] == "model"
    assert rules["embed"] is None

    # divisibility: a dim of 3 can't shard over nothing on 1 device anyway,
    # but the spec machinery must emit clean specs with trailing Nones cut
    assert shd.spec_for(("batch", "seq", "mlp"), rules, mesh,
                        shape=(4, 16, 8)) == P("data", None, "model")
    assert shd.spec_for(("embed",), rules, mesh, shape=(8,)) == P()

    # act() outside use_rules: identity
    x = jnp.ones((2, 3))
    assert shd.act(x, ("batch", "embed")) is x

    # act() inside use_rules: applies a constraint without changing values
    with shd.use_rules(mesh, shd.TRAIN_RULES):
        y = jax.jit(lambda v: shd.act(v, ("batch", "embed")))(x)
    assert jnp.allclose(y, x)

    # param_shardings over a real config's param tree
    from repro.configs import get_config
    from repro.models import param_axes, param_shapes
    cfg = get_config("qwen3-0.6b", smoke=True)
    shard = shd.param_shardings(param_shapes(cfg), param_axes(cfg), mesh, rules)
    leaves = jax.tree_util.tree_leaves(shard)
    assert leaves and all(
        isinstance(s, jax.sharding.NamedSharding) for s in leaves)


def test_sharding_rules_8_devices():
    """Rule tables on a 4x2 mesh: dedupe (expert vs mlp both -> 'model'),
    divisibility fallback, and that act() inside a jitted use_rules scope
    actually shards the output."""
    run_distributed(PRELUDE + """
from jax.sharding import PartitionSpec as P
from repro.dist import sharding as shd

rules = shd.trim_rules(shd.TRAIN_RULES, mesh2)
# first logical dim wins the 'model' axis; the duplicate is dropped
assert shd.spec_for(("expert", "embed", "mlp"), rules, mesh2,
                    shape=(8, 16, 32)) == P("model")
# divisibility: batch=3 not divisible by data=4 -> replicated
assert shd.spec_for(("batch", "seq"), rules, mesh2, shape=(3, 16)) == P()
assert shd.spec_for(("batch", "seq"), rules, mesh2, shape=(8, 16)) == P("data")

x = jnp.zeros((8, 16, 64))
with shd.use_rules(mesh2, shd.TRAIN_RULES):
    y = jax.jit(lambda v: shd.act(v, ("batch", "seq", "mlp")))(x)
spec = y.sharding.spec
assert tuple(spec) in ((("data",), None, ("model",)), ("data", None, "model")), spec

# multi-pod table: batch spans pod x data when the mesh has a pod axis
mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,)*3)
r3 = shd.trim_rules(shd.TRAIN_RULES, mesh3)
assert shd.spec_for(("batch", "seq"), r3, mesh3, shape=(8, 16)) == P(("pod", "data"))
print("ok")
""")


def test_collectives_cross_dcn_once():
    """dist.collectives on a (pod, data, model) mesh: the hierarchical
    monoid reductions equal flat collectives, with the DCN ('pod') axis
    crossed on pre-combined values."""
    run_distributed(PRELUDE + """
from repro.core import monoids
from repro.dist import collectives as col
mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,)*3)
assert col.dcn_axes(mesh3) == ("pod",)
assert col.ici_axes(mesh3) == ("data", "model")

x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
spec = jax.sharding.PartitionSpec("data")

def flat(v):
    return jax.lax.psum(v, ("pod", "data", "model"))

def hier_grad(v):
    return col.grad_sync(v, mesh3)

def hier_metrics(v):
    return col.metrics_sync(v, mesh3)

def hier_max(v):
    return col.cross_mesh_allreduce(monoids.max_, v, mesh3)

kw = dict(mesh=mesh3, in_specs=spec, out_specs=spec, check_vma=False)
want = np.asarray(jax.shard_map(flat, **kw)(x))
for fn in (hier_grad, hier_metrics):
    got = np.asarray(jax.shard_map(fn, **kw)(x))
    np.testing.assert_allclose(got, want, rtol=1e-6)

def flat_max(v):
    return jax.lax.pmax(v, ("pod", "data", "model"))

np.testing.assert_allclose(np.asarray(jax.shard_map(hier_max, **kw)(x)),
                           np.asarray(jax.shard_map(flat_max, **kw)(x)))
print("ok")
""")


def test_train_step_multi_device_matches_single():
    """2-device DP x 2-device TP training step == single-device step."""
    run_distributed(PRELUDE + """
import dataclasses
from repro.configs import get_config, ShapeCell
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import init_opt_state
cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True), dtype=jnp.float32)
shape = ShapeCell("t", "train", 32, 4)
mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,)*2)
toks = jax.random.randint(jax.random.PRNGKey(5), (4, 32), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
outs = {}
for name, m in (("single", mesh1), ("dist", mesh2)):
    built = make_train_step(cfg, m, shape, donate=False)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    params = jax.device_put(params, built.in_shardings[0])
    opt = jax.device_put(init_opt_state(params), built.in_shardings[1])
    _, _, metrics = built.fn(params, opt, batch)
    outs[name] = {k: float(v) for k, v in metrics.items()}
for k in ("loss", "grad_norm"):
    a, b = outs["single"][k], outs["dist"][k]
    assert abs(a - b) / max(abs(a), 1e-6) < 5e-3, (k, a, b)
print("ok", outs["dist"]["loss"])
""")


def test_async_microbatch_fold_equals_sync_dense():
    """The double-buffered async tier re-brackets, never re-weighs: forced
    async == forced sync == auto on a real (2 pod x 4 ici) mesh."""
    run_distributed(PRELUDE + """
from repro.core import execute_fold, monoids
mesh_ov = jax.make_mesh((2, 4), ("pod", "x"),
                        axis_types=(jax.sharding.AxisType.Auto,)*2)
rng = np.random.default_rng(21)
data = jnp.asarray(rng.normal(size=(8, 4, 16)).astype(np.float32))
want = np.asarray(data).sum((0, 1))
spec = jax.sharding.PartitionSpec(("pod", "x"))

def run(layout):
    body = lambda v: execute_fold(monoids.sum_, v[0], mesh_axes=("x", "pod"),
                                  layout=layout)
    return np.asarray(jax.shard_map(
        body, mesh=mesh_ov, in_specs=(spec,),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False)(data))

for layout in ("scan", "async", "auto"):
    np.testing.assert_allclose(run(layout), want, rtol=1e-4, atol=1e-4)
print("ok")
""")


def test_lossy_fold_ef_invariant_at_mesh_scale():
    """Sync and async lossy crossings on the (pod, x) mesh: the folded
    output plus the per-pod error-feedback residuals equals the dense sum —
    compression loses nothing, it only defers."""
    run_distributed(PRELUDE + """
from repro.core import execute_fold, monoids
mesh_ov = jax.make_mesh((2, 4), ("pod", "x"),
                        axis_types=(jax.sharding.AxisType.Auto,)*2)
rng = np.random.default_rng(22)
data = jnp.asarray(rng.normal(size=(8, 4, 16)).astype(np.float32))
want = np.asarray(data).sum((0, 1))
spec = jax.sharding.PartitionSpec(("pod", "x"))

def run(layout, lossy):
    def body(v):
        out, ef = execute_fold(monoids.sum_, v[0], mesh_axes=("x", "pod"),
                               layout=layout, lossy=lossy)
        return out + jax.lax.psum(ef, "pod")
    return np.asarray(jax.shard_map(
        body, mesh=mesh_ov, in_specs=(spec,),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False)(data))

for layout in ("scan", "async"):
    for lossy in ("topk:0.25", "int8"):
        got = run(layout, lossy)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"{layout}/{lossy}")
print("ok")
""")


def test_runs_pinned_to_chosen_devices():
    """make_host_mesh / build_engine / train span exactly the devices they
    are given (every visible device by default): a one-device run on an
    8-device host keeps its parameters on that device."""
    run_distributed(PRELUDE + """
from repro.launch.mesh import make_host_mesh
from repro.launch.train import TrainerConfig, train
from repro.serving import ServeConfig, build_engine
assert make_host_mesh().devices.size == 8
two = make_host_mesh(devices=jax.devices()[2:4])
assert [d.id for d in two.devices.flat] == [2, 3]
dev = jax.devices()[3]
def placed(tree):
    return {d.id for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}
eng = build_engine(ServeConfig(num_slots=2, prefill_buckets=(8,),
                               max_new_tokens=2), devices=[dev])
assert placed(eng.backend.params) == {3}, placed(eng.backend.params)
uid = eng.submit([5, 9, 2])
for _ in eng.run(max_steps=8):
    pass
assert len(eng.result(uid).tokens) == 2
out = train(TrainerConfig(steps=1, global_batch=2, seq_len=16, log_every=1),
            devices=[dev])
assert placed(out["params"]) == {3}, placed(out["params"])
assert out["step_compiles"] == 1
print("ok")
""")
