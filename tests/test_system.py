"""End-to-end behaviour: training converges, restart is exact, streaming ==
batch (the Summingbird property, paper §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import monoids, tree_fold
from repro.launch.train import TrainerConfig, train
from repro.runtime import PreemptionHandler


@pytest.fixture(scope="module")
def short_run(tmp_path_factory):
    tc = TrainerConfig(arch="qwen3-0.6b", steps=16, global_batch=4,
                       seq_len=64, ckpt_dir=str(tmp_path_factory.mktemp("ck")),
                       ckpt_every=8, log_every=4)
    return tc, train(tc)


def test_training_reduces_loss(short_run):
    _, out = short_run
    hist = out["history"]
    assert hist[-1]["loss"] < hist[0]["loss"]
    assert np.isfinite(hist[-1]["loss"])


def test_metrics_accumulator_is_sum_of_steps(short_run):
    tc, out = short_run
    acc = out["metrics_acc"]
    # the last position of every sequence has label -1 (masked)
    assert float(acc["tokens"]) == tc.steps * tc.global_batch * (tc.seq_len - 1)


def test_restart_continues_exactly(short_run, tmp_path):
    """Run 16 steps; separately run 8, 'crash', restore, run 8 more: the
    final params agree (same data by stateless pipeline, same state by
    checkpoint, same aggregate by monoid merge)."""
    tc_full, out_full = short_run
    tc = TrainerConfig(**{**tc_full.__dict__, "ckpt_dir": str(tmp_path),
                          "steps": 8})
    train(tc)                                     # first half, checkpoints at 8
    tc2 = TrainerConfig(**{**tc_full.__dict__, "ckpt_dir": str(tmp_path),
                           "steps": 16})
    out2 = train(tc2)                             # restores at 8, runs 8 more
    for a, b in zip(jax.tree_util.tree_leaves(out_full["params"]),
                    jax.tree_util.tree_leaves(out2["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(float(out_full["metrics_acc"]["tokens"]),
                               float(out2["metrics_acc"]["tokens"]))


def test_preemption_checkpoints_and_stops(tmp_path):
    tc = TrainerConfig(arch="qwen3-0.6b", steps=50, global_batch=4,
                       seq_len=64, ckpt_dir=str(tmp_path), ckpt_every=1000)
    h = PreemptionHandler(signals=())
    h.trigger()
    out = train(tc, preemption=h)
    assert out["steps_done"] < 50
    from repro.checkpoint import CheckpointStore
    assert CheckpointStore(str(tmp_path)).latest_step() == out["steps_done"]


def test_streaming_equals_batch_summingbird():
    """Paper §4: the same monoid gives identical answers via a streaming
    fold (one value at a time) and a batch tree-reduction."""
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    m = monoids.welford
    lifted = jax.vmap(m.lift)(xs)
    stream = m.identity_like(jax.tree_util.tree_map(lambda l: l[0], lifted))
    for i in range(64):
        stream = m.combine(stream, jax.tree_util.tree_map(lambda l: l[i], lifted))
    batch = tree_fold(m, lifted)
    s, b = m.extract(stream), m.extract(batch)
    np.testing.assert_allclose(float(s["mean"]), float(b["mean"]), rtol=1e-5)
    np.testing.assert_allclose(float(s["var"]), float(b["var"]), rtol=1e-4)
    np.testing.assert_allclose(float(s["mean"]), xs.mean(), rtol=1e-5)


def test_microbatched_train_step_matches_full():
    """Grad accumulation (in-mapper combining) == one big batch."""
    import dataclasses
    from repro.configs import get_config, ShapeCell
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_train_step
    from repro.models import init_params
    from repro.optim import init_opt_state
    cfg = dataclasses.replace(get_config("qwen3-0.6b", smoke=True),
                              dtype=jnp.float32)
    mesh = make_host_mesh()
    shape = ShapeCell("t", "train", 32, 4)
    toks = jax.random.randint(jax.random.PRNGKey(5), (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    res = {}
    for name, mb in (("full", 1), ("micro", 4)):
        built = make_train_step(cfg, mesh, shape, num_microbatches=mb,
                                donate=False)
        params, _ = init_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        _, _, metrics = built.fn(params, opt, batch)
        res[name] = {k: float(v) for k, v in metrics.items()}
    assert abs(res["full"]["loss"] - res["micro"]["loss"]) < 5e-3
    assert res["full"]["tokens"] == res["micro"]["tokens"]


def test_train_step_compiles_once(short_run):
    _, out = short_run
    assert out["step_compiles"] == 1


def test_compile_cache_in_checkout_by_default(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the entry points keep the cache at
    a fixed, git-ignored path in the checkout."""
    import pathlib

    from repro.launch.compile_cache import use_compile_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_env_wins(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, nothing is set in code and JAX
    writes its cache there."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(root, "src"))
    code = """
import os, jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
assert use_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert any(name.startswith("jit__lambda") for name in os.listdir(tmp_path))
