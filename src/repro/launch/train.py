"""End-to-end training driver: data -> train_step -> metrics -> checkpoint,
with preemption handling and monoid-merged restart.

This is the runnable (CPU-scale) counterpart of the dry-run: the same
make_train_step powers both; here it executes on the host mesh with a smoke
or custom config.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from ..configs import ShapeCell, context_spec, get_config
from ..core import monoids
from ..checkpoint import CheckpointStore
from ..data import DataConfig, SyntheticCorpus
from ..data import init_stats, make_stream_stats, update_stats
from ..models import RunCtx, init_params
from ..optim import OptConfig, init_opt_state
from ..runtime import PreemptionHandler
from .compile_cache import use_compile_cache
from .mesh import make_host_mesh
from .steps import make_train_step


@dataclasses.dataclass
class TrainerConfig:
    arch: str = "qwen3-0.6b"
    smoke: bool = True
    steps: int = 20
    global_batch: int = 8
    seq_len: int = 128
    seed: int = 0
    microbatches: int = 1
    lossy: Optional[str] = None  # grad-compression annotation ("topk:0.01",
                                 #   "blocktopk:0.001", "int8"); EF residual
                                 #   rides opt_state["ef"]
    ragged: bool = False   # corpus emits valid_mask; stats fold only real tokens
    moe_impl: str = "replicated"
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 10
    model_parallel: int = 1
    log_every: int = 5
    opt: OptConfig = dataclasses.field(default_factory=lambda: OptConfig(
        peak_lr=1e-3, warmup_steps=10, decay_steps=1000))


def train(tc: TrainerConfig, *, preemption: Optional[PreemptionHandler] = None,
          devices: Optional[Sequence[jax.Device]] = None) -> Dict[str, Any]:
    """Run ``tc.steps`` train steps on a (data, model) mesh over ``devices``
    (every visible device when None)."""
    cfg = get_config(tc.arch, smoke=tc.smoke)
    mesh = make_host_mesh(model=tc.model_parallel, devices=devices)
    shape = ShapeCell("custom", "train", tc.seq_len, tc.global_batch)
    ctx = RunCtx(mesh=mesh, moe_impl=tc.moe_impl)
    built = make_train_step(cfg, mesh, shape, opt=tc.opt, ctx=ctx,
                            num_microbatches=tc.microbatches, lossy=tc.lossy,
                            donate=True)

    # init (or restore) state, sharded per the step's in_shardings
    key = jax.random.PRNGKey(tc.seed)
    params, _ = init_params(cfg, key)
    params = jax.device_put(params, built.in_shardings[0])
    opt_state = jax.device_put(init_opt_state(params, with_ef=tc.lossy is not None),
                               built.in_shardings[1])

    # data: host-sharded synthetic corpus (+ stub modality context)
    ctx_spec = context_spec(cfg, tc.global_batch)
    corpus = SyntheticCorpus(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=tc.seq_len,
                   global_batch=tc.global_batch, seed=tc.seed,
                   ragged=tc.ragged),
        context_shape=None if ctx_spec is None else ctx_spec.shape[1:])

    # metrics stream: Sum-monoid accumulator across steps (in-mapper
    # combining), checkpointed and monoid-merged on restart.
    msum = monoids.sum_
    metrics_acc = None
    stats_monoid = make_stream_stats()
    stream_stats = init_stats(stats_monoid)

    store = CheckpointStore(tc.ckpt_dir) if tc.ckpt_dir else None
    start_step = 0
    if store is not None and store.latest_step() is not None:
        start_step, (params, opt_state) = store.restore(
            (params, opt_state),
            shardings=(built.in_shardings[0], built.in_shardings[1]))
        restored = store.restore_aggregate("metrics", like=_metrics_like(built))
        if restored is not None:
            metrics_acc = restored
        restored_ss = store.restore_aggregate("stream_stats", like=stream_stats)
        if restored_ss is not None:
            stream_stats = restored_ss
        print(f"restored checkpoint at step {start_step}")

    history = []
    t_last = time.time()
    for step in range(start_step, tc.steps):
        batch = corpus(step)
        # ragged corpora carry a valid_mask: the jitted step's in_shardings
        # cover the model inputs only, and the stream stats fold it through
        # the planner's mask path (padding tokens count nothing)
        mask = batch.pop("valid_mask", None)
        params, opt_state, metrics = built.fn(params, opt_state, batch)
        stream_stats = update_stats(stream_stats, batch["tokens"],
                                    valid_mask=mask)
        metrics_acc = metrics if metrics_acc is None else \
            msum.combine(metrics_acc, metrics)
        if (step + 1) % tc.log_every == 0 or step + 1 == tc.steps:
            m = {k: float(v) for k, v in metrics.items()}
            dt = time.time() - t_last
            t_last = time.time()
            print(f"step {step+1:5d} loss={m['loss']:.4f} "
                  f"grad_norm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                  f"({dt:.2f}s)", flush=True)
            history.append({"step": step + 1, **m})
        stop = preemption is not None and preemption.should_stop
        if store is not None and ((step + 1) % tc.ckpt_every == 0 or stop
                                  or step + 1 == tc.steps):
            store.save_async(step + 1, (params, opt_state), aggregates={
                "metrics": ("sum", metrics_acc),
                "stream_stats": (stats_monoid.name, stream_stats),
            })
        if stop:
            print(f"preempted at step {step+1}: checkpoint saved, exiting")
            break
    if store is not None:
        store.wait()
    return {"history": history, "metrics_acc": metrics_acc,
            "stream_stats": stream_stats, "params": params,
            "steps_done": step + 1 if tc.steps > start_step else start_step,
            "step_compiles": int(built.fn._cache_size())}


def _metrics_like(built) -> Dict[str, jnp.ndarray]:
    mshapes = jax.eval_shape(lambda a, b, c: built.fn(a, b, c),
                             *built.abstract_args)[2]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), mshapes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lossy", default=None,
                    help="gradient compression annotation: topk:R | "
                         "blocktopk:R | int8 (error feedback in opt state)")
    ap.add_argument("--ragged", action="store_true",
                    help="ragged corpus: whole docs + valid_mask, masked stats")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()
    tc = TrainerConfig(arch=args.arch, smoke=not args.full, steps=args.steps,
                       global_batch=args.batch, seq_len=args.seq,
                       microbatches=args.microbatches, lossy=args.lossy,
                       ragged=args.ragged,
                       model_parallel=args.model_parallel,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    handler = PreemptionHandler()
    out = train(tc, preemption=handler)
    print(f"done: {out['steps_done']} steps")


if __name__ == "__main__":
    main()
