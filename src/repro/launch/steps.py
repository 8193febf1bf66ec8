"""Step builders: jit-compiled train / prefill / decode steps with explicit
in/out shardings for a production mesh.

Everything here is dry-run-compatible: abstract params (ShapeDtypeStructs)
flow through the same code paths as real arrays, so ``.lower().compile()``
exercises exactly the program that would run on hardware.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import monoids
from ..core.plan import execute_fold
from ..models import (ModelConfig, RunCtx, decode_step, forward, init_cache,
                      loss_fn, param_axes, param_shapes, unembed)
from ..optim import OptConfig, adamw_update, opt_state_shapes
from ..dist import sharding as shd
from ..configs import ShapeCell, context_spec, input_specs

Pytree = Any


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

trim_rules = shd.trim_rules  # canonical definition lives in dist.sharding


def batch_sharding(mesh: Mesh, rules, dim0: Optional[int] = None) -> NamedSharding:
    """Batch-dim sharding, dropping axes that don't divide dim0 (e.g. B=1)."""
    ax = rules.get("batch")
    axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
    if dim0 is not None:
        kept, total = [], 1
        for a in axes:
            if dim0 % (total * mesh.shape[a]) == 0:
                kept.append(a)
                total *= mesh.shape[a]
        axes = tuple(kept)
    spec = P(axes if len(axes) > 1 else (axes[0] if axes else None))
    return NamedSharding(mesh, spec)


def data_shardings(cfg: ModelConfig, mesh: Mesh, rules, specs: Dict) -> Dict:
    """Shardings for the data inputs (tokens/labels/context): batch-sharded."""
    return {k: batch_sharding(mesh, rules, v.shape[0]) for k, v in specs.items()}


_CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "xk": ("batch", None, "kv_heads", None),
    "xv": ("batch", None, "kv_heads", None),
    "lat_c": ("batch", "kv_seq", None),
    "lat_r": ("batch", "kv_seq", None),
    "ssm_h": ("batch", "mlp", None),
    "ssm_conv": ("batch", None, "mlp"),
    "ml_C": ("batch", "heads", None, None),
    "ml_n": ("batch", "heads", None),
    "sl_h": ("batch", "heads", None),
    "sl_c": ("batch", "heads", None),
    "pos": (),
    "route": ("batch", None),
}


def cache_shardings(cache_shapes: Pytree, mesh: Mesh, rules) -> Pytree:
    """NamedSharding tree for a decode cache, by leaf name (see _CACHE_AXES).

    Leaves under the stacked 'layers' subtree get a leading None (period dim).
    """
    def one(path, leaf):
        name = None
        stacked = False
        for entry in path:
            key = getattr(entry, "key", None)
            if key == "layers":
                stacked = True
            if isinstance(key, str):
                name = key
        names = _CACHE_AXES.get(name, ())
        names = ((None,) if stacked else ()) + tuple(names)
        spec = shd.spec_for(names, rules, mesh, shape=leaf.shape)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def tree_of(val, tree: Pytree) -> Pytree:
    return jax.tree_util.tree_map(lambda _: val, tree)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BuiltStep:
    """A jit'd step + everything needed to call or dry-run it."""
    fn: Any                      # the jit-wrapped callable
    abstract_args: Tuple         # ShapeDtypeStruct pytrees for .lower()
    in_shardings: Tuple
    out_shardings: Any
    mesh: Mesh
    rules: Dict[str, Any]


def make_train_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeCell, *,
                    opt: OptConfig = OptConfig(),
                    ctx: Optional[RunCtx] = None,
                    num_microbatches: int = 1,
                    lossy: Optional[str] = None,
                    rules: Optional[Dict[str, Any]] = None,
                    donate: bool = True) -> BuiltStep:
    """Build the jit'd train step for (arch x train shape) on a mesh.

    num_microbatches > 1 folds gradients over microbatches with a lax.scan
    carry — the paper's in-mapper combining (Algorithm 4) applied to the
    gradient Sum monoid; nothing per-microbatch is materialized.

    ``lossy`` (e.g. ``"topk:0.01"`` / ``"blocktopk:0.001"`` / ``"int8"``)
    annotates the gradient fold with the compressor a cross-pod (DCN) wire
    would apply (optim/compress.py): the update consumes the compressed
    round-trip of the folded gradients, and the error-feedback residual is
    carried as ``opt_state["ef"]`` — resumable fold state that checkpoints
    with the optimizer, so the applied-update sum converges to the true
    gradient sum across steps.  Under this jit step the numerics are
    identical to what the planner's lossy DCN crossing applies under
    shard_map; the wire-byte savings themselves are the planner's story
    (core/plan.py, benchmarks/bench_overlap.py).
    """
    rules = trim_rules(rules or shd.TRAIN_RULES, mesh)
    ctx = ctx or RunCtx(mesh=mesh)
    if ctx.mesh is None:
        ctx = dataclasses.replace(ctx, mesh=mesh)
    spec = None
    if lossy is not None:
        from ..optim.compress import LossySpec
        spec = LossySpec.parse(lossy)

    def train_step(params, opt_state, batch):
        with shd.use_rules(mesh, rules):
            def one_loss(p, b):
                return loss_fn(p, cfg, b, ctx)

            if num_microbatches > 1:
                def reshape_mb(x):
                    B = x.shape[0]
                    mb = B // num_microbatches
                    return x.reshape((num_microbatches, mb) + x.shape[1:])

                mbatch = jax.tree_util.tree_map(reshape_mb, batch)
                grad_fn = jax.value_and_grad(one_loss, has_aux=True)

                def one_grad(mb):
                    (_, metrics), grads = grad_fn(params, mb)
                    return grads, metrics

                # in-mapper combining over microbatches: the planner's scan
                # tier folds the gradient Sum monoid without materializing
                # per-microbatch grads (paper, Algorithm 4)
                grads, metrics = execute_fold(monoids.sum_, mbatch,
                                              map_fn=one_grad, layout="scan")
                gscale = 1.0 / num_microbatches
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    one_loss, has_aux=True)(params, batch)
                gscale = 1.0
            if spec is not None:
                comp, new_ef = spec.compress(grads, opt_state["ef"])
                grads = spec.decompress(comp, grads)
                opt_state = {k: v for k, v in opt_state.items() if k != "ef"}
            new_params, new_opt, om = adamw_update(grads, opt_state, opt,
                                                   grad_scale=gscale)
            if spec is not None:
                new_opt["ef"] = new_ef
            metrics = dict(metrics)
            metrics.update(om)
            metrics["loss"] = metrics["loss_sum"] / jnp.maximum(metrics["tokens"], 1.0)
        return new_params, new_opt, metrics

    pshapes = param_shapes(cfg)
    paxes = param_axes(cfg)
    pshard = shd.param_shardings(pshapes, paxes, mesh, rules)
    oshapes = opt_state_shapes(pshapes, with_ef=spec is not None)
    oshard = {"step": replicated(mesh),
              "m": pshard, "v": pshard,
              "master": pshard}
    if spec is not None:
        oshard["ef"] = pshard
    specs = input_specs(cfg, shape)
    bshard = data_shardings(cfg, mesh, rules, specs)
    mshapes = jax.eval_shape(train_step, pshapes, oshapes, specs)[2]
    out_shardings = (pshard, oshard, tree_of(replicated(mesh), mshapes))
    fn = jax.jit(train_step,
                 in_shardings=(pshard, oshard, bshard),
                 out_shardings=out_shardings,
                 donate_argnums=(0, 1) if donate else ())
    return BuiltStep(fn=fn, abstract_args=(pshapes, oshapes, specs),
                     in_shardings=(pshard, oshard, bshard),
                     out_shardings=out_shardings, mesh=mesh, rules=rules)


# ---------------------------------------------------------------------------
# prefill / decode steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeCell, *,
                      ctx: Optional[RunCtx] = None,
                      rules: Optional[Dict[str, Any]] = None) -> BuiltStep:
    """Inference prefill: full-sequence forward + last-token logits."""
    rules = trim_rules(rules or shd.SERVE_RULES, mesh)
    ctx = ctx or RunCtx(mesh=mesh)
    if ctx.mesh is None:
        ctx = dataclasses.replace(ctx, mesh=mesh)

    def prefill(params, batch):
        with shd.use_rules(mesh, rules):
            h, _ = forward(params, cfg, batch["tokens"],
                           context=batch.get("context"), ctx=ctx)
            logits = unembed(params, cfg, h[:, -1:])
        return logits

    pshapes = param_shapes(cfg)
    pshard = shd.param_shardings(pshapes, param_axes(cfg), mesh, rules)
    specs = input_specs(cfg, shape)
    bshard = data_shardings(cfg, mesh, rules, specs)
    oshard = batch_sharding(mesh, rules, shape.global_batch)
    fn = jax.jit(prefill, in_shardings=(pshard, bshard),
                 out_shardings=oshard)
    return BuiltStep(fn=fn, abstract_args=(pshapes, specs),
                     in_shardings=(pshard, bshard),
                     out_shardings=oshard,
                     mesh=mesh, rules=rules)


def make_decode_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeCell, *,
                     ctx: Optional[RunCtx] = None,
                     rules: Optional[Dict[str, Any]] = None,
                     donate: bool = True) -> BuiltStep:
    """One-token decode against a seq_len KV cache (the serve_step).

    For long_500k cells ``ctx.decode_impl='flash'`` runs the sequence-sharded
    flash-decode path (AttnState monoid over the 'model' axis).
    """
    rules = trim_rules(rules or shd.SERVE_RULES, mesh)
    ctx = ctx or RunCtx(mesh=mesh)
    if ctx.mesh is None:
        ctx = dataclasses.replace(ctx, mesh=mesh)

    def serve_step(params, cache, tokens):
        with shd.use_rules(mesh, rules):
            logits, new_cache = decode_step(params, cfg, cache, tokens, ctx=ctx)
        return logits, new_cache

    pshapes = param_shapes(cfg)
    pshard = shd.param_shardings(pshapes, param_axes(cfg), mesh, rules)
    B, S = shape.global_batch, shape.seq_len
    ctx_in = context_spec(cfg, B)
    cache_shapes = jax.eval_shape(
        partial(init_cache, cfg=cfg, batch=B, max_seq=S, ctx=ctx),
        pshapes, context=ctx_in)
    cshard = cache_shardings(cache_shapes, mesh, rules)
    tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tshard = batch_sharding(mesh, rules, B)
    out_shardings = (tshard, cshard)
    fn = jax.jit(serve_step,
                 in_shardings=(pshard, cshard, tshard),
                 out_shardings=out_shardings,
                 donate_argnums=(1,) if donate else ())
    return BuiltStep(fn=fn, abstract_args=(pshapes, cache_shapes, tok_spec),
                     in_shardings=(pshard, cshard, tshard),
                     out_shardings=out_shardings, mesh=mesh, rules=rules)


def build_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeCell, **kw) -> BuiltStep:
    """Dispatch on the cell kind."""
    if shape.kind == "train":
        return make_train_step(cfg, mesh, shape, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, mesh, shape, **kw)
    if shape.kind == "decode":
        return make_decode_step(cfg, mesh, shape, **kw)
    raise ValueError(shape.kind)
