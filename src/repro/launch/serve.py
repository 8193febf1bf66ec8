"""Continuous-batching serving driver: decode as a rolling keyed MapReduce.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \\
      --requests 8 --slots 4 --buckets 8,16 --gen 8 --rate 50

This module wires the model substrate (configs/models/mesh) into the
model-agnostic :class:`repro.runtime.ContinuousEngine` and hosts the CLI.
Requests arrive on a Poisson trace, queue FIFO in the engine's admission
queue, and are admitted into *rolling slots*: a slot freed by an EOS or an
exhausted budget is handed to the next waiting request mid-decode.  The
per-request aggregation — logprob sums, generated token counts, and the
stop-condition reduction — is ONE planner-lowered keyed fold per decode
step (``request slot == segment id``) over whatever population currently
occupies the slots, with a ``valid_mask`` for the empty ones: the same way
the train step amortizes the shuffle with a combiner, the serve step
amortizes both the kernel launch and the aggregation across the rolling
batch.  Compilation is bounded by the prefill bucket ladder
(:class:`repro.runtime.ServeConfig`), so slot churn never recompiles.

The stable import surface for applications is :mod:`repro.serving`; the
production-mesh serving step (sequence-sharded KV for long contexts) is
still exercised by the dry-run via ``launch/steps.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import warnings
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ShapeCell, context_spec, get_config
from ..data.windows import WindowedMetrics
from ..dist import sharding as shd
from ..models import (RunCtx, decode_step, init_cache, init_params,
                      param_axes, param_shapes, positional_cache)
from ..runtime.batcher import DecodeBatch
from ..runtime.engine import (ContinuousEngine, EngineBackend, ServeConfig,
                              decode_metrics_init, decode_metrics_plan,
                              decode_metrics_step, extract_metrics,
                              METRIC_COLS)
from .compile_cache import use_compile_cache
from .mesh import make_host_mesh
from .steps import make_decode_step

__all__ = [
    "METRIC_COLS", "decode_metrics_init", "decode_metrics_plan",
    "decode_metrics_step", "extract_metrics", "ServeConfig", "BatchResult",
    "build_engine", "build_serve_step", "run_batched_decode", "main",
]


def build_engine(config: ServeConfig, *, clock=time.perf_counter,
                 devices: Optional[Sequence[jax.Device]] = None
                 ) -> ContinuousEngine:
    """A :class:`ContinuousEngine` over the real model substrate.

    Builds params + mesh for ``config.arch`` and hands the engine a
    traceable one-token decode (the same ``decode_step`` the dry-run
    lowers) plus a cache constructor with per-slot positions for the
    rolling cache.  Everything shape-dependent — slot count, prefill
    bucket ladder, generation budget — comes from ``config``.  The mesh
    spans ``devices`` (every visible device when None).
    """
    cfg = get_config(config.arch, smoke=not config.full)
    if context_spec(cfg, 1) is not None:
        raise NotImplementedError(
            f"{config.arch}: context-conditioned archs (audio/vision) are "
            f"not supported by the continuous engine yet")
    mesh = make_host_mesh(model=config.model_parallel, devices=devices)
    rules = shd.trim_rules(shd.SERVE_RULES, mesh)
    ctx = RunCtx(mesh=mesh)
    key = jax.random.PRNGKey(config.seed)
    params, _ = init_params(cfg, key)
    # commit params to the mesh: left uncommitted they would stay on the
    # default device whatever ``devices`` says
    if config.model_parallel > 1:
        pshard = shd.param_shardings(param_shapes(cfg), param_axes(cfg),
                                     mesh, rules)
    else:
        pshard = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
    params = jax.device_put(params, pshard)

    def decode(p, cache, cur):
        with shd.use_rules(mesh, rules):
            logits, cache = decode_step(p, cfg, cache, cur, ctx=ctx)
        return logits[:, -1].astype(jnp.float32), cache

    def make_cache(batch: int, pos_per_slot: bool):
        return init_cache(params, cfg, batch, config.max_seq, ctx=ctx,
                          pos_per_slot=pos_per_slot)

    def place(tree):
        # commit with the sharding the mesh-aware jitted programs emit, so
        # the engine's first write_slot call compiles once, not twice
        return jax.device_put(
            tree, jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))

    backend = EngineBackend(decode=decode, init_cache=make_cache,
                            params=params, vocab_size=cfg.vocab_size,
                            # prefix KV sharing needs position-indexed cache
                            # rows; recurrent/cross-attn substrates opt out
                            prefix_sharing=positional_cache(cfg),
                            place=place)
    return ContinuousEngine(backend, config, clock=clock)


def build_serve_step(config: ServeConfig):
    """(cfg, built, params, make_cache) for the FIXED-shape serve step.

    The pre-engine API, now driven by the same :class:`ServeConfig`: one
    jitted ``(num_slots, 1)`` decode step against a ``max_seq`` cache with
    explicit mesh shardings.  The dry-run and the step-level benchmark rows
    still exercise this; request-level serving goes through
    :func:`build_engine`.
    """
    cfg = get_config(config.arch, smoke=not config.full)
    mesh = make_host_mesh(model=config.model_parallel)
    shape = ShapeCell("serve", "decode", config.max_seq, config.num_slots)
    built = make_decode_step(cfg, mesh, shape, donate=False)
    key = jax.random.PRNGKey(config.seed)
    params, _ = init_params(cfg, key)
    params = jax.device_put(params, built.in_shardings[0])
    spec = context_spec(cfg, config.num_slots)
    context = None if spec is None else jax.random.normal(key, spec.shape,
                                                          cfg.dtype)

    def make_cache():
        cache = init_cache(params, cfg, config.num_slots, config.max_seq,
                           context=context)
        return jax.device_put(cache, built.in_shardings[1])

    return cfg, built, params, make_cache


@dataclasses.dataclass
class BatchResult:
    """Outcome of decoding one flushed batch (legacy shape, kept for the
    deprecated :func:`run_batched_decode` shim)."""

    batch: DecodeBatch
    tokens: np.ndarray            # (num_slots, max_new) generated ids (0-padded)
    metrics: Dict[str, np.ndarray]
    decode_steps: int
    prefill_s: float
    decode_s: float


def run_batched_decode(engine: ContinuousEngine, batch: DecodeBatch, *,
                       max_steps: Optional[int] = None) -> BatchResult:
    """DEPRECATED: decode one fixed batch to completion through the engine.

    The PR-3 API decoded a flushed :class:`DecodeBatch` as a unit; the
    engine subsumes it — this shim submits the batch's requests, drains the
    engine, and reassembles a :class:`BatchResult` (slot ``i`` of the
    result is request ``i`` of the batch).  Use
    :meth:`ContinuousEngine.submit` / :meth:`~ContinuousEngine.run`
    directly: the engine overlaps requests instead of waiting for the
    slowest one.
    """
    warnings.warn(
        "run_batched_decode is deprecated: submit requests to "
        "repro.serving.ContinuousEngine directly (continuous batching "
        "replaces batch-to-completion decode)", DeprecationWarning,
        stacklevel=2)
    t0 = time.perf_counter()
    uids = [engine.submit(r.prompt, max_new_tokens=r.max_new_tokens)
            for r in batch.requests]
    first_tok_s = None
    steps = 0
    while engine.pending or engine.num_active:
        for ev in engine.step():
            if ev.kind == "token" and ev.index == 0 and first_tok_s is None:
                first_tok_s = time.perf_counter() - t0
        steps += 1
        if max_steps is not None and steps >= max_steps:
            break
    total_s = time.perf_counter() - t0
    prefill_s = first_tok_s if first_tok_s is not None else total_s

    S = batch.num_slots
    budget = max(int(batch.max_new().max(initial=0)), 1)
    gen = np.zeros((S, budget), np.int64)
    logprob = np.zeros((S,), np.float32)
    tokens = np.zeros((S,), np.int64)
    stopped = np.zeros((S,), bool)
    for i, uid in enumerate(uids):
        res = engine.result(uid)
        gen[i, : len(res.tokens)] = res.tokens
        logprob[i] = res.logprob_sum
        tokens[i] = len(res.tokens)
        stopped[i] = res.stopped
    metrics = {"logprob_sum": logprob, "tokens": tokens, "stopped": stopped}
    return BatchResult(batch=batch, tokens=gen, metrics=metrics,
                       decode_steps=steps, prefill_s=prefill_s,
                       decode_s=max(total_s - prefill_s, 1e-9))


# ---------------------------------------------------------------------------
# CLI: Poisson arrival trace through the engine
# ---------------------------------------------------------------------------

def poisson_trace(rng: np.random.Generator, n: int, rate_hz: float,
                  min_prompt: int, max_prompt: int, vocab: int,
                  max_new: int, users: int = 1,
                  shared_frac: float = 0.0, shared_len: int = 0):
    """[(arrival_offset_s, prompt, max_new, user)] — synthetic open-loop
    traffic; requests attribute uniformly to ``users`` synthetic users.

    ``shared_frac`` of the requests open with a fixed ``shared_len``-token
    prefix (one "system prompt" drawn per trace) — the workload shape
    prefix KV caching exploits."""
    shared = rng.integers(1, vocab, shared_len).tolist() if shared_len else []
    t = 0.0
    out = []
    for _ in range(n):
        t += float(rng.exponential(1.0 / rate_hz)) if rate_hz > 0 else 0.0
        plen = int(rng.integers(min_prompt, max_prompt + 1))
        if shared and plen > shared_len and rng.random() < shared_frac:
            prompt = shared + rng.integers(1, vocab,
                                           plen - shared_len).tolist()
        else:
            prompt = rng.integers(1, vocab, plen).tolist()
        out.append((t, prompt, max_new, int(rng.integers(0, users))))
    return out


def serve_trace(engine: ContinuousEngine, trace, *,
                clock=time.perf_counter, quiet: bool = True):
    """Replay an arrival trace through the engine in (scaled) real time.

    Submits each request once its arrival offset elapses, stepping the
    engine whenever it has work.  Returns ``(results, wall_s)`` with
    results in submission order.
    """
    t0 = clock()
    uids = []
    i = 0
    while i < len(trace) or engine.pending or engine.num_active:
        now = clock() - t0
        while i < len(trace) and trace[i][0] <= now:
            _, prompt, max_new, *rest = trace[i]
            uids.append(engine.submit(prompt, max_new_tokens=max_new,
                                      user=rest[0] if rest else 0))
            i += 1
        if engine.pending or engine.num_active:
            for ev in engine.step():
                if not quiet and ev.kind == "done":
                    r = ev.result
                    print(f"  uid={r.uid} slot={r.slot} prompt={r.prompt_len} "
                          f"-> bucket={r.bucket} gen={len(r.tokens)} "
                          f"logprob_sum={r.logprob_sum:.2f} "
                          f"ttft={r.ttft_s * 1e3:.1f}ms")
        elif i < len(trace):
            time.sleep(min(max(trace[i][0] - now, 0.0), 0.01))
    wall = clock() - t0
    return [engine.result(u) for u in uids], wall


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--buckets", default="8,16",
                    help="comma-separated prefill bucket ladder")
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=12)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (requests/s); 0 = all at once")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--users", type=int, default=4,
                    help="synthetic user population for per-user windows")
    ap.add_argument("--prefill-batch", type=int, default=4,
                    help="max same-bucket admissions per prefill program")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the radix prefix KV cache")
    ap.add_argument("--prefix-block", type=int, default=4,
                    help="tokens per prefix-cache trie node")
    ap.add_argument("--shared-frac", type=float, default=0.0,
                    help="fraction of requests opening with a shared prefix")
    ap.add_argument("--shared-len", type=int, default=0,
                    help="token length of the shared prefix")
    args = ap.parse_args(argv)
    use_compile_cache()

    buckets = tuple(int(b) for b in args.buckets.split(","))
    if args.max_prompt > buckets[-1]:
        raise SystemExit(f"--max-prompt {args.max_prompt} exceeds the "
                         f"largest bucket {buckets[-1]}")
    config = ServeConfig(arch=args.arch, num_slots=args.slots,
                         prefill_buckets=buckets, max_new_tokens=args.gen,
                         temperature=args.temperature, seed=args.seed,
                         model_parallel=args.model_parallel, full=args.full,
                         prefill_batch=args.prefill_batch,
                         prefix_cache=not args.no_prefix_cache,
                         prefix_block=args.prefix_block)
    engine = build_engine(config)
    metrics = WindowedMetrics(window=32, half_life_s=60.0)
    engine.subscribe(metrics.observe)

    plan = decode_metrics_plan(config.num_slots, config.num_slots)
    print(f"arch={args.arch} slots={config.num_slots} buckets={buckets} "
          f"gen<={args.gen} requests={args.requests} rate={args.rate}/s")
    print(f"per-step aggregation plan: {plan.describe()}")

    rng = np.random.default_rng(args.seed)
    vocab = engine.backend.vocab_size
    trace = poisson_trace(rng, args.requests, args.rate, args.min_prompt,
                          args.max_prompt, vocab, args.gen,
                          users=max(1, args.users),
                          shared_frac=args.shared_frac,
                          shared_len=args.shared_len)
    results, wall = serve_trace(engine, trace, quiet=False)

    ttfts = np.array([r.ttft_s for r in results])
    new_tokens = sum(len(r.tokens) for r in results)
    st = engine.stats
    print(f"served {len(results)} requests, {new_tokens} tokens in "
          f"{wall:.2f}s ({new_tokens / wall:.0f} tok/s) | "
          f"steps={st.steps} slot_reuses={st.slot_reuses} "
          f"prefills={st.prefill_calls} batched={st.batched_admissions} "
          f"ttft p50={np.percentile(ttfts, 50) * 1e3:.1f}ms "
          f"p99={np.percentile(ttfts, 99) * 1e3:.1f}ms")
    print(f"compiled shapes: {engine.compile_counts()} "
          f"(bound: {engine.compile_bound()})")
    if engine.prefix is not None:
        ps = engine.prefix.stats
        fp = metrics.fleet_prefix()
        print(f"prefix cache: nodes={engine.prefix.node_count} "
              f"resident={engine.prefix.total_bytes}B "
              f"(fold-accounted {engine.prefix.accounted_bytes()}B) "
              f"hit_rate={ps.hit_rate():.0%} "
              f"bytes_saved={ps.bytes_saved} evictions={ps.evictions} "
              f"folds={ps.folds}")
        print(f"fleet prefix windows: hit_rate={fp['hit_rate']:.0%} "
              f"hit_tokens={fp['hit_tokens']:.0f}/"
              f"{fp['prompt_tokens']:.0f} "
              f"bytes_saved={fp['bytes_saved']:.0f}")
    now = time.perf_counter()
    print(f"per-user windows (last {metrics.window} requests, token-rate "
          f"half-life {metrics.half_life_s:g}s; fleet tokens "
          f"{metrics.fleet_tokens():.0f}):")
    for user, row in metrics.summary(now).items():
        print(f"  user={user} requests={row['requests']} "
              f"latency={row['latency_s'] * 1e3:.1f}ms "
              f"ttft={row['ttft_s'] * 1e3:.1f}ms "
              f"tokens/req={row['tokens']:.1f} "
              f"token_rate={row['token_rate']:.1f}")
    return 0


if __name__ == "__main__":
    main()
