#!/usr/bin/env python3
"""Drive the system's main path once on a TPU chip and check what comes out.

  python3 chip_smoke.py              # one chip: serve, keyed fold, train
  python3 chip_smoke.py --chips 4    # four chips: the sharded word count and
                                     # the data-parallel train step, each
                                     # against its one-chip comparison

One process holds the chip(s) for its whole life and starts no other.  With
no arguments it uses exactly one device, ``jax.devices()[0]``, however many
are visible, and runs three phases in order:

* serve — ``build_engine`` over qwen3-0.6b at its published width (random
  weights from ``--seed``), requests through ``submit``/``run`` with a shared
  prefix so the radix prefix cache hits, the per-step metrics fold on the
  Pallas kernel tier, and one batched request checked against the same
  prompt decoded alone by a one-slot engine;
* keyed fold — ``word_count_job`` with the combiner strategy over a Zipf
  corpus of 2**22 tokens, once with 4096 keys (kernel tier) and once with
  32768 keys (the planner sends it to segment-ops: the kernel would not fit
  fast memory), each equal to ``np.bincount``;
* train — three full-width train steps of ``repro.launch.train.train``.

Every failed check raises, so the exit code is non-zero and the result line
is not printed.  Without a TPU the script stops before any phase.  The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

_SRC = pathlib.Path(__file__).resolve().parent / "src"

ARCH = "qwen3-0.6b"
# serve: 4 rolling slots, prompts up to 256 tokens, up to 16 new tokens each
NUM_SLOTS = 4
BUCKETS = (64, 256)
MAX_NEW = 16
PREFIX_BLOCK = 16
SHARED_LEN = 48            # the shared "system prompt" of three requests
# (prompt length, opens with the shared prefix, max_new_tokens) per request;
# requests 5 and 6 are admitted after request 0 filled the prefix cache
REQUESTS = ((60, True, 16), (40, False, 9), (200, False, 16), (17, False, 5),
            (130, False, 16), (110, True, 12), (52, True, 16), (250, False, 7))
# the batched requests decoded again alone: one prefix-cache hit, one cold
SOLO = (5, 1)
# The same prompt decoded in a 4-slot engine, in a 1-slot engine and one
# token per call runs programs that differ in batch shape, and a prefix hit
# reads KV rows another prefill program wrote: bf16 activations may round
# differently in their last bit (2**-8 relative), which moves the logits
# and a per-token logprob by about that share of their magnitude.  With
# random weights the top logits over 151936 tokens often lie closer than
# that, so greedy decodes may part at such a near-tie: two logits at most
# NEAR_TIE_ULPS bf16 spacings (at the top logit's magnitude) apart.
LOGPROB_RTOL = 5e-3
NEAR_TIE_ULPS = 2
# keyed fold: 2**22 Zipf tokens keep every exact count below 2**24, the
# kernel tier's float32 accumulator bound
FOLD_TOKENS = 1 << 22
ZIPF_A = 1.2
FOLD_KEYS = ((4096, "kernel"), (32768, "segment_ops"))
SHARDED_KEYS = ((4096, "reduce_scatter"), (4095, "allreduce"))
# train: batch x sequence of the full-width step (about 9.5 GiB of the
# chip's 16 GB by the compiler's memory analysis)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 3
# data-parallel vs one chip, same global batch and weights: the first step's
# forward pass differs only in the order of float sums, so its loss agrees
# closely.  The gradients are bf16, and the two runs group the batch's
# per-token contributions differently (one sum, or four per-chip sums and a
# cross-chip one), so their bf16 roundings differ: at smoke widths on four
# virtual CPU devices, against float32 parameters, the one-device gradient
# was 1.9% off and the four-device one 0.47%, and their norms differed by
# 4e-3.  Later steps apply Adam updates of about lr = 1e-4 to bf16 weights
# of about 0.02, whose bf16 spacing is about 1e-4, so a gradient summed in
# another order can round an update the other way and the losses drift.
FIRST_STEP_RTOL = 1e-3
LOSS_RTOL = 1e-2
GRAD_NORM_RTOL = 1e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def tpu_devices(n: int):
    """The first ``n`` devices, which must be TPU chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX's devices are "
            f"{devs[0].platform!r}); this script runs on a TPU chip only")
    if len(devs) < n:
        raise SystemExit(f"chip_smoke: {n} TPU chips asked for, "
                         f"{len(devs)} visible")
    return devs[:n]


def zipf_tokens(rng: np.random.Generator, keys: int) -> np.ndarray:
    return ((rng.zipf(ZIPF_A, FOLD_TOKENS) - 1) % keys).astype(np.int32)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_serve(dev, seed: int, *, full: bool = True) -> None:
    from repro.serving import ServeConfig, build_engine, decode_metrics_plan

    config = ServeConfig(arch=ARCH, full=full, num_slots=NUM_SLOTS,
                         prefill_buckets=BUCKETS, max_new_tokens=MAX_NEW,
                         prefill_batch=2, prefix_block=PREFIX_BLOCK,
                         seed=seed)
    plan = decode_metrics_plan(NUM_SLOTS, NUM_SLOTS).describe()
    print(f"serve: per-step metrics fold plan: {plan}", flush=True)
    check("pallas segment_fold[sum]" in plan,
          "the decode-metrics fold is not on the Pallas kernel tier")

    engine = build_engine(config, devices=[dev])
    vocab = engine.backend.vocab_size
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, vocab, SHARED_LEN).tolist()
    prompts = []
    for plen, opens_shared, _ in REQUESTS:
        head = shared if opens_shared else []
        prompts.append(head + rng.integers(1, vocab, plen - len(head)).tolist())
    uids = [engine.submit(p, max_new_tokens=m)
            for p, (_, _, m) in zip(prompts, REQUESTS)]
    for _ in engine.run(max_steps=len(REQUESTS) * MAX_NEW):
        pass

    results = [engine.result(u) for u in uids]
    for r, (_, _, max_new) in zip(results, REQUESTS):
        check(len(r.tokens) == max_new or (r.stopped and r.tokens[-1]
                                           == config.eos_id),
              f"request {r.uid}: {len(r.tokens)} tokens for a budget of "
              f"{max_new} without stopping at EOS")
        check(math.isfinite(r.logprob_sum),
              f"request {r.uid}: logprob sum {r.logprob_sum}")
    counts, bound = engine.compile_counts(), engine.compile_bound()
    check(all(c <= 1 for c in counts.values())
          and sum(counts.values()) <= bound,
          f"compiled shapes {counts} exceed the bound {bound}")
    hits = engine.prefix.stats.hits
    check(hits >= 2, f"{hits} prefix-cache hits, expected >= 2")
    print(f"serve: {len(results)} requests, "
          f"{sum(len(r.tokens) for r in results)} tokens, "
          f"{engine.stats.slot_reuses} slot reuses, {hits} prefix hits; "
          f"compiled shapes {sum(counts.values())} <= bound {bound}; "
          f"logprob sums {[round(r.logprob_sum, 4) for r in results]}",
          flush=True)
    del engine
    gc.collect()

    solo = build_engine(dataclasses.replace(config, num_slots=1,
                                            prefill_batch=1), devices=[dev])
    step = jax.jit(solo.backend.decode)
    for i in SOLO:
        got = results[i]
        hit = got.bucket < config.bucket_for(len(prompts[i]))
        check(hit == (i == SOLO[0]),
              f"request {i}: prefix hit {hit}, expected {i == SOLO[0]}")
        uid = solo.submit(prompts[i], max_new_tokens=REQUESTS[i][2])
        for _ in solo.run(max_steps=MAX_NEW + 1):
            pass
        alone = solo.result(uid)
        # reference: the batched tokens fed back one per call (teacher
        # forcing), so it never parts from them
        logits = forced_logits(step, solo.backend, prompts[i], got.tokens)
        logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
        ref_sum = float(jnp.take_along_axis(
            logp, jnp.asarray(got.tokens)[:, None], axis=-1).sum())
        check(math.isclose(got.logprob_sum, ref_sum, rel_tol=LOGPROB_RTOL),
              f"request {i}: batched logprob sum {got.logprob_sum} vs "
              f"{ref_sum} decoded one token per call (rtol {LOGPROB_RTOL})")
        for j, tok in enumerate(got.tokens):
            check(near_tie(logits[j], tok),
                  f"request {i}: batched token {j} ({tok}) is not the "
                  f"greedy choice, nor within {NEAR_TIE_ULPS} bf16 spacings "
                  f"of it")
        same = next((j for j, (a, b) in enumerate(zip(got.tokens,
                                                      alone.tokens))
                     if a != b), None)
        if same is None:
            check(got.tokens == alone.tokens and math.isclose(
                      got.logprob_sum, alone.logprob_sum,
                      rel_tol=LOGPROB_RTOL),
                  f"request {i}: batched {got.tokens} {got.logprob_sum} vs "
                  f"solo {alone.tokens} {alone.logprob_sum}")
            parted = "equal tokens"
        else:
            # both decodes agree up to token `same`; there the reference
            # must see the solo engine's pick as a near-tie too
            check(near_tie(logits[same], alone.tokens[same]),
                  f"request {i}: solo decode parts from the batched one at "
                  f"token {same} ({alone.tokens[same]} vs "
                  f"{got.tokens[same]}), which is not a near-tie")
            top = np.sort(logits[same])[-2:]
            parted = (f"equal tokens up to token {same}, where they part "
                      f"at a near-tie (top two logits {top[1]} and "
                      f"{top[0]})")
        print(f"serve: request {i} (prefix hit: {hit}) vs its solo decode: "
              f"{parted}; logprob sum {got.logprob_sum}, solo "
              f"{alone.logprob_sum}, one token per call {ref_sum}",
              flush=True)
    del solo
    gc.collect()


def forced_logits(step, backend, prompt, tokens) -> np.ndarray:
    """(len(tokens), vocab) logits before each of ``tokens``: one row
    decoded one token per call over ``prompt + tokens``."""
    cache = backend.init_cache(1, True)
    out = []
    for t in prompt + tokens[:-1]:
        logits, cache = step(backend.params, cache,
                             jnp.asarray([[t]], jnp.int32))
        out.append(logits[0])
    return np.asarray(jnp.stack(out[len(prompt) - 1:]))


def near_tie(logits: np.ndarray, tok: int) -> bool:
    """Whether ``tok``'s logit is the largest, or within NEAR_TIE_ULPS bf16
    spacings of it."""
    top = float(logits.max())
    spacing = 2.0 ** (math.floor(math.log2(abs(top))) - 7) if top else 0.0
    return top - float(logits[tok]) <= NEAR_TIE_ULPS * spacing


def phase_fold(dev, seed: int) -> None:
    from repro.core import word_count_job

    rng = np.random.default_rng(seed)
    for keys, tier in FOLD_KEYS:
        host = zipf_tokens(rng, keys)
        tokens = jax.device_put(host, dev)
        job = word_count_job(keys)
        plan = job.plan(tokens, strategy="combiner", num_shards=1)
        print(f"keyed fold: {keys} keys: {plan.describe()}", flush=True)
        check(plan.local_tier.kind == tier,
              f"{keys} keys planned on {plan.local_tier.kind}, not {tier}")
        if tier != "kernel":
            check("fast memory" in plan.describe(),
                  "the plan does not say why the kernel tier was left out")
        compiled = jax.jit(
            lambda t, job=job: job.run_local(t, strategy="combiner")
        ).lower(tokens).compile()
        check(("tpu_custom_call" in compiled.as_text()) == (tier == "kernel"),
              f"{keys} keys: the compiled fold does not match its plan")
        got = np.asarray(compiled(tokens))
        want = np.bincount(host, minlength=keys)
        check(np.array_equal(got, want),
              f"{keys} keys: {int((got != want).sum())} counts differ from "
              f"np.bincount")
        print(f"keyed fold: {FOLD_TOKENS} Zipf tokens into {keys} keys on "
              f"{tier}: equal to np.bincount (largest count {want.max()})",
              flush=True)


def train_history(devs, seed: int, *, full: bool = True):
    from repro.launch.train import TrainerConfig, train

    out = train(TrainerConfig(arch=ARCH, smoke=not full, steps=TRAIN_STEPS,
                              global_batch=TRAIN_BATCH, seq_len=TRAIN_SEQ,
                              seed=seed, log_every=1), devices=devs)
    history = out["history"]
    losses = [h["loss"] for h in history]
    check(len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)),
          f"train losses {losses}")
    check(out["step_compiles"] == 1,
          f"the train step compiled {out['step_compiles']} times")
    placed = sorted({d.id for leaf in jax.tree_util.tree_leaves(out["params"])
                     for d in leaf.devices()})
    del out
    gc.collect()
    return history, placed


def phase_train(dev, seed: int, *, full: bool = True) -> None:
    history, placed = train_history([dev], seed, full=full)
    print(f"train: {TRAIN_STEPS} steps of {TRAIN_BATCH}x{TRAIN_SEQ} tokens, "
          f"losses {[h['loss'] for h in history]}, one compiled step, params "
          f"on devices {placed}", flush=True)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_sharded_fold(devs, seed: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import word_count_job
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(devices=devs)
    rng = np.random.default_rng(seed)
    for keys, shuffle in SHARDED_KEYS:
        host = zipf_tokens(rng, keys)
        tokens = jax.device_put(host, NamedSharding(mesh,
                                                    PartitionSpec("data")))
        job = word_count_job(keys)
        plan = job.plan(tokens, strategy="combiner", num_shards=len(devs),
                        axis_name="data")
        print(f"sharded fold: {keys} keys: {plan.describe()}", flush=True)
        check(plan.shuffle_algorithm == shuffle,
              f"{keys} keys shuffled by {plan.shuffle_algorithm}, not "
              f"{shuffle}")
        out = jax.jit(lambda t, job=job: job.run_sharded(
            t, mesh, strategy="combiner"))(tokens)
        want = np.bincount(host, minlength=keys)
        on = sorted(s.device.id for s in out.addressable_shards)
        for s in out.addressable_shards:
            check(np.array_equal(np.asarray(s.data), want),
                  f"{keys} keys: the copy on device {s.device.id} differs "
                  f"from np.bincount")
        print(f"sharded fold: {FOLD_TOKENS} Zipf tokens over {len(devs)} "
              f"chips into {keys} keys by {shuffle}: result shards on "
              f"devices {on}, each equal to np.bincount", flush=True)


def phase_dp_train(devs, seed: int, *, full: bool = True) -> None:
    ref, ref_on = train_history(devs[:1], seed, full=full)
    got, got_on = train_history(devs, seed, full=full)
    check(got_on == sorted(d.id for d in devs),
          f"data-parallel params live on devices {got_on}")
    for g, r in zip(got, ref):
        rtols = {"loss": FIRST_STEP_RTOL if g["step"] == 1 else LOSS_RTOL,
                 "grad_norm": GRAD_NORM_RTOL}
        for key, rtol in rtols.items():
            check(math.isclose(g[key], r[key], rel_tol=rtol),
                  f"step {g['step']}: data-parallel {key} {g[key]} vs one "
                  f"chip {r[key]} (rtol {rtol})")
    print(f"data-parallel train: {TRAIN_STEPS} steps of {TRAIN_BATCH}x"
          f"{TRAIN_SEQ} tokens over devices {got_on}: losses "
          f"{[h['loss'] for h in got]}, grad norms "
          f"{[h['grad_norm'] for h in got]}; on device {ref_on} alone: "
          f"losses {[h['loss'] for h in ref]}, grad norms "
          f"{[h['grad_norm'] for h in ref]}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paths that span four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (_SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: the repro package is not at {_SRC}; "
                         "run this script from a checkout of the repository")
    sys.path.insert(0, str(_SRC))

    devs = tpu_devices(args.chips)
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.chips == 1:
        phase_serve(devs[0], args.seed)
        phase_fold(devs[0], args.seed)
        phase_train(devs[0], args.seed)
    else:
        phase_sharded_fold(devs, args.seed)
        phase_dp_train(devs, args.seed)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
