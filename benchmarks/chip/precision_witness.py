#!/usr/bin/env python3
"""Tell a serve cell's rounding from a fault in its program: the program's
own forward pass over served requests, in its served dtype and in float32,
each held to the float32 reference.

  python3 benchmarks/chip/precision_witness.py \\
      --workload deepseek-v2.chat-decode --seeds 11,12 --seconds 15

Each seed serves a short window at the cell's load (``--rate`` overrides
it), as ``control.py`` does, and samples its served requests as a run's
check does.  For each sampled request the program's ``forward`` (its
prefill path, not the engine) then gives the logits before every served
token from the same weights, once in the configuration's dtype and once in
float32 at ``highest`` matmul precision.  Each is read as a run's check
reads ``token_gap``, for the token it would pick there: how far that
token's float32-reference logit lies below the reference's best.  So
``served`` is the check's own reading, ``program`` the forward's in the
served dtype, ``program_f32`` the forward's in float32; ``*_logit_max``
is the largest gap between a logit and the reference's.  Where
``program_f32`` reads near zero while ``program`` reads near ``served``,
the check's gap comes from the served dtype's rounding, not from the
program's arithmetic.  One JSON line per seed; the benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def program_logits(cfg, precision: str):
    """A jitted ``(params, tokens (S,), read (R,)) -> (R, vocab)`` float32
    logits of the program's forward at positions ``read``; call it under
    ``jax.default_matmul_precision(precision)``."""
    import jax
    import jax.numpy as jnp

    from repro.models import forward, unembed

    @jax.jit
    def f(params, tokens, read):
        h, _ = forward(params, cfg, tokens[None])
        return unembed(params, cfg, h[0, read]).astype(jnp.float32)

    def run(params, tokens, read):
        with jax.default_matmul_precision(precision):
            return f(params, tokens, read)

    return run


def gaps(ref, picks, rows) -> float:
    import numpy as np

    best = ref.max(axis=-1)
    return float(np.max(best - ref[rows, picks]))


def seed_readings(cell, seed: int, seconds: float, rate, devs) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from chipbench import spec
    from chipbench.record import WindowTracer
    from chipbench.serve import ServeCell, program_params

    mix = cell.traffic
    if rate is not None:
        mix = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
    sc = ServeCell(cell.config, mix, seed, devs)
    sc.warm_up()
    served, _, _ = sc.run_window(sc.requests(seconds), seconds,
                                 WindowTracer(False, seconds))
    sample = sc.sample(served, int(mix["check"]["sample_requests"]))
    sc.release()
    gc.collect()

    model, s = sc.model, sc.sizes
    adapter = spec.program_adapter(cell.config)
    cfg = adapter.program_config(cell.config)
    weights = model.make_weights(seed, s, devs[0])
    params = program_params(adapter, weights, cfg)
    length = sc.server["prefill_buckets"][-1] + sc.server["max_new_tokens"]
    width = sc.server["max_new_tokens"]
    low = program_logits(cfg, "default")
    high = program_logits(dataclasses.replace(cfg, dtype=jnp.float32),
                          "highest")
    out = {"served": 0.0, "program": 0.0, "program_f32": 0.0,
           "program_logit_max": 0.0, "program_f32_logit_max": 0.0,
           "served_picks_agree": 0.0}
    agree = total = 0
    for rec in sample:
        toks = np.asarray(rec.tokens)
        n = len(toks)
        rows = np.arange(n)
        ref = model.served_logits(weights, rec.req.prompt, rec.tokens, s,
                                  length, width)
        seq = np.zeros((length,), np.int32)
        body = list(rec.req.prompt) + list(rec.tokens[:-1])
        seq[:len(body)] = body
        read = np.zeros((width,), np.int32)
        read[:n] = np.arange(len(rec.req.prompt) - 1,
                             len(rec.req.prompt) - 1 + n)
        lo = np.asarray(low(params, jnp.asarray(seq), jnp.asarray(read)))[:n]
        hi = np.asarray(high(params, jnp.asarray(seq), jnp.asarray(read)))[:n]
        out["served"] = max(out["served"], gaps(ref, toks, rows))
        out["program"] = max(out["program"], gaps(ref, lo.argmax(-1), rows))
        out["program_f32"] = max(out["program_f32"],
                                 gaps(ref, hi.argmax(-1), rows))
        out["program_logit_max"] = max(out["program_logit_max"],
                                       float(np.abs(lo - ref).max()))
        out["program_f32_logit_max"] = max(out["program_f32_logit_max"],
                                           float(np.abs(hi - ref).max()))
        agree += int((lo.argmax(-1) == toks).sum())
        total += n
    out["served_picks_agree"] = agree / max(total, 1)
    out["sampled_requests"] = len(sample)
    out["sampled_tokens"] = total
    out["failed"] = sum(not r.ok for r in served)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rate", type=float)
    args = ap.parse_args(argv)

    from chipbench import device, spec

    cell = spec.load_cell(args.workload)
    if cell.traffic["kind"] != "serve":
        raise SystemExit(f"{args.workload} is not a serve cell")
    devs = device.tpu_devices(cell.chips)
    device.compile_cache()
    for seed in [int(x) for x in args.seeds.split(",")]:
        row = seed_readings(cell, seed, args.seconds, args.rate, devs)
        print(json.dumps(dict(row, seed=seed)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
