#!/usr/bin/env python3
"""Compile a cell's main programs for a described TPU v5e, no chip needed.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \\
      --workload qwen3-0.6b.chat-prefix

The cell, its configuration, reference and program adapter are read as
``run.py`` reads them.  Prints each program's memory analysis as one JSON
line.  A serve cell: the decode step at the mix's slots x (largest bucket
+ max_new_tokens) cache, one batched prefill token at (prefill_batch,
that length), and the float32 reference's logits program.  A fold cell:
the word count fold at the configuration's ids per chip on the cell's
chips.  Shapes only: nothing is allocated or run.  A compile that passes
here is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)


def sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def serve_programs(cell, devices) -> dict:
    from chipbench import serve, spec
    from repro.models import RunCtx, decode_step, init_cache

    config, server = cell.config, cell.traffic["server"]
    ref, adapter = spec.reference(config), spec.program_adapter(config)
    one = SingleDeviceSharding(devices[0])
    cfg = adapter.program_config(config)
    sizes = ref.Sizes.from_config(config)
    weights = jax.eval_shape(lambda: ref.make_weights(0, sizes))
    params = jax.eval_shape(
        lambda w: serve.program_params(adapter, w, cfg), weights)
    seq = server["prefill_buckets"][-1] + server["max_new_tokens"]
    ctx = RunCtx()

    def step(p, cache, cur):
        logits, cache = decode_step(p, cfg, cache, cur, ctx=ctx)
        return jnp.argmax(logits[:, -1], -1), cache

    out = {}
    for name, batch in (("serve_decode_step", server["num_slots"]),
                        ("prefill_token", server["prefill_batch"])):
        cache = jax.eval_shape(lambda p, b=batch: init_cache(
            p, cfg, b, seq, pos_per_slot=True), params)
        args = (sds(params, one), sds(cache, one),
                jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one))
        t = time.time()
        c = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
        out[name] = dict(memory(c), compile_s=round(time.time() - t, 1))
    toks = jax.ShapeDtypeStruct((seq,), jnp.int32, sharding=one)
    read = jax.ShapeDtypeStruct((server["max_new_tokens"],), jnp.int32,
                                sharding=one)
    t = time.time()
    c = ref.logits_at.lower(sds(weights, one), toks, read, sizes).compile()
    out["reference_logits"] = dict(memory(c),
                                   compile_s=round(time.time() - t, 1))
    return out


def fold_program(cell, devices) -> dict:
    from repro.core import word_count_job
    from repro.launch.mesh import make_mesh

    config = cell.config
    mesh = make_mesh((len(devices),), ("data",), devices)
    job = word_count_job(config["num_keys"])
    ids = jax.ShapeDtypeStruct((config["split_ids"] * len(devices),),
                               jnp.int32,
                               sharding=NamedSharding(mesh,
                                                      PartitionSpec("data")))
    acc = jax.ShapeDtypeStruct((config["num_keys"],), jnp.int32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    t = time.time()
    c = jax.jit(lambda a, r: a + job.run_sharded(
        r, mesh, strategy="combiner")).lower(acc, ids).compile()
    text = c.as_text()
    colls = sorted({w for w in ("reduce-scatter", "all-gather", "all-reduce")
                    if w in text})
    return {f"fold_{len(devices)}chip": dict(
        memory(c), collectives=colls, compile_s=round(time.time() - t, 1))}


def main(argv=None) -> int:
    from jax.experimental import topologies

    from chipbench import spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell.chips]
    kinds = {"serve": serve_programs, "fold": fold_program}
    for k, v in kinds[cell.traffic["kind"]](cell, devices).items():
        print(json.dumps({"program": k, **v}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
