#!/usr/bin/env python3
"""Compile the cells' main programs for a described TPU v5e, no chip needed.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py

Prints each program's memory analysis as one JSON line: the serve decode
step at the chat cells' 32 slots x 1280-row cache, one batched prefill
token at (4, 1280), the float32 reference's logits program, and the
four-chip word count fold at 2**24 ids per chip (``chipbench/fold.py``,
which no cell runs yet).  Shapes only: nothing is
allocated or run.  A compile that passes here is not a chip run.
"""
from __future__ import annotations

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
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402


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


def serve_programs(one, slots=32, seq=1280, k=4):
    from chipbench import serve
    from reference import qwen3
    from repro.models import RunCtx, decode_step, init_cache

    config = json.loads((HERE / "configs" / "qwen3-0.6b.json").read_text())
    cfg = serve.program_config(config)
    sizes = qwen3.Sizes.from_config(config)
    weights = jax.eval_shape(lambda: qwen3._make(qwen3.seed_key(0), sizes))
    params = jax.eval_shape(serve._program_layout, weights)
    ctx = RunCtx()

    def step(p, cache, cur):
        logits, cache = decode_step(p, cfg, cache, cur, ctx=ctx)
        return jnp.argmax(logits[:, -1], -1), cache

    out = {}
    for name, batch in (("serve_decode_step", slots), ("prefill_token", k)):
        cache = jax.eval_shape(lambda p, b=batch: init_cache(
            p, cfg, b, seq, pos_per_slot=True), params)
        args = (sds(params, one), sds(cache, one),
                jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one))
        t = time.time()
        c = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
        out[name] = dict(memory(c), compile_s=round(time.time() - t, 1))
    toks = jax.ShapeDtypeStruct((seq,), jnp.int32, sharding=one)
    read = jax.ShapeDtypeStruct((256,), jnp.int32, sharding=one)
    t = time.time()
    c = jax.jit(qwen3.logits_at, static_argnames=("s", "fp8")).lower(
        sds(weights, one), toks, read, s=sizes).compile()
    out["reference_logits"] = dict(memory(c),
                                   compile_s=round(time.time() - t, 1))
    return out


def fold_program(devices):
    from repro.core import word_count_job
    from repro.launch.mesh import make_mesh

    config = json.loads((HERE / "configs" /
                         "wordcount-zipf-152k.json").read_text())
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
    return {"fold_4chip": dict(memory(c), collectives=colls,
                               compile_s=round(time.time() - t, 1))}


def main() -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    programs = {**serve_programs(one), **fold_program(list(topo.devices))}
    for k, v in programs.items():
        print(json.dumps({"program": k, **v}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
