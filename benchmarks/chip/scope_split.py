#!/usr/bin/env python3
"""Split a serve cell's traced decode-step device time by the program's
named scopes.

On the chip, run the cell's window and keep its trace:

  python3 benchmarks/chip/scope_split.py \\
      --workload deepseek-v2.chat-decode --seed 12345 --seconds 51 \\
      --keep DIR

which runs ``program_spans.py``'s window (the same set-up, traffic,
profiled slice, span readings and counters), copies the ``.xplane.pb``
into DIR and prints ``program_spans.py``'s JSON line.  A TPU trace names
each op by its HLO instruction alone: it carries no ``op_name``, so no
``jax.named_scope``.  Then, without the chip:

  JAX_PLATFORMS=cpu python3 benchmarks/chip/scope_split.py \\
      --workload deepseek-v2.chat-decode --xplane DIR/host.xplane.pb

compiles the engine's decode step (``jit_step_impl``) for a described TPU
v5e from the cell's shapes, as the chip compiled it, and reads each of
its instructions' ``op_name``.  Every op of that program in the trace's
window is matched by instruction name and result shape; its self time
(its length less that of the ops nested in it, as a ``while`` holds its
body's) goes to the innermost of ``SCOPES`` on its path, else to
``unscoped``.  A fusion takes the path XLA gave it, and an op that lost
its path (a ``ragged_dot`` expansion) is listed under ``unscoped`` with
the name XLA gave it.  One JSON line: seconds per scope, each scope's
longest ops with their paths and shapes, and the share of the step's ops
matched.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import pathlib
import re
import shutil
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple
from unittest import mock

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import program_spans  # noqa: E402
from metrics.common import trace as trace_mod  # noqa: E402

# the program's named scopes (models/attention.py, models/moe.py)
SCOPES = ("mla", "moe.route", "moe.experts")
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"(?:^|[/\"'])(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?=/|$|[\"'])")

# (start_ns, end_ns, op name, scope)
Op = Tuple[float, float, str, str]


def scope_of(texts: Iterable[str]) -> str:
    """The innermost of ``SCOPES`` on the first name-stack path found in
    ``texts``, else ``UNSCOPED``."""
    for text in texts:
        found = _SCOPE.findall(text)
        if found:
            return found[-1]
    return UNSCOPED


def self_times(ops: Sequence[Op], lo: float, hi: float
               ) -> List[Tuple[str, str, float]]:
    """(name, scope, seconds) per op: its part of [lo, hi) less the parts
    of the ops directly nested in it (ops on one line nest or are apart)."""
    order = sorted(ops, key=lambda o: (o[0], -(o[1] - o[0])))
    own = [min(e, hi) - max(s, lo) for s, e, _, _ in order]
    stack: List[int] = []
    for i, (s, e, _, _) in enumerate(order):
        while stack and order[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = order[stack[-1]]
            own[stack[-1]] -= max(0.0, min(e, p[1], hi) - max(s, lo))
        stack.append(i)
    return [(o[2], o[3], max(t, 0.0) * 1e-9) for o, t in zip(order, own)]


def split(ops: Sequence[Op], lo: float, hi: float,
          about: Optional[Dict[str, str]] = None, top: int = 8) -> dict:
    """Self seconds per scope inside [lo, hi), and each scope's top ops
    (with ``about[name]``, their path and shape, where given)."""
    per_scope: Dict[str, float] = collections.defaultdict(float)
    per_op: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    for name, scope, t in self_times(ops, lo, hi):
        per_scope[scope] += t
        per_op[(scope, name)] += t
    tops = {}
    for scope in list(SCOPES) + [UNSCOPED]:
        mine = sorted(((t, n) for (s, n), t in per_op.items() if s == scope),
                      reverse=True)[:top]
        tops[scope] = [[n, t] + ([about.get(n, "")] if about else [])
                       for t, n in mine]
    return {"seconds": {s: per_scope.get(s, 0.0)
                        for s in list(SCOPES) + [UNSCOPED]},
            "top_ops": tops}


STEP_MODULE = "jit_step_impl"
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (result shape, op_name) of a compiled module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            path = _OP_NAME.search(line)
            out[m.group(1)] = (m.group(2), path.group(1) if path else "")
    return out


def step_text(cell) -> str:
    """The engine's decode step of ``cell``, compiled for a described TPU
    v5e from shapes alone, as ``chipbench/serve.py:make_engine`` wires it
    on one chip; its HLO text."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    from chipbench import serve, spec
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.models import RunCtx, decode_step, init_cache
    from repro.runtime.engine import (ContinuousEngine, EngineBackend,
                                      ServeConfig)

    config = cell.config
    server = dict(cell.traffic["server"],
                  prefix_capacity=config["serving"]["prefix_capacity"])
    ref, adapter = spec.reference(config), spec.program_adapter(config)
    cfg = adapter.program_config(config)
    params = jax.eval_shape(lambda w: serve.program_params(adapter, w, cfg),
                            jax.eval_shape(lambda: ref.make_weights(
                                0, ref.Sizes.from_config(config))))
    sc = ServeConfig(
        arch=cfg.name, num_slots=server["num_slots"],
        prefill_buckets=tuple(server["prefill_buckets"]),
        max_new_tokens=server["max_new_tokens"], eos_id=serve.NO_EOS,
        temperature=0.0, prefill_batch=server["prefill_batch"],
        prefix_cache=server["prefix_cache"],
        prefix_block=server["prefix_block"],
        prefix_capacity=server["prefix_capacity"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_host_mesh(devices=list(topo.devices)[:1])
    rules = shd.trim_rules(shd.SERVE_RULES, mesh)
    ctx = RunCtx(mesh=mesh)
    rep = NamedSharding(mesh, PartitionSpec())

    def decode(p, cache, cur):
        with shd.use_rules(mesh, rules):
            logits, new = decode_step(p, cfg, cache, cur, ctx=ctx)
        return logits[:, -1].astype(jnp.float32), new

    def shapes(batch, per_slot):
        return jax.eval_shape(lambda p: init_cache(
            p, cfg, batch, sc.max_seq, ctx=ctx, pos_per_slot=per_slot),
            params)

    def zeros(batch, per_slot):
        return jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                      shapes(batch, per_slot))

    engine = ContinuousEngine(EngineBackend(
        decode=decode, init_cache=zeros, params=params,
        vocab_size=cfg.vocab_size, place=lambda t: t), sc)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            tree)

    S = sc.num_slots
    row = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=rep)
    return engine._step_fn.lower(
        on_chip(params), on_chip(shapes(S, True)),
        jax.ShapeDtypeStruct((S, 1), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=rep), row, row,
        on_chip(jax.eval_shape(lambda: engine._table))).compile().as_text()


def step_ops(path: str, known: Dict[str, Tuple[str, str]]
             ) -> Tuple[List[Op], int]:
    """The ops of the step program's runs in an ``.xplane.pb``, each with
    the scope its instruction's path names where name and result shape
    match ``known``; and how many matched."""
    from jax.profiler import ProfileData

    ops: List[Op] = []
    matched = 0
    for plane in ProfileData.from_file(path).planes:
        if not trace_mod.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        runs = sorted((float(e.start_ns), float(e.start_ns)
                       + float(e.duration_ns))
                      for e in lines[trace_mod.MODULES_LINE].events
                      if e.name.startswith(STEP_MODULE + "("))
        starts = [r[0] for r in runs]
        for e in lines[trace_mod.OPS_LINE].events:
            s = float(e.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or runs[i][1] < s:
                continue
            name = trace_mod.op_name(e.name)
            shape = e.name.split(" = ", 1)[-1].split(" ", 1)[0]
            scope = UNSCOPED
            if name in known and known[name][0] == shape:
                matched += 1
                scope = scope_of([known[name][1]])
            ops.append((s, s + float(e.duration_ns), name, scope))
    return ops, matched


def offline(cell, xplane: str) -> dict:
    known = instructions(step_text(cell))
    t = trace_mod.from_planes(trace_mod.planes_of(xplane))
    ops, matched = step_ops(xplane, known)
    about = {n: f"{path} {shape}" for n, (shape, path) in known.items()}
    busy = trace_mod.length(trace_mod.union(
        [o[:3] for o in ops], *t.window)) * 1e-9
    return dict(split(ops, *t.window, about=about), window_s=t.window_s,
                step_busy_s=busy, step_ops=len(ops),
                matched_share=matched / max(len(ops), 1))


class KeepingTracer(program_spans.RecordingTracer):
    """``program_spans``'s tracer, which copies the raw trace into
    ``keep`` before its files are removed."""

    keep: Optional[str] = None

    def finish(self, now: float):
        if self._ann is not None:
            self._stop(now)
        if self.dir is not None and self.keep:
            os.makedirs(self.keep, exist_ok=True)
            shutil.copy(trace_mod.find_xplane(self.dir), self.keep)
        return super().finish(now)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--keep")
    ap.add_argument("--xplane")
    args = ap.parse_args(argv)

    from chipbench import device, spec

    cell = spec.load_cell(args.workload)
    if args.xplane:
        print(json.dumps(offline(cell, args.xplane)), flush=True)
        return 0
    if args.seed is None or args.seconds is None or not args.keep:
        ap.error("on the chip: --seed, --seconds and --keep")
    devs = device.tpu_devices(cell.chips)
    peaks = device.peaks(devs[0].device_kind)
    device.compile_cache()

    def make(*a):
        tracer = KeepingTracer(*a)
        tracer.keep = args.keep
        return tracer

    with mock.patch.object(program_spans, "RecordingTracer", make):
        out = program_spans.run(cell, args.seed, args.seconds, True, devs,
                                peaks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
