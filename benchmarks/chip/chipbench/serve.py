"""Serve cells: the continuous-batching engine under an open-loop mix.

Nothing here knows a model.  The configuration's ``reference`` key names
the plain reference (``reference/<model>.py``), and its program adapter
(``programs/<model>.py``) maps the file onto the program: see
``chipbench/spec.py`` for what each provides.

Set-up makes the weights on the chip in one jitted call from the seed (the
reference's generator, in the type they are served in), lays them out as
the program's parameters through the adapter, holds that tree to the
program's own shapes, wires it into the program's engine the way
``repro.launch.serve.build_engine`` does, and warms every program the
mix's shapes use.  The window then submits each request when it is due,
steps the engine whenever it has work, and times every request from its
due time on the engine's own clock; each decode step carries the model
FLOPs the reference counts for its live requests.  Requests due in the
window are served to the end after it closes.  The check compares a sample
of the served requests with the reference's float32 logits.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from chipbench import traffic as traffic_mod
from chipbench.device import CompileCounter, memory_peak_bytes
from chipbench.record import Run, Span, WindowTracer

# requests still unfinished this long after the window closed count failed
DRAIN_LIMIT_S = 120.0
# the engine never stops at an end-of-sequence id: the mix sets each
# request's output length
NO_EOS = -1


def program_params(adapter, weights: dict, cfg) -> dict:
    """The generated weights in the program's parameter tree, as ``adapter``
    lays them out; its structure, shapes and dtypes must be exactly the
    program's."""
    from repro.models import param_shapes

    params = adapter.program_params(weights, cfg)
    want = param_shapes(cfg)
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    need = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != need:
        raise ValueError(f"weights do not match the program's parameters: "
                         f"{got} vs {need}")
    return params


# faults planted under the timed path by the tests (never by a run)
def _fault(kind: str, logits, cache_in, cache_out):
    if kind == "state_unchanged":
        return logits, cache_in
    if kind == "half_batch":
        keep = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
        return jnp.where(keep[:, None], logits, 0.0), cache_out
    if kind == "token_altered":
        pos = jnp.broadcast_to(cache_in["pos"], logits.shape[:1])
        bump = jnp.where(pos % 5 == 3, 1e4, 0.0)
        return logits.at[:, 7].add(bump), cache_out
    raise ValueError(f"unknown fault {kind!r}")


def make_engine(cfg, params, server: dict, devs, fault: Optional[str] = None):
    """The program's ContinuousEngine over ``params`` on ``devs``."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.dist import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.models import RunCtx, decode_step, init_cache, positional_cache
    from repro.runtime.engine import (ContinuousEngine, EngineBackend,
                                      ServeConfig)

    sc = ServeConfig(
        arch=cfg.name, num_slots=server["num_slots"],
        prefill_buckets=tuple(server["prefill_buckets"]),
        max_new_tokens=server["max_new_tokens"], eos_id=NO_EOS,
        temperature=0.0, prefill_batch=server["prefill_batch"],
        prefix_cache=server["prefix_cache"],
        prefix_block=server["prefix_block"],
        prefix_capacity=server["prefix_capacity"])
    mesh = make_host_mesh(devices=devs)
    rules = shd.trim_rules(shd.SERVE_RULES, mesh)
    ctx = RunCtx(mesh=mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    params = jax.device_put(params, rep)

    def decode(p, cache, cur):
        with shd.use_rules(mesh, rules):
            logits, new = decode_step(p, cfg, cache, cur, ctx=ctx)
        logits = logits[:, -1].astype(jnp.float32)
        if fault is not None:
            logits, new = _fault(fault, logits, cache, new)
        return logits, new

    def make_cache(batch: int, per_slot: bool):
        return init_cache(params, cfg, batch, sc.max_seq, ctx=ctx,
                          pos_per_slot=per_slot)

    backend = EngineBackend(decode=decode, init_cache=make_cache,
                            params=params, vocab_size=cfg.vocab_size,
                            prefix_sharing=positional_cache(cfg),
                            place=lambda t: jax.device_put(t, rep))
    return ContinuousEngine(backend, sc)


@dataclasses.dataclass
class Served:
    """One request due in the window, as the harness saw it served."""

    req: traffic_mod.ServeRequest
    due: float
    submitted: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    hit_tokens: int = 0
    logprob_sum: Optional[float] = None
    done: bool = False
    failed: bool = False

    @property
    def ok(self) -> bool:
        return self.done and not self.failed


def _nearest_rank(values: List[float], q: float) -> float:
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


class ServeCell:
    """One configuration under one serve mix, for one seed."""

    def __init__(self, config: dict, mix: dict, seed: int, devs, *,
                 fault: Optional[str] = None):
        self.config, self.mix, self.seed, self.devs = config, mix, seed, devs
        self.model = spec.reference(config)
        adapter = spec.program_adapter(config)
        self.sizes = self.model.Sizes.from_config(config)
        server = dict(mix["server"],
                      prefix_capacity=config["serving"]["prefix_capacity"])
        self.server = server
        cfg = adapter.program_config(config)
        weights = self.model.make_weights(seed, self.sizes, devs[0])
        params = program_params(adapter, weights, cfg)
        del weights
        self.engine = make_engine(cfg, params, server, devs, fault)

    # -- set-up --------------------------------------------------------------

    def warm_up(self) -> None:
        """Run every program the mix can reach: each (k, bucket) prefill,
        slot write and prefix gather, the decode step, the prefix cache's
        stats fold and, by filling the trie past its capacity, eviction.
        The mix's shared prefixes (system prompts) are among the prompts
        prefilled, so the window starts as a server whose system prompts
        are resident, with their first user turn of 16 tokens."""
        eng, srv = self.engine, self.server
        rng = np.random.default_rng([self.seed, 2])
        vocab = self.sizes.vocab_size
        ladder = eng.config.prefill_k_ladder
        shared = [p + tuple(rng.integers(1, vocab, 16).tolist())
                  for p in traffic_mod.shared_prefixes(self.mix, self.seed,
                                                       vocab)]
        for b in srv["prefill_buckets"]:
            mine = [p for p in shared if eng.config.bucket_for(len(p)) == b]
            # k = 4, 2, 1 chunks, then what is left of the shared prefixes
            first = mine[:sum(ladder)]
            first += [tuple(rng.integers(1, vocab, b).tolist())
                      for _ in range(sum(ladder) - len(first))]
            for group in (first, mine[sum(ladder):]):
                for prompt in group:
                    eng.submit(prompt, max_new_tokens=2)
                self._drain()
        prefix = eng.prefix
        biggest = srv["prefill_buckets"][-1]
        while prefix is not None and prefix.stats.evictions == 0:
            for _ in range(ladder[-1]):
                eng.submit(rng.integers(1, vocab, biggest).tolist(),
                           max_new_tokens=2)
            self._drain()

    def _drain(self) -> None:
        for _ in self.engine.run():
            pass

    def requests(self, seconds: float) -> List[traffic_mod.ServeRequest]:
        return traffic_mod.serve_requests(self.mix, self.seed, seconds,
                                          self.sizes.vocab_size)

    def counters(self) -> Dict[str, float]:
        eng = self.engine
        out = {"prefill_calls": eng.stats.prefill_calls,
               "steps": eng.stats.steps,
               "generated_tokens": eng.stats.generated_tokens}
        if eng.prefix is not None:
            st = eng.prefix.stats
            out.update(hit_tokens=st.hit_tokens,
                       prompt_tokens=st.prompt_tokens,
                       evictions=st.evictions)
        return out

    # -- the window ----------------------------------------------------------

    def run_window(self, reqs, seconds: float, tracer: WindowTracer):
        """Offer ``reqs`` on their schedule, then serve them to the end.
        Returns (served, spans, counters over the window)."""
        eng = self.engine
        slots = eng.config.num_slots
        clock = time.perf_counter
        spans: List[Span] = []
        by_uid: Dict[int, Served] = {}
        live: Dict[int, Served] = {}
        t0 = clock()
        served = [Served(req=r, due=t0 + r.offset_s) for r in reqs]
        before = self.counters()
        after = None
        i = 0
        while True:
            now = clock()
            tracer.poll(now, t0)
            if after is None and now - t0 >= seconds:
                after = dict(self.counters(), pending_at_close=eng.pending)
            if now - t0 > seconds + DRAIN_LIMIT_S + tracer.stop_s:
                break
            while i < len(served) and served[i].due <= now:
                rec = served[i]
                rec.submitted = now
                try:
                    uid = eng.submit(rec.req.prompt,
                                     max_new_tokens=rec.req.max_new)
                except ValueError:
                    rec.failed = True
                else:
                    by_uid[uid] = rec
                i += 1
            if eng.pending or eng.num_active:
                admit = eng.pending > 0 and eng.num_active < slots
                attrs = {}
                if not admit:
                    attrs["flops"] = self.model.decode_flops(
                        self.config, [len(r.req.prompt) + len(r.tokens)
                                      for r in live.values()])
                name = "admit_step" if admit else "decode_step"
                s = clock()
                with jax.profiler.TraceAnnotation("bench." + name):
                    events = eng.step()
                spans.append(Span(name, s, clock(), attrs))
                for ev in events:
                    rec = by_uid[ev.uid]
                    if ev.kind == "token":
                        rec.tokens.append(int(ev.token))
                        rec.times.append(ev.time_s)
                        live[ev.uid] = rec
                    elif ev.kind == "cache":
                        rec.hit_tokens = int(ev.hit_tokens)
                    elif ev.kind == "done":
                        live.pop(ev.uid, None)
                        rec.done = True
                        rec.logprob_sum = float(ev.result.logprob_sum)
                        if len(rec.tokens) != rec.req.max_new:
                            rec.failed = True
            elif i < len(served):
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(served[i].due - now, 0.002)))
            else:
                break
        end = clock()
        tracer.poll(end, t0)
        if after is None:
            after = dict(self.counters(), pending_at_close=0)
        window = {k: after[k] - before.get(k, 0) for k in after}
        window["late_max_s"] = max((r.submitted - r.due for r in served
                                    if r.submitted is not None), default=0.0)
        window["drain_s"] = end - t0 - seconds
        return served, spans, window

    # -- end-to-end metrics --------------------------------------------------

    @staticmethod
    def end_to_end(served: List[Served]) -> Dict[str, float]:
        ttft = [r.times[0] - r.due if r.ok else math.inf for r in served]
        out = {}
        if ttft:
            for q in (50, 75, 80, 90, 95, 99):
                out[f"ttft_p{q}_ms"] = _nearest_rank(ttft, q / 100) * 1e3
        span = sum(r.times[-1] - r.times[0] for r in served
                   if r.ok and len(r.times) > 1)
        steps = sum(len(r.times) - 1 for r in served
                    if r.ok and len(r.times) > 1)
        if steps:
            out["tpot_ms"] = span / steps * 1e3
        return out

    # -- correctness ---------------------------------------------------------

    def sample(self, served: List[Served], n: int) -> List[Served]:
        """A sample drawn from the seed: the request with the most served
        tokens, one cold and one warm prefill where there are any, and the
        rest at random."""
        done = [r for r in served if r.ok]
        if not done:
            return []
        rng = np.random.default_rng([self.seed, 3])
        pick = [max(done, key=lambda r: (len(r.tokens), len(r.req.prompt)))]
        for group in ([r for r in done if r.hit_tokens == 0],
                      [r for r in done if r.hit_tokens > 0]):
            group = [r for r in group if r not in pick]
            if group:
                pick.append(group[int(rng.integers(len(group)))])
        rest = [r for r in done if r not in pick]
        extra = rng.permutation(len(rest))[:max(0, n - len(pick))]
        return pick + [rest[j] for j in sorted(extra)]

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        self.engine = None
        gc.collect()

    def compare(self, sample: List[Served], *, control: bool = False
                ) -> Dict[str, float]:
        """The widest gap by which a served token's logit lies below the
        reference's best, and the widest relative gap of a request's
        logprob sum.  With ``control`` also the same numbers for the fp8
        reference put in the program's place."""
        s, model = self.sizes, self.model
        weights = model.make_weights(self.seed, s, self.devs[0])
        length = self.server["prefill_buckets"][-1] \
            + self.server["max_new_tokens"]
        width = self.server["max_new_tokens"]
        out = {"token_gap": 0.0, "logprob_rel": 0.0}
        if control:
            out.update(control_token_gap=0.0, control_logprob_rel=0.0)
        for rec in sample:
            toks = np.asarray(rec.tokens)
            rows = np.arange(len(toks))
            ref = model.served_logits(weights, rec.req.prompt, rec.tokens, s,
                                      length, width)
            best = ref.max(axis=-1)
            ref_sum = float(_log_softmax(ref)[rows, toks].sum())
            out["token_gap"] = max(out["token_gap"],
                                   float((best - ref[rows, toks]).max()))
            out["logprob_rel"] = max(
                out["logprob_rel"],
                abs(rec.logprob_sum - ref_sum) / abs(ref_sum))
            if control:
                low = model.served_logits(weights, rec.req.prompt, rec.tokens,
                                          s, length, width, fp8=True)
                first = low.argmax(axis=-1)
                low_sum = float(_log_softmax(low)[rows, toks].sum())
                out["control_token_gap"] = max(
                    out["control_token_gap"],
                    float((best - ref[rows, first]).max()))
                out["control_logprob_rel"] = max(
                    out["control_logprob_rel"],
                    abs(low_sum - ref_sum) / abs(ref_sum))
        out["sampled_requests"] = len(sample)
        out["sampled_tokens"] = sum(len(r.tokens) for r in sample)
        return out


def _log_softmax(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    m = x.max(axis=-1, keepdims=True)
    return x - m - np.log(np.exp(x - m).sum(axis=-1, keepdims=True))


def run(cell, seed: int, seconds: float, trace: bool, devs, peaks,
        t_start: float, log) -> dict:
    """One run of a serve cell: the contract's fields, before the device
    block and the metrics are chosen."""
    mix = cell.traffic
    compiles = CompileCounter()
    sc = ServeCell(cell.config, mix, seed, devs)
    sc.warm_up()
    reqs = sc.requests(seconds)
    tracer = WindowTracer(trace, seconds)
    setup_s = time.perf_counter() - t_start
    in_setup = compiles.count
    served, spans, window = sc.run_window(reqs, seconds, tracer)
    log(f"serve: {len(reqs)} requests due in {seconds} s; counters "
        f"{window}; compiles in set-up {in_setup}, in the window "
        f"{compiles.count - in_setup}")
    log(f"serve: generator lateness max {window['late_max_s'] * 1e3:.3f} ms")
    trace_obj = tracer.finish(time.perf_counter())
    mem = memory_peak_bytes(devs)
    e2e = dict(sc.end_to_end(served), setup_s=setup_s)
    log(f"serve: end-to-end {e2e}")
    runrec = Run(config=cell.config, peaks=peaks,
                 chips=len(devs), spans=spans, counters=window,
                 trace=trace_obj, traced=tracer.host)
    sample = sc.sample(served, int(mix["check"]["sample_requests"]))
    sc.release()
    numbers = sc.compare(sample)
    log(f"serve: compared {numbers.pop('sampled_requests')} requests, "
        f"{numbers.pop('sampled_tokens')} served tokens, with the reference")
    return {"attempted": len(served),
            "failed": sum(not r.ok for r in served),
            "e2e": e2e, "run": runrec, "numbers": numbers,
            "memory_peak_bytes": mem}
