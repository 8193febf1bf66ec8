"""Mixture-of-Experts FFN with expert parallelism.

The MoE layer is the framework's clearest MapReduce instance (DESIGN.md §2):

    map     : the router assigns each token to top-k experts
    shuffle : tokens travel to expert-owning devices
    reduce  : expert outputs are combined per token, weighted by the gate —
              a weighted-Sum monoid; router load/drop statistics ride along
              as a piggybacked Sum-monoid tuple (one collective, not two).

Two executable strategies (mirroring the paper's naive-vs-combined framing):

* ``replicated`` (baseline) — activations are replicated across the expert
  axis; every expert shard computes the contributions of ITS experts for all
  local tokens and one ``psum`` combines. Wire cost: one psum of (T, D).
* ``a2a`` — GShard-style all_to_all dispatch: each device sends only the
  tokens routed to remote experts (capacity-bounded) and receives them back.
  Wire cost: 2 * T*k/P * D — the combiner-style reduction of shuffle bytes.

Both run inside ``shard_map`` over the expert ('model') axis and are
numerically identical up to capacity drops.

A device may hold only some of the router's experts (``num_held_experts``,
one chip's share of a wider expert-parallel layer): the one-device layer
then routes over all of them and returns the held experts' part, the same
function (:func:`held_experts_part`) the replicated strategy runs per shard.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, ParamBuilder, dense
from ..dist import sharding as shd


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_dense_ffn(pb: ParamBuilder, cfg: ModelConfig, d_ff: Optional[int] = None) -> None:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    if cfg.gated_ffn:
        pb.param("w_gate", (D, F), ("embed", "mlp"), scale=D)
    pb.param("w_up", (D, F), ("embed", "mlp"), scale=D)
    pb.param("w_down", (F, D), ("mlp", "embed"), scale=F)


def init_moe(pb: ParamBuilder, cfg: ModelConfig) -> None:
    """The router over all ``num_experts``; the weights of the held ones."""
    D, F = cfg.d_model, cfg.d_ff_expert
    pb.param("router", (D, cfg.num_experts), ("embed", None), scale=D)
    E = cfg.experts_held
    pb.param("we_gate", (E, D, F), ("expert", "embed", "mlp"), scale=D)
    pb.param("we_up", (E, D, F), ("expert", "embed", "mlp"), scale=D)
    pb.param("we_down", (E, F, D), ("expert", "mlp", "embed"), scale=F)
    if cfg.num_shared_experts > 0:
        shared = pb.child("shared")
        init_dense_ffn(shared, cfg, d_ff=cfg.num_shared_experts * F)


# ---------------------------------------------------------------------------
# dense FFN
# ---------------------------------------------------------------------------

def _act(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(x) if cfg.act_fn == "silu" else jax.nn.gelu(x)


def dense_ffn(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    up = dense(x, p["w_up"])
    if cfg.gated_ffn:
        up = _act(cfg, dense(x, p["w_gate"])) * up
    else:
        up = _act(cfg, up)
    up = shd.act(up, ("batch", "seq", "mlp"))
    out = dense(up, p["w_down"])
    return shd.act(out, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def route(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Token -> (top-k expert ids, gate weights). x: (T, D) flattened tokens.

    Softmax over every expert in float32.  With ``n_group`` > 1 (DeepSeek-
    V2's group-limited greedy), a group scores its best expert, the
    ``topk_group`` best groups are kept and the top-k is taken among their
    experts.  The weights are then renormalised (``norm_topk_prob``) or
    scaled by ``routed_scaling_factor``."""
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        if cfg.num_padded_experts:
            pad = jnp.arange(cfg.num_experts) >= cfg.num_experts - cfg.num_padded_experts
            logits = jnp.where(pad, -1e30, logits)
        scores = jax.nn.softmax(logits, axis=-1)
        if cfg.n_group > 1:
            T = scores.shape[0]
            grouped = scores.reshape(T, cfg.n_group, -1)
            _, keep = jax.lax.top_k(grouped.max(-1), cfg.topk_group)   # (T, g)
            kept = jnp.zeros((T, cfg.n_group), bool).at[
                jnp.arange(T)[:, None], keep].set(True)
            scores = jnp.where(kept[:, :, None], grouped, 0.0).reshape(T, -1)
        top_w, top_e = jax.lax.top_k(scores, cfg.moe_top_k)        # (T, k)
        if cfg.norm_topk_prob:
            top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        elif cfg.routed_scaling_factor != 1.0:
            top_w = top_w * cfg.routed_scaling_factor
        return top_e.astype(jnp.int32), top_w


# the decode-cache leaf in which a model with MoE layers hands the serving
# engine each row's ``row_pairs`` of its last decode step
ROUTE_KEY = "route"


def row_route_counts(cfg: ModelConfig, top_e: jnp.ndarray, B: int) -> jnp.ndarray:
    """(B, 1 + held) int32 per batch row: the (token, expert) pairs routed,
    then those routed to each held expert.  top_e: (B * L, k)."""
    top_e = top_e.reshape(B, -1)
    held = jax.nn.one_hot(top_e, cfg.experts_held, dtype=jnp.int32).sum(1)
    routed = jnp.full((B, 1), top_e.shape[1], jnp.int32)
    return jnp.concatenate([routed, held], axis=1)


def _expert_compute(cfg: ModelConfig, p: Dict, xbuf: jnp.ndarray,
                    group_sizes: jnp.ndarray) -> jnp.ndarray:
    """Grouped SwiGLU over a sorted token buffer via lax.ragged_dot.

    xbuf: (C, D) tokens sorted by expert; group_sizes: (E_local,), summing
    to at most C (rows past the last group are not any expert's)."""
    wg, wu, wd = p["we_gate"], p["we_up"], p["we_down"]
    dt = xbuf.dtype
    h = jax.nn.silu(jax.lax.ragged_dot(xbuf, wg.astype(dt), group_sizes)) \
        * jax.lax.ragged_dot(xbuf, wu.astype(dt), group_sizes)
    return jax.lax.ragged_dot(h, wd.astype(dt), group_sizes)


def held_experts_part(p: Dict, cfg: ModelConfig, xf: jnp.ndarray,
                      top_e: jnp.ndarray, top_w: jnp.ndarray, e0,
                      n_held: int, capacity: Optional[int] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """What experts e0 .. e0 + n_held - 1 (whose weights ``p`` holds) add
    to each token: gate weight x expert SwiGLU, summed over the token's
    pairs routed to them.  Pairs routed to other experts add nothing.

    xf: (T, D); top_e, top_w: (T, k) over the router's experts.  With
    ``capacity`` at most that many pairs are computed (the rest dropped);
    without, every pair is kept.  Returns (out (T, D), per-held-expert
    pairs computed (n_held,), pairs of held experts dropped)."""
    with jax.named_scope("moe.experts"):
        k = top_e.shape[-1]
        local_e = top_e.reshape(-1) - e0
        is_mine = (local_e >= 0) & (local_e < n_held)
        sort_key = jnp.where(is_mine, local_e, n_held)          # others last
        order = jnp.argsort(sort_key)
        if capacity is not None:
            order = order[:capacity]
        C = order.shape[0]
        tok = order // k
        gs = jnp.bincount(sort_key, length=n_held + 1)[:n_held]
        gs = jnp.diff(jnp.minimum(jnp.cumsum(gs), C), prepend=0)
        out_buf = _expert_compute(cfg, p, xf[tok], gs)
        kept = jnp.arange(C) < gs.sum()                         # held, in capacity
        w = top_w.reshape(-1)[order].astype(out_buf.dtype)
        contrib = jnp.where(kept[:, None], out_buf * w[:, None], 0)
        out = jnp.zeros_like(xf).at[tok].add(contrib)
        dropped = (is_mine.sum() - kept.sum()).astype(jnp.int32)
        return out, gs.astype(jnp.int32), dropped


# ---------------------------------------------------------------------------
# single-device reference (also the smoke-test path)
# ---------------------------------------------------------------------------

def moe_ffn_local(p: Dict, cfg: ModelConfig, x: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """MoE forward on one device: route over all the router's experts,
    compute the held experts' part with no pair dropped (every expert, when
    ``num_held_experts`` is 0), add the shared experts."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    top_e, top_w = route(p, cfg, xf)                            # (T,k)
    out, _, dropped = held_experts_part(p, cfg, xf, top_e, top_w, 0,
                                        cfg.experts_held)
    load = jnp.bincount(top_e.reshape(-1), length=cfg.num_experts)
    stats = {"expert_load": load, "dropped": dropped,
             "row_pairs": row_route_counts(cfg, top_e, B)}
    if cfg.num_shared_experts > 0:
        out = out + dense_ffn(p["shared"], cfg, x).reshape(-1, D)
    return out.reshape(B, S, D), stats


# ---------------------------------------------------------------------------
# expert-parallel strategies (shard_map over the expert axis)
# ---------------------------------------------------------------------------

def _divisible_batch_axes(mesh, batch_axes, B: int):
    """Keep only mesh axes present AND dividing the batch dim (B=1 decode)."""
    kept, total = [], 1
    for a in batch_axes:
        if a in mesh.shape and B % (total * mesh.shape[a]) == 0:
            kept.append(a)
            total *= mesh.shape[a]
    return tuple(kept)


def _capacity(cfg: ModelConfig, T: int, P: int) -> int:
    """Per-device token-buffer capacity (multiple of 8 for lane alignment)."""
    c = int(math.ceil(T * cfg.moe_top_k * cfg.moe_capacity_factor / P))
    return max(8, ((c + 7) // 8) * 8)


def moe_ffn_replicated(p: Dict, cfg: ModelConfig, x: jnp.ndarray, mesh,
                       *, axis_name: str = "model",
                       batch_axes: Tuple[str, ...] = ("pod", "data")
                       ) -> Tuple[jnp.ndarray, Dict]:
    """Baseline EP: tokens replicated over the expert axis; each shard
    computes only its experts' contributions; one psum combines (the
    weighted-Sum monoid across expert shards)."""
    B, S, D = x.shape
    E = cfg.num_experts
    P = mesh.shape[axis_name]
    assert cfg.experts_held % P == 0, (cfg.experts_held, P)
    E_local = cfg.experts_held // P
    batch_axes = _divisible_batch_axes(mesh, batch_axes, B)
    Pspec = jax.sharding.PartitionSpec

    def body(xl, router, wg, wu, wd):
        pl = {"router": router, "we_gate": wg, "we_up": wu, "we_down": wd}
        Bl, Sl = xl.shape[0], xl.shape[1]
        xf = xl.reshape(-1, D)
        C = _capacity(cfg, Bl * Sl, P)
        top_e, top_w = route(pl, cfg, xf)                       # identical on all shards
        e0 = jax.lax.axis_index(axis_name) * E_local
        out, gs, dropped = held_experts_part(pl, cfg, xf, top_e, top_w, e0,
                                             E_local, capacity=C)
        out = jax.lax.psum(out, axis_name)                      # the monoid combine
        stat_axes = (axis_name,) + batch_axes                   # total over fleet
        load = jax.lax.psum(
            jnp.zeros((E,), jnp.int32).at[e0 + jnp.arange(E_local)].set(gs),
            stat_axes)
        dropped = jax.lax.psum(dropped, stat_axes)
        return (out.reshape(Bl, Sl, D), load, dropped,
                row_route_counts(cfg, top_e, Bl))

    xspec = Pspec(batch_axes if batch_axes else None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, Pspec(), Pspec(axis_name), Pspec(axis_name), Pspec(axis_name)),
        out_specs=(xspec, Pspec(), Pspec(), xspec),
        check_vma=False)
    out, load, dropped, rows = fn(x, p["router"], p["we_gate"], p["we_up"],
                                  p["we_down"])
    stats = {"expert_load": load, "dropped": dropped, "row_pairs": rows}
    if cfg.num_shared_experts > 0:
        out = out + dense_ffn(p["shared"], cfg, x)
    return out, stats


def moe_ffn_a2a(p: Dict, cfg: ModelConfig, x: jnp.ndarray, mesh,
                *, axis_name: str = "model",
                batch_axes: Tuple[str, ...] = ("pod", "data")
                ) -> Tuple[jnp.ndarray, Dict]:
    """GShard-style dispatch: all_to_all tokens to expert owners and back.

    Each device packs, for every destination shard d, a capacity-C buffer of
    its tokens routed to d's experts. One all_to_all moves the buffers; the
    owner runs its experts; a second all_to_all returns outputs; a local
    weighted scatter-add (the Sum monoid) combines the k contributions.
    Wire bytes: 2 * P_send * C * D vs the replicated strategy's psum of the
    full (T, D) — the combiner-vs-naive byte reduction, measured in §Perf.

    The token set is PARTITIONED over the expert axis (seq-sharded into the
    shard_map) so each device routes a disjoint T/P slice — without this the
    expert axis holds replicated copies and every expert receives each token
    P times (§Perf iteration 6: the first a2a attempt cost 13x compute).
    Requires S % P == 0; smaller batches fall back to `replicated`.
    """
    B, S, D = x.shape
    E = cfg.num_experts
    P = mesh.shape[axis_name]
    assert E % P == 0, (E, P)
    assert cfg.experts_held == E, "a2a dispatch needs every expert held"
    if S % P != 0:
        return moe_ffn_replicated(p, cfg, x, mesh, axis_name=axis_name,
                                  batch_axes=batch_axes)
    E_local = E // P
    batch_axes = _divisible_batch_axes(mesh, batch_axes, B)
    Pspec = jax.sharding.PartitionSpec

    def body(xl, router, wg, wu, wd):
        pl = {"router": router}
        Bl, Sl = xl.shape[0], xl.shape[1]
        xf = xl.reshape(-1, D)
        T = Bl * Sl
        k = cfg.moe_top_k
        # per-destination capacity: tokens I send to each of P shards
        C = _capacity(cfg, T, P)
        top_e, top_w = route(pl, cfg, xf)                       # (T,k)
        flat_e = top_e.reshape(-1)                              # (T*k,)
        dst = flat_e // E_local                                 # owning shard
        # stable sort by destination; position within destination = rank
        order = jnp.argsort(dst, stable=True)
        dst_sorted = dst[order]
        # rank within each destination group
        idx = jnp.arange(dst_sorted.shape[0], dtype=jnp.int32)
        seg_start = jnp.full((P,), dst_sorted.shape[0], jnp.int32).at[
            dst_sorted].min(idx, mode="drop")
        rank = idx - seg_start[dst_sorted]
        keep = rank < C                                         # capacity drop
        tok_sorted = order // k
        slot = dst_sorted * C + rank                            # flat send slot
        send_x = jnp.zeros((P * C, D), xl.dtype).at[
            jnp.where(keep, slot, P * C)].set(xf[tok_sorted], mode="drop")
        send_e = jnp.full((P * C,), E_local, jnp.int32).at[
            jnp.where(keep, slot, P * C)].set(
                (flat_e[order] % E_local).astype(jnp.int32), mode="drop")
        # shuffle: (P, C, D) -> one buffer from each source shard
        recv_x = jax.lax.all_to_all(send_x.reshape(P, C, D), axis_name,
                                    split_axis=0, concat_axis=0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e.reshape(P, C), axis_name,
                                    split_axis=0, concat_axis=0, tiled=False)
        # local expert compute over the received (P*C) tokens
        rx = recv_x.reshape(P * C, D)
        re = recv_e.reshape(P * C)
        rorder = jnp.argsort(re)                                # sort by local expert
        gs_full = jnp.bincount(re, length=E_local + 1)
        wd_pad = jnp.concatenate([wd, jnp.zeros_like(wd[:1])], 0)
        wg_pad = jnp.concatenate([wg, jnp.zeros_like(wg[:1])], 0)
        wu_pad = jnp.concatenate([wu, jnp.zeros_like(wu[:1])], 0)
        out_sorted = _expert_compute(
            cfg, {"we_gate": wg_pad, "we_up": wu_pad, "we_down": wd_pad},
            rx[rorder], gs_full)
        out_r = jnp.zeros_like(rx).at[rorder].set(out_sorted)
        # shuffle back
        back = jax.lax.all_to_all(out_r.reshape(P, C, D), axis_name,
                                  split_axis=0, concat_axis=0, tiled=False)
        back = back.reshape(P * C, D)
        # combine: weighted scatter-add of the k expert contributions
        w_sorted = top_w.reshape(-1)[order].astype(xl.dtype)
        contrib = back[jnp.where(keep, slot, 0)] * (
            w_sorted * keep.astype(xl.dtype))[:, None]
        out = jnp.zeros_like(xf).at[tok_sorted].add(contrib)
        load = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        dropped = (~keep).sum().astype(jnp.int32)
        # tokens are partitioned over (batch axes x expert axis): total stats
        stat_axes = batch_axes + (axis_name,)
        load = jax.lax.psum(load, stat_axes)
        dropped = jax.lax.psum(dropped, stat_axes)
        rows = jax.lax.psum(row_route_counts(cfg, top_e, Bl), axis_name)
        return out.reshape(Bl, Sl, D), load, dropped, rows

    # tokens partitioned: batch over the data axes AND seq over the expert axis
    xspec = Pspec(batch_axes if batch_axes else None, axis_name)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, Pspec(), Pspec(axis_name), Pspec(axis_name),
                  Pspec(axis_name)),
        out_specs=(xspec, Pspec(), Pspec(), Pspec(xspec[0])),
        check_vma=False)
    out, load, dropped, rows = fn(x, p["router"], p["we_gate"], p["we_up"],
                                  p["we_down"])
    stats = {"expert_load": load, "dropped": dropped, "row_pairs": rows}
    if cfg.num_shared_experts > 0:
        out = out + dense_ffn(p["shared"], cfg, x)
    return out, stats


def moe_ffn(p: Dict, cfg: ModelConfig, x: jnp.ndarray, *, mesh=None,
            impl: str = "replicated", axis_name: str = "model") -> Tuple[jnp.ndarray, Dict]:
    """Dispatch to the configured expert-parallel strategy.

    -> (out, stats): ``expert_load`` (num_experts,) and ``dropped`` () sum
    over the tokens; ``row_pairs`` (B, 1 + held) is each batch row's
    :func:`row_route_counts`."""
    if mesh is None or axis_name not in getattr(mesh, "shape", {}) \
            or mesh.shape.get(axis_name, 1) == 1 \
            or cfg.experts_held % max(mesh.shape.get(axis_name, 1), 1) != 0:
        return moe_ffn_local(p, cfg, x)
    if impl == "replicated":
        return moe_ffn_replicated(p, cfg, x, mesh, axis_name=axis_name)
    if impl == "a2a":
        return moe_ffn_a2a(p, cfg, x, mesh, axis_name=axis_name)
    raise ValueError(impl)
