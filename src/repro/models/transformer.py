"""Model assembly: the period-scanned transformer covering all 10 archs.

A config's ``layer_pattern`` (e.g. jamba's ``(m,m,m,m,attn,m,m,m)``) is the
repeating *period*; the stack is executed as

    prelude layers (unrolled; DeepSeek's first-k-dense)
    -> lax.scan over num_periods, each step running one full period
    -> remainder layers (unrolled; gemma3's 26 = 4*6 + 2)

Parameters for the scanned region are stacked over periods (MaxText-style),
keeping HLO size O(period) instead of O(layers). Each scanned period is
rematerialized (jax.checkpoint) so live activations are O(period) too.

Decode runs the same program with per-slot caches carried through the scan
(KV for attention, latent for MLA, (h, conv) for mamba, (C, n) for mLSTM,
(h, c) for sLSTM, projected context-KV for cross-attention).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, ParamBuilder, dense, embed_lookup, rms_norm
from . import attention as attn
from . import moe as moe_mod
from .moe import ROUTE_KEY
from . import ssm
from ..dist import sharding as shd

Pytree = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(pb: ParamBuilder, cfg: ModelConfig, ltype: str, ftype: str) -> None:
    pb.param("norm1", (cfg.d_model,), ("embed",), init="ones")
    mix = pb.child("mix")
    if ltype in ("attn", "local"):
        if cfg.use_mla:
            attn.init_mla(mix, cfg)
        else:
            attn.init_attn(mix, cfg)
    elif ltype == "xattn":
        attn.init_cross_attn(mix, cfg)
    elif ltype == "mamba":
        ssm.init_mamba(mix, cfg)
    elif ltype == "mlstm":
        ssm.init_mlstm(mix, cfg)
    elif ltype == "slstm":
        ssm.init_slstm(mix, cfg)
    else:
        raise ValueError(ltype)
    if cfg.decoder_cross_attn and ltype in ("attn", "local"):
        xa = pb.child("xattn")
        pb.param("norm_x", (cfg.d_model,), ("embed",), init="ones")
        attn.init_cross_attn(xa, cfg)
    if ftype != "none":
        pb.param("norm2", (cfg.d_model,), ("embed",), init="ones")
        f = pb.child("ffn")
        if ftype == "dense":
            moe_mod.init_dense_ffn(f, cfg)
        elif ftype == "moe":
            moe_mod.init_moe(f, cfg)
        else:
            raise ValueError(ftype)


def _layer_plan(cfg: ModelConfig) -> Tuple[List[Tuple[str, str]], List[Tuple[str, str]], List[Tuple[str, str]]]:
    """-> (prelude, period_slots, remainder) lists of (ltype, ftype)."""
    P = cfg.period
    pre = cfg.prelude_dense_layers
    assert pre % P == 0 or P == 1 or pre == 0, "prelude must align with period"
    types = [(cfg.block_type(i), cfg.ffn_type(i)) for i in range(cfg.num_layers)]
    prelude = types[:pre]
    rest = types[pre:]
    n_main = (len(rest) // P) * P
    period_slots = rest[:P] if n_main else []
    remainder = rest[n_main:]
    return prelude, period_slots, remainder


def _num_periods(cfg: ModelConfig) -> int:
    return (cfg.num_layers - cfg.prelude_dense_layers) // cfg.period


def init_params(cfg: ModelConfig, key: Optional[jax.Array], *,
                abstract: bool = False) -> Tuple[Pytree, Pytree]:
    """Build (params, logical_axes). abstract=True builds ShapeDtypeStructs
    (no allocation — the dry-run path)."""
    pb = ParamBuilder(key, cfg.dtype, abstract=abstract)
    pb.param("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
             scale=cfg.d_model)
    prelude, period_slots, remainder = _layer_plan(cfg)
    n_periods = _num_periods(cfg)

    for i, (lt, ft) in enumerate(prelude):
        _init_layer(pb.child(f"prelude_{i}"), cfg, lt, ft)

    if period_slots:
        # one period's params, then stacked over periods via vmapped init
        def init_one_period(k):
            sub = ParamBuilder(k, cfg.dtype, abstract=abstract)
            for j, (lt, ft) in enumerate(period_slots):
                _init_layer(sub.child(f"slot_{j}"), cfg, lt, ft)
            return sub.params, sub.axes

        if abstract:
            one, one_axes = init_one_period(None)
            stacked = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((n_periods,) + s.shape, s.dtype), one)
        else:
            keys = jax.random.split(pb._next_key(), n_periods)
            stacked = jax.vmap(lambda k: init_one_period(k)[0])(keys)
            one_axes = init_one_period(keys[0])[1]
        pb.params["layers"] = stacked
        pb.axes["layers"] = jax.tree_util.tree_map(
            lambda ax: ("layers",) + tuple(ax), one_axes,
            is_leaf=lambda x: isinstance(x, tuple) and (
                len(x) == 0 or isinstance(x[0], (str, type(None)))))

    for i, (lt, ft) in enumerate(remainder):
        _init_layer(pb.child(f"rem_{i}"), cfg, lt, ft)

    pb.param("final_norm", (cfg.d_model,), ("embed",), init="ones")
    if not cfg.tie_embeddings:
        pb.param("lm_head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                 scale=cfg.d_model)

    if cfg.encoder_layers > 0:  # whisper encoder (conv frontend is a stub)
        enc = pb.child("encoder")
        enc_cfg = dataclasses.replace(cfg, decoder_cross_attn=False,
                                      num_layers=cfg.encoder_layers)
        for i in range(cfg.encoder_layers):
            _init_layer(enc.child(f"layer_{i}"), enc_cfg, "attn", "dense")
        enc.param("final_norm", (cfg.d_model,), ("embed",), init="ones")

    return pb.params, pb.axes


def param_axes(cfg: ModelConfig) -> Pytree:
    """Logical-axes pytree without materializing params."""
    return init_params(cfg, None, abstract=True)[1]


def param_shapes(cfg: ModelConfig) -> Pytree:
    """ShapeDtypeStruct pytree without materializing params (dry-run path)."""
    return init_params(cfg, None, abstract=True)[0]


# ---------------------------------------------------------------------------
# layer application (training / prefill)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RunCtx:
    """Execution-context knobs threaded through the stack (static)."""
    mesh: Any = None
    moe_impl: str = "replicated"
    attn_chunk: Optional[int] = None    # chunked-attention KV chunk (AttnState)
    ce_chunk: int = 0                   # chunked cross-entropy (0 = off)
    remat: str = "full"                 # full | none
    decode_impl: str = "dense"          # dense | flash (sharded-KV decode)


def _apply_ffn(p: Dict, cfg: ModelConfig, ftype: str, x: jnp.ndarray,
               ctx: RunCtx, stats: Optional[Dict],
               counts: Optional[jnp.ndarray] = None):
    """-> (x, stats, counts).  A MoE layer adds its router statistics to
    ``stats`` and, where the decode path passes them, each batch row's
    routed pairs to ``counts`` (its ``row_pairs``)."""
    if ftype == "none":
        return x, stats, counts
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    if ftype == "dense":
        out = moe_mod.dense_ffn(p["ffn"], cfg, h)
    else:
        out, s = moe_mod.moe_ffn(p["ffn"], cfg, h, mesh=ctx.mesh, impl=ctx.moe_impl)
        rows = s.pop("row_pairs")
        if stats is not None:
            stats = jax.tree_util.tree_map(jnp.add, stats, s)
        if counts is not None:
            counts = counts + rows
    return x + out, stats, counts


def _apply_layer(p: Dict, cfg: ModelConfig, ltype: str, ftype: str,
                 x: jnp.ndarray, positions: jnp.ndarray,
                 context: Optional[jnp.ndarray], ctx: RunCtx,
                 stats: Optional[Dict], *, causal: bool = True):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if ltype in ("attn", "local"):
        window = cfg.sliding_window if ltype == "local" else None
        if cfg.use_mla:
            mixed = attn.mla_attention(p["mix"], cfg, h, positions)
        else:
            mixed = attn.attention(p["mix"], cfg, h, positions, window=window,
                                   chunk_size=ctx.attn_chunk) if causal else \
                attn.attention_bidir(p["mix"], cfg, h, positions)
    elif ltype == "xattn":
        kv = attn.cross_kv(p["mix"], cfg, context)
        mixed = attn.cross_attention(p["mix"], cfg, h, kv, gated=True)
    elif ltype == "mamba":
        mixed = ssm.mamba_mix(p["mix"], cfg, h)
    elif ltype == "mlstm":
        mixed = ssm.mlstm_mix(p["mix"], cfg, h)
    elif ltype == "slstm":
        mixed = ssm.slstm_mix(p["mix"], cfg, h)
    else:
        raise ValueError(ltype)
    x = x + mixed
    if cfg.decoder_cross_attn and ltype in ("attn", "local") and context is not None:
        h = rms_norm(x, p["norm_x"], cfg.norm_eps)
        kv = attn.cross_kv(p["xattn"], cfg, context)
        x = x + attn.cross_attention(p["xattn"], cfg, h, kv)
    x, stats, _ = _apply_ffn(p, cfg, ftype, x, ctx, stats)
    return x, stats


def _zero_stats(cfg: ModelConfig) -> Optional[Dict]:
    if cfg.num_experts == 0:
        return None
    return {"expert_load": jnp.zeros((cfg.num_experts,), jnp.int32),
            "dropped": jnp.zeros((), jnp.int32)}


def forward(params: Pytree, cfg: ModelConfig, tokens: jnp.ndarray, *,
            context: Optional[jnp.ndarray] = None,
            ctx: RunCtx = RunCtx()) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Token ids -> final hidden states (B, S, D) + MoE stats (Sum monoid)."""
    B, S = tokens.shape
    x = embed_lookup(params["embed"], tokens).astype(cfg.dtype)
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    x = shd.act(x, ("batch", "seq", "embed"))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    stats = _zero_stats(cfg)

    if cfg.encoder_layers > 0 and context is not None:
        context = encode(params, cfg, context, ctx=ctx)

    prelude, period_slots, remainder = _layer_plan(cfg)
    for i, (lt, ft) in enumerate(prelude):
        x, stats = _apply_layer(params[f"prelude_{i}"], cfg, lt, ft, x,
                                positions, context, ctx, stats)

    if period_slots:
        def period_body(carry, slot_params):
            x, stats = carry
            for j, (lt, ft) in enumerate(period_slots):
                x, stats = _apply_layer(slot_params[f"slot_{j}"], cfg, lt, ft,
                                        x, positions, context, ctx, stats)
            return (x, stats), None

        body = period_body
        if ctx.remat == "full":
            body = jax.checkpoint(period_body, prevent_cse=True)
        (x, stats), _ = jax.lax.scan(body, (x, stats), params["layers"])

    for i, (lt, ft) in enumerate(remainder):
        x, stats = _apply_layer(params[f"rem_{i}"], cfg, lt, ft, x,
                                positions, context, ctx, stats)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, stats


def encode(params: Pytree, cfg: ModelConfig, features: jnp.ndarray, *,
           ctx: RunCtx = RunCtx()) -> jnp.ndarray:
    """Whisper encoder over precomputed frame embeddings (B, S_enc, D).

    Bidirectional attention; sinusoidal positions added to the stub features.
    """
    enc = params["encoder"]
    B, S, D = features.shape
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(D // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * dim / D)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    x = (features + pe.astype(features.dtype)).astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    enc_cfg = dataclasses.replace(cfg, decoder_cross_attn=False)
    for i in range(cfg.encoder_layers):
        x, _ = _apply_layer(enc[f"layer_{i}"], enc_cfg, "attn", "dense", x,
                            positions, None, ctx, None, causal=False)
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def _ce_from_logits(logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    loss = (lse - gold) * mask
    correct = (jnp.argmax(logits, axis=-1) == labels) & (mask > 0)
    return loss.sum(), correct.sum().astype(jnp.int32)


def unembed(params: Pytree, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jax.lax.dot_general(h, w.astype(h.dtype),
                                 (((h.ndim - 1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return shd.act(logits, ("batch", "seq", "vocab"))


def loss_fn(params: Pytree, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            ctx: RunCtx = RunCtx()) -> Tuple[jnp.ndarray, Dict]:
    """Mean CE loss + metrics (a Sum-monoid tuple: one psum for everything).

    ctx.ce_chunk > 0 enables chunked cross-entropy: the (S/V) logits are
    produced and folded chunk-by-chunk in a lax.scan — in-mapper combining of
    (loss_sum, correct) — so the full (B, S, V) logits are never live.
    """
    tokens = batch["tokens"]
    labels = batch["labels"]
    context = batch.get("context")
    mask = (labels >= 0).astype(jnp.float32)
    labels_safe = jnp.maximum(labels, 0)
    h, stats = forward(params, cfg, tokens, context=context, ctx=ctx)

    if ctx.ce_chunk and tokens.shape[1] % ctx.ce_chunk == 0:
        B, S = tokens.shape
        n_chunks = S // ctx.ce_chunk

        def chunked(t):
            return t.reshape((B, n_chunks, ctx.ce_chunk) + t.shape[2:]).swapaxes(0, 1)

        def step(acc, inp):
            hc, lc, mc = inp
            logits = unembed(params, cfg, hc)
            ls, cr = _ce_from_logits(logits, lc, mc)
            return (acc[0] + ls, acc[1] + cr), None

        (loss_sum, correct), _ = jax.lax.scan(
            jax.checkpoint(step, prevent_cse=True) if ctx.remat == "full" else step,
            (jnp.zeros(()), jnp.zeros((), jnp.int32)),
            (chunked(h), chunked(labels_safe), chunked(mask)))
    else:
        logits = unembed(params, cfg, h)
        loss_sum, correct = _ce_from_logits(logits, labels_safe, mask)

    ntok = mask.sum()
    metrics = {"loss_sum": loss_sum, "tokens": ntok,
               "correct": correct.astype(jnp.float32)}
    if stats is not None:
        metrics["expert_load"] = stats["expert_load"].astype(jnp.float32)
        metrics["moe_dropped"] = stats["dropped"].astype(jnp.float32)
    loss = loss_sum / jnp.maximum(ntok, 1.0)
    return loss, metrics


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

# A model with MoE layers keeps, beside its decode caches, each batch row's
# routing counts of its last decode step under ``moe.ROUTE_KEY``:
# (B, 1 + experts_held) int32, the (token, expert) pairs routed in every MoE
# layer, then those routed to each held expert (``moe.row_route_counts``),
# summed through the layer scan as a Sum monoid.
def _has_moe(cfg: ModelConfig) -> bool:
    return any(cfg.ffn_type(i) == "moe" for i in range(cfg.num_layers))


def _init_layer_cache(cfg: ModelConfig, ltype: str, batch: int, max_seq: int,
                      params: Optional[Dict] = None,
                      context: Optional[jnp.ndarray] = None):
    if ltype in ("attn", "local"):
        if cfg.use_mla:
            base = {"lat_c": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), cfg.dtype),
                    "lat_r": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), cfg.dtype)}
        else:
            base = {"k": jnp.zeros((batch, max_seq, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
                    "v": jnp.zeros((batch, max_seq, cfg.num_kv_heads, cfg.head_dim), cfg.dtype)}
        if cfg.decoder_cross_attn and params is not None and context is not None:
            k, v = attn.cross_kv(params["xattn"], cfg, context)
            base["xk"], base["xv"] = k, v
        return base
    if ltype == "xattn":
        if params is not None and context is not None:
            k, v = attn.cross_kv(params["mix"], cfg, context)
            return {"xk": k, "xv": v}
        ctx_len = context.shape[1] if context is not None else cfg.num_image_tokens
        return {"xk": jnp.zeros((batch, ctx_len, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
                "xv": jnp.zeros((batch, ctx_len, cfg.num_kv_heads, cfg.head_dim), cfg.dtype)}
    if ltype == "mamba":
        return ssm.init_mamba_cache(cfg, batch)
    if ltype == "mlstm":
        return ssm.init_mlstm_cache(cfg, batch)
    if ltype == "slstm":
        return ssm.init_slstm_cache(cfg, batch)
    raise ValueError(ltype)


def positional_cache(cfg: ModelConfig) -> bool:
    """True when every decode-cache leaf (besides ``pos``) is indexed by
    absolute sequence position — KV/MLA-latent rows at position i depend
    only on tokens 0..i and on i itself (RoPE at absolute positions).

    This is the property prefix KV sharing relies on: a cached prefix's
    rows can be scattered into a fresh slot cache verbatim and the decode
    is bit-identical to recomputing them.  Recurrent state (mamba/*lstm)
    and context KV (cross-attention, encoder-decoder) are not row-per-
    position, so those archs opt out of the prefix cache.
    """
    if cfg.encoder_layers > 0 or cfg.decoder_cross_attn:
        return False
    return all(cfg.block_type(i) in ("attn", "local")
               for i in range(cfg.num_layers))


def init_cache(params: Pytree, cfg: ModelConfig, batch: int, max_seq: int, *,
               context: Optional[jnp.ndarray] = None,
               ctx: RunCtx = RunCtx(), pos_per_slot: bool = False) -> Pytree:
    """Decode caches, mirroring the layer program's structure.

    For enc-dec / vision models, the cross-attention context KV is projected
    ONCE here and reused by every decode step (in-mapper combining of the
    static context — DESIGN.md §4).

    ``pos_per_slot=True`` makes ``pos`` a ``(batch,)`` vector instead of a
    scalar: every batch row (request slot) carries its own cache position,
    which is what lets a continuously-batched engine retire a request and
    restart the freed slot at position 0 while its neighbours keep decoding.
    """
    if cfg.encoder_layers > 0 and context is not None:
        context = encode(params, cfg, context, ctx=ctx)
    prelude, period_slots, remainder = _layer_plan(cfg)
    pos0 = jnp.zeros((batch,) if pos_per_slot else (), jnp.int32)
    cache: Dict[str, Any] = {"pos": pos0}
    if _has_moe(cfg):
        cache[ROUTE_KEY] = jnp.zeros((batch, 1 + cfg.experts_held), jnp.int32)
    for i, (lt, _) in enumerate(prelude):
        cache[f"prelude_{i}"] = _init_layer_cache(
            cfg, lt, batch, max_seq, params[f"prelude_{i}"], context)
    if period_slots:
        n = _num_periods(cfg)

        def one_period(slot_params):
            return {f"slot_{j}": _init_layer_cache(cfg, lt, batch, max_seq,
                                                   slot_params[f"slot_{j}"], context)
                    for j, (lt, _) in enumerate(period_slots)}

        if context is not None:
            cache["layers"] = jax.vmap(one_period)(params["layers"])
        else:
            # no context: caches are identical zero-trees; build once and tile
            one = one_period(jax.tree_util.tree_map(lambda p: p[0], params["layers"]))
            cache["layers"] = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (n,) + l.shape), one)
    for i, (lt, _) in enumerate(remainder):
        cache[f"rem_{i}"] = _init_layer_cache(
            cfg, lt, batch, max_seq, params[f"rem_{i}"], context)
    return cache


def _extends_in_parallel(ltype: str, ctx: RunCtx) -> bool:
    """Whether a layer of this kind takes L > 1 new tokens in one pass, and
    so writes its cache where it lies.

    Attention and MLA over a position-indexed cache write L rows in place
    (:func:`attention.cache_write`) and mask causally by position;
    cross-attention has no mask and only reads its context KV.  Recurrent
    state (mamba, mLSTM, sLSTM) and the sequence-sharded flash decode, which
    writes one row on its owning shard, take one token at a time, on their
    own layer's cache."""
    if ltype in ("attn", "local"):
        return not (ctx.decode_impl == "flash" and ctx.mesh is not None)
    return ltype == "xattn"


def _context_kv(cache: Dict, layer: Optional[jnp.ndarray]):
    """The layer's projected context (k, v), read and never written."""
    return (attn.cache_layer(cache["xk"], layer),
            attn.cache_layer(cache["xv"], layer))


def _decode_layer(p: Dict, cfg: ModelConfig, ltype: str, ftype: str,
                  x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
                  ctx: RunCtx, counts: Optional[jnp.ndarray],
                  layer: Optional[jnp.ndarray] = None):
    """-> (x, cache, counts): see :func:`_apply_ffn` for ``counts``.

    ``cache`` holds this layer's leaves, or with ``layer`` the stacked
    leaves (n, B, ...) of the scanned region, of which this is layer
    ``layer``.  What is written follows the leaf: position-indexed rows
    (attention ``k``/``v``, MLA ``lat_c``/``lat_r``) get only the step's new
    rows, in place; context KV (``xk``/``xv``) is only read; recurrent state
    and the sharded flash decode's KV are read for this layer and written
    back whole."""
    if layer is not None and not _extends_in_parallel(ltype, ctx):
        own = jax.tree_util.tree_map(lambda c: attn.cache_layer(c, layer),
                                     cache)
        x, own, counts = _decode_layer(p, cfg, ltype, ftype, x, own, pos,
                                       ctx, counts)
        cache = jax.tree_util.tree_map(
            lambda c, o: jax.lax.dynamic_update_index_in_dim(c, o, layer, 0),
            cache, own)
        return x, cache, counts
    L = x.shape[1]
    if L > 1 and not _extends_in_parallel(ltype, ctx):
        # scan the L columns one token at a time through this layer
        def column(carry, inp):
            c, n = carry
            xj, j = inp
            y, c, n = _decode_layer(p, cfg, ltype, ftype, xj[:, None], c,
                                    pos + j, ctx, n)
            return (c, n), y[:, 0]

        (cache, counts), ys = jax.lax.scan(
            column, (cache, counts), (jnp.swapaxes(x, 0, 1), jnp.arange(L)))
        return jnp.swapaxes(ys, 0, 1), cache, counts
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if ltype in ("attn", "local"):
        window = cfg.sliding_window if ltype == "local" else None
        if cfg.use_mla:
            mixed, (c, r) = attn.mla_decode(
                p["mix"], cfg, h, (cache["lat_c"], cache["lat_r"]), pos, layer)
            cache = {**cache, "lat_c": c, "lat_r": r}
        elif ctx.decode_impl == "flash" and ctx.mesh is not None:
            mixed, (k, v) = attn.flash_decode_shardmap(
                p["mix"], cfg, h, (cache["k"], cache["v"]), pos, ctx.mesh,
                window=window)
            cache = {**cache, "k": k, "v": v}
        else:
            mixed, (k, v) = attn.attention_decode(
                p["mix"], cfg, h, (cache["k"], cache["v"]), pos,
                window=window, layer=layer)
            cache = {**cache, "k": k, "v": v}
    elif ltype == "xattn":
        mixed = attn.cross_attention(p["mix"], cfg, h,
                                     _context_kv(cache, layer), gated=True)
    elif ltype == "mamba":
        mixed, new = ssm.mamba_decode(p["mix"], cfg, h, cache)
        cache = new
    elif ltype == "mlstm":
        mixed, new = ssm.mlstm_decode(p["mix"], cfg, h, cache)
        cache = new
    elif ltype == "slstm":
        mixed, new = ssm.slstm_decode(p["mix"], cfg, h, cache)
        cache = new
    else:
        raise ValueError(ltype)
    x = x + mixed
    if cfg.decoder_cross_attn and ltype in ("attn", "local") and "xk" in cache:
        h = rms_norm(x, p["norm_x"], cfg.norm_eps)
        x = x + attn.cross_attention(p["xattn"], cfg, h,
                                     _context_kv(cache, layer))
    x, _, counts = _apply_ffn(p, cfg, ftype, x, ctx, None, counts)
    return x, cache, counts


def decode_step(params: Pytree, cfg: ModelConfig, cache: Pytree,
                tokens: jnp.ndarray, *, ctx: RunCtx = RunCtx()
                ) -> Tuple[jnp.ndarray, Pytree]:
    """One serving step: (B, L) new tokens -> (B, 1, V) logits of the LAST
    column + new caches, ``pos`` advanced by L.

    L == 1 is the decode step.  L > 1 is an extend (a prefill chunk): token
    j sits at position ``pos + j`` (scalar or per-row ``pos``), attention
    and MLA layers write the L cache rows and attend causally in one
    parallel pass, and layers that cannot (see :func:`_extends_in_parallel`)
    scan the L columns.  Only the last column is unembedded, so an extend
    never holds (B, L, V) logits.  Rows that would fall past the cache's
    end are dropped (:func:`attention.cache_write`).  A model with
    MoE layers writes the step's routed pairs, summed over its layers, to
    each row's ``ROUTE_KEY`` counts.
    """
    B, L = tokens.shape
    pos = cache["pos"]
    x = embed_lookup(params["embed"], tokens).astype(cfg.dtype)
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    x = shd.act(x, ("batch", None, "embed"))
    new_cache: Dict[str, Any] = {"pos": pos + L}
    counts = jnp.zeros_like(cache[ROUTE_KEY]) if ROUTE_KEY in cache else None

    prelude, period_slots, remainder = _layer_plan(cfg)
    for i, (lt, ft) in enumerate(prelude):
        x, new_cache[f"prelude_{i}"], counts = _decode_layer(
            params[f"prelude_{i}"], cfg, lt, ft, x, cache[f"prelude_{i}"], pos,
            ctx, counts)

    if period_slots:
        # the stacked cache rides in the carry, beside the layer index, and
        # each layer writes into it where it lies: with the cache donated
        # the loop updates the caller's buffer, never a copy of the stack
        def body(carry, slot_params):
            x, counts, stack, i = carry
            stack = dict(stack)
            for j, (lt, ft) in enumerate(period_slots):
                x, stack[f"slot_{j}"], counts = _decode_layer(
                    slot_params[f"slot_{j}"], cfg, lt, ft, x,
                    stack[f"slot_{j}"], pos, ctx, counts, layer=i)
            return (x, counts, stack, i + 1), None

        (x, counts, new_cache["layers"], _), _ = jax.lax.scan(
            body, (x, counts, cache["layers"], jnp.int32(0)),
            params["layers"])

    for i, (lt, ft) in enumerate(remainder):
        x, new_cache[f"rem_{i}"], counts = _decode_layer(
            params[f"rem_{i}"], cfg, lt, ft, x, cache[f"rem_{i}"], pos, ctx,
            counts)
    if counts is not None:
        new_cache[ROUTE_KEY] = counts

    if L > 1:
        x = x[:, -1:]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, x)
    return logits, new_cache
