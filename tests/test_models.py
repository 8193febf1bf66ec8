"""Model-substrate correctness: decode==forward, chunked==dense, MLA
absorption, GQA, sliding windows."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import (ModelConfig, ParamBuilder, RunCtx, decode_step,
                          forward, init_cache, init_params, loss_fn, unembed)
from repro.models import attention as A
from repro.models import transformer as T
from repro.models.common import embed_lookup, rms_norm
from repro.models.moe import ROUTE_KEY

KEY = jax.random.PRNGKey(1)


def _f32(name):
    return dataclasses.replace(get_config(name, smoke=True), dtype=jnp.float32)


def _decode_diff(cfg, n=10, ctx_shape=None):
    params, _ = init_params(cfg, KEY)
    B = 2
    toks = jax.random.randint(KEY, (B, n), 0, cfg.vocab_size)
    context = None
    if ctx_shape is not None:
        context = jax.random.normal(KEY, (B,) + ctx_shape, cfg.dtype)
    h, _ = forward(params, cfg, toks, context=context)
    full = unembed(params, cfg, h)
    cache = init_cache(params, cfg, B, n + 4, context=context)
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))
    outs = []
    for i in range(n):
        lg, cache = step(params, cache, toks[:, i:i + 1])
        outs.append(lg)
    dec = jnp.concatenate(outs, 1)
    return float(jnp.max(jnp.abs(full - dec)))


@pytest.mark.parametrize("arch", [
    "qwen3-0.6b", "gemma3-1b", "qwen2.5-14b", "starcoder2-15b",
    "deepseek-v2-236b", "qwen2-moe-a2.7b", "jamba-v0.1-52b", "xlstm-1.3b",
])
def test_decode_matches_forward(arch):
    """Token-by-token decode reproduces the full-sequence forward logits.

    This exercises every cache type (KV, MLA latent, mamba (h, conv),
    mLSTM (C, n), sLSTM (h, c)) and the absorbed-MLA equivalence."""
    assert _decode_diff(_f32(arch)) < 2e-2


def test_decode_matches_forward_whisper():
    cfg = _f32("whisper-small")
    assert _decode_diff(cfg, ctx_shape=(cfg.encoder_seq, cfg.d_model)) < 2e-2


def test_decode_matches_forward_vlm():
    cfg = _f32("llama-3.2-vision-90b")
    assert _decode_diff(cfg, ctx_shape=(cfg.num_image_tokens, cfg.d_model)) < 2e-2


def test_chunked_attention_equals_dense():
    """The AttnState-monoid chunked form is a re-bracketing of softmax."""
    cfg = _f32("qwen3-0.6b")
    params, _ = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    h1, _ = forward(params, cfg, toks, ctx=RunCtx())
    h2, _ = forward(params, cfg, toks, ctx=RunCtx(attn_chunk=8))
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=2e-3, atol=2e-3)


def test_chunked_ce_equals_dense():
    cfg = _f32("qwen3-0.6b")
    params, _ = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    l1, m1 = loss_fn(params, cfg, batch, RunCtx())
    l2, m2 = loss_fn(params, cfg, batch, RunCtx(ce_chunk=8))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    np.testing.assert_allclose(float(m1["correct"]), float(m2["correct"]))


def test_mla_absorbed_decode_equals_train_form():
    cfg = dataclasses.replace(_f32("deepseek-v2-236b"))
    pb = ParamBuilder(KEY, jnp.float32)
    A.init_mla(pb, cfg)
    p = pb.params
    B, S = 2, 8
    x = jax.random.normal(KEY, (B, S, cfg.d_model), jnp.float32) * 0.3
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    full = A.mla_attention(p, cfg, x, positions)
    cache = (jnp.zeros((B, S, cfg.kv_lora_rank), jnp.float32),
             jnp.zeros((B, S, cfg.qk_rope_dim), jnp.float32))
    outs = []
    for i in range(S):
        o, cache = A.mla_decode(p, cfg, x[:, i:i + 1], cache, i)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full), rtol=1e-3, atol=1e-4)


def test_sliding_window_masks_old_tokens():
    """A 'local' layer must ignore keys beyond the window."""
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                      vocab_size=64, sliding_window=4,
                      layer_pattern=("local",), ffn_pattern=("dense",),
                      dtype=jnp.float32, tie_embeddings=True)
    params, _ = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (1, 16), 0, 64)
    h1, _ = forward(params, cfg, toks)
    # perturb a token far outside any window of the last position
    toks2 = toks.at[0, 2].set((toks[0, 2] + 1) % 64)
    h2, _ = forward(params, cfg, toks2)
    np.testing.assert_allclose(np.asarray(h1[0, -1]), np.asarray(h2[0, -1]),
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(h1[0, 2]), np.asarray(h2[0, 2]))


def test_moe_local_load_and_dropless():
    from repro.models import moe as M
    cfg = _f32("qwen2-moe-a2.7b")
    params, _ = init_params(cfg, KEY)
    layer = jax.tree_util.tree_map(lambda p: p[0], params["layers"])
    ffn = layer["slot_0"]["ffn"]
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32)
    out, stats = M.moe_ffn_local(ffn, cfg, x)
    assert out.shape == x.shape
    assert int(stats["expert_load"].sum()) == 2 * 16 * cfg.moe_top_k
    # padded experts never routed
    assert int(stats["expert_load"][-cfg.num_padded_experts:].sum()) == 0


def test_grad_flows_through_every_family():
    for arch in ("jamba-v0.1-52b", "xlstm-1.3b", "deepseek-v2-236b"):
        cfg = _f32(arch)
        params, _ = init_params(cfg, KEY)
        toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        g = jax.grad(lambda p: loss_fn(p, cfg, batch)[0])(params)
        norms = [float(jnp.sum(jnp.square(x))) for x in jax.tree_util.tree_leaves(g)]
        assert all(np.isfinite(n) for n in norms), arch
        assert sum(norms) > 0, arch


# ---------------------------------------------------------------------------
# extend: decode_step over (B, L) tokens == L one-token decode steps
# ---------------------------------------------------------------------------

# At float32 an extend and L one-token steps agree to float32 rounding.  In
# bfloat16 they round their matmuls over different shapes, so they may part
# by bfloat16 rounding: no more than the one-token steps themselves part
# from the same steps at float32.
F32_TOL = dict(rtol=1e-5, atol=1e-5)


def _max_gap(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# one jitted decode_step for every shape the extend tests use
_STEP = jax.jit(decode_step, static_argnums=(1,))


def _history_cache(cfg, params, batch, max_seq, starts, per_row):
    """A cache holding real history: max(starts) tokens decoded one at a
    time, then each row's position set to its start (rows past a row's
    start are stale, as a reused slot's are, and masked by position)."""
    cache = init_cache(params, cfg, batch, max_seq, pos_per_slot=per_row)
    n = max(starts)
    hist = jax.random.randint(jax.random.PRNGKey(7), (batch, n), 0,
                              cfg.vocab_size)
    for i in range(n):
        _, cache = _STEP(params, cfg, cache, hist[:, i:i + 1])
    pos = jnp.asarray(starts, jnp.int32) if per_row else jnp.int32(starts[0])
    return {**cache, "pos": pos}


def _extend_vs_steps(cfg, starts, per_row, L, max_seq):
    params, _ = init_params(cfg, KEY)
    B = len(starts)
    cache = _history_cache(cfg, params, B, max_seq, starts, per_row)
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, L), 0,
                              cfg.vocab_size)
    ref, routed = cache, 0
    for j in range(L):
        ref_logits, ref = _STEP(params, cfg, ref, toks[:, j:j + 1])
        routed = routed + ref.get(ROUTE_KEY, 0)
    if ROUTE_KEY in ref:
        # a MoE model's route counts are those of the last call: an
        # extend's are its L one-token steps' together
        ref = {**ref, ROUTE_KEY: routed}
    got_logits, got = _STEP(params, cfg, cache, toks)
    return ref_logits, ref, got_logits, got


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])
@pytest.mark.parametrize("arch", [
    "qwen3-0.6b",          # dense GQA attention
    "gemma3-1b",           # sliding-window 'local' layers (window 8)
    "deepseek-v2-236b",    # MLA latent cache
    "jamba-v0.1-52b",      # mamba layers scan their columns in the extend
])
def test_extend_matches_one_token_decodes(arch, per_row, dtype):
    """One decode_step over L tokens writes the same KV rows and gives the
    same last-column logits as L one-token decode steps."""
    def run(dt):
        cfg = dataclasses.replace(get_config(arch, smoke=True),
                                  dtype=jnp.dtype(dt).type)
        return cfg, _extend_vs_steps(cfg, starts, per_row, 8, max_seq=24)

    starts = [5, 9] if per_row else [7, 7]
    cfg, (ref_logits, ref, got_logits, got) = run(dtype)
    assert got_logits.shape == (2, 1, cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(got["pos"]),
                                  np.asarray(ref["pos"]))
    if dtype == "float32":
        np.testing.assert_allclose(np.asarray(got_logits),
                                   np.asarray(ref_logits), **F32_TOL)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       **F32_TOL)
        return
    _, (f32_logits, f32, _, _) = run("float32")
    assert _max_gap(got_logits, ref_logits) <= \
        _max_gap(ref_logits, f32_logits)
    assert _max_gap(got, ref) <= _max_gap(ref, f32)


def test_extend_past_cache_end_drops_pad_rows():
    """A row whose span runs past the cache's end keeps its real rows in
    place and drops the overflow: the cache equals the first S rows of the
    same extend into a longer cache."""
    cfg = _f32("qwen3-0.6b")
    params, _ = init_params(cfg, KEY)
    S, L, starts = 16, 8, [2, 12]              # row 1 spans 12..19
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, L), 0,
                              cfg.vocab_size)
    short = _history_cache(cfg, params, 2, S, starts, True)
    long_ = _history_cache(cfg, params, 2, S + L, starts, True)
    got_logits, got = _STEP(params, cfg, short, toks)
    ref_logits, ref = _STEP(params, cfg, long_, toks)
    np.testing.assert_array_equal(np.asarray(got["pos"]), [2 + L, 12 + L])
    for key in ("k", "v"):                     # (layers, B, S, KV, hd)
        np.testing.assert_allclose(
            np.asarray(got["layers"]["slot_0"][key]),
            np.asarray(ref["layers"]["slot_0"][key])[:, :, :S], **F32_TOL)
    # rows before the overflowing row's start were not shifted
    np.testing.assert_array_equal(
        np.asarray(got["layers"]["slot_0"]["k"][:, 1, :12]),
        np.asarray(short["layers"]["slot_0"]["k"][:, 1, :12]))
    # the row that fits attends to the same keys either way
    np.testing.assert_allclose(np.asarray(got_logits[0]),
                               np.asarray(ref_logits[0]), **F32_TOL)


# ---------------------------------------------------------------------------
# the stacked cache, carried and written in place == the per-layer scan
# ---------------------------------------------------------------------------

def _per_layer_scan_step(params, cfg, cache, tokens):
    """The serving step with the stacked cache threaded through the layer
    scan as ``xs``/``ys``: each layer's cache is sliced out of the stack,
    written, and stacked back.  The stack's rows are what ``decode_step``
    must leave, written in place."""
    B, L = tokens.shape
    pos = cache["pos"]
    x = embed_lookup(params["embed"], tokens).astype(cfg.dtype)
    if cfg.scale_embed:
        x = x * np.sqrt(cfg.d_model)
    new = {"pos": pos + L}
    counts = jnp.zeros_like(cache[ROUTE_KEY]) if ROUTE_KEY in cache else None
    prelude, period_slots, remainder = T._layer_plan(cfg)
    ctx = RunCtx()
    for i, (lt, ft) in enumerate(prelude):
        x, new[f"prelude_{i}"], counts = T._decode_layer(
            params[f"prelude_{i}"], cfg, lt, ft, x, cache[f"prelude_{i}"],
            pos, ctx, counts)

    def body(carry, inp):
        x, counts = carry
        slot_params, slot_cache = inp
        out = {}
        for j, (lt, ft) in enumerate(period_slots):
            x, out[f"slot_{j}"], counts = T._decode_layer(
                slot_params[f"slot_{j}"], cfg, lt, ft, x,
                slot_cache[f"slot_{j}"], pos, ctx, counts)
        return (x, counts), out

    (x, counts), new["layers"] = jax.lax.scan(
        body, (x, counts), (params["layers"], cache["layers"]))
    for i, (lt, ft) in enumerate(remainder):
        x, new[f"rem_{i}"], counts = T._decode_layer(
            params[f"rem_{i}"], cfg, lt, ft, x, cache[f"rem_{i}"], pos, ctx,
            counts)
    if counts is not None:
        new[ROUTE_KEY] = counts
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x), new


@pytest.mark.parametrize("arch", [
    "qwen3-0.6b",          # dense GQA: k/v rows written into the stack
    "deepseek-v2-236b",    # MLA latent rows; a dense prelude layer unstacked
    "jamba-v0.1-52b",      # mamba state written back whole at its layer
    "llama-3.2-vision-90b",  # read-only context KV beside attention rows
])
def test_stacked_cache_written_in_place_equals_per_layer_scan(arch):
    """An extend and several decode steps through ``decode_step``, which
    carries the stacked cache and writes only the new rows, give logits and
    a whole cache bit-identical to the per-layer scan's."""
    cfg = get_config(arch, smoke=True)
    params, _ = init_params(cfg, KEY)
    B = 2
    context = None
    if cfg.decoder_cross_attn or cfg.encoder_layers:
        context = jax.random.normal(KEY, (B, cfg.num_image_tokens,
                                          cfg.d_model), cfg.dtype)
    cache = init_cache(params, cfg, B, 24, context=context,
                       pos_per_slot=True)
    cache = {**cache, "pos": jnp.asarray([0, 5], jnp.int32)}
    got = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t),
                  donate_argnums=(1,))
    want = jax.jit(lambda p, c, t: _per_layer_scan_step(p, cfg, c, t))
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, 12), 0,
                              cfg.vocab_size)
    ref = cache
    cache = jax.tree_util.tree_map(jnp.copy, cache)
    for cur in (toks[:, :8],) + tuple(toks[:, j:j + 1] for j in range(8, 12)):
        ref_logits, ref = want(params, ref, cur)
        logits, cache = got(params, cache, cur)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        assert jax.tree_util.tree_structure(cache) == \
            jax.tree_util.tree_structure(ref)
        for a, b in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
