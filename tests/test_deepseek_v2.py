"""DeepSeek-V2's published gate and YaRN rope, and one chip's share of an
expert-parallel MoE layer (``num_held_experts``)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode_step, init_cache, init_params
from repro.models import common as C
from repro.models import moe as M
from repro.runtime.engine import ContinuousEngine, EngineBackend, ServeConfig

KEY = jax.random.PRNGKey(5)
# float32 sums of the same products in other orders
F32_TOL = dict(rtol=1e-5, atol=1e-5)


def _smoke(**kw):
    return dataclasses.replace(get_config("deepseek-v2-236b", smoke=True),
                               dtype=jnp.float32, **kw)


def _moe_params(cfg, key=KEY):
    params, _ = init_params(cfg, key)
    return jax.tree_util.tree_map(lambda p: p[0], params["layers"])[
        "slot_0"]["ffn"]


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def test_yarn_matches_the_published_numbers():
    cfg = get_config("deepseek-v2-236b")
    # the correction range of the 32 rope pairs: floor(10.47), ceil(22.51)
    dim = cfg.qk_rope_dim

    def pair(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) \
            / (2 * math.log(10000))

    assert (round(pair(32), 2), round(pair(1), 2)) == (10.47, 22.51)
    assert C.yarn_correction_range(cfg, dim) == (10, 23)
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    m = 1 - np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra / 40 * (1 - m) + extra * m
    np.testing.assert_allclose(np.asarray(C.yarn_inv_freq(cfg, dim)), want,
                               rtol=1e-6)
    # cos/sin factor mscale(40, 0.707) / mscale(40, 0.707)
    assert C.yarn_rope_mscale(cfg) == 1.0
    # 192^-0.5 x mscale(40, 0.707)^2 = 0.072169 x 1.589626
    assert C.yarn_get_mscale(40, 0.707) ** 2 == pytest.approx(1.589626,
                                                              abs=1e-6)
    assert C.attn_softmax_scale(cfg, 192) == pytest.approx(0.114721,
                                                           abs=1e-6)


def test_plain_rope_is_unchanged_without_yarn():
    cfg = get_config("qwen3-0.6b", smoke=True)
    x = jax.random.normal(KEY, (1, 5, 2, 16), jnp.float32)
    pos = jnp.arange(5)[None]
    np.testing.assert_array_equal(
        np.asarray(C.rotary_embed(x, pos, cfg.rope_theta)),
        np.asarray(C.rotary_embed(x, pos, cfg.rope_theta, cfg)))
    assert C.attn_softmax_scale(cfg, 128) == 1 / math.sqrt(128)


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def _route_one(cfg, logits):
    """route() of one token whose router logits are ``logits``."""
    D = cfg.d_model
    router = jnp.zeros((D, cfg.num_experts)).at[0].set(jnp.asarray(logits))
    x = jnp.zeros((1, D)).at[0, 0].set(1.0)
    e, w = M.route({"router": router}, cfg, x)
    return np.asarray(e[0]), np.asarray(w[0])


# 16 experts in 4 groups of 4: groups 0 and 1 hold the two best experts;
# group 2's best (expert 8, 4.8) beats every other expert of groups 0 and 1
LOGITS = [5.0, 2.0, 1.0, 1.0, 4.9, 1.0, 1.0, 1.0,
          4.8, 4.7, 1.0, 1.0, 0.0, 0.0, 0.0, 0.0]


def test_group_mask_excludes_an_expert_outside_the_kept_groups():
    cfg = _smoke()                      # 4 groups, the best 2 kept, top-3
    assert (cfg.num_experts, cfg.n_group, cfg.topk_group,
            cfg.moe_top_k) == (16, 4, 2, 3)
    e, _ = _route_one(cfg, LOGITS)
    assert list(e) == [0, 4, 1]
    plain, _ = _route_one(dataclasses.replace(cfg, n_group=1), LOGITS)
    assert list(plain) == [0, 4, 8]


def test_scaling_factor_applies_and_weights_are_not_renormalised():
    cfg = _smoke()
    assert not cfg.norm_topk_prob and cfg.routed_scaling_factor == 16.0
    e, w = _route_one(cfg, LOGITS)
    scores = np.asarray(jax.nn.softmax(jnp.asarray(LOGITS)))
    np.testing.assert_allclose(w, 16.0 * scores[e], rtol=1e-6)
    assert abs(w.sum() - 16.0) > 1.0            # not renormalised to 16
    normed = dataclasses.replace(cfg, norm_topk_prob=True)
    _, wn = _route_one(normed, LOGITS)
    np.testing.assert_allclose(wn.sum(), 1.0, rtol=1e-6)


def _parent_route(p, cfg, x):
    """route() as it was before group-limited routing: softmax, top-k,
    renormalised when norm_topk_prob."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.num_padded_experts:
        pad = jnp.arange(cfg.num_experts) >= cfg.num_experts - cfg.num_padded_experts
        logits = jnp.where(pad, -1e30, logits)
    scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(scores, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_e.astype(jnp.int32), top_w


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "jamba-v0.1-52b"])
def test_n_group_1_routes_exactly_as_before(arch):
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype=jnp.float32)
    assert cfg.n_group == 1 and cfg.routed_scaling_factor == 1.0
    p = _moe_params(cfg) if arch != "jamba-v0.1-52b" else {
        "router": jax.random.normal(KEY, (cfg.d_model, cfg.num_experts))}
    x = jax.random.normal(jax.random.PRNGKey(2), (32, cfg.d_model))
    for got, want in zip(M.route(p, cfg, x), _parent_route(p, cfg, x)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# held experts: one chip's share of the layer
# ---------------------------------------------------------------------------

def _dense_held(p, cfg, x, e0=0):
    """The part of experts e0 .. e0 + held - 1 computed densely: every held
    expert on every token, times the token's gate for it (0 where not
    routed there)."""
    xf = x.reshape(-1, cfg.d_model)
    e, w = M.route(p, cfg, xf)
    g = (jax.nn.one_hot(e, cfg.num_experts) * w[..., None]).sum(1)
    g = g[:, e0:e0 + p["we_gate"].shape[0]]
    a = jnp.einsum("td,edf->etf", xf, p["we_gate"])
    u = jnp.einsum("td,edf->etf", xf, p["we_up"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(a) * u, p["we_down"])
    return jnp.einsum("te,etd->td", g, y).reshape(x.shape)


def test_chip_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 groups, top-3 of the best 2 groups: the routed parts
    of 4 chips holding 4 experts each, plus the shared experts counted
    once, are the uncut layer."""
    cfg = _smoke()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.d_model))
    whole, _ = M.moe_ffn_local(p, cfg, x)
    xf = x.reshape(-1, cfg.d_model)
    e, w = M.route(p, cfg, xf)
    total = M.dense_ffn(p["shared"], cfg, x)
    for chip in range(4):
        held = {k: v[4 * chip:4 * chip + 4] if k.startswith("we_") else v
                for k, v in p.items()}
        part, _, _ = M.held_experts_part(held, cfg, xf, e, w, 4 * chip, 4)
        part = part.reshape(x.shape)
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(_dense_held(held, cfg, x, 4 * chip)),
            **F32_TOL)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               **F32_TOL)


def test_every_pair_is_kept_when_all_tokens_route_to_one_held_expert():
    cfg = _smoke(num_held_experts=4)
    p = _moe_params(cfg)
    # expert 1 (held) wins every token by far; the other two picks follow
    p = dict(p, router=p["router"].at[:, 1].set(10.0 / cfg.d_model))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (4, 16,
                                                          cfg.d_model)))
    xf = x.reshape(-1, cfg.d_model)
    e, w = M.route(p, cfg, xf)
    assert bool((e[:, 0] == 1).all())
    out, load, dropped = M.held_experts_part(p, cfg, xf, e, w, 0, 4)
    want = [int((np.asarray(e) == j).sum()) for j in range(4)]
    assert int(load[1]) == xf.shape[0] and list(np.asarray(load)) == want
    assert int(dropped) == 0
    shared = M.dense_ffn(p["shared"], cfg, x)
    got, stats = M.moe_ffn_local(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got - shared),
                               np.asarray(_dense_held(p, cfg, x)), **F32_TOL)
    assert int(stats["dropped"]) == 0


def test_capacity_bounds_the_pairs_computed():
    """With a capacity (the replicated strategy's) the same function keeps
    at most that many pairs and counts the rest of the held ones as
    dropped."""
    cfg = _smoke()
    p = _moe_params(cfg)
    xf = jax.random.normal(jax.random.PRNGKey(6), (24, cfg.d_model))
    e, w = M.route(p, cfg, xf)
    mine = int(((np.asarray(e) >= 0) & (np.asarray(e) < 8)).sum())
    held = {k: v[:8] if k.startswith("we_") else v for k, v in p.items()}
    _, load, dropped = M.held_experts_part(held, cfg, xf, e, w, 0, 8,
                                           capacity=mine - 5)
    assert int(load.sum()) == mine - 5 and int(dropped) == 5


# ---------------------------------------------------------------------------
# route counters through the serving engine
# ---------------------------------------------------------------------------

def _engine(cfg, params, **kw):
    def decode(p, cache, cur):
        logits, new = decode_step(p, cfg, cache, cur)
        return logits[:, -1].astype(jnp.float32), new

    backend = EngineBackend(
        decode=decode, params=params, vocab_size=cfg.vocab_size,
        init_cache=lambda b, per_slot: init_cache(params, cfg, b, 24,
                                                  pos_per_slot=per_slot))
    sc = ServeConfig(num_slots=3, prefill_buckets=(8, 16), max_new_tokens=8,
                     eos_id=-1, prefill_batch=2, prefix_block=4, **kw)
    return ContinuousEngine(backend, sc)


def _serve(eng, n=5):
    rng = np.random.default_rng(0)
    for i in range(n):
        eng.submit(rng.integers(1, 500, 5 + 2 * i).tolist(),
                   max_new_tokens=3 + i)
    for _ in eng.run(max_steps=200):
        pass


def test_engine_counts_routed_and_held_pairs_of_decode_steps():
    cfg = _smoke(num_held_experts=4)
    params, _ = init_params(cfg, KEY)
    eng = _engine(cfg, params)
    _serve(eng)
    st = eng.stats
    moe_layers = cfg.num_layers - cfg.prelude_dense_layers
    # every occupied slot of every decode step routes top-k pairs a layer
    assert st.routed_pairs == st.slot_steps * cfg.moe_top_k * moe_layers
    assert 0 < st.held_pairs < st.routed_pairs
    assert st.held_pairs / 4 <= st.held_expert_max <= st.held_pairs
    # holding every expert, every pair is held
    whole = _smoke()
    eng = _engine(whole, init_params(whole, KEY)[0])
    _serve(eng)
    assert eng.stats.held_pairs == eng.stats.routed_pairs > 0


def test_route_counts_ride_in_the_one_transfer_of_a_decode_step(monkeypatch):
    cfg = _smoke(num_held_experts=4)
    params, _ = init_params(cfg, KEY)
    eng = _engine(cfg, params)
    eng.submit([3, 4, 5, 6, 7], max_new_tokens=8)
    eng.step()                                  # admission
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real(x))
    before = eng.stats.routed_pairs
    eng.step()                                  # a plain decode step
    assert len(calls) == 1
    assert eng.stats.routed_pairs - before == cfg.moe_top_k * 3


def test_dense_models_carry_no_route_counts():
    cfg = get_config("qwen3-0.6b", smoke=True)
    params, _ = init_params(cfg, KEY)
    cache = init_cache(params, cfg, 2, 8)
    assert "route" not in cache
    eng = _engine(dataclasses.replace(cfg, dtype=jnp.float32),
                  init_params(dataclasses.replace(cfg, dtype=jnp.float32),
                              KEY)[0])
    _serve(eng, 2)
    assert eng.stats.routed_pairs == eng.stats.held_pairs == 0
