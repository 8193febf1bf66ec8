"""The DeepSeek-V2 reference against the program at smoke width, and the
configuration file against the program's model."""
import dataclasses
import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import CHIP
from chipbench import serve, spec
from programs import deepseek_v2 as program
from reference import deepseek_v2 as ref

FIXTURES = CHIP / "tests" / "fixtures"
CONFIG = CHIP / "configs" / "deepseek-v2.json"
# float32 on both sides: the same sums in other orders, and a router that
# sees float32 rounding of its inputs (no expert changes at these seeds)
F32_TOL = dict(atol=1e-4, rtol=1e-4)


def fixture(name: str) -> dict:
    return json.loads((FIXTURES / name).read_text())


@pytest.fixture(scope="module")
def smoke():
    config = fixture("deepseek-v2-smoke.json")
    cfg = dataclasses.replace(program.program_config(config),
                              dtype=jnp.float32)
    sizes = ref.Sizes.from_config(config)
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               ref.make_weights(2**33 + 5, sizes))
    return config, cfg, sizes, w


def test_weights_repeat_from_the_seed_in_the_programs_shapes():
    config = fixture("deepseek-v2-smoke.json")
    s = ref.Sizes.from_config(config)
    a, b = ref.make_weights(3, s), ref.make_weights(3, s)
    assert all(jnp.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert not jnp.array_equal(a["embed"], ref.make_weights(4, s)["embed"])
    cfg = program.program_config(config)
    params = serve.program_params(program, a, cfg)   # held to param_shapes
    # laid out without a copy: the chip holds the weights once
    assert {id(x) for x in jax.tree_util.tree_leaves(params)} == \
        {id(x) for x in jax.tree_util.tree_leaves(a)}


def test_forward_matches_the_program(smoke):
    from repro.models import forward, unembed

    _, cfg, sizes, w = smoke
    params = serve.program_params(program, w, cfg)
    toks = np.random.default_rng(0).integers(1, sizes.vocab_size, 24)
    h, _ = forward(params, cfg, jnp.asarray(toks)[None])
    prog = np.asarray(unembed(params, cfg, h)[0])
    want = np.asarray(ref.logits_at(w, jnp.asarray(toks), jnp.arange(24),
                                    sizes))
    np.testing.assert_allclose(want, prog, **F32_TOL)


def test_extend_then_decode_matches_the_reference(smoke):
    """A prefill extend of the prompt, then one-token decode steps through
    the latent cache (the absorbed form), against the reference's plain
    form."""
    from repro.models import decode_step, init_cache

    _, cfg, sizes, w = smoke
    params = serve.program_params(program, w, cfg)
    prompt = [5, 9, 200, 17, 3, 77, 41]
    served = [11, 400, 8, 64, 2]
    cache = init_cache(params, cfg, 1, 16, pos_per_slot=True)
    logits, cache = decode_step(params, cfg, cache,
                                jnp.asarray([prompt], jnp.int32))
    rows = [np.asarray(logits[0, -1])]
    for t in served[:-1]:
        logits, cache = decode_step(params, cfg, cache,
                                    jnp.asarray([[t]], jnp.int32))
        rows.append(np.asarray(logits[0, -1]))
    want = ref.served_logits(w, prompt, served, sizes, 16, 8)
    np.testing.assert_allclose(want, np.stack(rows), **F32_TOL)


def test_engine_serves_the_references_logits(smoke, monkeypatch):
    """Prefill (with prefix hits) and decode through the ContinuousEngine,
    in float32: every served token is the reference's best and each
    request's logprob sum the reference's, to float32 rounding."""
    config, cfg, _, _ = smoke
    adapter = spec.program_adapter(config)
    monkeypatch.setattr(adapter, "program_config", lambda c: cfg)
    lay = adapter.program_params
    monkeypatch.setattr(adapter, "program_params", lambda w, c: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), lay(w, c)))
    mix = fixture("chat-smoke.json")
    sc = serve.ServeCell(config, mix, 2**32 + 21, jax.devices()[:1])
    sc.warm_up()
    served, _, window = sc.run_window(sc.requests(1.0), 1.0,
                                      serve.WindowTracer(False, 1.0))
    assert window["hit_tokens"] > 0
    sample = sc.sample(served, 6)
    assert any(r.hit_tokens > 0 for r in sample)
    sc.release()
    numbers = sc.compare(sample)
    assert numbers["token_gap"] == 0.0
    assert numbers["logprob_rel"] < 1e-5


def test_a_bfloat16_smoke_run_is_judged_as_a_cell(monkeypatch):
    """The harness runs the configuration through its named files, counts
    each decode step's FLOPs with the reference, and judges the run."""
    import run

    config = fixture("deepseek-v2-smoke.json")
    c = spec.Cell(workload={"name": "ds", "chips": 1}, config=config,
                  traffic=fixture("chat-smoke.json"),
                  limits={"token_gap": {"limit": 5.0},
                          "logprob_rel": {"limit": 0.5}},
                  end_to_end=[{"name": "setup_s", "unit": "s"}],
                  per_layer=[{"name": "decode_mfu", "unit": "%"}])
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    out = serve.run(c, 2**32 + 17, 1.0, False, jax.devices()[:1], peaks,
                    time.perf_counter(), lambda _: None)
    line = run.result_line(c, out, False, jax.devices()[:1])
    assert line["correct"], line["checks"]
    assert spec.read_metrics(c.per_layer, out["run"])["decode_mfu"][
        "value"] > 0


def test_chip_shares_add_up_to_the_uncut_reference_layer():
    """16 experts in 4 groups, top-3 of the best 2 groups: the reference's
    routed parts of 4 chips holding 4 experts each, plus the shared
    experts counted once, are its uncut layer."""
    config = dict(fixture("deepseek-v2-smoke.json"), n_routed_experts=16,
                  deployment={"router_experts": 16})
    s = ref.Sizes.from_config(config)
    w = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.float32),
                               ref.make_weights(9, s)["moe"])
    h = jax.random.normal(jax.random.PRNGKey(1), (20, s.hidden_size))
    whole = ref.moe_layer(h, w, s)
    shared = whole - ref.held_routed(h, w, s, False, 0, 16)
    parts = sum(ref.held_routed(h, w, s, False, 4 * c, 4) for c in range(4))
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    # the program's part of the same share agrees with the reference's
    from repro.models import moe as M
    cfg = dataclasses.replace(program.program_config(
        fixture("deepseek-v2-smoke.json")), dtype=jnp.float32,
        num_held_experts=4)
    held = {"router": w["router"], "we_gate": w["we_gate"][8:12],
            "we_up": w["we_up"][8:12], "we_down": w["we_down"][8:12]}
    e, g = M.route(held, cfg, h)
    got, _, _ = M.held_experts_part(held, cfg, h, e, g, 8, 4)
    want = ref.held_routed(h, w, s, False, 8, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # and its whole layer holding experts 0-3, shared experts included
    first = {"router": w["router"], "we_gate": w["we_gate"][:4],
             "we_up": w["we_up"][:4], "we_down": w["we_down"][:4],
             "shared": {k: w["shared_" + k] for k in
                        ("w_gate", "w_up", "w_down")}}
    got, _ = M.moe_ffn_local(first, cfg, h[None])
    want = ref.held_routed(h, w, s, False, 0, 4) + shared
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_fp8_control_departs_from_float32(smoke):
    _, _, sizes, w = smoke
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 512, 24))
    hi = ref.logits_at(w, toks, jnp.arange(24), sizes)
    lo = ref.logits_at(w, toks, jnp.arange(24), sizes, fp8=True)
    assert 1e-2 < float(jnp.abs(hi - lo).max()) < 50.0


def test_the_configuration_file_is_the_published_share():
    config = json.loads(CONFIG.read_text())
    cfg = program.program_config(config)
    assert (cfg.num_layers, cfg.num_experts, cfg.experts_held) == (5, 160,
                                                                  10)
    assert sorted(config["reduced"]) == ["n_routed_experts",
                                         "num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 60,
                                   "n_routed_experts": 160}
    cell = spec.load_cell("deepseek-v2.chat-decode")
    assert cell.config["name"] == "deepseek-v2"
    # decode FLOPs: 2 x 1.69 G weights a token, 1,392,640 per live key
    s = ref.Sizes.from_config(config)
    per_expert = 3 * 5120 * 1536
    weights = (5 * 149_225_472 + 3 * 5120 * 12288
               + 4 * (5120 * 160 + 2 * per_expert + 6 * 10 / 160 * per_expert)
               + 5120 * 102400)
    assert ref.matmul_params(config) == pytest.approx(weights, rel=1e-12)
    assert round(weights / 1e9, 2) == 1.69
    assert ref.attention_flops_per_key(config) == 1_392_640
    assert ref.decode_flops(config, [10, 20]) == pytest.approx(
        2 * (2 * weights) + 1_392_640 * 30, rel=1e-12)
    assert s.router_experts == 160 and s.n_routed_experts == 10


@pytest.mark.parametrize("key,value", [
    ("topk_group", 4), ("routed_scaling_factor", 8), ("n_group", 1),
    ("norm_topk_prob", True), ("hidden_size", 4096),
    ("scoring_func", "sigmoid"),
    ("rope_scaling", {"type": "yarn", "factor": 4, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 4096})])
def test_a_mismatched_key_is_refused(key, value):
    config = dict(json.loads(CONFIG.read_text()), **{key: value})
    with pytest.raises(ValueError, match="differs"):
        program.program_config(config)


def test_a_program_without_the_routing_or_yarn_fields_is_refused(monkeypatch):
    """The parent program's ModelConfig had no group routing, held experts
    or YaRN: the adapter refuses it before any weight is drawn."""
    import repro.configs

    old = types.SimpleNamespace(**{f: 0 for f in program.FIELDS
                                   if f not in ("n_group", "topk_group")})
    monkeypatch.setattr(repro.configs, "get_config", lambda *a, **k: old)
    with pytest.raises(ValueError, match="has no"):
        program.program_config(json.loads(CONFIG.read_text()))


def test_an_unknown_reduced_key_is_refused():
    config = dict(json.loads(CONFIG.read_text()),
                  reduced=["num_hidden_layers", "vocab_size_slice"])
    with pytest.raises(ValueError, match="no setting"):
        program.program_config(config)
