"""The float32 reference against the program's model at smoke width."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import CHIP
from chipbench import serve
from programs import qwen3 as program
from reference import qwen3

FIXTURES = CHIP / "tests" / "fixtures"


@pytest.fixture(scope="module")
def smoke():
    config = json.loads((FIXTURES / "qwen3-smoke.json").read_text())
    cfg = dataclasses.replace(program.program_config(config),
                              dtype=jnp.float32)
    sizes = qwen3.Sizes.from_config(config)
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               qwen3.make_weights(2**33 + 7, sizes))
    return cfg, sizes, w


def test_weights_repeat_from_the_seed():
    config = json.loads((FIXTURES / "qwen3-smoke.json").read_text())
    s = qwen3.Sizes.from_config(config)
    a, b = qwen3.make_weights(3, s), qwen3.make_weights(3, s)
    c = qwen3.make_weights(4, s)
    assert all(jnp.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert not jnp.array_equal(a["embed"], c["embed"])


def test_forward_matches_the_program(smoke):
    from repro.models import forward, unembed

    cfg, sizes, w = smoke
    params = serve.program_params(program, w, cfg)
    toks = np.random.default_rng(0).integers(1, sizes.vocab_size, 24)
    h, _ = forward(params, cfg, jnp.asarray(toks)[None])
    prog = np.asarray(unembed(params, cfg, h)[0])
    ref = np.asarray(qwen3.logits_at(w, jnp.asarray(toks),
                                     jnp.arange(24), sizes))
    np.testing.assert_allclose(ref, prog, atol=2e-4, rtol=1e-4)


def test_cached_decode_matches_the_reference(smoke):
    from repro.models import decode_step, init_cache

    cfg, sizes, w = smoke
    params = serve.program_params(program, w, cfg)
    prompt = [5, 9, 200, 17, 3, 77]
    served = [11, 400, 8, 64]
    cache = init_cache(params, cfg, 1, 16, pos_per_slot=True)
    rows = []
    for t in prompt + served[:-1]:
        logits, cache = decode_step(params, cfg, cache,
                                    jnp.asarray([[t]], jnp.int32))
        rows.append(np.asarray(logits[0, -1]))
    prog = np.stack(rows[len(prompt) - 1:])
    ref = qwen3.served_logits(w, prompt, served, sizes, 16, 8)
    np.testing.assert_allclose(ref, prog, atol=2e-4, rtol=1e-4)


def test_fp8_control_departs_from_float32(smoke):
    _, sizes, w = smoke
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 512, 24))
    hi = qwen3.logits_at(w, toks, jnp.arange(24), sizes)
    lo = qwen3.logits_at(w, toks, jnp.arange(24), sizes, fp8=True)
    gap = float(jnp.abs(hi - lo).max())
    assert 1e-3 < gap < 5.0
