"""Plain float32 Qwen3 decoder: weights from a seed, forward pass, logits.

This is the yardstick the serve cells are held to.  It imports nothing of
the system under test and follows the published architecture
(huggingface.co/Qwen/Qwen3-0.6B, ``config.json`` and the ``Qwen3`` modelling
code):

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * norm1
                q, k, v = h @ wq, h @ wk, h @ wv          (no bias)
                q, k = rmsnorm(q) * q_norm, rmsnorm(k) * k_norm   (per head)
                q, k = rope(q), rope(k)                    (half-split, theta)
                x = x + softmax(q k^T / sqrt(head_dim) + causal) v @ wo
                h = rmsnorm(x) * norm2
                x = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down
    logits = rmsnorm(x) * final_norm @ embed^T             (tied embeddings)

Every matrix product runs at ``Precision.HIGHEST`` in float32.  Departures
from the published model: the weights are random (see :func:`make_weights`),
and there is no sampling, cache or batching.

``fp8=True`` is the precision control: every matrix-product operand is
rounded to float8 e4m3 with one scale per tensor before an exact float32
product, as an fp8 serving path would compute it.

The serve harness finds this module by the configuration file's
``reference`` key, and its program adapter as ``programs/qwen3.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from metrics.common import counts
from reference.seeds import seed_key

HIGHEST = jax.lax.Precision.HIGHEST
# per-layer weights, in the order they are drawn from the seed
LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "norm2",
                "w_gate", "w_up", "w_down")
NORM_LEAVES = ("norm1", "q_norm", "k_norm", "norm2")
# norm gains are 1 + NORM_JITTER * N(0, 1), so a program that skipped a gain
# would be seen; matrices are N(0, 1) / sqrt(fan_in)
NORM_JITTER = 0.1


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The shape of a Qwen3 decoder, named as in its ``config.json``."""

    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    vocab_size: int
    rope_theta: float
    rms_norm_eps: float

    @classmethod
    def from_config(cls, config: dict) -> "Sizes":
        return cls(**{f.name: config[f.name] for f in dataclasses.fields(cls)})


def layer_shapes(s: Sizes) -> dict:
    D, H, KV, hd, F = (s.hidden_size, s.num_attention_heads,
                       s.num_key_value_heads, s.head_dim, s.intermediate_size)
    return {"norm1": (D,), "wq": (D, H * hd), "wk": (D, KV * hd),
            "wv": (D, KV * hd), "q_norm": (hd,), "k_norm": (hd,),
            "wo": (H * hd, D), "norm2": (D,), "w_gate": (D, F),
            "w_up": (D, F), "w_down": (F, D)}


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, s: Sizes) -> dict:
    L = s.num_hidden_layers

    def draw(i, shape, fan_in=None):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if fan_in is None:                       # a norm gain
            return (1.0 + NORM_JITTER * z).astype(jnp.bfloat16)
        return (z / math.sqrt(fan_in)).astype(jnp.bfloat16)

    layers = {}
    for i, (name, shape) in enumerate(layer_shapes(s).items()):
        fan_in = None if name in NORM_LEAVES else shape[0]
        layers[name] = draw(i, (L,) + shape, fan_in)
    n = len(LAYER_LEAVES)
    return {"embed": draw(n, (s.vocab_size, s.hidden_size), s.hidden_size),
            "final_norm": draw(n + 1, (s.hidden_size,)),
            "layers": layers}


def make_weights(seed: int, s: Sizes, device=None) -> dict:
    """Every weight of the model in bfloat16, drawn on ``device`` in one
    jitted call from ``seed``.  The same seed gives the same weights."""
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _make(key, s)


# ---------------------------------------------------------------------------
# work per token, from the shapes alone
# ---------------------------------------------------------------------------

def matmul_params(c: dict) -> int:
    """Weights a token is multiplied by: every layer's projections and FFN,
    and the tied embedding once, as the output head (the embedding lookup
    is a gather)."""
    D, H, KV, hd, F = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * D


def attention_flops_per_key(c: dict) -> int:
    """QK^T and AV of one query token for each cached position it reads."""
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"]


def decode_flops(c: dict, keys_per_token) -> int:
    """One decode step: one token per live request, each attending over its
    own number of keys."""
    return counts.decode_flops(matmul_params(c), attention_flops_per_key(c),
                               keys_per_token)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fp8(x: jnp.ndarray) -> jnp.ndarray:
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / fmax
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, fp8: bool, eq: str):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _rope(x, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_states(weights: dict, tokens: jnp.ndarray, s: Sizes, *,
                  fp8: bool = False) -> jnp.ndarray:
    """(S,) token ids -> (S, hidden) final-normed hidden states, float32."""
    S = tokens.shape[0]
    H, KV, hd = s.num_attention_heads, s.num_key_value_heads, s.head_dim
    eps = s.rms_norm_eps
    x = weights["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, w):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        h = _rms(x, w["norm1"], eps)
        q = _mm(h, w["wq"], fp8, "sd,de->se").reshape(S, H, hd)
        k = _mm(h, w["wk"], fp8, "sd,de->se").reshape(S, KV, hd)
        v = _mm(h, w["wv"], fp8, "sd,de->se").reshape(S, KV, hd)
        q = _rope(_rms(q, w["q_norm"], eps), s.rope_theta)
        k = _rope(_rms(k, w["k_norm"], eps), s.rope_theta)
        # grouped-query attention: query head j reads KV head j // group
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        scores = _mm(q, k, fp8, "qhd,khd->hqk") / math.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = _mm(p, v, fp8, "hqk,khd->qhd").reshape(S, H * hd)
        x = x + _mm(o, w["wo"], fp8, "se,ed->sd")
        h = _rms(x, w["norm2"], eps)
        g = _mm(h, w["w_gate"], fp8, "sd,df->sf")
        u = _mm(h, w["w_up"], fp8, "sd,df->sf")
        x = x + _mm(jax.nn.silu(g) * u, w["w_down"], fp8, "sf,fd->sd")
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    return _rms(x, weights["final_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def logits_at(weights: dict, tokens: jnp.ndarray, read: jnp.ndarray,
              s: Sizes, fp8: bool = False) -> jnp.ndarray:
    """float32 logits (len(read), vocab) at positions ``read`` of ``tokens``.

    Positions past the end of a request may hold any padding: attention is
    causal, so they change nothing at or before the positions read.
    """
    h = hidden_states(weights, tokens, s, fp8=fp8)[read]
    return _mm(h, weights["embed"].astype(jnp.float32), fp8, "rd,vd->rv")


def served_logits(weights: dict, prompt, served, s: Sizes, length: int,
                  max_served: int, *, fp8: bool = False) -> np.ndarray:
    """Logits before each served token: row j predicts ``served[j]`` after
    ``prompt + served[:j]``.  The sequence is padded to ``length`` and the
    rows read to ``max_served``, so every request runs one compiled
    program."""
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    if len(seq) > length or n > max_served:
        raise ValueError(f"{len(seq)} tokens and {n} served exceed the "
                         f"padded sizes {length} and {max_served}")
    toks = np.zeros((length,), np.int32)
    toks[:len(seq)] = seq
    read = np.zeros((max_served,), np.int32)
    read[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    out = logits_at(weights, jnp.asarray(toks), jnp.asarray(read), s, fp8)
    return np.asarray(out[:n])
