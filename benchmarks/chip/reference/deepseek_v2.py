"""Plain float32 DeepSeek-V2 decoder, as one chip's share of its experts.

This is the yardstick the ``deepseek-v2`` cells are held to.  It imports
nothing of the system under test and follows the published architecture
(huggingface.co/deepseek-ai/DeepSeek-V2, ``config.json`` and
``modeling_deepseek.py``; arXiv:2405.04434):

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * norm1
                MLA, in the plain (non-absorbed) form:
                  q = rmsnorm(h @ wq_a) * q_a_norm @ wq_b   -> (nope, rope)
                  c, k_rope = h @ wkv_a;  c = rmsnorm(c) * kv_a_norm
                  q_rope, k_rope = yarn_rope(q_rope), yarn_rope(k_rope)
                  k_nope, v = c @ wk_b, c @ wv_b            (per head)
                  x = x + softmax((q_nope k_nope + q_rope k_rope) * scale
                                  + causal) v @ wo
                h = rmsnorm(x) * norm2
                layer 0 (first_k_dense_replace):  x = x + swiglu(h)
                MoE layers: scores = softmax(h @ router) over every expert;
                  group-limited greedy: the n_group groups score their best
                  expert, the topk_group best groups are kept, the others'
                  scores zeroed, and the top num_experts_per_tok taken;
                  gates = their scores x routed_scaling_factor (norm_topk_prob
                  false: no renormalisation);
                  x = x + sum over the HELD experts e of gate_e * swiglu_e(h)
                        + swiglu_shared(h)
    logits = rmsnorm(x) * final_norm @ lm_head             (untied)

YaRN (``DeepseekV2YarnRotaryEmbedding``): pair i of the rope dims turns at
base^(-2i/d) blended with that over ``factor``: the original below the
correction range [low, high] (pairs that turn beta_fast and beta_slow times
over the original context), the divided one above it, a linear ramp
between; cos and sin are scaled by mscale(factor, mscale) /
mscale(factor, mscale_all_dim), and the softmax scale is
(qk_nope + qk_rope)^-0.5 x mscale(factor, mscale_all_dim)^2, with
mscale(s, m) = 0.1 m ln s + 1.

One chip's share (the configuration's ``deployment``): the router keeps all
``router_experts`` outputs and its experts per token; this chip holds
the first ``n_routed_experts`` of them, and a token's pairs routed to any
other expert add nothing here, as in the program.  The
shared experts and the whole vocabulary are held.

Every matrix product runs at ``Precision.HIGHEST`` in float32.  Departures
from the published model: the weights are random (:func:`make_weights`);
rope rotates the two halves of the rope dims (x[i] with x[i + d/2]) where
the published code first de-interleaves them (x[2i] with x[2i + 1]): the
same rotation under one fixed permutation of the rope columns of ``wq_b``
and ``wkv_a``, which q.k does not see, so random weights drawn in either
layout give the same model; and there is no sampling, cache or batching.

``fp8=True`` is the precision control: every matrix-product operand is
rounded to float8 e4m3 with one scale per tensor before an exact float32
product, as an fp8 serving path would compute it.

The serve harness finds this module by the configuration file's
``reference`` key, and its program adapter as ``programs/deepseek_v2.py``.
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
# norm gains are 1 + NORM_JITTER * N(0, 1), so a program that skipped a gain
# would be seen; matrices are N(0, 1) / sqrt(fan_in)
NORM_JITTER = 0.1


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The shape of a DeepSeek-V2 decoder, named as in its ``config.json``
    (YaRN's keys from ``rope_scaling``, the share from ``deployment``)."""

    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    intermediate_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    n_shared_experts: int
    n_routed_experts: int          # held on this chip
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    vocab_size: int
    rope_theta: float
    rms_norm_eps: float
    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    router_experts: int            # the router's outputs

    @classmethod
    def from_config(cls, config: dict) -> "Sizes":
        merged = dict(config, **config["rope_scaling"],
                      router_experts=config["deployment"]["router_experts"])
        return cls(**{f.name: merged[f.name] for f in dataclasses.fields(cls)})

    @property
    def moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


def mla_shapes(s: Sizes) -> dict:
    D, H, ql, kvl = (s.hidden_size, s.num_attention_heads, s.q_lora_rank,
                     s.kv_lora_rank)
    dn, dr, dv = s.qk_nope_head_dim, s.qk_rope_head_dim, s.v_head_dim
    return {"norm1": (D,), "wq_a": (D, ql), "q_a_norm": (ql,),
            "wq_b": (ql, H, dn + dr), "wkv_a": (D, kvl + dr),
            "kv_a_norm": (kvl,), "wk_b": (kvl, H, dn), "wv_b": (kvl, H, dv),
            "wo": (H, dv, D), "norm2": (D,)}


def ffn_shapes(D: int, F: int) -> dict:
    return {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}


def moe_shapes(s: Sizes) -> dict:
    D, F, E = s.hidden_size, s.moe_intermediate_size, s.n_routed_experts
    shared = ffn_shapes(D, s.n_shared_experts * F)
    return {"router": (D, s.router_experts), "we_gate": (E, D, F),
            "we_up": (E, D, F), "we_down": (E, F, D),
            **{"shared_" + k: v for k, v in shared.items()}}


def _fan_in(name: str, shape: tuple):
    """None for a norm gain; else the inputs a column of the matrix sums."""
    if "norm" in name:
        return None
    if name == "wo":                                   # (H, dv, D)
        return shape[0] * shape[1]
    if name.startswith("we_") or name == "embed":      # (E, in, out); (V, D)
        return shape[1]
    return shape[0]


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, s: Sizes) -> dict:
    D, V = s.hidden_size, s.vocab_size
    plan = (("dense", (), {**mla_shapes(s),
                           **ffn_shapes(D, s.intermediate_size)}),
            ("moe", (s.moe_layers,), {**mla_shapes(s), **moe_shapes(s)}),
            ("top", (), {"embed": (V, D), "final_norm": (D,),
                         "lm_head": (D, V)}))
    out, i = {}, 0
    for group, lead, shapes in plan:
        out[group] = {}
        for name, shape in shapes.items():
            z = jax.random.normal(jax.random.fold_in(key, i), lead + shape,
                                  jnp.float32)
            i += 1
            fan_in = _fan_in(name, shape)
            z = 1.0 + NORM_JITTER * z if fan_in is None \
                else z / math.sqrt(fan_in)
            out[group][name] = z.astype(jnp.bfloat16)
    return {**out.pop("top"), **out}


def make_weights(seed: int, s: Sizes, device=None) -> dict:
    """Every weight of this chip's share in bfloat16, drawn on ``device`` in
    one jitted call from ``seed``, each in the shape the program holds it
    (so laying it out as the program's parameters copies nothing).  The
    same seed gives the same weights."""
    if s.first_k_dense_replace != 1:
        raise ValueError("the reference holds one leading dense layer")
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _make(key, s)


# ---------------------------------------------------------------------------
# work per token, from the shapes alone
# ---------------------------------------------------------------------------

def matmul_params(c: dict) -> float:
    """Weights a decode token is multiplied by on this chip: every layer's
    MLA (absorbed, as decode runs it: the same matrices), the dense layer,
    and in each MoE layer the router, the shared experts and the expected
    share of its routed experts held here (experts per token x held /
    router experts: a router that favours no expert); then the head.  The
    embedding lookup is a gather."""
    s = Sizes.from_config(c)
    mla = sum(math.prod(v) for k, v in mla_shapes(s).items()
              if not k.startswith("norm") and not k.endswith("norm"))
    D = s.hidden_size
    per_expert = 3 * D * s.moe_intermediate_size
    routed = s.num_experts_per_tok * s.n_routed_experts / s.router_experts
    moe = (D * s.router_experts + s.n_shared_experts * per_expert
           + routed * per_expert)
    return (s.num_hidden_layers * mla + 3 * D * s.intermediate_size
            + s.moe_layers * moe + D * s.vocab_size)


def attention_flops_per_key(c: dict) -> int:
    """Absorbed MLA decode, per cached position a query token reads: its
    scores against the latent and rope rows, 2 H (kv_lora + rope), and the
    latent weighted sum, 2 H kv_lora, in every layer."""
    H, kvl, dr = (c["num_attention_heads"], c["kv_lora_rank"],
                  c["qk_rope_head_dim"])
    return c["num_hidden_layers"] * (2 * H * (kvl + dr) + 2 * H * kvl)


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


def yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_range(s: Sizes) -> tuple:
    """The correction range: pairs turning beta_fast and beta_slow times
    over the original context, floored and ceiled."""
    d = s.qk_rope_head_dim

    def pair(rot):
        return d * math.log(s.original_max_position_embeddings
                            / (rot * 2 * math.pi)) / (2 * math.log(s.rope_theta))

    return (max(math.floor(pair(s.beta_fast)), 0),
            min(math.ceil(pair(s.beta_slow)), d - 1))


def yarn_inv_freq(s: Sizes) -> np.ndarray:
    d = s.qk_rope_head_dim
    extra = 1.0 / s.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / s.factor
    low, high = yarn_range(s)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return (inter * (1.0 - mask) + extra * mask).astype(np.float32)


def softmax_scale(s: Sizes) -> float:
    m = yarn_mscale(s.factor, s.mscale_all_dim)
    return (s.qk_nope_head_dim + s.qk_rope_head_dim) ** -0.5 * m * m


def _rope(x, s: Sizes):
    """x: (S, heads, d); rotate the two halves of each head by YaRN's
    frequencies, cos and sin scaled by its mscale ratio."""
    S, _, d = x.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(s))[None, :]
    m = yarn_mscale(s.factor, s.mscale) / yarn_mscale(s.factor,
                                                      s.mscale_all_dim)
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(w, x, s: Sizes, fp8: bool):
    S = x.shape[0]
    dn, kvl = s.qk_nope_head_dim, s.kv_lora_rank
    eps = s.rms_norm_eps
    cq = _rms(_mm(x, w["wq_a"], fp8, "sd,dl->sl"), w["q_a_norm"], eps)
    q = _mm(cq, w["wq_b"], fp8, "sl,lhk->shk")
    ckv = _mm(x, w["wkv_a"], fp8, "sd,dl->sl")
    c = _rms(ckv[:, :kvl], w["kv_a_norm"], eps)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], s)
    k_rope = _rope(ckv[:, None, kvl:], s)[:, 0]
    k_nope = _mm(c, w["wk_b"], fp8, "sl,lhk->shk")
    v = _mm(c, w["wv_b"], fp8, "sl,lhk->shk")
    scores = (_mm(q_nope, k_nope, fp8, "qhk,shk->hqs")
              + _mm(q_rope, k_rope, fp8, "qhk,sk->hqs")) * softmax_scale(s)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = _mm(p, v, fp8, "hqs,shv->qhv")
    return _mm(o, w["wo"], fp8, "qhv,hvd->qd")


def _swiglu(h, gate, up, down, fp8: bool):
    g = _mm(h, gate, fp8, "sd,df->sf")
    u = _mm(h, up, fp8, "sd,df->sf")
    return _mm(jax.nn.silu(g) * u, down, fp8, "sf,fd->sd")


def gates(h, router, s: Sizes, fp8: bool = False) -> jnp.ndarray:
    """(S, router_experts) gate weights: the published group-limited greedy
    gate, zero for experts a token is not routed to."""
    S, E = h.shape[0], s.router_experts
    scores = jax.nn.softmax(_mm(h, router, fp8, "sd,de->se"), axis=-1)
    grouped = scores.reshape(S, s.n_group, E // s.n_group)
    _, best = jax.lax.top_k(grouped.max(-1), s.topk_group)
    kept = jax.nn.one_hot(best, s.n_group).sum(1) > 0           # (S, G)
    masked = jnp.where(kept[:, :, None], grouped, 0.0).reshape(S, E)
    w, idx = jax.lax.top_k(masked, s.num_experts_per_tok)
    if s.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    else:
        w = w * s.routed_scaling_factor
    return (jax.nn.one_hot(idx, E) * w[..., None]).sum(1)


def held_routed(h, w, s: Sizes, fp8: bool, first: int, count: int):
    """What routed experts first .. first + count - 1 add: each token's gate
    for an expert times that expert's SwiGLU.  ``w["we_*"]`` hold experts
    0 .. ``s.n_routed_experts`` - 1."""
    g = gates(h, w["router"], s, fp8)[:, first:first + count]     # (S, count)
    held = slice(first, first + count)
    a = _mm(h, w["we_gate"][held], fp8, "sd,edf->esf")
    u = _mm(h, w["we_up"][held], fp8, "sd,edf->esf")
    y = _mm(jax.nn.silu(a) * u, w["we_down"][held], fp8, "esf,efd->esd")
    return jnp.einsum("se,esd->sd", g, y, precision=HIGHEST)


def moe_layer(h, w, s: Sizes, fp8: bool = False):
    """This chip's MoE layer: its held experts' part plus the shared
    experts."""
    return held_routed(h, w, s, fp8, 0, s.n_routed_experts) \
        + _swiglu(h, w["shared_w_gate"], w["shared_w_up"],
                  w["shared_w_down"], fp8)


def hidden_states(weights: dict, tokens: jnp.ndarray, s: Sizes, *,
                  fp8: bool = False) -> jnp.ndarray:
    """(S,) token ids -> (S, hidden) final-normed hidden states, float32."""
    eps = s.rms_norm_eps
    x = weights["embed"][tokens].astype(jnp.float32)

    def f32(w):
        return {k: v.astype(jnp.float32) for k, v in w.items()}

    w = f32(weights["dense"])
    x = x + _mla(w, _rms(x, w["norm1"], eps), s, fp8)
    x = x + _swiglu(_rms(x, w["norm2"], eps), w["w_gate"], w["w_up"],
                    w["w_down"], fp8)

    def layer(x, w):
        w = f32(w)
        x = x + _mla(w, _rms(x, w["norm1"], eps), s, fp8)
        return x + moe_layer(_rms(x, w["norm2"], eps), w, s, fp8), None

    x, _ = jax.lax.scan(layer, x, weights["moe"])
    return _rms(x, weights["final_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def logits_at(weights: dict, tokens: jnp.ndarray, read: jnp.ndarray,
              s: Sizes, fp8: bool = False) -> jnp.ndarray:
    """float32 logits (len(read), vocab) at positions ``read`` of ``tokens``.

    Positions past the end of a request may hold any padding: attention is
    causal, so they change nothing at or before the positions read.
    """
    h = hidden_states(weights, tokens, s, fp8=fp8)[read]
    return _mm(h, weights["lm_head"].astype(jnp.float32), fp8, "rd,dv->rv")


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
