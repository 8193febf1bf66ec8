"""Model substrate: config, parameter machinery, norms, rotary embeddings.

Parameters are plain nested dicts of jax arrays. Every parameter leaf is
created through :class:`ParamBuilder` which records a parallel pytree of
*logical axis names* (e.g. ``("embed", "mlp")``); the distribution layer
(`repro.dist.sharding`) maps logical names -> mesh axes per mode. This is the
MaxText-style two-level sharding scheme: models never mention mesh axes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Pytree = Any

# Block types that can appear in a layer pattern.
BLOCK_TYPES = ("attn", "local", "mamba", "mlstm", "slstm", "xattn")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config describes every architecture in the assigned pool.

    ``layer_pattern`` is the repeating period of block types; layer ``i`` has
    type ``layer_pattern[i % len(layer_pattern)]``.  ``ffn_pattern`` likewise
    gives the FFN type ('dense' | 'moe' | 'none') per pattern slot.
    ``prelude_dense_layers`` forces the first k layers to use dense FFN
    (DeepSeek-V2's first_k_dense_replace).
    """

    name: str
    family: str                      # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads
    # -- attention flavour
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 4096       # for 'local' blocks
    layer_pattern: Tuple[str, ...] = ("attn",)
    ffn_pattern: Tuple[str, ...] = ("dense",)
    prelude_dense_layers: int = 0
    # -- MoE
    num_experts: int = 0
    num_shared_experts: int = 0
    moe_top_k: int = 0
    d_ff_expert: int = 0
    norm_topk_prob: bool = True
    moe_capacity_factor: float = 1.25
    num_padded_experts: int = 0      # trailing experts masked out of routing
                                     # (qwen2-moe: 60 real + 4 pads for EP=16)
    # group-limited greedy routing (DeepSeek-V2): the experts form n_group
    # equal groups, a token keeps the topk_group groups with the largest
    # best score and picks its top-k among their experts; 1 = plain top-k
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0   # gate weights x this when not normed
    # one device's share of an expert-parallel layer: it holds experts
    # 0 .. num_held_experts - 1 of the router's num_experts (0 = all);
    # pairs routed elsewhere add nothing here
    num_held_experts: int = 0
    # -- MLA (DeepSeek-V2)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 64
    qk_nope_dim: int = 128
    v_head_dim: int = 128
    # YaRN rope scaling (DeepseekV2YarnRotaryEmbedding); factor 0 = plain rope
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # -- SSM (Mamba)
    d_state: int = 16
    d_conv: int = 4
    ssm_expand: int = 2
    # -- xLSTM
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 1.3333
    # -- encoder-decoder (whisper): decoder uses the main fields
    encoder_layers: int = 0
    encoder_seq: int = 1500          # whisper 30s @ 50Hz after conv stub
    # -- vision cross-attention (llama-3.2-vision)
    num_image_tokens: int = 0        # stubbed patch-embedding count
    # -- FFN flavour
    act_fn: str = "silu"             # silu | gelu
    gated_ffn: bool = True           # SwiGLU (llama-family) vs plain MLP (whisper)
    scale_embed: bool = False        # multiply embeddings by sqrt(d_model) (gemma)
    decoder_cross_attn: bool = False # every attn layer also cross-attends (whisper)
    # -- numerics / misc
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16        # activation/weight dtype
    # long-context capability: True for SSM/hybrid archs (O(1)/chunked state)
    subquadratic: bool = False

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        assert len(self.layer_pattern) == len(self.ffn_pattern), (
            "layer_pattern and ffn_pattern must be slot-aligned")
        if self.num_experts:
            assert self.num_experts % self.n_group == 0, (
                "experts must split into equal routing groups")
            assert self.experts_held <= self.num_experts, (
                "held experts beyond the router")

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.num_held_experts or self.num_experts

    # -- layer program ----------------------------------------------------------
    @property
    def period(self) -> int:
        return len(self.layer_pattern)

    @property
    def num_periods(self) -> int:
        return self.num_layers // self.period

    @property
    def remainder_slots(self) -> int:
        return self.num_layers % self.period

    def block_type(self, layer_idx: int) -> str:
        return self.layer_pattern[layer_idx % self.period]

    def ffn_type(self, layer_idx: int) -> str:
        if layer_idx < self.prelude_dense_layers:
            return "dense"
        return self.ffn_pattern[layer_idx % self.period]

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def num_params(self) -> int:
        """Analytic parameter count (for MODEL_FLOPS = 6*N*D)."""
        from . import transformer  # local import to avoid cycle
        shapes = transformer.param_shapes(self)
        return sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))

    def num_active_params(self) -> int:
        """Active params per token (MoE: shared + top_k routed experts)."""
        if self.num_experts == 0:
            return self.num_params()
        total = self.num_params()
        # each routed expert is 3 matrices of d_model x d_ff_expert
        per_expert = 3 * self.d_model * self.d_ff_expert
        n_moe_layers = sum(1 for i in range(self.num_layers) if self.ffn_type(i) == "moe")
        inactive = (self.experts_held - self.moe_top_k) * per_expert * n_moe_layers
        return total - inactive


# ---------------------------------------------------------------------------
# parameter builder: records logical axes alongside shapes
# ---------------------------------------------------------------------------

class ParamBuilder:
    """Collects parameter leaves and their logical sharding axes.

    Usage::

        pb = ParamBuilder(key, dtype)
        w = pb.param("wq", (d, h, hd), ("embed", "heads", "head_dim"), scale=d)

    ``pb.axes`` mirrors the params dict with tuples of logical names.
    """

    def __init__(self, key: Optional[jax.Array], dtype=jnp.bfloat16, *,
                 abstract: bool = False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: Dict[str, Any] = {}
        self.axes: Dict[str, Any] = {}

    def _next_key(self):
        if self.abstract:
            return None
        self._key, sub = jax.random.split(self._key)
        return sub

    def param(self, name: str, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
              *, scale: Optional[float] = None, init: str = "normal") -> Any:
        assert len(shape) == len(axes), (name, shape, axes)
        if self.abstract:
            w = jax.ShapeDtypeStruct(shape, self.dtype)
        elif init == "zeros":
            w = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            w = jnp.ones(shape, self.dtype)
        else:
            fan_in = scale if scale is not None else (shape[0] if shape else 1)
            std = 1.0 / math.sqrt(max(fan_in, 1))
            w = (jax.random.normal(self._next_key(), shape, jnp.float32) * std).astype(self.dtype)
        self.params[name] = w
        self.axes[name] = axes
        return w

    def child(self, name: str) -> "ParamBuilder":
        sub = ParamBuilder(self._next_key(), self.dtype, abstract=self.abstract)
        self.params[name] = sub.params
        self.axes[name] = sub.axes
        return sub


# ---------------------------------------------------------------------------
# norms / embeddings
# ---------------------------------------------------------------------------

# --- precision-chain policy (EXPERIMENTS.md §Perf, iteration 2) -------------
# f32_chains=True  : norms/rotary/projections upcast to f32 and cast back —
#                    the initial (baseline) implementation.
# f32_chains=False : f32 only where it buys accuracy (variance reductions,
#                    softmax logits, MXU internal accumulation); the big
#                    (B,S,D)-shaped elementwise chains — and therefore their
#                    backward cotangent chains — stay in bf16.
_F32_CHAINS = False


def set_f32_chains(value: bool) -> None:
    global _F32_CHAINS
    _F32_CHAINS = bool(value)


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm: fp32 for the variance REDUCTION; elementwise multiplies in
    the input dtype unless the baseline f32-chain policy is active.

    Perf note (§Perf iter 2): upcasting the whole activation to f32 makes
    every residual-stream cotangent chain f32 — 2x HBM traffic on (B,S,D)
    tensors per layer."""
    if _F32_CHAINS:
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps)
        return (out * gamma.astype(jnp.float32)).astype(x.dtype)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * gamma.astype(x.dtype)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention-temperature factor, 0.1 m ln(s) + 1 (1 for s <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(cfg: ModelConfig, dim: int) -> Tuple[int, int]:
    """The rotary pairs between which YaRN blends interpolated and original
    frequencies: those that turn beta_fast and beta_slow times over the
    original context, floored and ceiled and clamped to [0, dim - 1]."""
    def pair(rotations):
        return dim * math.log(cfg.yarn_original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))
    return (max(math.floor(pair(cfg.yarn_beta_fast)), 0),
            min(math.ceil(pair(cfg.yarn_beta_slow)), dim - 1))


def yarn_inv_freq(cfg: ModelConfig, dim: int) -> jnp.ndarray:
    """YaRN's inverse frequencies of the dim // 2 rotated pairs: pair i
    blends the original frequency base^(-2i/dim) with it divided by
    ``yarn_factor``: the original below the correction range, the divided
    one above it, a linear ramp between."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = jnp.exp(-math.log(cfg.rope_theta) * 2 * i / dim)
    low, high = yarn_correction_range(cfg, dim)
    keep = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / cfg.yarn_factor * (1.0 - keep) + extra * keep


def yarn_rope_mscale(cfg: ModelConfig) -> float:
    """YaRN's factor on cos and sin: mscale over mscale_all_dim (1 for
    DeepSeek-V2, where the two are equal)."""
    return yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
        / yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)


def attn_softmax_scale(cfg: ModelConfig, head_dim: int) -> float:
    """head_dim^-0.5, times YaRN's mscale(factor, mscale_all_dim)^2 when the
    configuration sets ``yarn_mscale_all_dim``."""
    scale = 1.0 / math.sqrt(head_dim)
    if cfg.yarn_factor > 0 and cfg.yarn_mscale_all_dim:
        scale *= yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def rotary_embed(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                 cfg: Optional[ModelConfig] = None) -> jnp.ndarray:
    """Apply rotary position embedding.

    x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    Rotates pairs (x[i], x[i + head_dim/2]) — the HF 'half-split'
    convention.  ``cfg`` with a ``yarn_factor`` gives YaRN's frequencies
    and cos/sin factor (:func:`yarn_inv_freq`, :func:`yarn_rope_mscale`).
    """
    hd = x.shape[-1]
    half = hd // 2
    if cfg is not None and cfg.yarn_factor > 0:
        freqs, mscale = yarn_inv_freq(cfg, hd), yarn_rope_mscale(cfg)
    else:
        freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
        mscale = 1.0
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., seq, half)
    # sin/cos tables in f32 (cheap, (S, half)); the rotation multiplies stay
    # in x's dtype so fwd/bwd chains on (B,S,H,hd) are bf16 (§Perf iter 2)
    dt = jnp.float32 if _F32_CHAINS else x.dtype

    def table(f):
        t = f(angles)
        return (t * mscale if mscale != 1.0 else t)[..., None, :].astype(dt)

    cos, sin = table(jnp.cos), table(jnp.sin)
    x1, x2 = x[..., :half].astype(dt), x[..., half:].astype(dt)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def embed_lookup(embedding: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Token embedding lookup via one-hot matmul on the MXU when the vocab is
    sharded (gather over a sharded axis lowers to all-gather; one-hot matmul
    reduce-scatters instead), plain take otherwise. XLA SPMD handles `take`
    on sharded operands, so we keep `take` and let the partitioner choose."""
    return jnp.take(embedding, tokens, axis=0)


def dense(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """x @ w. The MXU accumulates bf16 inputs in f32 internally and rounds
    once at the output; emitting bf16 directly (instead of
    preferred_element_type=f32 + convert) halves the dot's output traffic
    (§Perf iteration 2). Softmax logits keep explicit f32 (attention.py)."""
    if _F32_CHAINS:
        out = jax.lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
    else:
        out = jax.lax.dot_general(
            x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())))
    if b is not None:
        out = out + b.astype(out.dtype)
    return out
