"""deepseek-v2-236b [moe] — 60L d_model=5120 128H d_ff=1536 (expert)
vocab=102400, MoE 160e top-6 + 2 shared; MLA kv_lora=512. [arXiv:2405.04434]

The published config (huggingface.co/deepseek-ai/DeepSeek-V2
``config.json``).  MLA: q_lora_rank=1536, kv_lora_rank=512, qk_rope=64,
qk_nope=128, v=128, with YaRN rope scaling (factor 40 over an original 4096
positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707, so
the softmax scale is 192^-0.5 x mscale(40, 0.707)^2).  Decode uses the
weight-absorbed form: the KV cache is (512+64) floats/token shared across
all 128 heads.  First layer FFN is dense (d_ff 12288,
first_k_dense_replace=1); the other 59 are MoE: a softmax router over 160
experts, group-limited greedy (8 groups of 20, the best 3 kept, top-6
among them), weights x16 and not renormalised, plus 2 shared experts.

One chip's share: the config's 16-way expert parallelism puts 160 / 16 =
10 experts on each chip of a MoE layer, with attention data-parallel
across the same 16 chips.  ``num_held_experts=10`` gives a chip that share:
it routes over all 160 and computes its own 10 experts' part
(``models/moe.py:held_experts_part``).
"""
from ..models import ModelConfig

ARCH_ID = "deepseek-v2-236b"

# YaRN and the router, as published
_PUBLISHED = dict(
    rope_theta=10_000.0, yarn_factor=40.0, yarn_original_max_position=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707, norm_topk_prob=False,
    routed_scaling_factor=16.0)


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="moe",
        num_layers=60, d_model=5120, num_heads=128, num_kv_heads=128,
        d_ff=12288, vocab_size=102400,
        layer_pattern=("attn",), ffn_pattern=("moe",),
        prelude_dense_layers=1,
        num_experts=160, num_shared_experts=2, moe_top_k=6, d_ff_expert=1536,
        n_group=8, topk_group=3,
        use_mla=True, q_lora_rank=1536, kv_lora_rank=512,
        qk_rope_dim=64, qk_nope_dim=128, v_head_dim=128, head_dim=192,
        **_PUBLISHED,
    )


def smoke_config() -> ModelConfig:
    """Small widths, the published gate and YaRN: 16 experts in 4 groups,
    the best 2 kept, top-3, so the CPU tests exercise the group mask."""
    return ModelConfig(
        name=ARCH_ID + "-smoke", family="moe",
        num_layers=4, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=128, vocab_size=512,
        layer_pattern=("attn",), ffn_pattern=("moe",),
        prelude_dense_layers=1,
        num_experts=16, num_shared_experts=2, moe_top_k=3, d_ff_expert=32,
        n_group=4, topk_group=2,
        use_mla=True, q_lora_rank=32, kv_lora_rank=16,
        qk_rope_dim=8, qk_nope_dim=16, v_head_dim=16, head_dim=24,
        **_PUBLISHED,
    )
