"""The program's side of the DeepSeek-V2 reference (``reference/deepseek_v2.py``).

``program_config`` is the program's ``ModelConfig`` for what the
configuration file states, one chip's share of its experts included, and
``program_params`` regroups the reference's weights, drawn in the shapes
the program holds them, into the program's parameter tree: no array is
copied, so the chip holds the weights once.
"""
from __future__ import annotations

import dataclasses

# the program's ModelConfig field for each top-level key of the file
FIELDS = {"d_model": "hidden_size", "num_layers": "num_hidden_layers",
          "prelude_dense_layers": "first_k_dense_replace",
          "d_ff": "intermediate_size", "num_heads": "num_attention_heads",
          "num_kv_heads": "num_key_value_heads",
          "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
          "qk_nope_dim": "qk_nope_head_dim", "qk_rope_dim": "qk_rope_head_dim",
          "v_head_dim": "v_head_dim", "d_ff_expert": "moe_intermediate_size",
          "num_shared_experts": "n_shared_experts",
          "num_held_experts": "n_routed_experts",
          "moe_top_k": "num_experts_per_tok", "n_group": "n_group",
          "topk_group": "topk_group", "norm_topk_prob": "norm_topk_prob",
          "routed_scaling_factor": "routed_scaling_factor",
          "vocab_size": "vocab_size", "rope_theta": "rope_theta",
          "norm_eps": "rms_norm_eps", "tie_embeddings": "tie_word_embeddings",
          "qkv_bias": "attention_bias"}
# ... and for each key of its ``rope_scaling`` (YaRN) and ``deployment``
YARN = {"yarn_factor": "factor",
        "yarn_original_max_position": "original_max_position_embeddings",
        "yarn_beta_fast": "beta_fast", "yarn_beta_slow": "beta_slow",
        "yarn_mscale": "mscale", "yarn_mscale_all_dim": "mscale_all_dim"}
SHARE = {"num_experts": "router_experts"}
# what the published block computes, which the program must match
BLOCK = {"hidden_act": "silu", "scoring_func": "softmax",
         "topk_method": "group_limited_greedy", "moe_layer_freq": 1}


def program_config(config: dict):
    """The program's model configuration, held to the configuration file.

    The keys the file lists under ``reduced`` (the depth and the experts
    held on this chip) are set on the registered architecture; every key is then compared, and any
    difference raises, as does a program without group-limited routing,
    held experts or YaRN."""
    from repro.configs import get_config

    cfg = get_config(config["program_arch"],
                     smoke=bool(config.get("program_smoke", False)))
    missing = [f for f in list(FIELDS) + list(YARN) + list(SHARE)
               if not hasattr(cfg, f)]
    if missing:
        raise ValueError(f"{config['program_arch']}: the program has no "
                         f"{missing}: it cannot route by groups over a "
                         f"chip's share of the experts with YaRN rope")
    field = {key: name for name, key in FIELDS.items()}
    reduced = list(config.get("reduced", []))
    unknown = [k for k in reduced if k not in field]
    if unknown:
        raise ValueError(f"{config['program_arch']}: the program has no "
                         f"setting for the reduced keys {unknown}")
    cfg = dataclasses.replace(cfg, **{field[k]: config[k] for k in reduced})
    want = {p: config[k] for p, k in FIELDS.items()}
    want.update({p: config["rope_scaling"][k] for p, k in YARN.items()})
    want.update({p: config["deployment"][k] for p, k in SHARE.items()})
    diff = {p: (getattr(cfg, p), v) for p, v in want.items()
            if getattr(cfg, p) != v}
    for key, value in BLOCK.items():
        if config[key] != value:
            diff[key] = config[key]
    if config["rope_scaling"].get("type") != "yarn":
        diff["rope_scaling.type"] = config["rope_scaling"].get("type")
    if not (cfg.use_mla and cfg.gated_ffn and cfg.act_fn == "silu"
            and cfg.family == "moe" and cfg.layer_pattern == ("attn",)
            and cfg.ffn_pattern == ("moe",)
            and cfg.head_dim == cfg.qk_nope_dim + cfg.qk_rope_dim):
        diff["block"] = "not a DeepSeek-V2 MLA + MoE block"
    if diff:
        raise ValueError(f"{config['program_arch']}: the program's "
                         f"configuration differs from the file: {diff}")
    return cfg


MLA = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wk_b", "wv_b",
       "wo")


def program_params(weights: dict, cfg) -> dict:
    """The reference's weights in the program's parameter tree for
    ``cfg``: the dense first layer, then one scanned MLA + MoE slot."""
    del cfg                        # the weights' shapes say everything

    def layer(w, ffn):
        return {"norm1": w["norm1"], "norm2": w["norm2"],
                "mix": {k: w[k] for k in MLA}, "ffn": ffn}

    dense, moe = weights["dense"], weights["moe"]
    ffn = {k: moe[k] for k in ("router", "we_gate", "we_up", "we_down")}
    ffn["shared"] = {k: moe["shared_" + k]
                     for k in ("w_gate", "w_up", "w_down")}
    return {"embed": weights["embed"], "final_norm": weights["final_norm"],
            "lm_head": weights["lm_head"],
            "prelude_0": layer(dense, {k: dense[k] for k in
                                       ("w_gate", "w_up", "w_down")}),
            "layers": {"slot_0": layer(moe, ffn)}}
