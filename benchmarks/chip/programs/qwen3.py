"""The program's side of the Qwen3 reference (``reference/qwen3.py``).

``program_config`` is the program's ``ModelConfig`` for what the
configuration file states, and ``program_params`` lays the reference's
weights out as the program's parameter tree.
"""
from __future__ import annotations

import dataclasses

import jax

# the program's ModelConfig field for each key of the configuration file
FIELDS = {"d_model": "hidden_size", "num_layers": "num_hidden_layers",
          "num_heads": "num_attention_heads",
          "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
          "d_ff": "intermediate_size", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
          "tie_embeddings": "tie_word_embeddings",
          "qkv_bias": "attention_bias"}


def program_config(config: dict):
    """The program's model configuration, held to the configuration file.

    The keys the file lists under ``reduced`` (a configuration cut to fit
    its chips) are set on the registered architecture; every key is then
    compared, and any difference, or a block that is not Qwen3's, raises."""
    from repro.configs import get_config

    cfg = get_config(config["program_arch"],
                     smoke=bool(config.get("program_smoke", False)))
    field = {key: name for name, key in FIELDS.items()}
    reduced = list(config.get("reduced", []))
    unknown = [k for k in reduced if k not in field]
    if unknown:
        raise ValueError(f"{config['program_arch']}: the program has no "
                         f"setting for the reduced keys {unknown}")
    cfg = dataclasses.replace(cfg, **{field[k]: config[k] for k in reduced})
    diff = {k: (getattr(cfg, p), config[k]) for p, k in FIELDS.items()
            if getattr(cfg, p) != config[k]}
    if not (cfg.qk_norm and cfg.gated_ffn and cfg.act_fn == "silu"
            and cfg.family == "dense" and cfg.layer_pattern == ("attn",)):
        diff["block"] = "not a Qwen3 dense block"
    if diff:
        raise ValueError(f"{config['program_arch']}: the program's "
                         f"configuration differs from the file: {diff}")
    return cfg


@jax.jit
def _layout(w: dict) -> dict:
    lay = w["layers"]
    L, D, _ = lay["wq"].shape
    hd = lay["q_norm"].shape[1]
    slot = {"norm1": lay["norm1"], "norm2": lay["norm2"],
            "mix": {"wq": lay["wq"].reshape(L, D, -1, hd),
                    "wk": lay["wk"].reshape(L, D, -1, hd),
                    "wv": lay["wv"].reshape(L, D, -1, hd),
                    "wo": lay["wo"].reshape(L, -1, hd, D),
                    "q_norm": lay["q_norm"], "k_norm": lay["k_norm"]},
            "ffn": {k: lay[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "layers": {"slot_0": slot}}


def program_params(weights: dict, cfg) -> dict:
    """The reference's weights in the program's parameter tree for
    ``cfg``: one scanned slot of attention and gated FFN, the per-head
    projections split by head."""
    del cfg                        # the weights' shapes say everything
    return _layout(weights)
