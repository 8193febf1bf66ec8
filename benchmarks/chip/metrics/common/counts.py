"""Operations and bytes the algorithms need, from their shapes alone.

These are the model's and the job's own counts, never the compiled
program's: padding, masked cache rows and recomputation are not work.
"""
from __future__ import annotations

from typing import Iterable


def matmul_params(c: dict) -> int:
    """Weights a Qwen3-style decoder multiplies per token (the embedding
    lookup is a gather; the tied embedding is multiplied once, as the
    output head)."""
    D, H, KV, hd, F = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * D


def attention_flops(c: dict, keys: int) -> int:
    """QK^T and AV of one query token over ``keys`` cached positions."""
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * keys


def decode_flops(c: dict, keys_per_token: Iterable[int]) -> int:
    """One decode step: one token per live request, each attending over its
    own number of keys."""
    w = 2 * matmul_params(c)
    return sum(w + attention_flops(c, k) for k in keys_per_token)


def fold_bytes(ids: int, num_keys: int, id_bytes: int = 4,
               count_bytes: int = 4) -> int:
    """One local fold of ``ids`` records: read each id once, write the
    count table once."""
    return ids * id_bytes + num_keys * count_bytes
