"""Operations and bytes the algorithms need, from their shapes alone.

These are the model's and the job's own counts, never the compiled
program's: padding, masked cache rows and recomputation are not work.
What a model multiplies per token is its configuration's reference's to
count (``decode_flops`` there); the arithmetic shared by every model is
here.
"""
from __future__ import annotations

from typing import Iterable


def decode_flops(weights_per_token: int, attention_flops_per_key: int,
                 keys_per_token: Iterable[int]) -> int:
    """One decode step, one token per live request: 2 x the weights a token
    is multiplied by, as the configuration's reference counts them (for a
    mixture of experts, the experts it is routed to), plus attention over
    each request's own number of keys."""
    return sum(2 * weights_per_token + attention_flops_per_key * k
               for k in keys_per_token)


def fold_bytes(ids: int, num_keys: int, id_bytes: int = 4,
               count_bytes: int = 4) -> int:
    """One local fold of ``ids`` records: read each id once, write the
    count table once."""
    return ids * id_bytes + num_keys * count_bytes
