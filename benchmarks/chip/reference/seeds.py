"""The one derivation of a PRNG key from ``--seed``, for every reference and
cell kind: weights and inputs drawn from the same seed repeat exactly."""
from __future__ import annotations

import jax


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
