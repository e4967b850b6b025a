"""Keys from ``--seed``.  The seed may exceed 32 bits, and
``jax.random.key`` keeps only the low 32, so both halves go into the
threefry key's two words."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64): {seed}")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")
