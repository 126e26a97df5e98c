"""Random weights from the seed, made by the benchmark and not the program.

Each model module (``bench/models/<model>.py``) draws its own tree from
``root_key(seed)``; the numbers are the published checkpoint's: drawn in
float32 and rounded to the dtype the configuration file states
(``torch_dtype``, ``published``), whatever the program stores.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, including ones past 32 bits."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def published(w: Dict, weight_dtype: str) -> Dict:
    """Rounded to the checkpoint's dtype, held in float32."""
    dt = jnp.dtype(weight_dtype)
    return {k: v.astype(dt).astype(jnp.float32) for k, v in w.items()}
