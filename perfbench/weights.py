"""Weights from the seed, on the device, in one jitted call, in the type
they are used in and in the layout the program takes (stacked layers:
``layers.wq`` [L, d, h, hd] ...).  The benchmark's own: the plain reference
and the program are both given what this makes, so the reference uses
nothing the program made."""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def key_of(seed: int) -> jax.Array:
    """Any whole number, also beyond 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, L, h = c["n_embd"], c["n_layer"], c["n_head"]
    hd, ff, v = d // h, c["n_inner"], c["vocab_size"]
    ks = iter(jax.random.split(key, 8))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    ones = lambda *s: jnp.ones(s, dtype)      # noqa: E731
    zeros = lambda *s: jnp.zeros(s, dtype)    # noqa: E731
    return {
        "embed": {"tok": normal((v, d), 0.02),
                  "pos": normal((c["n_positions"], d), 0.01)},
        "layers": {
            "attn_norm": ones(L, d), "attn_norm_b": zeros(L, d),
            "wq": normal((L, d, h, hd), 1 / math.sqrt(d)),
            "wk": normal((L, d, h, hd), 1 / math.sqrt(d)),
            "wv": normal((L, d, h, hd), 1 / math.sqrt(d)),
            "wo": normal((L, h, hd, d), 1 / math.sqrt(d)),
            "mlp_norm": ones(L, d), "mlp_norm_b": zeros(L, d),
            "w_in": normal((L, d, ff), 1 / math.sqrt(d)),
            "w_out": normal((L, ff, d), 1 / math.sqrt(ff)),
        },
        "final_norm": ones(d), "final_norm_b": zeros(d),
    }


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    """Token ids below the PUBLISHED vocabulary (the padding rows are never
    asked for)."""
    return jax.random.randint(key, shape, 0,
                              c["published"]["vocab_size"], jnp.int32)
