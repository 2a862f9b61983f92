"""Rotary position embeddings (RoPE), applied in fp32.

Shapes follow the framework convention: activations are
``[batch, seq, heads, head_dim]``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


@jax.named_scope("projections")
def rotary_angles(seq_len: int, head_dim: int, base: float = 10000.0,
                  offset: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables of shape [seq_len, head_dim//2]."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim))
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    angles = jnp.outer(pos, inv_freq)
    return jnp.cos(angles), jnp.sin(angles)


@jax.named_scope("projections")
def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate [batch, seq, heads, head_dim] by per-position angles.

    Uses the split-halves convention (rotate_half), matching the Llama
    family.  cos/sin are [seq, head_dim//2].
    """
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)
