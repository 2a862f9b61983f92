"""Multi-head latent attention (MLA): low-rank queries, and ONE compressed
key-value latent plus ONE rotary key a position, shared by all heads.

    c_q            = rmsnorm(y W_qa)                       [.., q_lora]
    q_nope | q_rope = c_q W_qb          per head           [.., h, nope|rope]
    c_kv | k_r     = y W_kva                               [.., kv_lora|rope]
    latent         = rmsnorm(c_kv) | rotary(k_r)           what a cache holds
    k_nope | v     = rmsnorm(c_kv) W_kvb   per head        [.., h, nope|v]
    scores         = (q_nope . k_nope + rotary(q_rope) . rotary(k_r))
                     / sqrt(nope + rope)

Two forms of the same function of (queries, latents):

* `attend_plain` builds every head's keys and values from the latents and
  runs ordinary attention: the form for training and for a whole-sequence
  forward, where nothing is cached.
  A model with an indexer (`ops/sparse_index.py`) hands it a
  ``selection``: the positions each query attends, in the mask's place.
* `attend_absorbed` never builds them.  ``q_nope . (c W_k) = (q_nope W_k^T)
  . c`` and ``(p c) W_v = p (c W_v)``: the key up-projection is folded into
  the query and the value up-projection applied after the probabilities,
  so the few queries of a chunk or a decode step attend over the cached
  latents directly.  Per cached position a layer then reads ``kv_lora +
  rope`` values, not ``heads x (qk + v)``.

Shapes are ``[batch, seq, heads, dim]`` like `ops/attention.py`; a cache
layer is ``[batch, kv_lora + rope, positions]``, positions last, as
`models/generate.py` stores it.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import multi_head_attention
from .norms import rmsnorm
from .sparse_index import rows_seen

Rotate = Callable[[jnp.ndarray], jnp.ndarray]   # [b, s, heads, rope] -> same


@jax.named_scope("projections")
def queries(y: jnp.ndarray, wq_a, q_norm, wq_b, *, nope: int, eps: float,
            rotate: Rotate
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Normed input ``y`` [b, s, d] -> (q_nope [b, s, h, nope], q_rope
    [b, s, h, rope] already rotated, and the query latent ``c_q`` [b, s,
    q_lora] they were made from: an indexer's queries come from it too,
    `ops/sparse_index.py`)."""
    dt = y.dtype
    c_q = rmsnorm(jnp.einsum("bsd,dr->bsr", y, wq_a.astype(dt)), q_norm, eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, wq_b.astype(dt))
    return q[..., :nope], rotate(q[..., nope:]), c_q


@jax.named_scope("projections")
def latents(y: jnp.ndarray, wkv_a, kv_norm, *, kv_lora: int, eps: float,
            rotate: Rotate) -> jnp.ndarray:
    """Normed input ``y`` [b, s, d] -> [b, s, kv_lora + rope]: the normed
    key-value latent beside the rotated shared key.  This, and nothing
    else, is what a cache of this attention kind holds."""
    dt = y.dtype
    ckv = jnp.einsum("bsd,dr->bsr", y, wkv_a.astype(dt))
    c = rmsnorm(ckv[..., :kv_lora], kv_norm, eps)
    k_r = rotate(ckv[..., None, kv_lora:])[..., 0, :]
    return jnp.concatenate([c, k_r], axis=-1)


@jax.named_scope("attention")
def attend_plain(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                 latent: jnp.ndarray, wkv_b, wo, *, causal: bool = True,
                 impl: str = "auto",
                 selection: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Every head's keys and values built from ``latent`` [b, s, kv_lora +
    rope], ordinary attention over them -> [b, s, d].  ``selection`` [b, s,
    s] bool (a model with an indexer): the positions each query attends,
    which then IS the mask (a selection is causal by how it was made)."""
    dt = q_nope.dtype
    nope, rope = q_nope.shape[-1], q_rope.shape[-1]
    kv_lora, h = wkv_b.shape[0], wkv_b.shape[1]
    with jax.named_scope("projections"):
        kv = jnp.einsum("bsr,rhk->bshk", latent[..., :kv_lora],
                        wkv_b.astype(dt))
    k_r = jnp.broadcast_to(latent[:, :, None, kv_lora:],
                           latent.shape[:2] + (h, rope))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    v = kv[..., nope:]
    if v.shape[-1] != q.shape[-1]:
        impl = "reference"      # the flash kernel has one head size
    if selection is not None:
        scores = jnp.einsum("bshk,bthk->bhst", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(nope + rope)
        probs = jax.nn.softmax(
            jnp.where(selection[:, None], scores, -1e30), axis=-1)
        attn = jnp.einsum("bhst,bthv->bshv", probs.astype(dt), v)
    else:
        attn = multi_head_attention(q, k, v, causal=causal, impl=impl,
                                    sm_scale=1.0 / math.sqrt(nope + rope))
    with jax.named_scope("projections"):
        return jnp.einsum("bshk,hkd->bsd", attn, wo.astype(dt))


@jax.named_scope("attention")
def attend_absorbed(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                    cached: jnp.ndarray, wkv_b, wo, mask: jnp.ndarray,
                    key_block: int = 0) -> jnp.ndarray:
    """``cached`` [b, kv_lora + rope, T] is one layer of a latent cache,
    ``mask`` [b|1, s, T] which positions each query may see -> [b, s, d].
    The probabilities meet all ``kv_lora + rope`` cached rows (the rotary
    rows' part of the result is dropped): slicing the latent rows out of
    the cache first would copy them.  Its three parts stand alone for a
    caller that attends a row of the batch at a time BETWEEN the two that
    read weights: `absorb`, `attend_latents`, `unabsorb`."""
    nope = q_nope.shape[-1]
    o_lat = attend_latents(absorb(q_nope, q_rope, wkv_b), cached, mask,
                           math.sqrt(nope + q_rope.shape[-1]), key_block)
    return unabsorb(o_lat, wkv_b, wo, nope)


@jax.named_scope("attention")
def absorb(q_nope: jnp.ndarray, q_rope: jnp.ndarray, wkv_b) -> jnp.ndarray:
    """Queries [b, s, h, nope] and [b, s, h, rope] (rotated) -> [b, s, h,
    kv_lora + rope]: the key up-projection folded into the query."""
    nope = q_nope.shape[-1]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope,
                       wkv_b.astype(q_nope.dtype)[..., :nope])
    return jnp.concatenate([q_lat, q_rope], axis=-1)


@jax.named_scope("attention")
def attend_latents(q_abs: jnp.ndarray, cached: jnp.ndarray,
                   mask: jnp.ndarray, scale: float,
                   key_block: int = 0) -> jnp.ndarray:
    """Absorbed queries [b, s, h, kv_lora + rope] over ``cached`` [b, kv_lora
    + rope, T] under ``mask``, scores over ``scale`` -> [b, s, h, kv_lora +
    rope]: the part that reads the cache, and no weight.  ``key_block`` (a
    divisor of T; 0: all rows at once): the rows are read so many at a time
    under a running softmax, and NO BLOCK PAST THE LAST ROW ANY QUERY MAY
    SEE (`_attend_blocks`): a chunk's float32 scores of 64 heads over 33 k
    rows would be 1.1 GB, most of them of rows its mask hides."""
    if key_block:
        return _attend_blocks(q_abs, cached, mask, scale, key_block)
    dt = q_abs.dtype
    scores = jnp.einsum("bshr,brt->bsht", q_abs, cached.astype(dt),
                        preferred_element_type=jnp.float32)
    scores = scores / scale
    scores = jnp.where(mask[:, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bsht,brt->bshr", probs.astype(dt), cached.astype(dt))


def _attend_blocks(q_abs, cached, mask, scale: float, block: int):
    """`attend_latents` a block of ``block`` cached rows at a time: the
    softmax's maximum and sum run along (each block's probabilities are
    taken against the maximum so far, and what was summed before is scaled
    down when it rises), and the loop ends with the last block that holds a
    row some query may see."""
    dt = q_abs.dtype
    b, s, h, r = q_abs.shape

    def some_rows(j, carry):
        top, total, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(cached, j * block, block,
                                            axis=2).astype(dt)
        m = jax.lax.dynamic_slice_in_dim(mask, j * block, block, axis=2)
        scores = jnp.einsum("bshr,brt->bsht", q_abs, rows,
                            preferred_element_type=jnp.float32) / scale
        scores = jnp.where(m[:, :, None, :], scores, -1e30)
        new_top = jnp.maximum(top, scores.max(-1))
        # (a hidden score stays at -1e30: it weighs 0 once a real one is in)
        p = jnp.exp(scores - new_top[..., None])
        p = jnp.where(m[:, :, None, :], p, 0.0)
        fade = jnp.exp(top - new_top)
        return (new_top, total * fade + p.sum(-1),
                acc * fade[..., None] + jnp.einsum(
                    "bsht,brt->bshr", p.astype(dt), rows,
                    preferred_element_type=jnp.float32))

    top, total, acc = jax.lax.fori_loop(
        0, (rows_seen(mask) + block - 1) // block, some_rows,
        (jnp.full((b, s, h), -1e30, jnp.float32),
         jnp.zeros((b, s, h), jnp.float32),
         jnp.zeros((b, s, h, r), jnp.float32)))
    return (acc / jnp.maximum(total, 1e-30)[..., None]).astype(dt)


@jax.named_scope("attention")
def unabsorb(o_lat: jnp.ndarray, wkv_b, wo, nope: int) -> jnp.ndarray:
    """`attend_latents`' [b, s, h, kv_lora + rope] -> the block's [b, s, d]:
    the value up-projection on the latent rows, then the output's."""
    dt = o_lat.dtype
    kv_lora = wkv_b.shape[0]
    attn = jnp.einsum("bshr,rhv->bshv", o_lat[..., :kv_lora],
                      wkv_b.astype(dt)[..., nope:])
    with jax.named_scope("projections"):
        return jnp.einsum("bshv,hvd->bsd", attn, wo.astype(dt))
