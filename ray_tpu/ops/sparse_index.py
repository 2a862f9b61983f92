"""Learned sparse attention's INDEXER: which cached positions a query attends.

An indexing layer scores every position at or before a query with a few
small heads of its own and keeps the ``topk`` best; the layer, and the layers
behind it that share its choice, run their softmax over the chosen positions
only.  With ``y_t`` the block's normed input and ``c_q,t`` the query latent
that latent attention already forms (`ops/latent_attention.py`)::

    qI[t, j] = (c_q,t W_iq)[j]                  j = 1..heads, ``dim`` wide
    kI[t]    = layernorm(y_t W_ik)              ONE key a position
    rotary on the first ``rope`` dims of qI[t, j] and kI[t], at position t
    w[t, j]  = (y_t W_w)[j] / sqrt(heads x dim)               float32
    I[t, s]  = sum_j w[t, j] relu(qI[t, j] . kI[s])           float32
    S_t      = the min(t + 1, topk) allowed positions of largest I[t, s],
               equal scores: the earlier position first

The products take operands in the compute type and accumulate in float32;
the scores, the head sum and the choice are float32.  The choice is EXACT:
the ``topk``-th largest score is found by a search over the bits of its
float32 (32 counts of "how many scores are at least this", no sort and no
approximation), everything above it is taken, and of the scores that equal
it the earliest positions until ``topk`` are.

Keys are stored as a cache stores them, ``[batch, dim, positions]``,
positions last (`models/generate.py`: the fifth kind of state).  Everything
here runs under ``jax.named_scope("indexer")`` INSIDE ``attention``: the
model's ten parts (`util.device_profile.MODEL_PARTS`) still add up, attention
counts the indexer, and a reader that wants it apart takes the operations
with an ``indexer`` component in their path (the rotary turn of its queries
and keys is `ops/rotary.py`'s and falls in ``projections``).
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

from .norms import layernorm

Rotate = Callable[[jnp.ndarray], jnp.ndarray]   # [b, s, heads, rope] -> same

#: keys scored at a time where a caller bounds the rows that matter: the
#: float32 products of all heads of a chunk of 128 queries over 33 k keys
#: would be 0.55 GB, and most of them of rows no query may see
KEY_BLOCK = 1024


def _scoped(fn):
    """``fn`` under the ``indexer`` scope inside ``attention``."""
    return jax.named_scope("attention")(jax.named_scope("indexer")(fn))


def _rotate_first(t: jnp.ndarray, rotate: Rotate, rope: int) -> jnp.ndarray:
    """``rotate`` over the first ``rope`` dims of each head of ``t``."""
    return jnp.concatenate([rotate(t[..., :rope]), t[..., rope:]], axis=-1)


@_scoped
def index_queries(c_q: jnp.ndarray, wi_q, *, rotate: Rotate,
                  rope: int) -> jnp.ndarray:
    """Query latents ``c_q`` [b, s, q_lora] -> qI [b, s, heads, dim],
    rotated."""
    q = jnp.einsum("bsr,rhk->bshk", c_q, wi_q.astype(c_q.dtype))
    return _rotate_first(q, rotate, rope)


@_scoped
def index_keys(y: jnp.ndarray, wi_k, scale, bias, *, rotate: Rotate,
               rope: int, eps: float = 1e-6) -> jnp.ndarray:
    """Normed input ``y`` [b, s, d] -> kI [b, s, dim]: ONE key a position,
    layer-normed then rotated.  This is what the index cache holds."""
    k = layernorm(jnp.einsum("bsd,dk->bsk", y, wi_k.astype(y.dtype)),
                  scale, bias, eps)
    return _rotate_first(k[:, :, None, :], rotate, rope)[:, :, 0, :]


@_scoped
def head_weights(y: jnp.ndarray, wi_w, dim: int) -> jnp.ndarray:
    """Normed input ``y`` [b, s, d] -> w [b, s, heads] float32, scaled by
    ``1 / sqrt(heads x dim)``."""
    w = jnp.einsum("bsd,dh->bsh", y, wi_w.astype(y.dtype),
                   preferred_element_type=jnp.float32)
    return w / math.sqrt(wi_w.shape[-1] * dim)


def key_block(rows: int) -> int:
    """The block of cached rows a blocked read takes at a time: a divisor of
    ``rows`` (0: they have none worth blocking by)."""
    block = math.gcd(rows, KEY_BLOCK)
    return block if 128 <= block < rows else 0


#: float32 scores XLA's forms build over every row at once; past it their
#: rows are read a block at a time in a loop, which over a context of a few
#: thousand rows costs more than the hidden rows it skips
_WHOLE_SCORES = 160 << 20


def loop_block(queries: int, heads: int, rows: int) -> int:
    """The block of cached rows XLA'S forms read at a time for ``queries``
    queries of ``heads`` heads over ``rows`` positions (0: all at once):
    blocked where their float32 scores would pass `_WHOLE_SCORES`, which no
    program of a context of a few thousand rows does, and a decode step
    (one query a slot) does at none.  Asked of a latent layer's reads where
    no kernel serves them (`latent_attention.attend_latents`) and of an
    indexer's scores (`index_scores`), each about its own."""
    return key_block(rows) if queries * heads * rows * 4 > _WHOLE_SCORES \
        else 0


def rows_seen(mask: jnp.ndarray) -> jnp.ndarray:
    """``mask`` [..., T] bool -> int32 scalar: one past the last column any
    query may see."""
    t = mask.shape[-1]
    seen = mask.any(axis=tuple(range(mask.ndim - 1)))
    return jnp.max(jnp.where(seen, jnp.arange(t, dtype=jnp.int32) + 1, 0))


@_scoped
def index_scores(q_i: jnp.ndarray, w: jnp.ndarray, keys: jnp.ndarray,
                 rows=None) -> jnp.ndarray:
    """qI [b, s, heads, dim], w [b, s, heads] float32, ``keys`` [b, dim, T]
    -> I [b, s, T] float32: the heads' rectified products under their
    weights.  ``rows`` (a traced count): only the first ``rows`` keys
    matter (the rest score 0), and they are scored a block at a time, no
    block past them."""
    keys = keys.astype(q_i.dtype)

    def score(k):
        dots = jnp.einsum("bshk,bkt->bsht", q_i, k,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("bsht,bsh->bst", jax.nn.relu(dots), w)

    block = key_block(keys.shape[-1])
    if rows is None or not block:
        return score(keys)

    def some_keys(j, out):
        k = jax.lax.dynamic_slice_in_dim(keys, j * block, block, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(out, score(k), j * block,
                                                   axis=2)

    return jax.lax.fori_loop(
        0, (rows + block - 1) // block, some_keys,
        jnp.zeros(q_i.shape[:2] + keys.shape[-1:], jnp.float32))


def _ordered(scores: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 that compares as the floats do (no NaN)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


@_scoped
def select(scores: jnp.ndarray, allowed: jnp.ndarray,
           topk: int) -> jnp.ndarray:
    """``scores`` [..., T] float32, ``allowed`` [..., T] bool -> [..., T]
    bool: the ``min(allowed, topk)`` allowed positions of largest score,
    equal scores the earlier position first.  Exact."""
    scores = jnp.where(scores == 0, 0.0, scores)         # -0.0 is 0.0
    key = jnp.where(allowed, _ordered(scores), jnp.uint32(0))
    # (an allowed score's key is never 0: that would be a NaN's)

    def bit(i, found):
        # the largest key that at least ``topk`` keys reach, a bit a turn
        trial = found | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        reach = (key >= trial[..., None]).sum(-1)
        return jnp.where(reach >= topk, trial, found)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(key.shape[:-1], jnp.uint32))[..., None]
    above = key > kth
    level = (key == kth) & allowed
    room = topk - above.sum(-1, keepdims=True)
    # scores that EQUAL the topk-th: the earliest until topk are reached
    # (a running count, made only where some query has more than it takes)
    level = jax.lax.cond(
        jnp.all(level.sum(-1, keepdims=True) <= room), lambda: level,
        lambda: level & (jnp.cumsum(level, axis=-1, dtype=jnp.int32)
                         <= room))
    return above | level


@_scoped
def selection_mask(q_i: jnp.ndarray, w: jnp.ndarray, keys: jnp.ndarray,
                   allowed: jnp.ndarray, topk: int,
                   blocked: bool = False) -> jnp.ndarray:
    """`index_scores` then `select`: [b, s, T] bool, a query's chosen
    positions among those ``allowed`` [b | 1, s, T]; ``blocked``: the keys
    scored a block at a time, up to the last one any query may see."""
    scores = index_scores(q_i, w, keys,
                          rows_seen(allowed) if blocked else None)
    return select(scores, jnp.broadcast_to(allowed, scores.shape), topk)
