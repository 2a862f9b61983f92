"""EVA attention: softmax attention by control variates (Zheng et al., ICLR
2023) in the form a byte-level model ships it.

Position ``t`` lies in the BLOCK ``w = t // window`` (block-aligned, not
sliding).  It attends

  * the positions ``i <= t`` of its own block exactly, and
  * every chunk of ``chunk`` positions of the EARLIER blocks through ONE
    pooled (key, value) pair, its summary (``chunk`` divides ``window``, so
    every such chunk is complete); no summary of its own block, no token of
    an earlier one,

under ONE softmax over both sets, in float32.  A chunk's summary, for each
key-value head with its learned ``phi`` and ``mu`` [head_dim]::

    a_i  = softmax over the chunk's i of (k_i . phi)       (k_i ROTATED)
    kbar = sum_i a_i k_i + mu          vbar = sum_i a_i v_i

Two limits tie it to plain attention (tests/test_eva_attention.py): with
``window >= seq`` no summary is ever visible and this is causal softmax
attention; with ``chunk = 1`` and ``mu = 0`` a summary is its token and this
is causal attention over the whole context.

Here: the pooling (`pool_chunks`, positions last as a cache holds them), the
joint softmax over two row sets of different origin (`attend_two`: a
ring's rows and the summaries' in the cached programs of
`models/generate.py`), the two masks, and the plain whole-sequence form
(`eva_attention`, dense: what `forward`, `lm_loss` and a whole-prompt
`prefill` run at sizes where ``[seq, seq]`` scores fit).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def pool_chunks(k: jnp.ndarray, v: jnp.ndarray, phi: jnp.ndarray,
                mu: jnp.ndarray):
    """Keys ``k`` [..., heads, head_dim, n, chunk] and values ``v`` [...,
    heads, value_dim, n, chunk] of ``n`` chunks (positions LAST, as a cache
    holds them), ``phi`` and ``mu`` [heads, head_dim] → float32 ``(kbar
    [..., heads, head_dim, n], vbar [..., heads, value_dim, n])``: each
    chunk pooled under the softmax of its keys against ``phi``, the key's
    offset by ``mu``."""
    k, v = k.astype(F32), v.astype(F32)
    a = jax.nn.softmax(jnp.einsum("...hdnc,hd->...hnc", k, phi.astype(F32)),
                       axis=-1)
    kbar = jnp.einsum("...hdnc,...hnc->...hdn", k, a) \
        + mu.astype(F32)[:, :, None]
    return kbar, jnp.einsum("...hdnc,...hnc->...hdn", v, a)


def block_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
               window: int) -> jnp.ndarray:
    """Positions ``q_pos`` [..., q, 1] against ``k_pos`` [..., 1 | q, k] →
    bool: the key lies in the query's own block, at or before it."""
    return (k_pos >= 0) & (k_pos <= q_pos) \
        & (k_pos // window == q_pos // window)


def summary_mask(q_pos: jnp.ndarray, n: int, window: int,
                 chunk: int) -> jnp.ndarray:
    """``q_pos`` [..., q] → [..., q, n] bool: summary ``j`` of ``n`` is
    visible where its whole chunk lies in a block before the query's."""
    ends = (jnp.arange(n) + 1) * chunk
    return ends <= (q_pos[..., None] // window) * window


def as_chunks(cols: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Columns [..., s] (positions last) → [..., ceil(s / chunk), chunk],
    the last chunk padded with zeros (it is not complete: no query sees
    its summary)."""
    n = -(-cols.shape[-1] // chunk)
    cols = jnp.pad(cols, ((0, 0),) * (cols.ndim - 1)
                   + ((0, n * chunk - cols.shape[-1]),))
    return cols.reshape(cols.shape[:-1] + (n, chunk))


@jax.named_scope("attention")
def attend_two(q: jnp.ndarray, k_a, v_a, k_b, v_b, m_a, m_b) -> jnp.ndarray:
    """``q`` [b, s, kv_heads, group, head_dim] over TWO row sets ``a`` and
    ``b``, each keys [b, kv_heads, head_dim, rows], values [b, kv_heads,
    value_dim, rows] (positions last) and a mask [b | 1, s, rows] → [b, s,
    kv_heads, group, value_dim] in ``q``'s type: ONE softmax over the rows
    of both, its scores and statistics float32, normalised after the values
    are summed.  A query needs one visible row in either set (its own
    position)."""
    dt = q.dtype
    scale = q.shape[-1] ** -0.5

    def scores(k, m):
        s = jnp.einsum("bskgd,bkdt->bskgt", q, k.astype(dt),
                       preferred_element_type=F32) * scale
        return jnp.where(m[:, :, None, None, :], s, -1e30)

    s_a, s_b = scores(k_a, m_a), scores(k_b, m_b)
    top = jnp.maximum(s_a.max(-1, keepdims=True), s_b.max(-1, keepdims=True))
    e_a, e_b = jnp.exp(s_a - top), jnp.exp(s_b - top)
    den = e_a.sum(-1, keepdims=True) + e_b.sum(-1, keepdims=True)

    def values(e, v):
        return jnp.einsum("bskgt,bkdt->bskgd", e.astype(dt), v.astype(dt),
                          preferred_element_type=F32)

    return ((values(e_a, v_a) + values(e_b, v_b)) / den).astype(dt)


@jax.named_scope("attention")
def eva_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  phi: jnp.ndarray, mu: jnp.ndarray, *, window: int,
                  chunk: int) -> jnp.ndarray:
    """The plain form over a whole sequence from position 0: ``q`` [b, s,
    heads, head_dim], rotated ``k`` [b, s, kv_heads, head_dim], ``v`` [b, s,
    kv_heads, value_dim] → [b, s, heads, value_dim].  Dense, float32:
    scores of ``[s, s]`` beside ``[s, s / chunk]`` a head."""
    b, s, h, hd = q.shape
    hk = k.shape[2]

    def cols(t):    # [b, s, hk, w] -> [b, hk, w, s], positions last
        return jnp.transpose(t, (0, 2, 3, 1)).astype(F32)

    kc, vc = cols(k), cols(v)
    kbar, vbar = pool_chunks(as_chunks(kc, chunk), as_chunks(vc, chunk),
                             phi, mu)
    pos = jnp.arange(s)
    out = attend_two(
        q.reshape(b, s, hk, h // hk, hd).astype(F32), kc, vc, kbar, vbar,
        block_mask(pos[:, None], pos[None, :], window)[None],
        summary_mask(pos, kbar.shape[-1], window, chunk)[None])
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)
