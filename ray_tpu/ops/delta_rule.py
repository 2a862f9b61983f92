"""Gated delta rule: the state update of a ``"kda"`` layer (Kimi Delta
Attention), an operator that is no attention at all.

A head carries a MATRIX of state ``S`` [dk, dv] in float32 from token to
token, whatever the context.  With the head's query ``q`` and key ``k``
(L2-normalised, the query scaled by ``dk ** -0.5``), value ``v``, a LOG decay
``a <= 0`` for every key channel and a step size ``beta`` in (0, 1)::

    S'  = Diag(exp(a_t)) S_{t-1}                    decay, channel by channel
    S_t = S' + beta_t k_t (v_t - k_t^T S')^T        the delta rule:
                                                    (I - beta k k^T) S' + beta k v^T
    o_t = S_t^T q_t

Three forms that agree (`tests/test_delta_rule.py` holds each to a NumPy
statement of the recurrence):

* `step`: one token a row against a carried state: the decode step.  Both
  reads of the state (``k^T S'`` and ``q^T S'``; ``o = S'^T q + u (k . q)``)
  are ONE pass over it, the write a second: float32 multiply-adds, never a
  matmul that would round the state to the MXU's bfloat16 operands.
* `chunk`: a chunk of tokens a row against a carried state, the CHUNKWISE
  form (algebraically the recurrence, not an approximation).  Inside a block
  of `BLOCK` tokens, with ``G_t = sum_{s <= t} a_s``, the pseudo-values
  ``u~_i = beta_i (v_i - S'_i^T k_i)`` obey ``(I + A) U~ = beta V - (beta K
  exp(G)) S`` with ``A_ij = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])``
  for ``j < i``: ONE unit-lower-triangular solve a block gives ``U = T (beta
  V)`` and ``W = T (beta K exp(G))``, everything that does not depend on the
  carried state is computed for all blocks at once, and a short loop over
  the blocks does the three products that do (``U~ = U - W S``, ``o``, the
  state's update).  The decays inside a block enter as DIFFERENCES ``G_i -
  G_j <= 0`` (``i >= j``), never as ``exp(G_i) exp(-G_j)``: no ``exp`` of a
  positive number is formed, however strong the decay.
* `sequence`: a scan of `step` over a whole sequence from a zero state: the
  plain form of `models.transformer.forward` / ``lm_loss``.

A state written ahead of a row's position is not harmless (nothing repairs
it), so `chunk` and `step` advance a row by its VALID tokens only: padded
tokens get ``beta = 0`` and ``a = 0`` (they neither decay nor write), and a
row with no valid token keeps its state bit for bit.

Plain `jax.numpy`; every function runs under ``jax.named_scope("kda")``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: tokens a block of the chunkwise form: the pairwise decays of a block are
#: ``BLOCK x BLOCK x dk`` exponentials a head, its solve ``BLOCK`` rows deep
BLOCK = 16

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST     # a product that reads the state


@jax.named_scope("kda")
def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """``x / ||x||`` over the last axis, in float32."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@jax.named_scope("kda")
def gates(f: jnp.ndarray, b: jnp.ndarray, a_log: jnp.ndarray,
          dt_bias: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The decay's projection ``f`` [..., h, dk] and the step's ``b`` [...,
    h] -> (``a`` [..., h, dk] the LOG decay ``-exp(A_log[h]) softplus(f +
    dt_bias)``, ``beta`` [..., h] = ``sigmoid(b)``), both float32."""
    a = -jnp.exp(a_log.astype(_F32))[:, None] * jax.nn.softplus(
        f.astype(_F32) + dt_bias.astype(_F32))
    return a, jax.nn.sigmoid(b.astype(_F32))


@jax.named_scope("kda")
def step(q, k, v, a, beta, state: jnp.ndarray,
         live: Optional[jnp.ndarray] = None
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ONE token a row: ``q``, ``k`` [b, h, dk], ``v`` [b, h, dv], ``a`` [b,
    h, dk], ``beta`` [b, h], ``state`` [b, h, dk, dv] float32 -> (``o`` [b,
    h, dv] float32, state').  ``live`` [b] bool (None: all): a row that is
    not live keeps its state bit for bit (its ``o`` means nothing)."""
    q, k, v = (t.astype(_F32) for t in (q, k, v))
    keep = jnp.exp(a)
    # k^T S' and q^T S' in ONE pass over the state as it lies (the decay
    # folded into the two vectors: k^T Diag(e^a) S = (k e^a)^T S), float32
    # multiply-adds; the write is the second pass
    read = jnp.sum((jnp.stack([k, q], axis=2) * keep[:, :, None])[..., None]
                   * state[:, :, None], axis=3)             # [b, h, 2, dv]
    u = beta[..., None] * (v - read[:, :, 0])
    new = keep[..., None] * state + k[..., None] * u[..., None, :]
    o = read[:, :, 1] + u * jnp.sum(q * k, axis=-1, keepdims=True)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return o, new


@jax.named_scope("kda")
def sequence(q, k, v, a, beta) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The plain form: `step` token by token from a zero state.  ``q``,
    ``k`` [b, s, h, dk], ``v`` [b, s, h, dv], ``a`` [b, s, h, dk], ``beta``
    [b, s, h] -> (``o`` [b, s, h, dv] float32, the last state)."""
    b, _, h, dk = q.shape
    state = jnp.zeros((b, h, dk, v.shape[-1]), _F32)

    def one(state, x):
        o, state = step(*x, state)
        return state, o

    state, o = jax.lax.scan(one, state, tuple(
        jnp.swapaxes(t, 0, 1) for t in (q, k, v, a, beta)))
    return jnp.swapaxes(o, 0, 1), state


@jax.named_scope("kda")
def chunk(q, k, v, a, beta, state: jnp.ndarray,
          n_valid: Optional[jnp.ndarray] = None, block: int = BLOCK
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A chunk of ``c`` tokens a row against a carried state, chunkwise in
    blocks of ``block`` tokens: shapes as `sequence`'s, ``state`` [b, h, dk,
    dv] float32 -> (``o`` [b, c, h, dv] float32, state').  ``n_valid`` [b]
    int32 (0 .. c; None: c): the row's real tokens; the rest neither decay
    nor write the state, and a row of none keeps it bit for bit."""
    b, c, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v, a, beta = (t.astype(_F32) for t in (q, k, v, a, beta))
    if n_valid is not None:
        real = jnp.arange(c)[None, :] < n_valid[:, None]        # [b, c]
        a = jnp.where(real[..., None, None], a, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    block = min(block, c)
    pad = -c % block
    nb = (c + pad) // block

    def blocks(t):      # [b, c, h, ...] -> [nb, b, h, block, ...]
        t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        t = t.reshape((b, nb, block) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 1), 2, 0)

    q, k, v, a = (blocks(t) for t in (q, k, v, a))
    beta = blocks(beta[..., None])                  # [nb, b, h, block, 1]
    g = jnp.cumsum(a, axis=3)                       # [nb, b, h, block, dk]
    at = jnp.arange(block)
    seen = at[:, None] >= at[None, :]               # token i sees j <= i
    # the pairwise decays exp(G_i - G_j), j <= i: no exponent above 0
    decay = jnp.exp(jnp.where(seen[..., None],
                              g[..., :, None, :] - g[..., None, :, :],
                              -jnp.inf))            # [.., block, block, dk]
    kk = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    qk = jnp.sum(q[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    unit = jnp.eye(block, dtype=_F32)
    lower = unit + beta * kk * (at[:, None] > at[None, :])       # I + A
    into = jnp.exp(g)                   # from the block's start to token i
    solved = jax.lax.linalg.triangular_solve(
        lower, jnp.concatenate([beta * v, beta * k * into], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    last = g[..., -1:, :]                           # the block's whole decay
    xs = (solved[..., :dv], solved[..., dv:], q * into, qk,
          k * jnp.exp(last - g), jnp.exp(last[..., 0, :]))

    def one(s, x):      # what depends on the carried state, a block
        u, w, q_in, qk, k_out, through = x
        u = u - jnp.einsum("bhik,bhkv->bhiv", w, s, precision=_EXACT)
        o = jnp.einsum("bhik,bhkv->bhiv", q_in, s, precision=_EXACT) \
            + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_EXACT)
        s = through[..., None] * s + jnp.einsum(
            "bhik,bhiv->bhkv", k_out, u, precision=_EXACT)
        return s, o

    new, o = jax.lax.scan(one, state, xs)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # [b, nb, block, h, dv]
    o = o.reshape(b, nb * block, h, dv)[:, :c]
    if n_valid is not None:
        new = jnp.where((n_valid > 0)[:, None, None, None], new, state)
    return o, new
