"""Gated short convolution: the operator of a ``"conv"`` layer.

A depthwise causal convolution of ``L`` taps over the sequence, gated before
and after, between two projections::

    [B | C | X] = y W_in            W_in [d, 3d], three parts of d
    u_t = B_t * X_t
    v_t = sum_{j < L} w[:, j] * u_{t - (L-1) + j}     u_t = 0 for t < 0
    out = (C * v) W_out             W_out [d, d]

What a sequence carries from token to token is NOT a row a position: it is
the last ``L - 1`` inputs of the convolution, ``u_{t-L+1} .. u_{t-1}``,
whatever the context (`models/generate.py` keeps it as a state of its own
kind beside the attention layers' rows).

`short_conv` is ONE function in three forms: over a whole sequence (no
state), over a chunk with the state carried in and out, and over one token
(a chunk of one).  Unlike a key or a value, a state written ahead of a
row's position is not harmless, so the carry-out is taken where the row's
VALID tokens end (``n_new``): a padded chunk's state is its last real
token's, and a row that does not advance keeps its state bit for bit.

A ``"kda"`` layer (`ops/delta_rule.py`) runs its queries, keys and values
through the same convolution, over channels of its own and with an
``activation`` after it (`short_conv`: any width of channels, the carried
inputs and ``n_new`` as above); a state-space mixer (`ops/ssd.py`) its
values, keys and queries, with a ``bias`` a channel before the activation.

Plain `jax.numpy`: ``L`` shifted multiply-adds that XLA fuses between the
two matmuls.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp


@jax.named_scope("conv")
def conv_inputs(y: jnp.ndarray, w_in: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Normed input ``y`` [b, s, d] -> (``u`` = B * X, the convolution's
    input, and the output gate ``C``), both [b, s, d] in ``y``'s type."""
    b, c, x = jnp.split(
        jnp.einsum("bsd,de->bse", y, w_in.astype(y.dtype)), 3, axis=-1)
    return b * x, c


@jax.named_scope("conv")
def short_conv(u: jnp.ndarray, w: jnp.ndarray,
               state: Optional[jnp.ndarray] = None,
               n_new: Optional[jnp.ndarray] = None,
               activation: Optional[Callable] = None,
               bias: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``u`` [b, s, d], taps ``w`` [d, L] (tap ``L - 1`` meets the current
    token), ``state`` [b, L - 1, d] the inputs before the chunk (None: the
    sequence starts here, zeros) -> (``v`` [b, s, d], state' [b, L - 1, d]).

    ``n_new`` [b] int32 (0 .. s; None: s) says by how many tokens each
    row ADVANCES: state' holds the inputs ``n_new - (L-1) .. n_new - 1`` of
    the chunk, reaching back into ``state`` where the chunk is shorter.  0
    returns the row's state as it came.  ``v`` is computed for all ``s``
    rows whatever ``n_new`` (row ``t`` sees only rows ``<= t``), takes
    ``bias`` [d] (None: none) and goes through ``activation`` (None: as it
    is) in float32."""
    b, s, d = u.shape
    taps = w.shape[-1]
    if state is None:
        state = jnp.zeros((b, taps - 1, d), u.dtype)
    ext = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    w32 = w.astype(jnp.float32)
    v = sum(w32[:, j] * ext[:, j:j + s].astype(jnp.float32)
            for j in range(taps))
    if bias is not None:
        v = v + bias.astype(jnp.float32)
    if activation is not None:
        v = activation(v)
    if n_new is None:
        carry = ext[:, s:]
    else:
        rows = n_new[:, None] + jnp.arange(taps - 1)[None, :]
        carry = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
    return v.astype(u.dtype), carry


@jax.named_scope("conv")
def conv_block(y: jnp.ndarray, w_in: jnp.ndarray, w: jnp.ndarray,
               w_out: jnp.ndarray, state: Optional[jnp.ndarray] = None,
               n_new: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The whole operator on a normed input ``y`` [b, s, d] -> (what the
    layer adds to the residual [b, s, d], state')."""
    u, gate = conv_inputs(y, w_in)
    v, state = short_conv(u, w, state, n_new)
    return jnp.einsum("bsd,de->bse", gate * v, w_out.astype(y.dtype)), state
