"""State-space recurrence of a Mamba-2 mixer (SSD): the state update of an
``"ssm+full"`` layer's state-space half, a SELECTIVE SCAN.

A head carries a MATRIX of state ``S`` [n, p] in float32 from token to token,
whatever the context: ``n`` the state's size (the width of a key ``B`` and a
query ``C``), ``p`` the head's width (a value ``x``).  Keys and queries are
shared by GROUPS of heads: head ``h`` of ``heads`` reads group ``h // (heads
// groups)``.  With a step size ``dt > 0`` a head a token (``softplus`` of a
projection plus a bias) and ONE decay rate ``A < 0`` a head (`gates`)::

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T       a scalar decay a head
    y_t = S_t^T C_t + D x_t                            D a skip a head

Unlike a gated delta rule (`ops/delta_rule.py`) nothing READS the state to
write it (no correction), and the decay is a scalar a head, not a channel's:
so a chunk needs no triangular solve.  Three forms that agree
(`tests/test_ssd.py` holds each to a NumPy statement of the recurrence):

* `step`: one token a row against a carried state: the decode step, float32
  multiply-adds, never a matmul that would round the state to the MXU's
  bfloat16 operands.  `step_in_place` is the same step against layer ``l`` of
  the STACKED states of a cache where they lie (below).
* `chunk`: a chunk of ``c`` tokens a row against a carried state, the
  CHUNKWISE form (algebraically the recurrence, not an approximation), the
  whole chunk ONE block (a serve engine's chunk of 128 is one
  ``mamba_chunk_size``): with ``G_t = sum_{s <= t} dt_s A``, the masked
  products of the chunk ``y_i += sum_{j <= i} exp(G_i - G_j) (C_i . B_j) dt_j
  x_j``, the carried state's share ``exp(G_i) S^T C_i``, and the state out
  ``exp(G_c) S + sum_j exp(G_c - G_j) B_j (dt_j x_j)^T``.  The decays enter
  as DIFFERENCES ``G_i - G_j <= 0`` only: no ``exp`` of a positive number is
  formed, however strong the decay.
* `sequence`: a scan of `step` over a whole sequence from a zero state: the
  plain form of `models.transformer.forward`.

A state written ahead of a row's position is not harmless (nothing repairs
it), so `chunk` and `step` advance a row by its VALID tokens only: padded
tokens get ``dt = 0`` (they neither decay nor write), and a row with no valid
token keeps its state bit for bit.

THE STEP WHERE THE STATES LIE.  `step_in_place` is ONE `pl.pallas_call` a
layer (named ``ssd_step`` in a trace) over the stacked array ``[L, b, heads,
n, p]``, aliased in and out, in the form `ops/delta_rule.py` `step_in_place`
has and through its call (`delta_rule.in_place_call`: its plan of the grid's
steps, its budget of heads a block; its turn of a vector onto the sublanes):
the grid runs over (slot, block of heads), a
grid step loads its heads' ``S`` once and with it in VMEM decays it, adds
``B (dt x)^T``, writes it to the same block and sums ``C`` down the
sublanes.  The LIVE slots come first; a slot that stands is neither read nor
written and keeps its state bit for bit.  One token a row, a float32 state
whose ``n`` and ``p`` are whole 128-lane tiles, groups of whole sublane
tiles of heads, a program lowered for a TPU (`kernel_shape`;
`RAY_TPU_PALLAS_INTERPRET=1` runs the kernel through the interpreter); every
other shape and platform runs `step` between a cut of the layer and its
placement back, which is the kernel's reference too.

`gated_norm` is what stands between the recurrence and the output
projection: the gate FIRST (``y silu(z)``), then an RMS norm over each
GROUP's channels.  Everything here runs under ``jax.named_scope("ssm")``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .delta_rule import (_GROUP, _LEADS, _LIVE, _columns, _head_block,
                         in_place_call)
from .flash_attention import _LANES, _interpret
from .latent_attention import on_the_chip

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST     # a product that reads the state


@jax.named_scope("ssm")
def gates(dt: jnp.ndarray, dt_bias: jnp.ndarray, a_log: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The step's projection ``dt`` [..., h] -> (the step size ``softplus(dt
    + dt_bias)`` [..., h], the LOG decay ``-exp(A_log) dt`` [..., h] <= 0),
    both float32.  No clamp: a configuration that states none has none."""
    dt = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    return dt, -jnp.exp(a_log.astype(_F32)) * dt


def _by_group(t: jnp.ndarray, groups: int) -> jnp.ndarray:
    """``t`` [b, h, ...] -> [b, groups, h // groups, ...]: the heads of a
    group side by side, as its one key and query meet them."""
    b, h = t.shape[:2]
    return t.reshape((b, groups, h // groups) + t.shape[2:])


@jax.named_scope("ssm")
def step(x, B, C, dt, a, D, state: jnp.ndarray,
         live: Optional[jnp.ndarray] = None
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ONE token a row: ``x`` [b, h, p], ``B``, ``C`` [b, g, n], ``dt``,
    ``a`` [b, h] (`gates`), ``D`` [h], ``state`` [b, h, n, p] float32 ->
    (``y`` [b, h, p] float32, state').  ``live`` [b] bool (None: all): a row
    that is not live keeps its state bit for bit (its ``y`` means
    nothing)."""
    x, B, C = (t.astype(_F32) for t in (x, B, C))
    g = B.shape[1]
    s = _by_group(state, g)                                 # [b, g, k, n, p]
    xdt = _by_group(x * dt[..., None], g)                   # [b, g, k, p]
    new = _by_group(jnp.exp(a), g)[..., None, None] * s \
        + B[:, :, None, :, None] * xdt[..., None, :]
    y = jnp.sum(C[:, :, None, :, None] * new, axis=3).reshape(x.shape) \
        + D.astype(_F32)[:, None] * x
    new = new.reshape(state.shape)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return y, new


@jax.named_scope("ssm")
def sequence(x, B, C, dt, a, D) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The plain form: `step` token by token from a zero state.  ``x`` [b,
    s, h, p], ``B``, ``C`` [b, s, g, n], ``dt``, ``a`` [b, s, h], ``D`` [h]
    -> (``y`` [b, s, h, p] float32, the last state [b, h, n, p])."""
    b, _, h, p = x.shape
    state = jnp.zeros((b, h, B.shape[-1], p), _F32)

    def one(state, t):
        y, state = step(*t, D, state)
        return state, y

    state, y = jax.lax.scan(one, state, tuple(
        jnp.swapaxes(t, 0, 1) for t in (x, B, C, dt, a)))
    return jnp.swapaxes(y, 0, 1), state


@jax.named_scope("ssm")
def chunk(x, B, C, dt, a, D, state: jnp.ndarray,
          n_valid: Optional[jnp.ndarray] = None
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A chunk of ``c`` tokens a row against a carried state, chunkwise, the
    chunk one block: shapes as `sequence`'s, ``state`` [b, h, n, p] float32
    -> (``y`` [b, c, h, p] float32, state').  ``n_valid`` [b] int32 (0 ..
    c; None: c): the row's real tokens; the rest neither decay nor write the
    state, and a row of none keeps it bit for bit."""
    b, c, h, p = x.shape
    g = B.shape[2]
    x, B, C, dt, a = (t.astype(_F32) for t in (x, B, C, dt, a))
    if n_valid is not None:
        real = jnp.arange(c)[None, :] < n_valid[:, None]        # [b, c]
        dt = jnp.where(real[..., None], dt, 0.0)
        a = jnp.where(real[..., None], a, 0.0)
    through = jnp.cumsum(a, axis=1)                 # G_t, [b, c, h]
    at = jnp.arange(c)
    # the pairwise decays exp(G_i - G_j), j <= i: no exponent above 0
    gt = jnp.swapaxes(through, 1, 2)                # [b, h, c]
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              gt[..., :, None] - gt[..., None, :], -jnp.inf))
    cb = jnp.einsum("bign,bjgn->bgij", C, B, precision=_EXACT)
    xdt = (x * dt[..., None]).reshape(b, c, g, h // g, p)
    w = decay.reshape(b, g, h // g, c, c) * cb[:, :, None]
    s = _by_group(state, g)
    y = jnp.einsum("bgkij,bjgkp->bigkp", w, xdt, precision=_EXACT) \
        + jnp.exp(through).reshape(b, c, g, h // g)[..., None] * jnp.einsum(
            "bign,bgknp->bigkp", C, s, precision=_EXACT)
    last = through[:, -1:]                          # the chunk's whole decay
    out = jnp.exp(last - through).reshape(b, c, g, h // g)[..., None] * xdt
    new = jnp.exp(last[:, 0]).reshape(b, g, h // g)[..., None, None] * s \
        + jnp.einsum("bjgn,bjgkp->bgknp", B, out, precision=_EXACT)
    y = y.reshape(b, c, h, p) + D.astype(_F32)[:, None] * x
    new = new.reshape(state.shape)
    if n_valid is not None:
        new = jnp.where((n_valid > 0)[:, None, None, None], new, state)
    return y, new


@jax.named_scope("ssm")
def gated_norm(y: jnp.ndarray, z: jnp.ndarray, weight: jnp.ndarray,
               groups: int, eps: float) -> jnp.ndarray:
    """``y``, ``z`` [..., channels] -> ``rmsnorm(y silu(z))`` over each of
    the ``groups`` GROUPS of channels apart, times ``weight`` [channels],
    float32: the gate FIRST, then the norm."""
    y = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    by = y.reshape(y.shape[:-1] + (groups, -1))
    by = by * jax.lax.rsqrt(jnp.mean(by * by, axis=-1, keepdims=True) + eps)
    return by.reshape(y.shape) * weight.astype(_F32)


# ------------------------------------------- the step, where the states lie

def kernel_shape(tokens: int, states: jax.ShapeDtypeStruct,
                 groups: int) -> bool:
    """Whether `step_in_place`'s kernel takes ``tokens`` new tokens a row
    against stacked ``states`` [L, b, h, n, p] (on a TPU, or under the
    interpreter): ONE token a row, a float32 state whose two axes are whole
    128-lane tiles, a block of heads inside the budget, and every sublane
    tile of heads inside one group (it turns ONE key and one query for the
    tile)."""
    heads, n, p = states.shape[2:]
    hb = _head_block(heads, n, p)
    return tokens == 1 and states.dtype == _F32 and n % _LANES == 0 \
        and p % _LANES == 0 and hb > 0 and heads % groups == 0 \
        and (heads // groups) % (_GROUP if hb % _GROUP == 0 else hb) == 0


def engages(tokens: int, states: jax.ShapeDtypeStruct, groups: int) -> bool:
    """Whether a step lowered by THIS process's backend runs the kernel (a
    host answer from shapes, as `ops.delta_rule.engages`)."""
    return (jax.default_backend() == "tpu" or _interpret()) \
        and kernel_shape(tokens, states, groups)


def _step_kernel(l_ref, plan_ref, bc_ref, w_ref, s_ref, y_ref, out_ref, *,
                 group: int):
    del l_ref
    hb, _, p = s_ref.shape
    does = plan_ref[2, pl.program_id(0)]

    @pl.when(does == _LIVE)
    def _():
        def heads(g, _):
            at = pl.multiple_of(g * group, group)
            # the tile's heads share ONE key and one query: the first row's
            key, query = (_columns(bc_ref[i, pl.ds(at, group), :][:1], p)
                          for i in range(2))                # [1, n, p]
            xdt, keep = (w_ref[i, pl.ds(at, group), :] for i in range(2))
            s = keep[:, None, :] * s_ref[pl.ds(at, group)] \
                + key * xdt[:, None, :]
            y_ref[pl.ds(at, group), :] = jnp.sum(query * s, axis=1)
            out_ref[pl.ds(at, group)] = s
            return _

        jax.lax.fori_loop(0, hb // group, heads, 0)

    @pl.when(does != _LIVE)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(does == _LEADS)
    def _():
        out_ref[...] = s_ref[...]


@jax.named_scope("ssm")
def step_in_place(x, B, C, dt, a, D, s_all: jnp.ndarray, l,
                  live: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`step` against layer ``l`` of the STACKED states ``s_all`` [L, b, h,
    n, p] where they lie -> (``y`` [b, h, p] float32, the stack with that
    layer advanced).  One kernel call where `kernel_shape` and the platform
    allow: a live row's states are read once and written once, a row that
    stands is neither read nor written (its ``y`` means nothing); `step`
    between a cut and a placement elsewhere."""
    x, B, C, dt, a = (t.astype(_F32) for t in (x, B, C, dt, a))
    l = jnp.asarray(l, jnp.int32)

    def slices(x, B, C, dt, a, s_all, l, live):
        y, new = step(x, B, C, dt, a, D, jax.lax.dynamic_index_in_dim(
            s_all, l, 0, keepdims=False), live)
        return y, jax.lax.dynamic_update_slice(s_all, new[None],
                                               (l, 0, 0, 0, 0))

    if not kernel_shape(1, s_all, B.shape[1]):
        return slices(x, B, C, dt, a, s_all, l, live)
    if live is None:
        live = jnp.ones(x.shape[:1], bool)

    def kernel(x, B, C, dt, a, s_all, l, live):
        per = s_all.shape[2] // B.shape[1]      # heads a group
        xdt = x * dt[..., None]
        y, new = in_place_call(
            _step_kernel, "ssd_step",
            jnp.stack([jnp.repeat(B, per, axis=1),
                       jnp.repeat(C, per, axis=1)], axis=1),
            jnp.stack([xdt, jnp.broadcast_to(jnp.exp(a)[..., None],
                                             xdt.shape)], axis=1),
            s_all, l, live)
        return y + D.astype(_F32)[:, None] * x, new

    return on_the_chip(kernel, slices, x, B, C, dt, a, s_all, l, live)
