"""Selective scan of a Mamba-1 mixer: the state update of a ``"mamba"``
layer, a decay a CHANNEL a STATE COLUMN.

A sequence carries a matrix of state ``h`` [n, d] in float32 from token to
token, whatever the context: ``d`` the mixer's channels (``d_inner``), ``n``
the state's columns (``d_state``).  With a step size ``dt > 0`` a channel a
token (``softplus`` of a low-rank projection plus a bias), a decay rate ``A <
0`` a channel a column (``-exp(A_log)``), one key ``B`` and one query ``C`` of
``n`` values a token, and ``a`` the channel's input (after its convolution)::

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] a_t[c] B_t[n]
    m_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] a_t[c]

`ops/ssd.py` (Mamba-2) cannot run it: there the decay is ONE scalar a head,
so a chunk's pairwise decays are a ``[c, c]`` matrix a head and the chunk a
few matmuls.  Here the decay differs by channel AND column: the pairwise form
would be ``[c, c, n, d]``, and the chunk stays a recurrence over time.  Three
forms that agree (`tests/test_selective_scan.py` holds each to a NumPy
statement of the recurrence):

* `step`: one token a row against a carried state: the decode step, float32
  multiply-adds over ``[b, n, d]``.  `step_in_place` is the same step against
  layer ``l`` of the STACKED states of a cache: a cut of the layer, the step,
  its placement back, which the compiler runs where the stack lies (an
  elementwise update of one layer of a loop's state: the compiled step of a
  described v5e holds no copy of the stack, `tests/test_chip_compile.py`).
  `ops/delta_rule.py` `in_place_call` is not its call: a grid step there is
  handed a slot's vectors and its block of states, and has no operand for
  the layer's ``A`` [n, d], as large as a slot's state.
* `chunk`: a chunk of ``c`` tokens a row against a carried state.  Where the
  shapes are whole tiles and the program is lowered for a TPU, ONE
  `pl.pallas_call` (named ``selective_scan_chunk`` in a trace): the grid over
  (row, block of channels), the channels on the LANES, the state's columns
  on the SUBLANES, a block's state ``[n, block]`` held in registers and VMEM
  across the chunk's tokens, eight tokens a loop turn; what it moves is its
  operands once (``a``, ``dt`` and ``m`` ``[c, d]``, the keys and queries
  broadcast to a lane tile, the state in and out).  An associative scan in
  XLA would carry ``[c, n, d]`` float32 through memory a pass (42 MB a row a
  layer at 128 x 16 x 5120).  It is bound by the vector and transcendental
  units (an ``exp`` a float of state a token), not by memory.  Every other
  shape and platform scans `step` over the chunk's tokens, which is the
  kernel's reference too.  A row that has no valid token is not computed at
  all by the kernel.
* `sequence`: a scan of `step` over a whole sequence from a zero state: the
  plain form of `models.transformer.forward`.

A state written ahead of a row's position is not harmless (nothing repairs
it), so `chunk` and `step` advance a row by its VALID tokens only: padded
tokens get ``dt = 0`` (they neither decay nor write), and a row with no valid
token keeps its state bit for bit.

Everything here runs under ``jax.named_scope("selective_scan")``.  The
backward pass is XLA's of `sequence`; the kernel has none.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _VMEM_LIMIT, _interpret
from .latent_attention import on_the_chip

_F32 = jnp.float32
#: tokens a turn of the kernel's loop: a float32 sublane tile of ``a``, ``dt``
_ROWS = 8
#: the widest block of channels a grid step holds
_BLOCK = 512


@jax.named_scope("selective_scan")
def gates(r: jnp.ndarray, w_dt: jnp.ndarray, dt_bias: jnp.ndarray,
          a_log: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The step's low-rank part ``r`` [..., rank] -> (the step size
    ``softplus(r W_dt + b_dt)`` [..., d] float32, the decay RATE ``-exp(
    A_log)`` [n, d] float32 < 0).  No clamp: a configuration that states
    none has none."""
    dt = jnp.einsum("...r,rd->...d", r, w_dt.astype(r.dtype),
                    preferred_element_type=_F32)
    return (jax.nn.softplus(dt + dt_bias.astype(_F32)),
            -jnp.exp(a_log.astype(_F32)))


@jax.named_scope("selective_scan")
def step(a, B, C, dt, A, D, state: jnp.ndarray,
         live: Optional[jnp.ndarray] = None
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ONE token a row: ``a`` [b, d], ``B``, ``C`` [b, n], ``dt`` [b, d]
    (`gates`), ``A`` [n, d], ``D`` [d], ``state`` [b, n, d] float32 -> (``m``
    [b, d] float32, state').  ``live`` [b] bool (None: all): a row that is
    not live keeps its state bit for bit (its ``m`` means nothing)."""
    a, B, C, dt = (t.astype(_F32) for t in (a, B, C, dt))
    new = jnp.exp(dt[:, None, :] * A) * state \
        + B[:, :, None] * (dt * a)[:, None, :]
    m = jnp.sum(C[:, :, None] * new, axis=1) + D.astype(_F32) * a
    if live is not None:
        new = jnp.where(live[:, None, None], new, state)
    return m, new


@jax.named_scope("selective_scan")
def step_in_place(a, B, C, dt, A, D, s_all: jnp.ndarray, l,
                  live: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`step` against layer ``l`` of the STACKED states ``s_all`` [L, b, 1,
    n, d] -> (``m`` [b, d] float32, the stack with that layer advanced): a
    cut, the step and a placement, which the compiler fuses into one
    elementwise update of the layer where it lies."""
    state = jax.lax.dynamic_index_in_dim(s_all, l, 0, keepdims=False)[:, 0]
    m, new = step(a, B, C, dt, A, D, state, live)
    return m, jax.lax.dynamic_update_slice(
        s_all, new[None, :, None], (l, 0, 0, 0, 0))


def _scan(a, B, C, dt, A, D, state):
    """`step` token by token: ``a``, ``dt`` [b, s, d], ``B``, ``C`` [b, s,
    n] -> (``m`` [b, s, d] float32, the last state)."""
    def one(state, t):
        m, state = step(*t, A, D, state)
        return state, m

    state, m = jax.lax.scan(one, state, tuple(
        jnp.swapaxes(t, 0, 1) for t in (a, B, C, dt)))
    return jnp.swapaxes(m, 0, 1), state


@jax.named_scope("selective_scan")
def sequence(a, B, C, dt, A, D) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The plain form: `step` token by token from a zero state.  ``a``,
    ``dt`` [b, s, d], ``B``, ``C`` [b, s, n], ``A`` [n, d], ``D`` [d] ->
    (``m`` [b, s, d] float32, the last state [b, n, d])."""
    return _scan(a, B, C, dt, A, D,
                 jnp.zeros((a.shape[0], B.shape[-1], a.shape[-1]), _F32))


def _block(d: int) -> int:
    """Channels a grid step of the kernel: the widest whole number of lane
    tiles up to `_BLOCK` that divides ``d`` (0: none)."""
    return next((w for w in range(_BLOCK, 0, -_LANES) if d % w == 0), 0)


def kernel_shape(a_shape: Tuple[int, ...], state: jax.ShapeDtypeStruct
                 ) -> bool:
    """Whether `chunk`'s kernel takes inputs ``a`` [b, c, d] against
    ``state`` [b, n, d] (on a TPU, or under the interpreter): whole loop
    turns of tokens, whole lane tiles of channels, whole sublane tiles of
    state columns, a float32 state."""
    _, c, d = a_shape
    return c > 1 and c % _ROWS == 0 and _block(d) > 0 \
        and state.shape[-2] % _ROWS == 0 and state.dtype == _F32


def engages(a_shape: Tuple[int, ...], state: jax.ShapeDtypeStruct) -> bool:
    """Whether a chunk lowered by THIS process's backend runs the kernel."""
    return (jax.default_backend() == "tpu" or _interpret()) \
        and kernel_shape(a_shape, state)


def _chunk_kernel(nv_ref, a_ref, dt_ref, b_ref, c_ref, A_ref, D_ref, h_ref,
                  m_ref, out_ref):
    c, block = a_ref.shape
    live = nv_ref[pl.program_id(0)] > 0

    def tiled(t):       # [n, lanes] (every lane the value) -> [n, block]
        return t if block == _LANES else jnp.concatenate(
            [t] * (block // _LANES), axis=1)

    @pl.when(live)
    def _():
        A, D = A_ref[...], D_ref[...]               # [n, block], [1, block]

        def turn(g, h):
            at = pl.multiple_of(g * _ROWS, _ROWS)
            a8 = a_ref[pl.ds(at, _ROWS), :]
            dt8 = dt_ref[pl.ds(at, _ROWS), :]
            rows = []
            for i in range(_ROWS):
                a_t, dt_t = a8[i:i + 1], dt8[i:i + 1]           # [1, block]
                h = jnp.exp(dt_t * A) * h \
                    + tiled(b_ref[at + i]) * (dt_t * a_t)
                rows.append(jnp.sum(tiled(c_ref[at + i]) * h, axis=0,
                                    keepdims=True) + D * a_t)
            m_ref[pl.ds(at, _ROWS), :] = jnp.concatenate(rows, axis=0)
            return h

        out_ref[...] = jax.lax.fori_loop(0, c // _ROWS, turn, h_ref[...])

    @pl.when(jnp.logical_not(live))
    def _():
        m_ref[...] = jnp.zeros(m_ref.shape, m_ref.dtype)
        out_ref[...] = h_ref[...]


def _chunk_pallas(a, B, C, dt, A, D, state, n_valid):
    b, c, d = a.shape
    n = state.shape[-2]
    block = _block(d)
    wide = lambda t: jnp.broadcast_to(t[..., None], t.shape + (_LANES,))
    by_row = lambda i, j, nv: (i, 0, j)
    keys = pl.BlockSpec((None, c, n, _LANES), lambda i, j, nv: (i, 0, 0, 0))
    held = pl.BlockSpec((None, n, block), by_row)
    return pl.pallas_call(
        _chunk_kernel,
        name="selective_scan_chunk",
        out_shape=(jax.ShapeDtypeStruct((b, c, d), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, d // block),
            in_specs=[
                pl.BlockSpec((None, c, block), by_row),
                pl.BlockSpec((None, c, block), by_row),
                keys, keys,
                pl.BlockSpec((n, block), lambda i, j, nv: (0, j)),
                pl.BlockSpec((1, block), lambda i, j, nv: (0, j)),
                held,
            ],
            out_specs=(pl.BlockSpec((None, c, block), by_row), held),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(n_valid.astype(jnp.int32), a, dt, wide(B), wide(C), A,
      D.astype(_F32)[None], state)


@jax.named_scope("selective_scan")
def chunk(a, B, C, dt, A, D, state: jnp.ndarray,
          n_valid: Optional[jnp.ndarray] = None
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A chunk of ``c`` tokens a row against a carried state: shapes as
    `sequence`'s, ``state`` [b, n, d] float32 -> (``m`` [b, c, d] float32,
    state').  ``n_valid`` [b] int32 (0 .. c; None: c): the row's real tokens;
    the rest neither decay nor write the state, and a row of none keeps it
    bit for bit (its ``m`` means nothing)."""
    b, c, _ = a.shape
    a, B, C, dt = (t.astype(_F32) for t in (a, B, C, dt))
    if n_valid is None:
        n_valid = jnp.full((b,), c, jnp.int32)
    else:
        dt = jnp.where((jnp.arange(c)[None, :] < n_valid[:, None])[..., None],
                       dt, 0.0)

    def scan(a, B, C, dt, A, D, state, n_valid):
        m, new = _scan(a, B, C, dt, A, D, state)
        return m, jnp.where((n_valid > 0)[:, None, None], new, state)

    if not kernel_shape(a.shape, state):
        return scan(a, B, C, dt, A, D, state, n_valid)
    return on_the_chip(_chunk_pallas, scan, a, B, C, dt, A, D, state, n_valid)
