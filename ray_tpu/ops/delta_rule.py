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
  `step_in_place` is the same step against layer ``l`` of the STACKED states
  of a cache where they lie (below).
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

THE STEP WHERE THE STATES LIE.  As XLA lowers `step` between a cut of the
layer and its placement back, a decode step reads every slot's states twice
(one reduce for the two products, one fusion that decays, corrects and
writes) and writes them once, live or not: 0.385 ms a layer of 32 slots x 32
heads x 64 KB where one read and one write are 0.164 at the memory's rate
(PERF.md, PR 50).  `step_in_place` is ONE `pl.pallas_call` a layer (named
``delta_rule_step`` in a trace) over the stacked array ``[L, b, h, dk, dv]``,
aliased in and out as `ops/cache_write.py` takes a cache array: the layer and
a plan of the grid's steps (`_plan`) are prefetched scalars of the blocks'
index maps, the grid runs over (slot, block of heads), a grid step loads its
heads' ``S`` once and with it in VMEM computes what `step` states, eight
heads at a time: ``S' = Diag(exp(a)) S``, ``k^T S'`` and ``q^T S'`` as
float32 multiply-adds summed down the sublanes, ``u``, ``S' + k u^T`` written
to the same block, ``o``.  ``q``, ``k`` and ``a`` arrive as the projections
leave them, ``dk`` on the lanes, and are turned onto the state's sublanes
inside the kernel (`_columns`); handed over ``[..., dk, 1]`` each would be a
stream as large as the state.  The grid takes the LIVE slots first; every
step behind them names the last live block again, so a slot that stands is
neither read nor written (a call with no live slot hands one block on as it
came) and keeps its state bit for bit.

The path ADAPTS to what the call shows, no knob (`kernel_shape`): one token
a row, a float32 state whose ``dk`` and ``dv`` are whole 128-lane tiles, a
program lowered for a TPU (`jax.lax.platform_dependent`;
`RAY_TPU_PALLAS_INTERPRET=1` runs the kernel through the interpreter).  Every
other shape and platform runs `step` between the cut and the placement, which
is the kernel's reference too; `engages` is the host's answer for this
process's backend (what the serve engine's ``state_bytes_fetched`` counts).

`step`, `chunk` and `sequence` are plain `jax.numpy`; every function runs
under ``jax.named_scope("kda")``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_LANES, _VMEM_BLOCK_BUDGET, _VMEM_LIMIT,
                              _interpret)
from .grouped_matmul import _cumsum
from .latent_attention import on_the_chip

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


# ------------------------------------------- the step, where the states lie

#: heads the kernel's inner loop takes at a time: a sublane tile of vectors
_GROUP = 8


def _head_block(heads: int, dk: int, dv: int) -> int:
    """Heads a grid step: the most whose states (in and out, two of each in
    flight) stay inside the budget, whole sublane tiles of heads or all of
    them (the vectors' blocks hold the heads on their sublanes); 0 where
    none does."""
    for hb in range(heads, 0, -1):
        if heads % hb == 0 and (hb % _GROUP == 0 or hb == heads) \
                and 4 * hb * dk * dv * 4 <= _VMEM_BLOCK_BUDGET:
            return hb
    return 0


def kernel_shape(tokens: int, states: jax.ShapeDtypeStruct) -> bool:
    """Whether `step_in_place`'s kernel takes ``tokens`` new tokens a row
    against stacked ``states`` [L, b, h, dk, dv] (on a TPU, or under the
    interpreter): ONE token a row, a float32 state whose two axes are whole
    128-lane tiles, a block of heads inside the budget."""
    heads, dk, dv = states.shape[2:]
    return tokens == 1 and states.dtype == _F32 and dk % _LANES == 0 \
        and dv % _LANES == 0 and _head_block(heads, dk, dv) > 0


def engages(tokens: int, states: jax.ShapeDtypeStruct) -> bool:
    """Whether a step lowered by THIS process's backend runs the kernel (a
    host answer from shapes, as `ops.cache_attention.engages`)."""
    return (jax.default_backend() == "tpu" or _interpret()) \
        and kernel_shape(tokens, states)


#: what a grid step does (`_plan`): a live slot's states are advanced; a
#: step behind the last live slot names that slot's last block again, which
#: moves nothing; where no slot is live every step names the first block and
#: hands it on as it came
_STANDS, _LIVE, _LEADS = 0, 1, 2


def _plan(live: jnp.ndarray) -> jnp.ndarray:
    """``live`` [slots] bool -> [3, slots] int32 of a call's grid steps, the
    LIVE SLOTS FIRST (ascending), then the ones that stand: the slot whose
    vectors a step takes and whose ``o`` it writes, the slot its index map
    names in the states, and what the step does.  Live slots in a row keep a
    block in flight behind the one being advanced; a standing slot between
    them would not.  Comparisons over ``[slots, slots]``, no sort."""
    at = jnp.arange(live.shape[0], dtype=jnp.int32)
    lives = live.astype(jnp.int32)
    n = lives.sum()
    step_of = jnp.where(live, _cumsum(lives) - 1, n + _cumsum(1 - lives) - 1)
    slot_of = jnp.where(step_of[None, :] == at[:, None], at[None, :],
                        0).sum(1)
    runs = at < n
    return jnp.stack([
        slot_of,
        jnp.where(runs, slot_of, jnp.max(jnp.where(live, at, 0))),
        jnp.where(runs, _LIVE, jnp.where(n > 0, _STANDS, _LEADS)),
    ]).astype(jnp.int32)


def _columns(rows: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """[n, dk] on the lanes -> [n, dk, lanes] on the sublanes, every lane
    the value: rows of one value, transposed (data movement only, as
    `ops.cache_write._kernel` turns its columns)."""
    n, dk = rows.shape
    return jnp.swapaxes(
        jnp.broadcast_to(rows[:, None, :], (n, lanes, dk)), 1, 2)


def _step_kernel(l_ref, plan_ref, x_ref, w_ref, s_ref, o_ref, out_ref, *,
                 group: int):
    del l_ref
    hb, _, dv = s_ref.shape
    does = plan_ref[2, pl.program_id(0)]

    @pl.when(does == _LIVE)
    def _():
        def heads(g, _):
            at = pl.multiple_of(g * group, group)
            q, k, a = (x_ref[i, pl.ds(at, group), :] for i in range(3))
            v, beta = (w_ref[i, pl.ds(at, group), :] for i in range(2))
            kq = jnp.sum(q * k, axis=-1, keepdims=True)      # [group, 1]
            q, k, keep = (_columns(t, dv) for t in (q, k, jnp.exp(a)))
            s = keep * s_ref[pl.ds(at, group)]               # S'
            u = beta * (v - jnp.sum(k * s, axis=1))          # [group, dv]
            o_ref[pl.ds(at, group), :] = jnp.sum(q * s, axis=1) + u * kq
            out_ref[pl.ds(at, group)] = s + k * u[:, None, :]
            return _

        jax.lax.fori_loop(0, hb // group, heads, 0)

    @pl.when(does != _LIVE)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(does == _LEADS)
    def _():
        out_ref[...] = s_ref[...]


def in_place_call(kernel, name: str, x, w, s_all, l, live):
    """ONE aliased `pl.pallas_call` named ``name`` over the stacked states
    ``s_all`` [L, slots, heads, dk, dv] where they lie: the grid over (slot
    in `_plan`'s order, block of heads); a grid step is handed its slot's
    vectors ``x`` [slots, nx, heads, dk] and ``w`` [slots, nw, heads, dv],
    the block of layer ``l``'s states and ``kernel(l_ref, plan_ref, x_ref,
    w_ref, s_ref, o_ref, out_ref, group=)`` -> (``o`` [slots, heads, dv]
    float32, the stack).  What `step_in_place` here and `ops/ssd.py`'s
    share."""
    _, slots, heads, dk, dv = s_all.shape
    hb = _head_block(heads, dk, dv)
    nb = heads // hb
    # a live slot's own blocks of heads; behind them the last one again
    states = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda i, j, l, plan: (l[0], plan[1, i], jnp.where(
            plan[2, i] == _LIVE, j, (plan[2, i] == _STANDS) * (nb - 1)),
            0, 0))
    by_slot = lambda i, j, l, plan: (plan[0, i], 0, j, 0)
    return pl.pallas_call(
        functools.partial(kernel, group=_GROUP if hb % _GROUP == 0 else hb),
        name=name,
        out_shape=(jax.ShapeDtypeStruct((slots, heads, dv), _F32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, nb),
            in_specs=[
                pl.BlockSpec((None, x.shape[1], hb, dk), by_slot),
                pl.BlockSpec((None, w.shape[1], hb, dv), by_slot),
                states,
            ],
            out_specs=(pl.BlockSpec(
                (None, hb, dv), lambda i, j, l, plan: (plan[0, i], j, 0)),
                states),
        ),
        # operand 4 (after the two prefetched scalars and the vectors)
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(l.reshape(1), _plan(live), x, w, s_all)


def _step_pallas(q, k, v, a, beta, s_all, l, live):
    return in_place_call(
        _step_kernel, "delta_rule_step", jnp.stack([q, k, a], axis=1),
        jnp.stack([v, jnp.broadcast_to(beta[..., None], v.shape)], axis=1),
        s_all, l, live)


def _step_slices(q, k, v, a, beta, s_all, l, live):
    """`step` on layer ``l`` cut out of the stack and placed back."""
    o, new = step(q, k, v, a, beta, jax.lax.dynamic_index_in_dim(
        s_all, l, 0, keepdims=False), live)
    return o, jax.lax.dynamic_update_slice(s_all, new[None], (l, 0, 0, 0, 0))


@jax.named_scope("kda")
def step_in_place(q, k, v, a, beta, s_all: jnp.ndarray, l,
                  live: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`step` against layer ``l`` of the STACKED states ``s_all`` [L, b, h,
    dk, dv] where they lie -> (``o`` [b, h, dv] float32, the stack with that
    layer advanced).  One kernel call where `kernel_shape` and the platform
    allow: a live row's states are read once and written once, a row that
    stands is neither read nor written (its ``o`` is zeros); `step` between
    a cut and a placement elsewhere."""
    q, k, v, a, beta = (t.astype(_F32) for t in (q, k, v, a, beta))
    l = jnp.asarray(l, jnp.int32)
    if not kernel_shape(1, s_all):
        return _step_slices(q, k, v, a, beta, s_all, l, live)
    if live is None:
        live = jnp.ones(q.shape[:1], bool)
    return on_the_chip(_step_pallas, _step_slices, q, k, v, a, beta, s_all,
                       l, live)
