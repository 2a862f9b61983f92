"""Grouped matmul as a Pallas TPU kernel: row block i of the sorted rows
meets group i's matrix, and the cost is the BYTES of the matrices that hold
rows, not the rows.

The routed experts' three matmuls (`ops/moe.py` `routed_ffn`) are memory
bound by two orders of magnitude in the served programs: a handful of rows a
group against matrices of 6-19 MB.  What the chip must do is read each
touched expert's matrix once.  So:

  * a WORK LIST is built from the group sizes and prefetched as scalars
    (`pltpu.PrefetchScalarGridSpec`): one item for each (group that holds
    rows, row tile it reaches into), in order.  The weight block's
    ``index_map`` names only those groups: an empty group costs no DMA and no
    grid step, consecutive items of one group reuse the resident block, and
    the grid's extent is the list's length (a traced scalar), so rows past
    the last group cost nothing and hold nothing defined;
  * the grid is (output tiles, work items): for one tile of output columns
    the kernel walks the list once, each item ONE dot over the whole
    contraction (float32 on the MXU, rounded ONCE to the output's dtype)
    stored under a mask of the group's rows, since a row tile may hold the
    rows of several groups;
  * the matrices may be one layer of a stack ``(stack [L, G, k, n], layer)``:
    the layer is a prefetched scalar of the ``index_map``, so a run's stack
    is indexed where it lies and no layer's experts are sliced out (a copy)
    or flattened into ``L * G`` groups.

Tiles are a function of ``(m, k, n, itemsize)`` alone (`tiles`).  The path
is chosen by the LOWERING PLATFORM (`jax.lax.platform_dependent`): TPU -> the
kernel, anything else -> `jax.lax.ragged_dot`.  The backward pass is the
`ragged_dot` formulation's.  `jax.experimental.pallas.ops.tpu.megablox` is
the published design this follows, in its memory-bound corner.
"""

from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

# a weight block [k, tn]: two of them in flight beside the rows and the
# output stay inside the 16 MiB of VMEM a kernel is given by default
_WEIGHT_BLOCK_BYTES = 3 << 20
_ROW_TILE = 128


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def tiles(m: int, k: int, n: int, itemsize: int) -> Tuple[int, int]:
    """(row tile, output-column tile) for rows ``[m, k]`` against matrices
    ``[k, n]``: the rows of one MXU pass (or all of them, in whole
    sublanes), and the widest multiple of 128 dividing ``n`` whose weight
    block stays within `_WEIGHT_BLOCK_BYTES` (``n`` itself where 128 does
    not divide it)."""
    tm = min(_ROW_TILE, _round_up(m, 32 // itemsize))
    if n % 128:
        return tm, n
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and k * t * itemsize <= _WEIGHT_BLOCK_BYTES]
    return tm, max(fits, default=128)


def _cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Running sum of a short vector as ONE fusion (a triangle of
    comparisons): `jnp.cumsum` is a window reduction between two layout
    copies on the chip, three programs for 64 numbers."""
    at = jnp.arange(x.shape[0])
    return jnp.where(at[None, :] <= at[:, None], x[None, :], 0).sum(1)


def work_list(sizes: jnp.ndarray, m_tiles: int, tm: int):
    """Group sizes [G] -> (group [W], row tile [W], each group's first row
    [G], its end [G], the list's length), all int32: an item for each
    (group with rows, row tile of ``tm`` it reaches into), groups ascending
    and each one's tiles ascending, ``W = m_tiles + G - 1`` the most there
    can be; entries past the length name valid blocks.
    Comparisons over ``[W, G]``, no scatter and no search: a few small
    fusions beside kernels of a fraction of a millisecond."""
    n_groups = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = _cumsum(sizes)
    first = (ends - sizes) // tm
    reach = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    done = _cumsum(reach)                    # items of the groups up to g
    item = jnp.arange(m_tiles + n_groups - 1, dtype=jnp.int32)
    group = jnp.minimum((item[:, None] >= done[None, :]).sum(1),
                        n_groups - 1).astype(jnp.int32)
    mine = group[:, None] == jnp.arange(n_groups)[None, :]
    # the group's first tile plus the item's rank among the group's items
    tile = item + jnp.where(mine, (first + reach - done)[None, :], 0).sum(1)
    return (group, jnp.clip(tile, 0, m_tiles - 1), ends - sizes, ends,
            done[-1])


def _kernel(layer_ref, group_ref, tile_ref, start_ref, end_ref,
            lhs_ref, rhs_ref, out_ref, *, tm: int):
    del layer_ref
    item = pl.program_id(1)
    group = group_ref[item]
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    rows = tile_ref[item] * tm + jax.lax.broadcasted_iota(
        jnp.int32, acc.shape, 0)
    mine = (rows >= start_ref[group]) & (rows < end_ref[group])
    # the tile's other rows are other groups', written by their own items
    out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def _pallas(lhs, stack, layer, sizes):
    m, k = lhs.shape
    n = stack.shape[-1]
    tm, tn = tiles(m, k, n, lhs.dtype.itemsize)
    rows = _round_up(m, tm)
    if rows != m:
        lhs = jnp.pad(lhs, ((0, rows - m), (0, 0)))
    *work, length = work_list(sizes, rows // tm, tm)
    block_bytes = (k * tn + tm * k + tm * tn) * lhs.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        name="grouped_matmul",
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, length),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, l, g, t, s, e: (t[i], 0)),
                pl.BlockSpec((None, None, k, tn),
                             lambda j, i, l, g, t, s, e: (l[0], g[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, l, g, t, s, e: (t[i], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, 2 * block_bytes + (4 << 20))),
        interpret=_interpret(),
    )(layer.reshape(1), *work, lhs, stack)
    return out[:m]


def _ragged(lhs, stack, layer, sizes):
    """The same product as XLA's grouped matmul: the stack handed over as
    ``L * G`` groups of which only ``layer``'s hold rows."""
    n_layers, n_groups = stack.shape[:2]
    if n_layers == 1:
        return jax.lax.ragged_dot(lhs, stack[0], sizes)
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((n_layers * n_groups,), sizes.dtype), sizes,
        (layer * n_groups,))
    return jax.lax.ragged_dot(
        lhs, stack.reshape((-1,) + stack.shape[2:]), groups)


@jax.custom_vjp
def _grouped(lhs, stack, layer, sizes):
    if _interpret():
        return _pallas(lhs, stack, layer, sizes)
    return jax.lax.platform_dependent(lhs, stack, layer, sizes,
                                      tpu=_pallas, default=_ragged)


def _grouped_fwd(lhs, stack, layer, sizes):
    return _grouped(lhs, stack, layer, sizes), (lhs, stack, layer, sizes)


def _grouped_bwd(res, g):
    lhs, stack, layer, sizes = res
    _, vjp = jax.vjp(lambda a, b: _ragged(a, b, layer, sizes), lhs, stack)
    return (*vjp(g), None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs: jnp.ndarray,
                   rhs: Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]],
                   group_sizes: jnp.ndarray) -> jnp.ndarray:
    """lhs [m, k] (rows sorted by group), rhs [G, k, n] or ``(stack [L, G,
    k, n], layer)``, group_sizes [G] int32 -> [m, n] in lhs's dtype: rows
    ``sum(sizes[:i]) .. sum(sizes[:i+1])`` times matrix i, as
    `jax.lax.ragged_dot`.  Rows past the last group hold nothing defined."""
    stack, layer = rhs if isinstance(rhs, tuple) else (rhs[None], 0)
    return _grouped(lhs, stack, jnp.asarray(layer, jnp.int32), group_sizes)
