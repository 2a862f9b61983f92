"""Every slot's new column into a slot cache, in place: one kernel call an
array a layer where slots x columns one-column slices were.

A decode step feeds each slot ONE token at the slot's own position, so an
array ``[layers, slots, heads, width, rows]`` (positions last,
`models/generate.py`) takes ``slots`` columns a layer, each at a column of
its own.  As a ``dynamic_update_slice`` each costs the same whatever it
moves: a one-column slice into an array whose positions are minor touches
every ``(sublane, 128)`` tile of ``heads x width``, and a step holds ``slots
x arrays x layers`` of them (3.4-16 us each on a v5e: a quarter to a third
of a step at GPT-2 and byte-model shapes, PERF.md).  An XLA scatter, or a
``vmap`` of the update (which lowers to one), is not the cure: under the
cache's layout a scatter is not updated in place and puts the cache's
conversions back inside the layer loop (PR 26 took it out).

So `write_columns` is ONE `pl.pallas_call` an array a layer that ALIASES the
cache (``input_output_aliases``) and moves only the 128-row blocks that hold
the new columns: the layer and each slot's column are prefetched scalars of
the block's ``index_map`` (`pltpu.PrefetchScalarGridSpec`, as
`ops/grouped_matmul.py` names its weight blocks), the grid runs over the
slots (and over blocks of heads where ``heads x width x 128`` would pass
`flash_attention._VMEM_BLOCK_BUDGET`), block ``(l, s, heads, 0, col[s] //
128)`` is read, the lane ``col[s] % 128`` of it replaced, and the block
written back where it lay.  A call reads and writes ``slots x heads x width x
128`` elements, whatever ``rows``.

The new columns arrive as the projections leave them, ``[slots, heads,
width]`` with ``width`` on the lanes; the cache wants ``width`` on the
sublanes.  The kernel turns each head's row itself (rows of one value,
transposed: data movement only, so the column is the slices' bit for bit),
where handing them over ``[..., width, 1]`` would pad each value to 128
lanes in memory: a third stream as large as the blocks.

The path ADAPTS to what the call sees, no knob: ``rows % 128 != 0`` (tiny
test models) and every platform but the TPU (`jax.lax.platform_dependent`)
keep the slices, which are this module's reference too.
`RAY_TPU_PALLAS_INTERPRET=1` runs the kernel through the interpreter
(tests).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _VMEM_BLOCK_BUDGET, _interpret


def kernel_shape(shape: Tuple[int, ...]) -> bool:
    """Whether an array of this shape is one the kernel takes (on a TPU, or
    under the interpreter): whole 128-row blocks."""
    return shape[-1] % _LANES == 0


def device_calls(shape: Tuple[int, ...]) -> int:
    """The device calls that write a layer's column a slot of an array of
    this shape on THIS process's backend: one where the kernel engages, a
    slice a slot elsewhere (a host count from shapes, what the serve
    engine's ``column_write_calls`` sums)."""
    on_tpu = jax.default_backend() == "tpu" or _interpret()
    if on_tpu and kernel_shape(shape):
        return 1
    return shape[1]


def _head_block(heads: int, width: int, itemsize: int) -> int:
    """Heads a grid step: all of them where the step's blocks (the cache's
    in and out, two of each in flight) stay inside the budget, else the
    largest divisor that does."""
    for hb in range(heads, 0, -1):
        if heads % hb == 0 and \
                4 * hb * width * _LANES * itemsize <= _VMEM_BLOCK_BUDGET:
            return hb
    return 1


def _kernel(l_ref, col_ref, new_ref, old_ref, out_ref):
    del l_ref
    hb, width, lanes = old_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, old_ref.shape, 2)
    # [hb, width] on the lanes -> [hb, width, 128] on the sublanes, every
    # lane the value: rows of one value, transposed
    rows = new_ref[...][:, 0].astype(jnp.float32)
    new = jnp.swapaxes(
        jnp.broadcast_to(rows[:, None, :], (hb, lanes, width)), 1, 2)
    out_ref[...] = jnp.where(lane == col_ref[pl.program_id(0)] % lanes,
                             new.astype(out_ref.dtype), old_ref[...])


def _pallas(c_all, l, cols, col):
    _, slots, heads, width, rows = c_all.shape
    hb = _head_block(heads, width, c_all.dtype.itemsize)
    block = pl.BlockSpec(
        (None, None, hb, width, _LANES),
        lambda s, j, l, c: (l[0], s, j, 0, c[s] // _LANES))
    return pl.pallas_call(
        _kernel,
        name="cache_column_write",
        out_shape=jax.ShapeDtypeStruct(c_all.shape, c_all.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, heads // hb),
            in_specs=[
                pl.BlockSpec((None, hb, 1, width),
                             lambda s, j, l, c: (s, j, 0, 0)),
                block,
            ],
            out_specs=block,
        ),
        # operand 3 (after the two prefetched scalars and the columns)
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(l.reshape(1), jnp.clip(col, 0, rows - 1), cols[:, :, None, :], c_all)


def _slices(c_all, l, cols, col):
    """A ``dynamic_update_slice`` a slot: a start past the end is clamped
    onto the last column."""
    for s in range(cols.shape[0]):
        c_all = jax.lax.dynamic_update_slice(
            c_all, cols[None, s:s + 1, :, :, None], (l, s, 0, 0, col[s]))
    return c_all


def write_columns(c_all: jnp.ndarray, l, cols: jnp.ndarray,
                  col: jnp.ndarray) -> jnp.ndarray:
    """``c_all`` [L, S, heads, width, rows] with ``cols`` [S, heads, width]
    in column ``col`` [S] int32 of layer ``l``, slot by slot: the result,
    bit for bit, of ``dynamic_update_slice(c_all, cols[None, s:s+1, ...,
    None], (l, s, 0, 0, col[s]))`` over the slots (a column past the end
    lands on the last one).  One kernel call where `kernel_shape` and the
    platform allow, the slices elsewhere."""
    cols = cols.astype(c_all.dtype)
    l, col = jnp.asarray(l, jnp.int32), col.astype(jnp.int32)
    if not kernel_shape(c_all.shape):
        return _slices(c_all, l, cols, col)
    if _interpret():
        return _pallas(c_all, l, cols, col)
    return jax.lax.platform_dependent(c_all, l, cols, col,
                                      tpu=_pallas, default=_slices)
