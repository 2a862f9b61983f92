"""A program's attention over the cache blocks its rows SEE, and no other:
a decode step's one query a slot (`attend_blocks`), a chunk program's chunk
of queries a lane (`attend_chunk_blocks`).

One query a slot (a fused decode step) attends a few hundred to a few
thousand cached rows, but the arrays that hold them are as long as the
longest context a slot may reach: dense dots under a mask read every row of
every slot, live or not (`ops/eva_attention.py` `attend_two`: 3,840 rows a
slot a layer where a slot at 5-25 k sees about 1,700).  A bound on the rows
taken from the batch's deepest slot shows nothing where the slots stand at
phases of their own; each slot's own bound is no static shape, so it is a
kernel: `attend_blocks`, ONE `pl.pallas_call` a layer over the state arrays
WHERE THEY LIE.

The rows come as ROW SETS under ONE softmax: each set is (keys ``[L, S, hk,
hd, T]``, values ``[L, S, hk, vd, T]``, mask ``[S | 1, 1, T]``), positions
last as `models/generate.py` stores them; a summary layer hands two (its
ring, its summaries), a full layer would hand its live prefix, a window
layer its ring, an indexer its choice.  Visibility has ONE definition, the
masks the dense form applies: `block_work` lists the `BLOCK`-row blocks
whose mask has a set row, as items (slot, set, block), slots ascending, and
the grid walks that list (its length a traced scalar, as
`ops/grouped_matmul.py`'s and `ops/latent_attention.py` `attend_cache`'s);
layer, slot and block are prefetched scalars of the blocks' index maps.  An
item moves ONE set's key and value block: every other set's index map
repeats the block it named last, which moves nothing.  A slot with nothing
to see, or that ``live`` says stands, is one item that moves nothing and
writes zeros.  Inside an item the mask is still applied row by row.

A set's VALUE heads are as many as its key rows whatever either's width
(``hk`` of ``hd`` and of ``vd``): where a model's key heads and value heads
differ in COUNT (a differential pair's two keys of 64 beside ONE value of
128) a key ROW holds what one value head's queries meet, the pair's two keys
side by side, each query padded to its half, and ``scale`` says what a score
is multiplied by (one head's ``head_dim ** -0.5``, not the row's).

The arithmetic is `attend_two`'s: operands in the compute type, float32
scores x ``head_dim ** -0.5`` (or ``scale``), ``-1e30`` under the mask, float32 running
maximum and sum across all sets, probabilities cast to the compute type for
the value product, float32 accumulation, normalised after the values are
summed.

A CHUNK's ``C`` queries a lane (`attend_chunk_blocks`, call name
``cache_chunk_attention``) walk the same list, built by one reduction more:
a block is listed where ANY of the lane's queries has a set row in it
(masks ``[P | 1, C, T]``).  The dense forms they replace cut a lane's
arrays out of the cache first (63 MB a lane a layer of the byte cell's
rings and summaries) and carry float32 scores ``[C, heads, T]`` through
memory; here a block moves once, and the scores stay in VMEM, TRANSPOSED:
the block's rows down the sublanes, a key-value head's ``g x C`` queries
along the lanes, so that no reduction crosses lanes.  An item takes as many
heads as the budget holds (`_chunk_heads`), the rest a second grid axis.

`fetched_blocks` is the host's count of the same blocks from positions
(what the serve engine's ``rows_fetched`` and ``chunk_rows_fetched`` sum);
`engages` says whether this process's backend runs the kernel, as
`ops/cache_write.py` `device_calls`.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_NEG_INF, _VMEM_BLOCK_BUDGET, _VMEM_LIMIT,
                              _interpret)
from .grouped_matmul import _cumsum

#: cached rows a work item: the lanes of one tile, and a divisor of every
#: array the served configurations hold (rings of 17, summaries of 13 blocks)
BLOCK = 128

RowSet = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]  # keys, values, mask


def kernel_shape(q_shape: Tuple[int, ...], sets: Sequence[RowSet],
                 sink: bool = False) -> bool:
    """Whether queries ``[S, C, hk, g, hd]`` over ``sets`` have a kernel
    here (on a TPU, or under the interpreter): `attend_blocks` for ONE
    query a slot (``C`` 1), `attend_chunk_blocks` for a chunk's ``C``.
    Either way every set's rows are whole blocks, key and value widths whole
    sublane tiles of the cache's type, and no attention ``sink`` joins the
    softmax (the kernels have no word for one).  A step's item is every
    head's two blocks (two of each in flight) inside the budget; a chunk's
    item is `_chunk_heads` heads of them, its ``C`` queries whole lane
    tiles, and some number of heads has to fit.

    No class of shapes is refused for its speed: alone on the chip (4 lanes
    x 2 layers a call unless said, dense / kernel in ms; my chip runs, PR
    52) heads 32 / 32 of 128 over 17 + 13 blocks 2.378 / 0.407 (one lane
    0.479 / 0.073), 48 / 8 of 128 over 132 blocks 9.454 / 1.155 and over 33
    blocks 1.362 / 0.700, 64 / 4 of 192 and 128 over 76 blocks 6.324 /
    1.606, 32 / 8 of 64 over 32 blocks 0.624 / 0.092, 25 / 25 of 64 over 8
    blocks 0.201 / 0.039, and one lane of 16 / 16 of 64 over 8 blocks
    0.031 / 0.011-0.014."""
    if sink:
        return False
    _, c, hk, g, _ = q_shape
    for k, v, _ in sets:
        tile = 32 // k.dtype.itemsize
        if k.shape[-1] % BLOCK or k.shape[-2] % tile or v.shape[-2] % tile:
            return False
        if c == 1 and 2 * (hk * (k.shape[-2] + v.shape[-2]) * BLOCK
                           * k.dtype.itemsize) > _VMEM_BLOCK_BUDGET:
            return False
    if c == 1:
        return True
    # (a chunk's queries lie along the lanes, a mask's columns beside them)
    return c % BLOCK == 0 and _chunk_heads(q_shape, sets) > 0


def _chunk_heads(q_shape: Tuple[int, ...], sets: Sequence[RowSet]) -> int:
    """Key-value heads a grid step of `attend_chunk_blocks` (a divisor of
    ``hk``; 0: not one head fits): the most whose pipelined blocks (every
    set's key and value block, the queries, the output and the mask, two of
    each in flight) and float32 scratch (maximum and sum, a sublane tile
    each, and the accumulator) stay inside `_VMEM_BLOCK_BUDGET`."""
    _, c, hk, g, hd = q_shape
    rows, vd = c * g, sets[0][1].shape[-2]
    size = sets[0][0].dtype.itemsize
    a_head = sum(2 * (k.shape[-2] + v.shape[-2]) * BLOCK * k.dtype.itemsize
                 for k, v, _ in sets) \
        + 2 * rows * (hd + vd) * size + rows * (2 * 8 + vd) * 4
    for hb in range(hk, 0, -1):
        if hk % hb == 0 and hb * a_head + 2 * c * BLOCK \
                <= _VMEM_BLOCK_BUDGET:
            return hb
    return 0


def engages(q_shape: Tuple[int, ...], sets: Sequence[RowSet],
            sink: bool = False) -> bool:
    """Whether a program lowered by THIS process's backend runs the kernel
    (a host answer from shapes, as `ops.cache_write.device_calls`)."""
    return (jax.default_backend() == "tpu" or _interpret()) \
        and kernel_shape(q_shape, sets, sink)


def fetched_blocks(first: int, rows: int, size: int) -> int:
    """Blocks of an array of ``size`` rows that hold one of the ``rows``
    visible rows from row ``first`` on, wrapping at the array's end (a
    ring): what `block_work` lists for such a mask, counted on the host."""
    if rows <= 0:
        return 0
    n, last = size // BLOCK, first + rows - 1
    return min(last // BLOCK - first // BLOCK + 1, n)


def _set_starts(masks: Sequence[jnp.ndarray]) -> Tuple[Tuple[int, ...], int]:
    """The first block of each set in a slot's flat numbering, and ``nb``,
    the blocks a slot over all sets."""
    starts, nb = [], 0
    for m in masks:
        starts.append(nb)
        nb += m.shape[-1] // BLOCK
    return tuple(starts), nb


def block_work(masks: Sequence[jnp.ndarray], live: Optional[jnp.ndarray]):
    """Masks ``[S, C, T_i]`` bool of the row sets (``live`` [S] bool: the
    slots that run; None: all) -> the list of blocks the kernel walks,
    ``(item [W], runs [W], held [sets, W], items)``, all int32.  A block has
    the FLAT number ``slot x nb + (blocks of the sets before its own) +
    block``, ``nb`` the blocks a slot over all sets; ``item[w]`` is the w-th
    block in flat order whose mask has a set row FOR ANY of the slot's C
    queries, so slots ascend, a slot's sets ascend and a set's blocks
    ascend.  A slot with no such block has
    ONE item, its block 0 with ``runs`` 0: it moves nothing and writes
    zeros.  ``held[i, w]`` is what set ``i``'s index maps name at item
    ``w``: the item's own block where it is of set ``i``, else the last
    block of the set named before (none: the first named after), so that
    only an item's own set moves.  ``items`` is the list's length (``W = S
    x nb`` the most there can be; entries past it name valid blocks).
    Comparisons over ``[W, W]``, no gather, no sort: a few small fusions a
    step."""
    slots = masks[0].shape[0]
    seen = jnp.concatenate(
        [m.reshape(slots, m.shape[1], -1, BLOCK).any((1, 3)) for m in masks],
        axis=1)
    if live is not None:
        seen = seen & live[:, None]
    starts, nb = _set_starts(masks)
    # a standing slot takes its block 0 as a placeholder
    first = (jnp.arange(nb) == 0)[None, :]
    takes = (seen | (first & ~seen.any(1, keepdims=True))).reshape(-1)
    seen = seen.reshape(-1)
    flat = jnp.arange(slots * nb, dtype=jnp.int32)
    place = _cumsum(takes.astype(jnp.int32)) - 1     # the item a block is
    hit = takes[None, :] & (place[None, :] == flat[:, None])      # [W, F]

    def of_item(per_block):             # [F] -> [W], by comparisons
        return jnp.where(hit, per_block[None, :], 0).sum(1).astype(jnp.int32)

    items = takes.sum().astype(jnp.int32)
    held = []
    for start, end in zip(starts, starts[1:] + (nb,)):
        own = seen & (flat % nb >= start) & (flat % nb < end)
        before = jnp.where(own[None, :] & (flat[None, :] <= flat[:, None]),
                           flat[None, :], -1).max(1)              # [F]
        after = jnp.where(own, flat, slots * nb).min()
        # (a set no slot sees: block 0 of slot 0, never read)
        after = jnp.where(after < slots * nb, after, start)
        held.append(jnp.where(
            flat < items, of_item(jnp.where(before >= 0, before, after)),
            start))
    return (of_item(flat), of_item(seen.astype(jnp.int32)), jnp.stack(held),
            items)


def _kernel(l_ref, item_ref, runs_ref, held_ref, q_ref, m_ref, *refs,
            nb: int, starts: Tuple[int, ...], scale: float):
    del l_ref, held_ref
    n = len(starts)
    kv_refs, o_ref = refs[:2 * n], refs[2 * n]
    top_ref, sum_ref, acc_ref = refs[2 * n + 1:]
    at, items = pl.program_id(0), pl.num_programs(0)
    slot, block = item_ref[at] // nb, item_ref[at] % nb
    first = (at == 0) | (item_ref[jnp.maximum(at - 1, 0)] // nb != slot)
    last = (at == items - 1) | (item_ref[
        jnp.minimum(at + 1, item_ref.shape[0] - 1)] // nb != slot)
    runs = runs_ref[at] > 0
    dt = q_ref.dtype

    @pl.when(first)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, _NEG_INF, jnp.float32)
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    for i, (start, end) in enumerate(zip(starts, starts[1:] + (nb,))):

        @pl.when(runs & (block >= start) & (block < end))
        def _(k_ref=kv_refs[2 * i], v_ref=kv_refs[2 * i + 1]):
            # every head at once, a batched dot: one query row a head
            # leaves the MXU waiting on its weight loads either way, and
            # the items' DMA binds (my chip runs, PR 48: 0.459 ms a layer
            # of the byte cell's cache, 82 % of the memory's rate; a loop
            # over the heads 0.53-1.77, a multiply-and-reduce on the
            # vector units 0.58-1.46)
            seen = (m_ref[...] != 0)[None]                  # [1, 1, BLOCK]
            s = jnp.einsum("hgd,hdt->hgt", q_ref[...], k_ref[...].astype(dt),
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _NEG_INF)
            top = top_ref[...]                              # [hk, g, 1]
            new_top = jnp.maximum(top, s.max(-1, keepdims=True))
            # (a hidden score stays at -1e30: it weighs 0 once a real one
            # is in, and an item's block has a real one)
            p = jnp.where(seen, jnp.exp(s - new_top), 0.0)
            fade = jnp.exp(top - new_top)
            top_ref[...] = new_top
            sum_ref[...] = sum_ref[...] * fade + p.sum(-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * fade + jnp.einsum(
                "hgt,hdt->hgd", p.astype(dt), v_ref[...].astype(dt),
                preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        total = jnp.maximum(sum_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / total).astype(o_ref.dtype)


@jax.named_scope("attention")
def attend_blocks(q: jnp.ndarray, sets: Sequence[RowSet], l,
                  live: Optional[jnp.ndarray] = None,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """``q`` [S, 1, hk, g, hd] (ONE query a slot) over layer ``l`` of the
    row ``sets`` (`kernel_shape` accepted them) under ONE softmax, only where
    ``live`` [S] is set (None: every slot; zeros elsewhere) -> [S, 1, hk, g,
    vd] in ``q``'s type: `ops.eva_attention.attend_two`'s result over the
    same rows, moving only the blocks `block_work` lists.  ``scale`` (None:
    ``hd ** -0.5``) multiplies the scores: a row that holds a differential
    pair's two keys side by side is two heads wide, a score one head's, and
    its value heads are as many as its key rows whatever their width."""
    slots, _, hk, g, hd = q.shape
    vd = sets[0][1].shape[-2]
    masks = [jnp.broadcast_to(m, (slots, 1, m.shape[-1])) for _, _, m in sets]
    item, runs, held, items = block_work(masks, live)
    starts, nb = _set_starts(masks)

    def kv_spec(i, width):
        return pl.BlockSpec(
            (None, None, hk, width, BLOCK),
            lambda w, l, item, runs, held: (
                l[0], held[i, w] // nb, 0, 0, held[i, w] % nb - starts[i]))

    by_slot = lambda w, l, item, runs, held: (item[w] // nb, 0, 0, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, nb=nb, starts=starts,
                          scale=hd ** -0.5 if scale is None else scale),
        name="cache_block_attention",
        out_shape=jax.ShapeDtypeStruct((slots, hk, g, vd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(items,),
            in_specs=[
                pl.BlockSpec((None, hk, g, hd), by_slot),
                pl.BlockSpec((None, 1, BLOCK),
                             lambda w, l, item, runs, held: (
                                 item[w] // nb, 0, item[w] % nb)),
            ] + [kv_spec(i, a.shape[-2])
                 for i, (k, v, _) in enumerate(sets) for a in (k, v)],
            out_specs=pl.BlockSpec((None, hk, g, vd), by_slot),
            scratch_shapes=[pltpu.VMEM((hk, g, 1), jnp.float32),
                            pltpu.VMEM((hk, g, 1), jnp.float32),
                            pltpu.VMEM((hk, g, vd), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(jnp.asarray(l, jnp.int32).reshape(1), item, runs, held, q[:, 0],
      jnp.concatenate(masks, axis=-1).astype(jnp.int32),
      *(a for k, v, _ in sets for a in (k, v)))
    return out[:, None]


def _chunk_kernel(l_ref, item_ref, runs_ref, held_ref, q_ref, m_ref, *refs,
                  nb: int, starts: Tuple[int, ...], scale: float, group: int):
    del l_ref, held_ref
    n = len(starts)
    kv_refs, o_ref = refs[:2 * n], refs[2 * n]
    top_ref, sum_ref, acc_ref = refs[2 * n + 1:]
    at, items = pl.program_id(1), pl.num_programs(1)
    lane, block = item_ref[at] // nb, item_ref[at] % nb
    first = (at == 0) | (item_ref[jnp.maximum(at - 1, 0)] // nb != lane)
    last = (at == items - 1) | (item_ref[
        jnp.minimum(at + 1, item_ref.shape[0] - 1)] // nb != lane)
    runs = runs_ref[at] > 0
    dt = q_ref.dtype

    @pl.when(first)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, _NEG_INF, jnp.float32)
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    for i, (start, end) in enumerate(zip(starts, starts[1:] + (nb,))):

        @pl.when(runs & (block >= start) & (block < end))
        def _(k_ref=kv_refs[2 * i], v_ref=kv_refs[2 * i + 1]):
            # TRANSPOSED: the block's rows run down the sublanes, a head's
            # g x C queries along the lanes, so a query's maximum and sum
            # fold vector against vector and no reduction crosses lanes, on
            # the v5e the dearest thing the vector units do (the queries
            # down the sublanes and a lane reduction an item: 5.25 ms a
            # call of 4 lanes x 2 full layers of 64 / 4 heads where this
            # form takes 1.61, 0.52 against 0.41 at 32 / 32; my chip runs,
            # PR 52).  Both products take the cached block as it lies:
            # ``k^T q`` contracts the keys' first axis, ``v p`` is plain.
            # Every head of the item in ONE batched product: a Python loop
            # that spells the heads out is no faster (0.415 ms where this
            # takes 0.407) and is traced and lowered again at every start
            # of the program, 3.5 s of a warm set-up of 26.
            seen = m_ref[...].astype(jnp.int32) != 0            # [BLOCK, C]
            if group > 1:       # group-major lanes: the same mask g times
                seen = jnp.concatenate([seen] * group, axis=1)
            seen = seen[None]                           # [1, BLOCK, R]
            s = jnp.einsum("hdt,hdr->htr", k_ref[...].astype(dt), q_ref[...],
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _NEG_INF)
            top = top_ref[...]                          # [hb, 1, R]
            new_top = jnp.maximum(top, s.max(1, keepdims=True))
            # (a hidden score stays at -1e30: it weighs 0 once a real one
            # is in; a query that sees nothing here adds nothing)
            p = jnp.where(seen, jnp.exp(s - new_top), 0.0)
            fade = jnp.exp(top - new_top)
            top_ref[...] = new_top
            sum_ref[...] = sum_ref[...] * fade + p.sum(1, keepdims=True)
            acc_ref[...] = acc_ref[...] * fade + jnp.einsum(
                "hdt,htr->hdr", v_ref[...].astype(dt), p.astype(dt),
                preferred_element_type=jnp.float32)     # [hb, vd, R]

    @pl.when(last)
    def _():
        total = jnp.maximum(sum_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / total).astype(o_ref.dtype)


@jax.named_scope("attention")
def attend_chunk_blocks(q: jnp.ndarray, sets: Sequence[RowSet], l,
                        live: Optional[jnp.ndarray] = None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """`attend_blocks` for the ``C`` queries of a CHUNK a lane: ``q`` [P, C,
    hk, g, hd] over layer ``l`` of the row ``sets`` (masks ``[P | 1, C, T]``;
    `kernel_shape` accepted them) under ONE softmax a query, only where
    ``live`` [P] is set (None: every lane; zeros elsewhere) -> [P, C, hk, g,
    vd] in ``q``'s type: the dense forms' result over the same rows
    (`ops.eva_attention.attend_two`, `models/generate.py` `heads`), moving
    only the blocks in which SOME query of the lane's chunk has a set row
    (`block_work`), each once, where the dense forms cut a lane's arrays
    out of the cache and carry float32 scores ``[C, heads, T]`` through
    memory.

    The grid is (blocks of `_chunk_heads` heads, the work list); an item's
    scores ``[heads, BLOCK, g x C]`` (the queries along the lanes), their
    maximum, sum and accumulator stay in VMEM from the first product to the
    second.  A lane that stands is one item
    that moves nothing and writes zeros."""
    lanes, c, hk, g, hd = q.shape
    vd = sets[0][1].shape[-2]
    hb = _chunk_heads(q.shape, sets)
    masks = [jnp.broadcast_to(m, (lanes, c, m.shape[-1])) for _, _, m in sets]
    item, runs, held, items = block_work(masks, live)
    starts, nb = _set_starts(masks)

    def kv_spec(i, width):
        return pl.BlockSpec(
            (None, None, hb, width, BLOCK),
            lambda j, w, l, item, runs, held: (
                l[0], held[i, w] // nb, j, 0, held[i, w] % nb - starts[i]))

    by_lane = lambda j, w, l, item, runs, held: (item[w] // nb, j, 0, 0)
    # a head's queries along the lanes, group-major: [P, hk, hd, g x C]
    cols = jnp.transpose(q, (0, 2, 4, 3, 1)).reshape(lanes, hk, hd, g * c)
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, nb=nb, starts=starts,
                          scale=hd ** -0.5 if scale is None else scale,
                          group=g),
        name="cache_chunk_attention",
        out_shape=jax.ShapeDtypeStruct((lanes, hk, vd, g * c), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(hk // hb, items),
            in_specs=[
                pl.BlockSpec((None, hb, hd, g * c), by_lane),
                pl.BlockSpec((None, BLOCK, c),
                             lambda j, w, l, item, runs, held: (
                                 item[w] // nb, item[w] % nb, 0)),
            ] + [kv_spec(i, a.shape[-2])
                 for i, (k, v, _) in enumerate(sets) for a in (k, v)],
            out_specs=pl.BlockSpec((None, hb, vd, g * c), by_lane),
            scratch_shapes=[pltpu.VMEM((hb, 1, g * c), jnp.float32),
                            pltpu.VMEM((hb, 1, g * c), jnp.float32),
                            pltpu.VMEM((hb, vd, g * c), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(jnp.asarray(l, jnp.int32).reshape(1), item, runs, held, cols,
      jnp.swapaxes(jnp.concatenate(masks, axis=-1), 1, 2).astype(jnp.int8),
      *(a for k, v, _ in sets for a in (k, v)))
    return jnp.transpose(out.reshape(lanes, hk, vd, g, c), (0, 4, 1, 3, 2))
