"""Multi-head latent attention (MLA): low-rank queries, and ONE compressed
key-value latent plus ONE rotary key a position, shared by all heads.

    c_q            = rmsnorm(y W_qa)                       [.., q_lora]
    q_nope | q_rope = c_q W_qb          per head           [.., h, nope|rope]
                     (or y W_q directly, of a model with no query latent)
    c_kv | k_r     = y W_kva                               [.., kv_lora|rope]
    latent         = rmsnorm(c_kv) | rotary(k_r)           what a cache holds
    k_nope | v     = rmsnorm(c_kv) W_kvb   per head        [.., h, nope|v]
    scores         = (q_nope . k_nope + rotary(q_rope) . rotary(k_r))
                     / sqrt(nope + rope)

Two forms of the same function of (queries, latents), the second read in
three ways, which the SHAPE and the PLATFORM choose between (`kernel_shape`,
`on_the_chip`), never a size of scores:

* `attend_plain` builds every head's keys and values from the latents and
  runs ordinary attention: the form for training and for a whole-sequence
  forward, where nothing is cached.
  A model with an indexer (`ops/sparse_index.py`) hands it a
  ``selection``: the positions each query attends, in the mask's place.
* `attend_absorbed` never builds them.  ``q_nope . (c W_k) = (q_nope W_k^T)
  . c`` and ``(p c) W_v = p (c W_v)``: the key up-projection is folded into
  the query and the value up-projection applied after the probabilities,
  so the few queries of a chunk or a decode step attend over the cached
  latents directly.  Per cached position a layer then reads ``kv_lora +
  rope`` values, not ``heads x (qk + v)``.  Between `absorb` and `unabsorb`
  the cached rows are read

  - by ONE KERNEL CALL a layer (`attend_cache`) wherever the program is
    lowered for a TPU and the shapes are whole tiles (`kernel_shape`), a
    chunk's queries and a DECODE STEP's one query a slot alike: a block of
    rows at a time under a running softmax, no block past the last row a
    lane's or a slot's queries may see and nothing for one that stands;
    the score block, its running maximum and sum and the float32
    accumulator stay in VMEM between the two dots, the cache is read where
    it lies (no lane's layer is cut out of the state array), and the second
    dot meets the ``kv_lora`` latent rows alone: 82 % of the MXU's peak
    where XLA's loop, the cut in front of it, ran at 68 % (4 lanes at 6-12 k
    rows, PERF.md, PR 47).  A step's heads, which all read the same rows
    under the same mask, are one head tile's query rows there (PR 54);
  - all at once (`attend_latents`) on any platform but the TPU, at a shape
    the kernel refuses (a chunk whose heads fill no head tile), and as the
    tests' reference: a decode step, and a chunk whose float32 scores are
    a few tens of MB (a context of a few thousand rows);
  - the kernel's blocks in plain `jax.numpy` (`_attend_blocks`, a loop),
    in those same places, where a chunk's scores over every row would be a
    GB (`sparse_index.loop_block`).

A model may multiply both latents by a fixed ``scale`` after their norms
(`queries`, `latents`), gate each head's output by one value a head
(``gate``: `attend_plain`, `unabsorb`) and give a kind of layer a WINDOW:
`attend_plain` masks by it, and the cached forms read that kind's latents
from a RING (`models/generate.py`) under a mask by the position a column
holds: `attend_cache` and XLA's forms take a mask as it comes, so a window
that wraps the ring's seam is columns at both ends of it, and a row of
another width than the full layers' (a latent of 1024 beside the rotary key:
1088 values) is the same call at another shape.

Shapes are ``[batch, seq, heads, dim]`` like `ops/attention.py`; a cache
layer is ``[batch, kv_lora + rope, positions]``, positions last, as
`models/generate.py` stores it.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import multi_head_attention
from .flash_attention import _LANES, _NEG_INF, _VMEM_LIMIT, _interpret
from .grouped_matmul import _cumsum
from .norms import rmsnorm
from .sparse_index import rows_seen

Rotate = Callable[[jnp.ndarray], jnp.ndarray]   # [b, s, heads, rope] -> same


@jax.named_scope("projections")
def queries(y: jnp.ndarray, wq_a, q_norm, wq_b, *, nope: int, eps: float,
            rotate: Rotate, scale: float = 1.0
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Normed input ``y`` [b, s, d] -> (q_nope [b, s, h, nope], q_rope
    [b, s, h, rope] already rotated, and the query latent ``c_q`` [b, s,
    q_lora] they were made from, times a model's fixed ``scale``: an
    indexer's queries come from it too, `ops/sparse_index.py`)."""
    dt = y.dtype
    c_q = times(rmsnorm(jnp.einsum("bsd,dr->bsr", y, wq_a.astype(dt)),
                         q_norm, eps), scale)
    q = jnp.einsum("bsr,rhk->bshk", c_q, wq_b.astype(dt))
    return q[..., :nope], rotate(q[..., nope:]), c_q


def times(x: jnp.ndarray, by: float) -> jnp.ndarray:
    """``x`` times a model's fixed multiplier, in float32 and back; 1 is no
    multiplier and no instruction."""
    if by == 1.0:
        return x
    return (x.astype(jnp.float32) * by).astype(x.dtype)


def no_turn(t: jnp.ndarray) -> jnp.ndarray:
    """The ``rotate`` of a model that turns nothing (no position enters
    it): the shared key and the queries' second part as projected."""
    return t


@jax.named_scope("projections")
def direct_queries(y: jnp.ndarray, wq, *, nope: int, rotate: Rotate
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`queries` of a model with NO query latent: ``y`` [b, s, d] through
    ``wq`` [d, h, nope + rope] -> (q_nope, q_rope turned by ``rotate``)."""
    q = jnp.einsum("bsd,dhk->bshk", y, wq.astype(y.dtype))
    return q[..., :nope], rotate(q[..., nope:])


@jax.named_scope("projections")
def latents(y: jnp.ndarray, wkv_a, kv_norm, *, kv_lora: int, eps: float,
            rotate: Rotate, scale: float = 1.0) -> jnp.ndarray:
    """Normed input ``y`` [b, s, d] -> [b, s, kv_lora + rope]: the normed
    key-value latent (times a model's fixed ``scale``) beside the rotated
    shared key.  This, and nothing else, is what a cache of this attention
    kind holds."""
    dt = y.dtype
    ckv = jnp.einsum("bsd,dr->bsr", y, wkv_a.astype(dt))
    c = times(rmsnorm(ckv[..., :kv_lora], kv_norm, eps), scale)
    k_r = rotate(ckv[..., None, kv_lora:])[..., 0, :]
    return jnp.concatenate([c, k_r], axis=-1)


@jax.named_scope("attention")
def attend_plain(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                 latent: jnp.ndarray, wkv_b, wo, *, causal: bool = True,
                 impl: str = "auto",
                 selection: Optional[jnp.ndarray] = None,
                 window: Optional[int] = None,
                 gate: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Every head's keys and values built from ``latent`` [b, s, kv_lora +
    rope], ordinary attention over them -> [b, s, d].  ``selection`` [b, s,
    s] bool (a model with an indexer): the positions each query attends,
    which then IS the mask (a selection is causal by how it was made).
    ``window`` (a window layer): position t attends j with ``0 <= t - j <
    window``.  ``gate`` [b, s, h] (`transformer.head_gate`): a head's output
    times its value, before the output projection."""
    dt = q_nope.dtype
    nope, rope = q_nope.shape[-1], q_rope.shape[-1]
    kv_lora, h = wkv_b.shape[0], wkv_b.shape[1]
    with jax.named_scope("projections"):
        kv = jnp.einsum("bsr,rhk->bshk", latent[..., :kv_lora],
                        wkv_b.astype(dt))
    k_r = jnp.broadcast_to(latent[:, :, None, kv_lora:],
                           latent.shape[:2] + (h, rope))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    v = kv[..., nope:]
    if v.shape[-1] != q.shape[-1]:
        impl = "reference"      # the flash kernel has one head size
    if selection is not None:
        scores = jnp.einsum("bshk,bthk->bhst", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(nope + rope)
        probs = jax.nn.softmax(
            jnp.where(selection[:, None], scores, -1e30), axis=-1)
        attn = jnp.einsum("bhst,bthv->bshv", probs.astype(dt), v)
    else:
        attn = multi_head_attention(q, k, v, causal=causal, impl=impl,
                                    sm_scale=1.0 / math.sqrt(nope + rope),
                                    window=window)
    if gate is not None:
        attn = attn * gate[..., None]
    with jax.named_scope("projections"):
        return jnp.einsum("bshk,hkd->bsd", attn, wo.astype(dt))


@jax.named_scope("attention")
def attend_absorbed(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                    cached: jnp.ndarray, wkv_b, wo, mask: jnp.ndarray,
                    key_block: int = 0,
                    gate: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``cached`` [b, kv_lora + rope, T] is one layer of a latent cache,
    ``mask`` [b|1, s, T] which positions each query may see -> [b, s, d].
    The probabilities meet all ``kv_lora + rope`` cached rows (the rotary
    rows' part of the result is dropped): in XLA, slicing the latent rows
    out of the cache first would copy them (`attend_cache`'s kernel slices
    the block it holds in VMEM, which costs nothing).  Its three parts stand
    alone for a caller that attends a row of the batch at a time BETWEEN
    the two that read weights: `absorb`, `attend_latents`, `unabsorb`."""
    nope = q_nope.shape[-1]
    o_lat = attend_latents(absorb(q_nope, q_rope, wkv_b), cached, mask,
                           math.sqrt(nope + q_rope.shape[-1]), key_block)
    return unabsorb(o_lat, wkv_b, wo, nope, gate=gate)


@jax.named_scope("attention")
def absorb(q_nope: jnp.ndarray, q_rope: jnp.ndarray, wkv_b,
           heads_major: bool = False) -> jnp.ndarray:
    """Queries [b, s, h, nope] and [b, s, h, rope] (rotated) -> [b, s, h,
    kv_lora + rope]: the key up-projection folded into the query.
    ``heads_major``: ``[b, h, s, kv_lora + rope]``, as `attend_cache` takes
    them (the product written so, not turned afterwards)."""
    nope = q_nope.shape[-1]
    q_lat = jnp.einsum("bshn,rhn->bhsr" if heads_major else "bshn,rhn->bshr",
                       q_nope, wkv_b.astype(q_nope.dtype)[..., :nope])
    if heads_major:
        q_rope = jnp.swapaxes(q_rope, 1, 2)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


@jax.named_scope("attention")
def attend_latents(q_abs: jnp.ndarray, cached: jnp.ndarray,
                   mask: jnp.ndarray, scale: float,
                   key_block: int = 0) -> jnp.ndarray:
    """Absorbed queries [b, s, h, kv_lora + rope] over ``cached`` [b, kv_lora
    + rope, T] under ``mask``, scores over ``scale`` -> [b, s, h, kv_lora +
    rope]: the part that reads the cache, and no weight, in XLA'S FORMS
    (`attend_cache` is the kernel's).  ``key_block`` (a divisor of T; 0: all
    rows at once; `sparse_index.loop_block` says which): the rows are read
    so many at a time under a running softmax, and NO BLOCK PAST THE LAST
    ROW ANY QUERY MAY SEE (`_attend_blocks`): a chunk's float32 scores of
    64 heads over 33 k rows would be 1.1 GB, most of them of rows its mask
    hides."""
    if key_block:
        return _attend_blocks(q_abs, cached, mask, scale, key_block)
    dt = q_abs.dtype
    scores = jnp.einsum("bshr,brt->bsht", q_abs, cached.astype(dt),
                        preferred_element_type=jnp.float32)
    scores = scores / scale
    scores = jnp.where(mask[:, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bsht,brt->bshr", probs.astype(dt), cached.astype(dt))


def _attend_blocks(q_abs, cached, mask, scale: float, block: int):
    """`attend_latents` a block of ``block`` cached rows at a time: the
    softmax's maximum and sum run along (each block's probabilities are
    taken against the maximum so far, and what was summed before is scaled
    down when it rises), and the loop ends with the last block that holds a
    row some query may see."""
    dt = q_abs.dtype
    b, s, h, r = q_abs.shape

    def some_rows(j, carry):
        top, total, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(cached, j * block, block,
                                            axis=2).astype(dt)
        m = jax.lax.dynamic_slice_in_dim(mask, j * block, block, axis=2)
        scores = jnp.einsum("bshr,brt->bsht", q_abs, rows,
                            preferred_element_type=jnp.float32) / scale
        scores = jnp.where(m[:, :, None, :], scores, -1e30)
        new_top = jnp.maximum(top, scores.max(-1))
        # (a hidden score stays at -1e30: it weighs 0 once a real one is in)
        p = jnp.exp(scores - new_top[..., None])
        p = jnp.where(m[:, :, None, :], p, 0.0)
        fade = jnp.exp(top - new_top)
        return (new_top, total * fade + p.sum(-1),
                acc * fade[..., None] + jnp.einsum(
                    "bsht,brt->bshr", p.astype(dt), rows,
                    preferred_element_type=jnp.float32))

    top, total, acc = jax.lax.fori_loop(
        0, (rows_seen(mask) + block - 1) // block, some_rows,
        (jnp.full((b, s, h), -1e30, jnp.float32),
         jnp.zeros((b, s, h), jnp.float32),
         jnp.zeros((b, s, h, r), jnp.float32)))
    return (acc / jnp.maximum(total, 1e-30)[..., None]).astype(dt)


#: query heads a grid step of `attend_cache`: with a chunk's 128 queries the
#: left operand of both dots is 1024 rows
_HEAD_TILE = 8
#: cached rows a grid step under a chunk's `_STACKED` query rows (a divisor
#: of `sparse_index.KEY_BLOCK`): the float32 score block is then 2 MiB
_ROW_TILE = 512
_STACKED = 1024


def kernel_shape(q_shape: Tuple[int, ...], kv_lora: int, block: int) -> bool:
    """Whether `attend_cache` takes queries ``[b, s, h, r]`` a block of
    ``block`` cached rows at a time (`sparse_index.key_block`'s: 0, or whole
    lanes that divide the cache's rows) on a TPU, or under the interpreter:
    whole tiles.  The queries of a head tile stack to the rows of one
    operand (``s`` a multiple of a bfloat16 tile's 16 sublanes), and the
    latent rows are a sublane-aligned slice of the cached block that fills
    the output's lanes.  A STEP (``s`` 1) has any number of heads: they are
    the one tile's rows (`attend_cache`)."""
    _, s, h, _ = q_shape
    tiles = s == 1 or (s % 16 == 0 and h % min(h, _HEAD_TILE) == 0)
    return bool(block) and tiles and kv_lora % _LANES == 0


def engages(q_shape: Tuple[int, ...], kv_lora: int, block: int) -> bool:
    """Whether a program lowered by THIS process's backend reads a latent
    layer through `attend_cache` (a host answer from shapes, as
    `ops.cache_attention.engages`)."""
    return (jax.default_backend() == "tpu" or _interpret()) \
        and kernel_shape(q_shape, kv_lora, block)


def row_tile(q_shape: Tuple[int, ...], block: int) -> int:
    """Cached rows a grid step of `attend_cache` for queries ``[b, s, h,
    r]`` over blocks of ``block``: `_ROW_TILE` under a chunk's `_STACKED`
    query rows, and as many more as the rows are fewer, up to the block: a
    step stacks its heads alone (32), and what an item costs beside its
    rows' bytes (a grid step, the block made the stationary operand of two
    dots) is paid half as often over a block of 1024."""
    _, s, h, _ = q_shape
    stacked = -(-h // 16) * 16 if s == 1 else min(h, _HEAD_TILE) * s
    return math.gcd(block, _ROW_TILE * max(1, _STACKED // stacked))


def fetched_rows(seen: int, tile: int) -> int:
    """Rows `attend_cache` moves for a lane whose queries see ``seen`` rows
    (0: it stands, and its one item moves nothing) a tile of ``tile`` at a
    time: `_cache_work`'s items for it, counted on the host."""
    return -(-seen // tile) * tile


def _cache_work(rows: jnp.ndarray, block: int, most: int):
    """Visible rows a lane [B] -> the list of (lane, block of ``block``
    rows) the kernel walks, lanes ascending and each one's blocks
    ascending: (the lane an item writes [W], the lane and the block it
    reads [W] [W], the list's length), ``W = B x most`` the most there can
    be.  A lane with no row to see (it stands) has ONE item, which reads
    what the item before it read (a repeated block index moves nothing) and
    writes zeros."""
    lanes = rows.shape[0]
    blocks = jnp.maximum((rows + block - 1) // block, 1)
    ends = _cumsum(blocks)
    # what a standing lane's item reads: the last block of the nearest
    # lane before it that runs (none: the first block of lane 0); triangles
    # of comparisons as `_cumsum`'s, whatever the lanes' number
    at_lane = jnp.arange(lanes, dtype=jnp.int32)
    runs = rows > 0
    src = jnp.where((at_lane[None, :] <= at_lane[:, None]) & runs[None, :],
                    at_lane[None, :], 0).max(1)
    last = jnp.where((src[:, None] == at_lane[None, :]) & runs[None, :],
                     blocks[None, :] - 1, 0).sum(1)
    item = jnp.arange(lanes * most, dtype=jnp.int32)
    lane = jnp.minimum((item[:, None] >= ends[None, :]).sum(1),
                       lanes - 1).astype(jnp.int32)
    mine = lane[:, None] == at_lane[None, :]

    def of_lane(per_lane):              # [B] -> [W], by comparisons
        return jnp.where(mine, per_lane[None, :], 0).sum(1).astype(jnp.int32)

    at = jnp.clip(item - of_lane(ends - blocks), 0, most - 1)
    return (lane, of_lane(src),
            jnp.where(of_lane(rows) > 0, at, of_lane(last)), ends[-1])


def _cache_kernel(l_ref, lane_ref, src_ref, at_ref, rows_ref, q_ref, kv_ref,
                  seen_ref, o_ref, top_ref, sum_ref, acc_ref, *,
                  inv_scale: float, kv_lora: int):
    del l_ref, src_ref, at_ref
    item, items = pl.program_id(1), pl.num_programs(1)
    lane = lane_ref[item]
    first = (item == 0) | (lane_ref[jnp.maximum(item - 1, 0)] != lane)
    last = (item == items - 1) | (lane_ref[
        jnp.minimum(item + 1, lane_ref.shape[0] - 1)] != lane)
    hb, c, r = q_ref.shape

    @pl.when(first)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, _NEG_INF, jnp.float32)
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(rows_ref[lane] > 0)
    def _():
        dt = q_ref.dtype
        rows = kv_ref[...].astype(dt)                          # [r, tk]
        scores = jnp.dot(q_ref[...].reshape(hb * c, r), rows,
                         preferred_element_type=jnp.float32) * inv_scale
        # (one row where all the tile's queries see the same: a step's)
        seen = (seen_ref[...].astype(jnp.int32) != 0)[None]  # [1, c|1, tk]
        scores = jnp.where(seen, scores.reshape(hb, c, -1), _NEG_INF)
        top = top_ref[...]                                     # [hb, c, 1]
        new_top = jnp.maximum(top, scores.max(-1, keepdims=True))
        # (a hidden score stays at -1e30: it weighs 0 once a real one is in)
        p = jnp.where(seen, jnp.exp(scores - new_top), 0.0)
        fade = jnp.exp(top - new_top)
        top_ref[...] = new_top
        sum_ref[...] = sum_ref[...] * fade + p.sum(-1, keepdims=True)
        # the probabilities over the block's LATENT rows only: a sublane
        # slice of the block where it lies, contracted over its lanes
        acc_ref[...] = acc_ref[...] * fade.reshape(hb * c, 1) \
            + jax.lax.dot_general(
                p.astype(dt).reshape(hb * c, -1), rows[:kv_lora],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        total = jnp.maximum(sum_ref[...], 1e-30).reshape(hb * c, 1)
        o_ref[...] = (acc_ref[...] / total).reshape(hb, c, kv_lora).astype(
            o_ref.dtype)


@jax.named_scope("attention")
@functools.partial(jax.jit, static_argnames=("scale", "kv_lora", "block"))
def attend_cache(q_abs: jnp.ndarray, kv_all: jnp.ndarray, l,
                 mask: jnp.ndarray, live: Optional[jnp.ndarray],
                 scale: float, kv_lora: int, block: int) -> jnp.ndarray:
    """`_attend_blocks` as ONE kernel call over the cache WHERE IT LIES:
    absorbed queries HEADS-MAJOR ``[b, h, s, kv_lora + rope]`` over layer
    ``l`` of ``kv_all`` [L, b, 1, kv_lora + rope, T] under ``mask`` [b | 1,
    s, T], a batch row at a time and only where ``live`` [b] is set (None:
    everywhere; zeros elsewhere) -> ``[b, h, s, kv_lora]``, the latent rows'
    part of `attend_latents`' result (all `unabsorb` reads).  ``block``: a
    divisor of T that `kernel_shape` accepted; a grid step takes
    `row_tile`'s rows of it.

    The same arithmetic: operands in the compute type, float32 scores over
    ``scale``, the mask a query's own set of columns, float32 running
    maximum and sum, probabilities cast for the second dot, float32
    accumulation.  What differs is where it happens: a grid step's score
    block (`_HEAD_TILE` heads x ``s`` queries stacked to the rows of ONE
    left operand, x `_ROW_TILE` cached rows), its maximum, sum and
    accumulator stay in VMEM from the first dot to the second; the cached
    block arrives by an index map over the whole state array (layer, batch
    row and block are prefetched scalars: no lane's layer is cut out), and
    the second dot leaves the rotary rows out.  The grid walks
    `_cache_work`'s list, its length a traced scalar as
    `ops/grouped_matmul.py`'s: no block past the last row a lane's queries
    may see, one item for a lane that stands, no grid step that does
    nothing.  Tiles (my chip runs, PR 47, 4 lanes at 6-12 k rows: 8 x 512
    4.13 ms, 8 x 1024 4.19, 16 x 512 4.00-4.11, 8 x 256 5.23; the 8 heads
    as two or more chains of fewer rows 4.29-5.22): the dots bind, at 82 %
    of the MXU's peak (87 % of what a contraction of 576 leaves of it: the
    MXU runs it as 640).

    A DECODE STEP (``s`` 1: one query a slot) is the same call: all heads
    of a latent layer read the SAME rows under the SAME mask, so a slot's
    heads ARE one head tile's query rows (``[b, 1, h, r]``, zero rows up to
    a whole bfloat16 tile, whose result is dropped) and its mask stays one
    row.  A slot at depth ``t`` then costs ``t`` rows a tile at a time and
    one that stands an item that moves nothing, where dense dots under a
    mask move every row of every slot whatever stands in them.

    Jitted in its own right: a model's latent layers sit in loop segments
    of their own (seven in Kimi-Linear's three served programs), and a
    process traces the work list and the kernel and lowers them ONCE a
    program and not once a segment (warm `setup.warmup_s` read +3.0 s with
    21 lowerings; PERF.md, PR 54)."""
    b, h, s, r = q_abs.shape
    t = kv_all.shape[-1]
    tile = row_tile((b, s, h, r), block)
    q = q_abs
    if s == 1:
        q = jnp.pad(q_abs.reshape(b, 1, h, r),
                    ((0, 0), (0, 0), (0, -h % 16), (0, 0)))
    tiled, c = q.shape[1:3]
    hb = min(tiled, _HEAD_TILE)
    seen = jnp.broadcast_to(mask, (b,) + mask.shape[1:])
    rows = jax.vmap(rows_seen)(seen)                # a lane's own, [b]
    if live is not None:
        rows = jnp.where(live, rows, 0)
    lane, src, at, items = _cache_work(rows, tile, t // tile)
    out = pl.pallas_call(
        functools.partial(_cache_kernel, inv_scale=1.0 / scale,
                          kv_lora=kv_lora),
        name="latent_attention_cache",
        out_shape=jax.ShapeDtypeStruct((b, tiled, c, kv_lora), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tiled // hb, items),
            in_specs=[
                pl.BlockSpec((None, hb, c, r),
                             lambda j, i, l, ln, src, at, n: (src[i], j, 0, 0)),
                pl.BlockSpec((None, None, None, r, tile),
                             lambda j, i, l, ln, src, at, n:
                             (l[0], src[i], 0, 0, at[i])),
                pl.BlockSpec((None, seen.shape[1], tile),
                             lambda j, i, l, ln, src, at, n:
                             (src[i], 0, at[i])),
            ],
            out_specs=pl.BlockSpec(
                (None, hb, c, kv_lora),
                lambda j, i, l, ln, src, at, n: (ln[i], j, 0, 0)),
            scratch_shapes=[pltpu.VMEM((hb, c, 1), jnp.float32),
                            pltpu.VMEM((hb, c, 1), jnp.float32),
                            pltpu.VMEM((hb * c, kv_lora), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(jnp.asarray(l, jnp.int32).reshape(1), lane, src, at, rows, q,
      kv_all, seen.astype(jnp.int8))
    return out[:, :, :h].reshape(b, h, 1, kv_lora) if s == 1 else out


@jax.named_scope("attention")
def unabsorb(o_lat: jnp.ndarray, wkv_b, wo, nope: int,
             heads_major: bool = False,
             gate: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """`attend_latents`' [b, s, h, kv_lora + rope] (``heads_major``:
    `attend_cache`'s [b, h, s, kv_lora]) -> the block's [b, s, d]: the value
    up-projection on the latent rows, a head's ``gate`` [b, s, h] where the
    model has one, then the output's."""
    dt = o_lat.dtype
    kv_lora = wkv_b.shape[0]
    attn = jnp.einsum("bhsr,rhv->bshv" if heads_major else "bshr,rhv->bshv",
                      o_lat[..., :kv_lora], wkv_b.astype(dt)[..., nope:])
    if gate is not None:
        attn = attn * gate[..., None]
    with jax.named_scope("projections"):
        return jnp.einsum("bshv,hvd->bsd", attn, wo.astype(dt))


def on_the_chip(kernel, loop, *operands):
    """``kernel(*operands)`` where the program is lowered for a TPU (or
    under the interpreter), ``loop(*operands)`` elsewhere: the one program
    text serves both, as `ops/cache_write.py` `write_columns` does."""
    if _interpret():
        return kernel(*operands)
    return jax.lax.platform_dependent(*operands, tpu=kernel, default=loop)
