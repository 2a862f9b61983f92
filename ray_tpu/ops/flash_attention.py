"""Blockwise fused attention (flash attention) as Pallas TPU kernels.

The reference framework has no fused attention of its own — it delegates all
model math to torch (SURVEY.md §2.3); in a TPU-native stack the attention
inner loop is the single hottest op, so it gets hand-written kernels: a
softmax forward and a custom-VJP backward, ONE kernel (dq, dk, dv) where the
plan holds a head block's whole query side, two (dq; dk, dv) elsewhere.

**Layout.**  Inputs are ``[batch, seq, heads, head_dim]``, the framework's
activation layout, and the kernels read them where they lie: that array IS
``[batch, seq, heads x head_dim]``, and a block ``(1, rows, G x head_dim)``
of it is ``G`` whole heads side by side on the lanes.  The wrapper transposes
and copies nothing, no operand has a minor dimension narrower than the 128
lanes (two heads of 64 share them; a head is a lane slice of the block), and
the row statistics (``lse``, ``delta``) travel as ``[batch, head blocks, G,
seq]``: the sequence on the lanes, never ``[.., seq, 1]``.

**Heads a grid step.**  A grid step works on ``G`` query heads in a static
loop, so the scheduler has one head's matmuls to put beside another's
exponentials (`make_plan`): with several kv heads a step takes each one's
whole GQA group, with one kv head a divisor of the group, so kv heads are
never repeated.  ``G`` is the largest such count up to `_MAX_HEADS` whose lane
width is a multiple of 128 (or all heads) and whose blocks fit
`_VMEM_BLOCK_BUDGET`.  A head count that no such block divides (25 heads of
64) runs whole lane tiles of heads a step all the same (8: three blocks of
eight and a block of one), and the last block, which lies partly outside
the array, gets a kernel body of its own for the heads inside (`_inside`):
nothing is computed from what lies outside, Pallas drops what would be
written there, and no operand is padded or copied.

**Blocks.**  The grid walks q blocks (forward, dq) or k blocks (dkv, with
or without dq) of ``block_q`` / ``block_k`` rows; the OTHER operand arrives
as one major block, the whole sequence when `_VMEM_BLOCK_BUDGET` allows (then it is fetched once
a head block, not once a q block), else the largest multiple of the tile
that fits.  Inside, a loop walks tiles of ``block_q x block_k`` over the part
of the major block that causality leaves live, each with one compare and
select against a hoisted iota.  (A second, unmasked body for the tiles the
diagonal does not cross was built and measured: it saves 0.1-0.4 % of the
backward kernels' time and 3 % of the forward's, and costs a second copy
of every unrolled body to trace and lower at each start of a program and to
compile; PERF.md, PRs 35 and 61.  Without ``causal`` nothing is masked.)  Major
blocks that are wholly dead have their index clamped to the last live one,
so they cost no transfer.  The softmax scale is folded into the resident operand where that
is exact (a power of two: head sizes 64 and 256), and the dead-row select
exists only where ``s_q > s_kv`` makes dead rows possible.

**No reduction across lanes in a tile.**  The forward and the dkv kernel
hold a tile's scores transposed, ``[block_k, block_q]`` (``k q^T``): the keys
run down the sublanes, so the forward's maxima and sums fold vector against
vector and its statistics, like the dkv kernel's, are rows as stored; no
matmul of either transposes a score tile.  dq takes the statistics as
columns, transposed once a q block.

**One forward pass** (`_fwd_kernel`): a tile's scores are computed once,
the online softmax rescales a transposed accumulator (``acc^T = acc^T alpha
+ v^T e``), and all heads' FIRST matmul is issued before any head's
exponentials.  Measured on one v5e chip, the forward alone, the kernel's own
ms at ``[8, 1024, 16, 64]`` / ``[2, 1024, 25, 64]`` (PERF.md, PR 61).  The
two passes it replaces (row maxima from ``k q^T``, then ``q k^T`` again,
exponentials, lane-folded sums and a float32 `HIGHEST` product to sum them;
PR 35): 0.671 / 0.283.  One pass, HEAD BY HEAD (a head's two matmuls and
its exponentials in turn): ``v^T`` held in scratch for the resident rows
0.626 / 0.258 (the sums folded to one row a tile 0.625 / 0.256; all heads'
``acc^T`` transposed at once at the end 0.605 / 0.249), ``v^T`` made every
grid step 0.653 / 0.271, the contraction over the v tile's rows left to
Mosaic 0.608 / 0.247 (0.587 / 0.242 with the one transpose at the end), the
accumulator untransposed and ``e`` transposed by Mosaic 0.667 / 0.277, ``q^T``
made once a grid step for the first matmul 0.627 / 0.258: one matmul and
the reductions fewer bought 7-13 %.  Taking pieces out of the 0.605 form said
why: without the mask 0.601, without the exponential 0.605, without the sum
0.584, without the maximum 0.496, but without the SECOND matmul 0.289,
without the first 0.251, without both 0.242: matmuls are issued in program
order, so head by head the vector units wait for a head's scores and the MXU
for its exponentials.  ALL HEADS' SCORES FIRST, then each head's softmax
and second matmul: **0.319 / 0.140** (kept: -52 / -51 %); the same with
``v^T`` held 0.325 / 0.144, ``o`` written a head 0.340 / 0.148, in three
phases 0.319 / 0.142, one head ahead 0.370 / 0.159, in halves of four
0.326 / 0.144; a second, unmasked body for the tiles under the diagonal
0.309 / 0.138 (not kept: a second copy of the body to trace and lower at
every start for 3 %).  Tiles (the plan's 8 heads, the forward's own tile;
kept order): 512 x 128 0.313 / 0.136, 512 x 256 0.340 / 0.144, 256 x 128
0.308 / 0.142, 128 x 256 0.367 / 0.169: none gains at both shapes, the tile
stays the plan's.  Head by head a wider q tile gained most (512 x 512 0.421),
the same waiting seen from the other side.  Every other plan, two passes ->
one: 4 heads of 64 over 4096 rows 1.471 -> 0.786, GQA 8 over 2 at 2048 rows
0.987 -> 0.529, 4 heads of 128 over one kv head 0.571 -> 0.355, 16 heads
over ONE kv head 0.331 -> 0.158, ViT's one tile of 197 rows at batch 32
0.291 -> 0.096, 1024 queries on 2048 keys 0.394 -> 0.207, 2048 on 1024
(dead rows) 0.267 -> 0.120, 16384 rows in major blocks of 8192 (k, v not
resident) 13.76 -> 8.10: one algorithm for all, nothing keeps the two
passes.

**One backward kernel** (`one_backward`, a function of the plan alone).  The
dq and the dkv kernel walk the same live tiles and each computes a tile's
scores, exponentials, ``dp`` and ``ds``: seven matmuls and two passes of
exponentials where the mathematics has five and one.  Where the whole query
side is resident (``major_q == s_q``), a kv head block meets all its query
heads in one grid step (``q_steps == 1``) and the accumulator fits, the dkv
kernel accumulates dq as well (``with_dq``, call name
``flash_attention_bwd``): a float32 ``dq^T [hq x d, s_q]`` that lives
across a head block's k steps (so the k axis is ``arbitrary``), added to at
the tile's columns on every live tile, written as a third result at the last
k block.  It costs 2 MB of VMEM at 8 heads of 64 over 1024 rows beside 0.25
of the transposed k block and 2 of the dq block (`_block_bytes`).  Same
operands, same float32 statistics and accumulators, the same ``ds`` cast
before its matmuls, dq summed over k tiles in ascending order: dk and dv are
the dkv kernel's to the bit, dq the dq kernel's to float32 rounding.  Plans
with major blocks smaller than the sequence, or a kv head whose query heads
take several grid steps, keep the two kernels.

The fifth matmul contracts the tile's FIRST dimension (``dq = ds^T k`` with
scores held ``[block_k, block_q]``).  Measured on one v5e chip, a call of the
backward alone, the kernels' own ms at ``[8, 1024, 16, 64]`` /
``[2, 1024, 25, 64]`` (PERF.md, PR 56): the two kernels 1.082 / 0.451; one
kernel with `dot_general` contracting dimension 0 of both (Mosaic transposes
the ``ds`` tile) 0.800 / 0.331, the same into a lane-dense accumulator
``[s_q, hq x d]`` 0.798 / 0.330, ``ds`` transposed by hand in bfloat16 0.798
/ 0.330 and in float32 0.772 / 0.321; ``dq^T += k^T ds``, the tile as it lies
against the k block transposed once a grid step, one transpose of the
accumulator at the end, **0.715 / 0.299** (kept); no fifth matmul at all
0.612 / 0.257.  The one kernel also won at every other plan tried (4 heads
of 64 over 4096 rows 2.504 -> 1.766, GQA 8 over 2 at 2048 rows 1.722 ->
1.127, 4 heads of 128 over one 0.900 -> 0.690, ViT's one tile of 197 rows
0.388 -> 0.237), so no plan that can hold the accumulator takes the two.

**The order of a head's products in the backward** (`_one_ahead`, in
`_bwd_dkv_kernel` and `_bwd_dq_kernel`).  The chain as first written, a
head: scores, ``exp``, ``dv +=``, ``dp``, ``ds``, ``dk +=``, ``dq^T +=``.
Matmuls go to the MXU in program order, and ``dp``, which waits for nothing,
stood behind ``dv``'s product and in front of ``ds``, which needs it.
Measured as above (PERF.md, PR 62), the chain: 0.7147 / 0.2999.  Pieces out
of it (results wrong, times only): no ``exp`` 0.7150 / 0.2999, no mask
0.7116 / 0.2985, neither 0.7112 / 0.2971 (the vector work is hidden as it
is: unlike the forward's, this body waits for the MXU); no ``dp`` 0.6121 /
0.2569, no ``dq^T`` 0.6073 / 0.2596, no ``dv`` 0.5875 / 0.2482, none of the
three accumulations 0.3609 / 0.1594 (no ``dk`` alone: SLOWER, 0.8025 /
0.3334), nothing but the loop, the loads and the ends 0.1890 / 0.0877: a
product's absence frees its own 0.10-0.13 ms of five, the products run end
to end.  Orders (the same operations; dq, dk and dv the chain's to the bit
in every one): ALL heads' scores and ``dp`` before any head's exponentials,
as the forward has them, 0.6585 / 0.2745 (the mask and the scale moved
behind them 0.6585 / 0.2745, in three phases 0.6585 / 0.2745, the
accumulations one head late 0.6585 / 0.2745, scores and ``dp`` in turn
0.6464 / 0.2699; without ``exp`` 0.6592, without the mask 0.6566, without
``dq^T`` 0.5951), all scores first and ``dp`` beside each head's
exponentials 0.7064 / 0.2968, halves of four heads 0.6594 / 0.2748, pairs
0.6592 / 0.2747, three heads ahead 0.6593 / 0.2742, two ahead 0.6593 /
0.2748, **ONE head ahead 0.6460 / 0.2695 (kept: -9.6 / -10.1 %)**: the next
head's two products issued after this head's ``exp`` 0.6461 / 0.2695, its
``dp`` after this head's ``dv`` 0.6462 / 0.2698, its scores after ``dv``
and its ``dp`` after ``dk`` 0.6458 / 0.2685, the accumulations in the seven
other orders that keep ``ds`` before ``dk`` and ``dq^T`` 0.6455-0.6470 /
0.2690-0.2737; the scores one head ahead and ``dp`` where the chain had it
0.7105 / 0.2984; a head's scores and ``dp`` both before ITS exponentials,
nothing ahead, 0.6454 / 0.2696.  One thing was lost, ``dp``'s place; two
heads' tiles or more held across the body cost 2 % (0.25 MB a tile, spilled
and read back).  Nothing ahead is as fast at this tile and loses elsewhere
(256 x 128 0.789 for 0.689, 128 x 128 0.953 for 0.778, and the dq kernel,
where ``dp`` already followed ``exp``, 9.76 for 8.63 at 16384 rows), so one
head ahead is the module's one order.  Tiles with the plan re-made (chain
/ all first / one ahead): 256 x 128 0.924 / 0.708 / 0.689, 128 x 256 0.817
/ 0.695 / 0.716, 512 x 256 (4 and 6 heads) 0.773 / 0.773 / 0.772, 512 x
128 0.896 / 0.833 / 0.833, 128 x 128 1.104 / 0.710 / 0.778 at
``[8, 1024, 16, 64]`` and the same ranking at 25 heads: the small tiles
lost most of their waiting and still none reaches 256 x 256, which stands.
Every other plan, chain -> kept: 4 heads of 64 over 4096 rows 1.766 ->
1.486, GQA 8 over 2 at 2048 rows 1.127 -> 1.003, 4 heads of 128 over one kv
head 0.690 -> 0.591, ViT's one tile of 197 rows 0.237 -> 0.185, 1024
queries on 2048 keys 0.492 -> 0.421, and the two kernels where they stay:
12 heads over 16384 rows in major blocks of 8192 dq 9.76 -> 8.63, dkv 13.43
-> 11.01, 16 heads over ONE kv head dq 0.249 -> 0.239, dkv 0.261 -> 0.246.

The default tile (`DEFAULT_BLOCK_Q` x `DEFAULT_BLOCK_K`) and `_MAX_HEADS`
were measured on one v5e chip on the kernels alone at
``[8, 1024, 16, 64]`` and ``[2, 1024, 25, 64]`` (device time from a profiler
trace; PERF.md, PRs 35, 56 and 61); ``block_q`` / ``block_k`` stay as arguments for tests
and sweeps.  bf16 in and out (operands enter the MXU in their storage
dtype), float32 scores, statistics and accumulators.  A tile divides its
sequence and is a multiple of 128 rows, or it is the whole sequence, of any
length from 8 rows (ViT's 197 tokens: one tile, sliced statically, the row
reductions taking their general forms): `flash_attention` clamps the block
(halving) to a divisor, raises where that is neither (`tile_ok`), and
callers (`ray_tpu.ops.attention.multi_head_attention`) fall back to the
reference jnp implementation there.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test-only switch: RAY_TPU_PALLAS_INTERPRET=1 runs the kernels through the
# Pallas interpreter on any backend, so the suite proves the kernel math on
# the CPU.  Nothing that runs on the chip sets it (chip_smoke.py refuses to
# start with it).
import os as _os


def _interpret() -> bool:
    return _os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"


DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
_NEG_INF = -1e30  # avoids -inf - -inf = nan in the online softmax
_LANES = 128
_MAX_HEADS = 8    # heads unrolled in one kernel body (code size, compile time)
# what a kernel's pipelined blocks and scratch may take of the chip's VMEM
# (128 MiB on a v5e, of which the compiler scopes a kernel `_VMEM_LIMIT`)
_VMEM_BLOCK_BUDGET = 14 << 20
_VMEM_LIMIT = 48 << 20
# `checkpoint_name`s of what the backward kernels take from the forward: the
# two values a layer's checkpoint keeps under full remat
# (`models.transformer.remat_policy`), so the forward kernel is not run again
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"


def fit_block(block: int, s: int) -> int:
    """Largest block <= ``block`` that divides ``s`` (halving search, so a
    128-aligned sequence shorter than the default still lands on a
    128-multiple block instead of being rejected)."""
    b = min(block, s)
    while b > 1 and s % b:
        b //= 2
    return b


def tile_ok(block: int, s: int) -> bool:
    """Whether the kernels walk a sequence of ``s`` rows in tiles of
    ``block``: whole lanes (the row statistics travel with the sequence on
    the lanes, and the tile loop slices them there), or the whole sequence
    as one tile of any length from 8 rows."""
    return s % block == 0 and (block % _LANES == 0 or block == s) \
        and block >= 8


class Plan(NamedTuple):
    """The static shape of one call's kernels."""
    h: int            # query heads
    h_kv: int
    d: int
    hq: int           # query heads a grid step
    hk: int           # kv heads a grid step
    block_q: int      # tile rows
    block_k: int      # tile columns
    major_k: int      # k, v rows resident in the forward and dq kernels
    major_q: int      # q, do rows resident in the dkv kernel
    s_q: int          # query rows of the call
    itemsize: int     # bytes of an operand's element

    @property
    def head_blocks(self) -> int:
        return -(-self.h // self.hq)

    @property
    def q_steps(self) -> int:
        """Query head blocks a kv head block meets, one a grid step of the
        kernels that walk k blocks."""
        return 1 if self.hk > 1 else self.h // self.h_kv // self.hq


def _lane_ok(n: int, d: int, total: int) -> bool:
    return n == total or (n * d) % _LANES == 0


def _block_bytes(hq, hk, d, bq, bk, major_k, major_q, itemsize,
                 with_dq: bool = False) -> int:
    """VMEM of the hungriest of the forward, the dq and the dkv kernel:
    double buffered blocks, scratch, and the float32 tiles of one head
    (``tiles``: the backward bodies hold the next head's scores and ``dp``
    beside this head's ``e``, ``dp``, ``ds``, their bfloat16 casts and the
    hoisted iota, `_one_ahead`; the compiler scopes 1.3 MB for them at 256 x
    256 where this counts 1.5, and 13.6 MB for the whole one-kernel call at
    the train cells' plan where this says 14.4: described v5e, CPU, PR 62).
    The
    forward and the dq kernel take the same blocks; the forward's scratch is
    the scaled q, the float32 ``acc^T``, row statistics of 9 sublanes a head
    and every head's tile of scores beside the one head's ``tiles`` (it
    issues all heads' first matmul before any exponential: 0.5 + 0.5 + 0.07
    + 2 MB at 8 heads of 64 over 1024 rows), the dq kernel's the scaled q, a
    float32 accumulator a head padded to the lanes and two columns of
    statistics (3.5 MB there).
    ``with_dq``: of the dkv kernel where it accumulates dq as well
    (`one_backward`; ``major_q`` is then the whole query side): beside its
    own blocks the float32 accumulator ``[hq x d, major_q]`` (lane-dense:
    the sequence on the lanes, no head padded to 128), the k block
    transposed, and the dq block, double buffered: 2 + 0.25 + 2 MB over the
    dkv kernel's 10.4 at the train cells' plan (8 heads of 64, 1024 rows)."""
    dp = -(-d // _LANES) * _LANES
    tiles = 6 * bq * bk * 4
    forward = hq * bq * (dp * itemsize + (d + 9 + bk) * 4)
    dq = hq * bq * (dp * (4 + itemsize) + 2 * _LANES * 4)
    walk_q = (2 * 3 * bq * hq * d * itemsize            # q, do / o, dq
              + 2 * 2 * major_k * hk * d * itemsize     # k, v
              + max(forward, dq))
    walk_k = (2 * 2 * major_q * hq * d * itemsize       # q, do
              + 2 * 4 * bk * hk * d * itemsize          # k, v, dk, dv
              + 2 * 2 * hq * major_q * 4                # lse, delta
              + hk * bk * dp * (8 + itemsize))
    if with_dq:
        return (walk_k + tiles
                + hq * d * major_q * 4                  # dq^T, float32
                + hk * d * bk * itemsize                # k^T
                + 2 * major_q * hq * d * itemsize)      # dq
    return max(walk_q, walk_k) + tiles


def one_backward(p: Plan) -> bool:
    """Whether the call's backward is ONE kernel (dq accumulated in the dkv
    kernel, ``flash_attention_bwd``) and not two: where the plan holds the
    whole query side, a kv head block meets all its query heads in one grid
    step, and the accumulator fits half of what the compiler is given
    (`_block_bytes` leaves the compiler's own temporaries out; measured
    faster than the two kernels at every plan tried, up to 20.1 MB: 4 heads
    of 64 over 4096 rows).  A plan made under `_VMEM_BLOCK_BUDGET` that
    holds its query side seldom comes near the bound."""
    return (p.major_q == p.s_q and p.q_steps == 1
            and _block_bytes(p.hq, p.hk, p.d, p.block_q, p.block_k,
                             p.major_k, p.major_q, p.itemsize,
                             with_dq=True) <= _VMEM_LIMIT // 2)


def _major(block: int, s: int, fits) -> int:
    """The largest multiple of ``block`` dividing ``s`` that ``fits``."""
    n = s // block
    for parts in range(1, n + 1):
        if n % parts == 0 and fits(block * (n // parts)):
            return block * (n // parts)
    return block


def make_plan(h: int, h_kv: int, d: int, s_q: int, s_kv: int, itemsize: int,
              block_q: int, block_k: int) -> Plan:
    """Heads a step and major blocks from the shape alone (module docstring).
    Whole-sequence residency is preferred over more heads a step: it removes
    the re-reads, more heads only amortise what is then a small fixed cost."""
    assert h % h_kv == 0, f"query heads {h} not a multiple of kv heads {h_kv}"
    group = h // h_kv
    cands = [(hq, 1) for hq in range(1, group + 1) if group % hq == 0]
    cands += [(hk * group, hk) for hk in range(2, h_kv + 1) if h_kv % hk == 0]
    cands = sorted((hq, hk) for hq, hk in cands
                   if _lane_ok(hq, d, h) and _lane_ok(hk, d, h_kv))
    capped = [c for c in cands if c[0] <= _MAX_HEADS]
    if not capped and group == 1 and _LANES % d == 0:
        # no divisor of the heads fills whole lanes (an odd count of narrow
        # heads): whole lane tiles of heads a step, and the last step's
        # block lies partly outside the array (`_inside`)
        n = _LANES // d
        capped = [(m, m) for m in range(n, _MAX_HEADS + 1, n)]
    capped = capped or cands[:1]

    def bytes_of(hq, hk, mk, mq):
        return _block_bytes(hq, hk, d, block_q, block_k, mk, mq, itemsize)

    for hq, hk in reversed(capped):
        if bytes_of(hq, hk, s_kv, s_q) <= _VMEM_BLOCK_BUDGET:
            return Plan(h, h_kv, d, hq, hk, block_q, block_k, s_kv, s_q,
                        s_q, itemsize)
    hq, hk = capped[0]
    major_k = _major(block_k, s_kv, lambda m: bytes_of(
        hq, hk, m, block_q) <= _VMEM_BLOCK_BUDGET)
    major_q = _major(block_q, s_q, lambda m: bytes_of(
        hq, hk, block_k, m) <= _VMEM_BLOCK_BUDGET)
    return Plan(h, h_kv, d, hq, hk, block_q, block_k, major_k, major_q,
                s_q, itemsize)


def _folds(sm_scale: float) -> bool:
    """A power-of-two scale multiplies an operand exactly in any float
    dtype, so it is applied to the resident operand once and not to every
    score."""
    return math.frexp(sm_scale)[0] == 0.5


def _nt(a, b):
    """``a @ b.T`` with float32 accumulation: operands enter the MXU in
    their storage dtype (bf16); casting them to float32 first would force
    the multi-pass float32 path (measured 0.9x of unfused attention on a
    v5e)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a.T @ b``: Mosaic transposes ``a``."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scores(a, b, sm_scale, seen):
    """A tile of scores ``a @ b.T``: scaled here unless the scale is folded
    into an operand (`_fill_scaled`), and `_NEG_INF` outside ``seen`` (None:
    not causal)."""
    s = _nt(a, b)
    if not _folds(sm_scale):
        s = s * sm_scale
    return s if seen is None else jnp.where(seen, s, _NEG_INF)


def _fill_scaled(dst_ref, src_ref, heads: int, d: int, sm_scale):
    """Each head's lanes of a block into ``dst_ref[head]``, times the scale
    where that is exact."""
    for n in range(heads):
        x = _heads(src_ref, slice(None), n, d)
        dst_ref[n] = x * jnp.asarray(sm_scale, x.dtype) if _folds(sm_scale) \
            else x


def _query_minus_key(shape, query_dim: int):
    """Query index less key index over a tile of scores, the queries along
    ``query_dim``: a query sees the keys where this is at least the tile's
    own offset (the caller's one compare a tile)."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, query_dim)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - query_dim))


def _row_to_col(row):
    """``(1, n)`` -> ``(n, 128)``, the column on every lane, through a
    whole-tile transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))


def _div(a, b: int):
    """``a // b`` of a traced int that is not negative: `lax.div`, which
    truncates; ``//`` would add its fix-ups for signs, a sub-function each
    to trace and lower."""
    return a if b == 1 else jax.lax.div(a, jnp.int32(b))


def _cdiv_pos(a, b: int):
    """``ceil(max(a, 0) / b)`` of a traced int."""
    return _div(jnp.maximum(a, 0) + (b - 1), b)


def _k_tiles(qi, ki, p: Plan, causal: bool, q_offset: int):
    """How many tiles of major k block ``ki`` q block ``qi`` attends (the
    first ones; local tile indices)."""
    n = p.major_k // p.block_k
    if not causal:
        return n
    live = _cdiv_pos(qi * p.block_q + q_offset + p.block_q, p.block_k)
    return jnp.clip(live - ki * n, 0, n)


def _first_q_tile(ki, qm, p: Plan, causal: bool, q_offset: int):
    """The first tile of major q block ``qm`` that attends k block ``ki``
    (every later one does)."""
    n = p.major_q // p.block_q
    if not causal:
        return 0
    live = _div(jnp.maximum(ki * p.block_k - q_offset, 0), p.block_q)
    return jnp.clip(live - qm * n, 0, n)


def _last_live_k(qi, p: Plan, q_offset: int):
    """Index of the last major k block q block ``qi`` attends."""
    live = _cdiv_pos(qi * p.block_q + q_offset + p.block_q, p.block_k)
    return _div(jnp.maximum(live - 1, 0), p.major_k // p.block_k)


def _first_live_q(ki, p: Plan, q_offset: int):
    """Index of the first major q block that attends k block ``ki``."""
    return _div(jnp.maximum(ki * p.block_k - q_offset, 0), p.major_q)


def _heads(ref, rows, n, d):
    """Head ``n``'s lanes of a ``(1, rows, heads x d)`` block."""
    return ref[0, rows, n * d:(n + 1) * d]


def _inside(block, p: Plan, body):
    """``body(query heads)`` for the heads of head block ``block`` that lie
    inside the array.  Only a head count that no lane-dense block divides
    (`make_plan`) has a last block with fewer: that block gets a body of its
    own, so no head is computed from what lies outside."""
    last = p.head_blocks - 1
    rest = p.h - last * p.hq
    if rest == p.hq:
        body(p.hq)
    else:
        pl.when(block < last)(lambda: body(p.hq))
        pl.when(block == last)(lambda: body(rest))


def _tile_rows(t, size: int, total: int):
    """Rows ``[t size, (t + 1) size)`` of a resident block of ``total``; a
    block that is one tile (the only way a tile is no multiple of the
    hardware's) is sliced statically."""
    if size == total:
        return slice(None)
    return pl.ds(pl.multiple_of(t * size, size), size)


def _for_tiles(lo, hi, body):
    """``body(t)`` for tiles ``lo <= t < hi``; the state lives in refs."""
    def step(t, carry):
        body(t)
        return carry
    jax.lax.fori_loop(lo, hi, step, 0)


def _one_ahead(n: int, products):
    """``(g, *products(g))`` for each head ``g < n`` of a backward body,
    ``products(g + 1)`` issued before head ``g``'s are handed out.  A body's
    matmuls go to the MXU in program order: a head's scores and ``dp`` wait
    for nothing, so they stand in front of the head BEFORE's exponentials
    and accumulations, which then find them done (module docstring)."""
    ahead = products(0)
    for g in range(n):
        now, ahead = ahead, products(g + 1) if g + 1 < n else None
        yield (g, *now)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fold_rows(x, op, reduce_rows):
    """``[n, cols]`` -> ``[8, cols]``: ``op`` over the rows that share a
    sublane, vector against vector, by halves (a 256-row tile is 15
    operations to trace and lower, not 63: the kernels are traced at every
    start of a program, compile cache or not); a tile that is not whole
    sublanes (a short sequence that is one tile) takes ``reduce_rows`` to
    one row."""
    if x.shape[0] % 8:
        return reduce_rows(x, axis=0, keepdims=True)
    while x.shape[0] % 16 == 0:
        half = x.shape[0] // 2
        x = op(x[:half], x[half:])
    return functools.reduce(op, [x[r:r + 8] for r in range(0, x.shape[0], 8)])


def _fold_height(n: int) -> int:
    """Rows `_fold_rows` leaves of ``n``."""
    return 1 if n % 8 else 8


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs_ref, acc_ref, m_ref,
                l_ref, *, p: Plan, sm_scale, causal, q_offset, dead_rows):
    """ONE pass over the live tiles of the resident k, v rows, the scores
    held ``[block_k, block_q]`` as the dkv kernel holds them: the keys run
    down the sublanes, so a tile's maxima and sums fold vector against
    vector into 8 rows (`_fold_rows`) and the statistics are rows
    ``[1, block_q]``, broadcast down the sublanes: no reduction across the
    lanes, no second pass, no sum on the MXU.  The accumulator is kept
    transposed, ``acc^T [d, block_q] = acc^T alpha + v^T e``, the tile as it
    lies under a contraction over the v tile's rows (Mosaic transposes the
    ``[block_k, d]`` tile on the transpose unit, which has nothing else to
    do; a ``v^T`` held in scratch was slower by what making it costs).  One
    transpose of all heads' ``acc^T`` at the end gives ``o``; ``lse`` is
    the row the result wants.  Every tile rescales by ``alpha``, across
    major blocks too.

    A tile's matmuls are issued in program order, so the FIRST matmul of
    every head comes before any head's exponentials: head by head the MXU
    and the vector units wait for each other, and the whole one-pass gain
    is in this order (module docstring: 0.587 -> 0.319 ms)."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    last_k = pl.num_programs(3) - 1
    bq, bk, d = p.block_q, p.block_k, p.d
    per_kv = p.hq // p.hk

    def run(nq):
        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            # all of it: a last block's heads outside the array stay zero,
            # and Pallas drops them
            acc_ref[...] = jnp.zeros_like(acc_ref)
            _fill_scaled(qs_ref, q_ref, nq, d, sm_scale)

        rel = _query_minus_key((bk, bq), 1) if causal else None

        def tile(t):
            rows = _tile_rows(t, bk, p.major_k)
            # visible: q_offset + qi bq + c >= (ki n + t) bk + r
            seen = (rel >= (ki * p.major_k + t * bk) - qi * bq - q_offset) \
                if causal else None
            scores = [_scores(_heads(k_ref, rows, g // per_kv, d), qs_ref[g],
                              sm_scale, seen) for g in range(nq)]  # [bk, bq]
            for g, st in enumerate(scores):
                head = slice(g * d, (g + 1) * d)
                m_prev = m_ref[g:g + 1, :]
                m_new = jnp.maximum(m_prev, jnp.max(
                    _fold_rows(st, jnp.maximum, jnp.max), axis=0,
                    keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                e = jnp.exp(st - m_new)
                if dead_rows:
                    # A query that sees no key so far (causal with s_q > s_kv:
                    # queries above the diagonal of their first k tile) has m
                    # == _NEG_INF, making exp(s - m) == 1 for every masked key
                    # — zero those instead of averaging V uniformly.
                    e = jnp.where(m_new <= _NEG_INF * 0.5, 0.0, e)
                m_ref[g:g + 1, :] = m_new
                l_ref[g] = l_ref[g] * alpha + _fold_rows(e, jnp.add, jnp.sum)
                acc_ref[head, :] = acc_ref[head, :] * alpha + _tn(
                    _heads(v_ref, rows, g // per_kv, d),
                    e.astype(v_ref.dtype))

        _for_tiles(0, _k_tiles(qi, ki, p, causal, q_offset), tile)

        @pl.when(ki == last_k)
        def _finish():
            for g in range(nq):
                head = slice(g * d, (g + 1) * d)
                m = m_ref[g:g + 1, :]
                l = jnp.sum(l_ref[g], axis=0, keepdims=True)
                if dead_rows:
                    # Dead rows (m still _NEG_INF) get lse = 0 so the backward
                    # kernels' exp(s - lse) = exp(_NEG_INF) underflows to zero
                    # gradient; the natural m + log(l) would be ~ -1e30 - 69,
                    # making s - lse positive.
                    lse = jnp.where(m <= _NEG_INF * 0.5, 0.0,
                                    m + jnp.log(jnp.maximum(l, 1e-30)))
                    l = jnp.where(l == 0.0, 1.0, l)
                else:
                    lse = m + jnp.log(l)
                lse_ref[0, 0, g:g + 1, :] = lse
                acc_ref[head, :] = acc_ref[head, :] / l
            o_ref[0] = jnp.transpose(acc_ref[...]).astype(o_ref.dtype)

    _inside(pl.program_id(1), p, run)


def _specs_walk_q(p: Plan, causal: bool, q_offset: int):
    """Block specs of the kernels whose grid is ``(batch, head block, q
    block, major k block)``: q-shaped, kv-shaped, statistics."""
    wq, wk = p.hq * p.d, p.hk * p.d
    # head blocks that share a kv head (hk == 1), else each has its own
    per_kv = p.h // p.h_kv // p.hq if p.hk == 1 else 1

    def kv_index(b_, hb, qi, ki):
        if causal:      # a dead block costs no transfer
            ki = jnp.minimum(ki, _last_live_k(qi, p, q_offset))
        return b_, ki, _div(hb, per_kv)

    q_spec = pl.BlockSpec((1, p.block_q, wq),
                          lambda b_, hb, qi, ki: (b_, qi, hb))
    kv_spec = pl.BlockSpec((1, p.major_k, wk), kv_index)
    row_spec = pl.BlockSpec((1, 1, p.hq, p.block_q),
                            lambda b_, hb, qi, ki: (b_, hb, 0, qi))
    return q_spec, kv_spec, row_spec


def _params(n_grid: int, n_arbitrary: int):
    sem = ("parallel",) * (n_grid - n_arbitrary) + ("arbitrary",) * n_arbitrary
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _flash_fwd(q, k, v, causal, sm_scale, p: Plan):
    """``q [b, s_q, h d]``, ``k``, ``v [b, s_kv, h_kv d]`` -> ``o`` like q
    and ``lse [b, head blocks, hq, s_q]`` float32.  Under `jax.jit` so that
    a program that holds the forward twice (the primal and the `custom_vjp`
    rule; a remat's repeat) traces the unrolled kernel body once."""
    b, s_q, _ = q.shape
    s_kv = k.shape[1]
    q_offset = s_kv - s_q
    grid = (b, p.head_blocks, s_q // p.block_q, s_kv // p.major_k)
    q_spec, kv_spec, row_spec = _specs_walk_q(p, causal, q_offset)
    kernel = functools.partial(
        _fwd_kernel, p=p, sm_scale=sm_scale, causal=causal,
        q_offset=q_offset, dead_rows=causal and s_q > s_kv)
    return pl.pallas_call(
        kernel,
        # the HLO instruction takes this name, and a profiler trace's
        # `XLA Ops` events are named by instruction: the kernel shows as
        # itself there and not as `checkpoint.19` (scope_probe, PERF.md)
        name="flash_attention_fwd",
        grid=grid,
        interpret=_interpret(),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        scratch_shapes=[
            pltpu.VMEM((p.hq, p.block_q, p.d), q.dtype),            # q scaled
            pltpu.VMEM((p.hq * p.d, p.block_q), jnp.float32),       # acc^T
            pltpu.VMEM((p.hq, p.block_q), jnp.float32),             # m
            pltpu.VMEM((p.hq, _fold_height(p.block_k), p.block_q),
                       jnp.float32),                                # l
        ],
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, p.head_blocks, p.hq, s_q), jnp.float32),
        ),
        compiler_params=_params(4, 1),
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   qs_ref, lse_col, delta_col, dq_acc, *, p: Plan, sm_scale,
                   causal, q_offset):
    qi, ki = pl.program_id(2), pl.program_id(3)
    last_k = pl.num_programs(3) - 1
    bq, bk, d = p.block_q, p.block_k, p.d
    per_kv = p.hq // p.hk

    def run(nq):
        @pl.when(ki == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)
            _fill_scaled(qs_ref, q_ref, nq, d, sm_scale)
            for g in range(nq):
                lse_col[g] = _row_to_col(lse_ref[0, 0, g:g + 1, :])[:, :1]
                delta_col[g] = _row_to_col(
                    delta_ref[0, 0, g:g + 1, :])[:, :1]

        rel = _query_minus_key((bq, bk), 0) if causal else None

        def tile(t):
            rows = _tile_rows(t, bk, p.major_k)
            seen = (rel >= (ki * p.major_k + t * bk) - qi * bq - q_offset) \
                if causal else None

            def products(g):    # a head's scores and dp, [bq, bk]
                n = g // per_kv
                return (_scores(qs_ref[g], _heads(k_ref, rows, n, d),
                                sm_scale, seen),
                        _nt(_heads(do_ref, slice(None), g, d),
                            _heads(v_ref, rows, n, d)))

            for g, s, dp in _one_ahead(nq, products):
                k = _heads(k_ref, rows, g // per_kv, d)
                e = jnp.exp(s - lse_col[g])
                ds = (e * (dp - delta_col[g])).astype(k.dtype)
                dq_acc[g] += _nn(ds, k)

        _for_tiles(0, _k_tiles(qi, ki, p, causal, q_offset), tile)

        @pl.when(ki == last_k)
        def _finish():
            for g in range(nq):
                dq_ref[0, :, g * d:(g + 1) * d] = (dq_acc[g] * sm_scale).astype(
                    dq_ref.dtype)

    _inside(pl.program_id(1), p, run)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *rest, p: Plan, sm_scale, causal,
                    q_offset, with_dq):
    """Scores transposed, ``[block_k, block_q]``: the statistics are rows
    as stored, and dv = p^T do, dk = ds^T q are plain matmuls.

    ``with_dq`` (`one_backward`: the whole query side resident, so the grid
    walks k blocks alone): dq is accumulated beside them, TRANSPOSED, in a
    float32 ``[hq x d, s_q]`` that lives across the k steps of a head block:
    ``dq^T[:, tile] += k^T ds``, a plain matmul of the tile as it lies
    against the k block transposed once a grid step.  Zeroed at the first k
    block, transposed back, scaled and written at the last.

    A head's two products that wait for nothing (its scores and its ``dp``)
    are issued ONE HEAD AHEAD (`_one_ahead`), its exponentials and its three
    accumulations in the chain's order: a body's matmuls go to the MXU in
    program order, and ``dp`` between ``dv +=`` and ``ds`` made the vector
    units wait for it behind ``dv``'s product (module docstring: 0.715 ->
    0.646 ms, all of the gain any order had).  Live at once: the next head's
    scores and ``dp`` beside this head's ``e``, ``dp`` and ``ds``, the six
    tiles `_block_bytes` counts; the accumulations run head by head, so the
    query heads of a kv head add to ``dk`` and ``dv`` in the order of
    ``g``."""
    ki, gi, qm = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    last_step = (gi == pl.num_programs(3) - 1) & (qm == pl.num_programs(4) - 1)
    bq, bk, d = p.block_q, p.block_k, p.d
    per_kv = p.hq // p.hk
    if with_dq:
        dq_ref, ks_ref, dk_acc, dv_acc, dq_acc, kt_ref = rest
    else:
        ks_ref, dk_acc, dv_acc = rest

    def run(nq):
        @pl.when((gi == 0) & (qm == 0))
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)
            _fill_scaled(ks_ref, k_ref, nq // per_kv, d, sm_scale)
            if with_dq:     # through float32: exact, and any tile transposes
                kt_ref[...] = jnp.transpose(
                    k_ref[0].astype(jnp.float32)).astype(kt_ref.dtype)

        if with_dq:
            @pl.when(ki == 0)
            def _init_dq():
                dq_acc[...] = jnp.zeros_like(dq_acc)

        rel = _query_minus_key((bk, bq), 1) if causal else None

        def tile(t):
            rows = _tile_rows(t, bq, p.major_q)
            seen = (rel >= ki * bk - (qm * p.major_q + t * bq) - q_offset) \
                if causal else None

            def products(g):    # a head's scores and dp, [bk, bq]
                n = g // per_kv
                return (_scores(ks_ref[n], _heads(q_ref, rows, g, d),
                                sm_scale, seen),
                        _nt(_heads(v_ref, slice(None), n, d),
                            _heads(do_ref, rows, g, d)))

            for g, s, dp in _one_ahead(nq, products):
                n = g // per_kv
                q = _heads(q_ref, rows, g, d)
                do = _heads(do_ref, rows, g, d)
                e = jnp.exp(s - lse_ref[0, 0, g:g + 1, rows])
                dv_acc[n] += _nn(e.astype(do.dtype), do)
                ds = (e * (dp - delta_ref[0, 0, g:g + 1, rows])).astype(q.dtype)
                dk_acc[n] += _nn(ds, q)
                if with_dq:
                    dq_acc[g * d:(g + 1) * d, rows] += _nn(
                        kt_ref[n * d:(n + 1) * d, :], ds)

        _for_tiles(_first_q_tile(ki, qm, p, causal, q_offset),
                   p.major_q // bq, tile)

        @pl.when(last_step)
        def _finish():
            for n in range(nq // per_kv):
                dk_ref[0, :, n * d:(n + 1) * d] = (dk_acc[n] * sm_scale).astype(
                    dk_ref.dtype)
                dv_ref[0, :, n * d:(n + 1) * d] = dv_acc[n].astype(dv_ref.dtype)

        if with_dq:
            @pl.when(ki == pl.num_programs(2) - 1)
            def _finish_dq():
                # the heads of a last block that lie outside the array were
                # zeroed and never added to; Pallas drops them
                dq_ref[0] = (jnp.transpose(dq_acc[...]) * sm_scale).astype(
                    dq_ref.dtype)

    _inside(pl.program_id(1), p, run)


def _bwd_dq(q, k, v, do, lse, delta, causal, sm_scale, p: Plan):
    b, s_q, _ = q.shape
    s_kv = k.shape[1]
    q_spec, kv_spec, row_spec = _specs_walk_q(p, causal, s_kv - s_q)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, p=p, sm_scale=sm_scale,
                          causal=causal, q_offset=s_kv - s_q),
        name="flash_attention_dq",
        grid=(b, p.head_blocks, s_q // p.block_q, s_kv // p.major_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((p.hq, p.block_q, p.d), q.dtype),
            pltpu.VMEM((p.hq, p.block_q, 1), jnp.float32),
            pltpu.VMEM((p.hq, p.block_q, 1), jnp.float32),
            pltpu.VMEM((p.hq, p.block_q, p.d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params(4, 1),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)


def _flash_bwd(q, k, v, o, lse, do, causal, sm_scale, p: Plan):
    b, s_q, _ = q.shape
    s_kv = k.shape[1]
    q_offset = s_kv - s_q
    # the row sums of do * o, laid out like lse: [b, head blocks, hq, s_q]
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, s_q, p.h, p.d), axis=-1)
    delta = jnp.pad(jnp.swapaxes(delta, 1, 2),
                    ((0, 0), (0, p.head_blocks * p.hq - p.h), (0, 0)))
    delta = delta.reshape(lse.shape)
    one = one_backward(p)

    # grid (batch, kv head block, k block, q head blocks of it, major q)
    def q_index(b_, h2, ki, g_, qm):
        if causal:      # a dead block costs no transfer
            qm = jnp.maximum(qm, _first_live_q(ki, p, q_offset))
        return b_, qm, h2 * p.q_steps + g_

    def row_index(b_, h2, ki, g_, qm):
        b_, qm, hb = q_index(b_, h2, ki, g_, qm)
        return b_, hb, 0, qm

    qd_spec = pl.BlockSpec((1, p.major_q, p.hq * p.d), q_index)
    k_spec = pl.BlockSpec((1, p.block_k, p.hk * p.d),
                          lambda b_, h2, ki, g_, qm: (b_, ki, h2))
    rows_spec = pl.BlockSpec((1, 1, p.hq, p.major_q), row_index)
    out_specs = [k_spec, k_spec]
    out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    scratch = [pltpu.VMEM((p.hk, p.block_k, p.d), k.dtype),
               pltpu.VMEM((p.hk, p.block_k, p.d), jnp.float32),
               pltpu.VMEM((p.hk, p.block_k, p.d), jnp.float32)]
    if one:
        # the whole query side of the head block, the same block at every k
        # step: written once a head block
        out_specs.append(pl.BlockSpec(
            (1, s_q, p.hq * p.d), lambda b_, h2, ki, g_, qm: (b_, 0, h2)))
        out_shape.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch += [pltpu.VMEM((p.hq * p.d, s_q), jnp.float32),
                    pltpu.VMEM((p.hk * p.d, p.block_k), k.dtype)]
    out = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, p=p, sm_scale=sm_scale,
                          causal=causal, q_offset=q_offset, with_dq=one),
        name="flash_attention_bwd" if one else "flash_attention_dkv",
        grid=(b, -(-p.h_kv // p.hk), s_kv // p.block_k, p.q_steps,
              s_q // p.major_q),
        in_specs=[qd_spec, k_spec, k_spec, qd_spec, rows_spec, rows_spec],
        out_specs=out_specs,
        scratch_shapes=scratch,
        out_shape=out_shape,
        # dq is summed along the k axis
        compiler_params=_params(5, 3 if one else 2),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    if one:
        dk, dv, dq = out
        return dq, dk, dv
    return (_bwd_dq(q, k, v, do, lse, delta, causal, sm_scale, p), *out)


# ---------------------------------------------------------------------------
# custom-vjp wrapper (operates on [b, s, heads x d])
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, plan):
    return _flash_fwd(q, k, v, causal, sm_scale, plan)[0]


def _flash_fwd_rule(q, k, v, causal, sm_scale, plan):
    o, lse = _flash_fwd(q, k, v, causal, sm_scale, plan)
    # the NAMED output is the primal too: once a checkpoint policy saves
    # both names, its recompute pass wants no output of the forward call
    o = checkpoint_name(o, FLASH_OUT)
    return o, (q, k, v, o, checkpoint_name(lse, FLASH_LSE))


def _flash_bwd_rule(causal, sm_scale, plan, res, do):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, do, causal, sm_scale, plan)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jnp.ndarray:
    """Fused attention over ``[batch, seq, heads, head_dim]`` inputs.

    KV heads may be a divisor of query heads (GQA/MQA).  Differentiable via
    flash backward kernels.  ``block_q`` / ``block_k`` (the score tile;
    `DEFAULT_BLOCK_Q` / `DEFAULT_BLOCK_K` when not given) are clamped
    (halving search) to the largest divisor of each seq length; raises
    where that is no tile the kernels walk (`tile_ok`: a multiple of 128
    rows, or the whole sequence) — use `multi_head_attention` for automatic
    fallback.
    """
    b, s_q, h, d = q.shape
    s_kv, h_kv = k.shape[1], k.shape[2]
    block_q = DEFAULT_BLOCK_Q if block_q is None else block_q
    block_k = DEFAULT_BLOCK_K if block_k is None else block_k
    bq, bk = fit_block(block_q, s_q), fit_block(block_k, s_kv)
    if not (tile_ok(bq, s_q) and tile_ok(bk, s_kv)):
        raise ValueError(
            f"seq lengths ({s_q}, {s_kv}) under blocks ({block_q}, "
            f"{block_k}) give tiles ({bq}, {bk}): a tile is a multiple of "
            f"{_LANES} rows or the whole sequence (8 rows or more)")
    if sm_scale is None:
        sm_scale = d ** -0.5
    # the kernels feed q/k/v straight into MXU dots in their storage dtype
    # (bf16 in + fp32 accumulation); normalize mixed-dtype inputs (e.g. an
    # fp32 query against a bf16 KV cache) to the query's dtype up front
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    plan = make_plan(h, h_kv, d, s_q, s_kv, q.dtype.itemsize, bq, bk)
    out = _flash(q.reshape(b, s_q, h * d), k.reshape(b, s_kv, h_kv * d),
                 v.reshape(b, s_kv, h_kv * d), causal, float(sm_scale), plan)
    return out.reshape(b, s_q, h, d)
