"""Autoregressive generation with a KV cache, fully jitted.

The serving-side decode path behind the BASELINE north star #5 (p50 TTFT
for TP-sharded replicas): prefill runs the prompt once and materializes
per-layer K/V into a fixed-capacity cache; each decode step then attends
one query position against the cache — O(seq) memory traffic instead of
O(seq²) recompute — and the whole prefill + N-step decode loop compiles
into two XLA programs (`prefill`, `lax.scan` of `decode_step`).  The
cache is a pytree of layer-stacked arrays, so pjit shards it with the
same logical rules as the parameters (heads → tp, batch → dp).

A cache holds up to EIGHT KINDS OF STATE behind the same functions
(`cache_rows`, `position_bytes`, `cache_bytes`, the slot insert and gather):
rows for the whole context (``full``), a ring of a window's rows (``ring``),
a conv layer's last inputs (``state``), a row a chunk of positions
(``summary``), an indexer's keys on the indexing layers alone (``index``), a
KDA layer's matrix of state a head beside its convolutions' last inputs
(``delta``), a state-space mixer's matrix of state a head beside its
convolution's (``ssm``), which ONE LAYER holds together with rows of the
first kind, and a selective scan's state beside its convolution's
(``mamba``); they follow one by one below.  A cache's arrays may differ in
TYPE (`array_dtype`): a delta state, a state-space state and a selective
scan's are float32 whatever the model computes in.  And a LAYER MAY HOLD
NOTHING: it reads what another layer holds (the last paragraphs).

What a cache holds is a property of the model's attention kind
(`cache_rows`): keys and values of ``kv_heads x head_dim`` (``"k"``,
``"v"``) for MHA/GQA, ONE array of compressed latents (``"kv"``: the
normed key-value latent beside the rotated shared key, ``kv_lora_rank +
qk_rope_head_dim`` values a position a layer) for latent attention.
Beside its arrays a cache carries ``pos``.  Every function here, the
serve engine and the prefix reuse work on whatever arrays a cache has
(`cache_arrays`); none names one.  Each array has a row of its own
(`cache_rows`: heads and width): a model whose window layers have other
key-value heads than its full layers, or values of another width than its
keys, holds arrays of as many shapes.

A cache holds TWO KINDS OF STATE where a model mixes window and full
layers (`TransformerConfig.layer_kinds`): the full layers' arrays
``[L_full, batch, heads, width, max_len]`` hold the whole context, the
window layers' (``k_win``, ``v_win``) are a RING ``[L_win, batch, heads,
width, ring]`` of ``sliding_window + window_chunk`` rows whatever
``max_len`` (`window_ring`): position ``p`` lives in column ``p mod ring``,
a chunk that straddles the seam is written in two pieces, and a column is
masked by the POSITION IT HOLDS (`_ring_mask`), which follows from the
last position written.  A LATENT-attention model's window layers hold a ring
too, of LATENTS (``kv_win`` ``[L_win, batch, 1, window_kv_lora_rank + rope,
ring]`` beside the full layers' ``kv`` and, of a model with an indexer, their
``k_idx``: three arrays, three row widths, two layer counters): a row of its
kind's own width (`TransformerConfig.latent_of`), a ring of whole 128-row
blocks whatever the window (513 + 128 rows are 768: the kernels that read
and write it take whole blocks, and the mask, not the ring's width, says
what is seen), read by the same absorbed forms and the same kernel as the
full layers' rows (`attend_cache` under `_ring_mask`).  The ring is wider than the window by the widest
chunk a program may feed, so whatever a program writes ahead of a row's
``pos`` (a padded chunk's tail, an inactive slot's token) overwrites only
positions that no later query's window reaches.
Both kinds sit behind the same functions, and each kind has a layer
counter of its own in the one layer loop.

A THIRD KIND OF STATE IS NOT POSITIONS AT ALL: a ``"conv"`` layer
(`ops/short_conv.py`) carries the last ``conv_kernel - 1`` inputs of its
convolution, ``[L_conv, batch, 1, conv_kernel - 1, d_model]`` (``*_state``;
channels last, where the device wants its lanes), whatever ``max_len``.
It sits behind the same functions too (`cache_arrays`, `cache_bytes`, the
slot insert and gather), but unlike a key or a value a state written AHEAD
of a row's ``pos`` is not harmless: there is no later write that repairs
it.  So every program advances a row's state by its VALID tokens only: a
padded chunk takes the carry-out at its last real token, a slot that is
not active keeps its state bit for bit, and what would need a state to be
taken back is refused (`_check_state_rewind`): a chunk window set back at
the cache's end.

A FOURTH KIND OF STATE HAS A ROW A CHUNK OF POSITIONS: a summary layer
(``"eva"``, `ops/eva_attention.py`) attends its own block-aligned window of
``sliding_window`` positions exactly and every earlier window through ONE
pooled key and value a chunk of ``summary_chunk`` positions, under one
softmax.  Its cache is a ring (``k_win``, ``v_win``: ``sliding_window +
window_chunk`` rows under a BLOCK mask, `_ring_mask`) beside summary arrays
(``k_sum``, ``v_sum``: ``[L_eva, batch, heads, width, max_len /
summary_chunk]``, `cache_rows`), masked by ``(j + 1) chunk <= (t // window)
window``.  A summary is a function of ring rows that are still held when its
chunk completes (``summary_chunk`` divides the window and the ring), so
EVERY program pools the chunks its new tokens reach from the ring as written
(`_summary_write`) and the mask hides a row until its whole window lies
behind the query: no branch on where a chunk ends, and, unlike a conv state,
a summary pooled over tokens written ahead of a row's ``pos`` is pooled
again by the program that feeds the true ones, so a chunk window set back
need not be refused for it.  A DECODE STEP (one query a slot) attends both
row sets by ONE kernel call a layer that fetches only the 128-row blocks a
slot's masks let it see (`ops/cache_attention.py` `attend_blocks`), and a
CHUNK PROGRAM (a chunk of queries a lane, the lanes program and the lone one
alike) by one that fetches the blocks SOME query of the lane's chunk sees
(`attend_chunk_blocks`; a full or window layer's one row set likewise,
`_attend_cached` `attend_mha`, unless a sink joins its softmax), wherever the
program is lowered for a TPU and the arrays' rows are whole blocks; every
other platform and shape takes the dense form under the same masks
(`ops/eva_attention.py` `attend_two`, `attend_mha`'s `heads`).

A FIFTH KIND OF STATE CHOOSES WHAT THE OTHERS' ROWS ARE READ FOR: a latent-
attention model with an indexer (`ops/sparse_index.py`; layer kinds
``"index"`` | ``"shared"``) holds, beside the latents ``"kv"`` of EVERY
layer, the indexer's keys of the INDEXING layers alone: ``k_idx``
``[L_index, batch, 1, index_head_dim, max_len]``, one key a position.  Every
cached program writes the new positions' index keys as it writes their
latents (the decode step through `ops/cache_write.py`, one call an array a
layer), scores the rows up to each query's own position, takes the exact
``index_topk`` best (`sparse_index.select`) and hands that choice down the
layer loop (the ``sel`` of `_scan_cached`'s carry) to the shared layers
behind the indexing one; each attends under the choice as its mask, a
query's own set of columns.  Only a position a query may SEE can be chosen
(the same mask as a full layer's: a column past a row's ``pos``, a padded
chunk's tail, an inactive slot's token, a former session's stale rows lie
outside it), so what a program writes ahead of ``pos`` is as harmless here
as in a full layer.

A SIXTH KIND OF STATE IS A MATRIX A HEAD: a ``"kda"`` layer
(`ops/delta_rule.py`, `transformer.kda_operator`) carries ``s_delta``
``[L_kda, batch, heads, dim, dim]`` in FLOAT32, which every token decays,
corrects and reads WHOLE, and the last ``kda_conv_kernel - 1`` inputs of its
three convolutions, ``conv_delta`` ``[L_kda, batch, 1, taps - 1, 3 x heads x
dim]`` in the model's type: 2 MB and 72 KB a slot a layer at 32 heads of
128, whatever ``max_len``.  It lives by the conv state's rules: every
program advances a row by its VALID tokens only (a padded chunk's tail
neither decays nor writes, a slot that is not active keeps both arrays bit
for bit), and `_check_state_rewind` refuses what would need it taken back.
A decode step and a chunk run the same recurrence in two forms
(`delta_rule.step`, `delta_rule.chunk`), and the fused step advances the
state in its layer of the donated array in place: where the states are
whole 128 x 128 tiles and the program is lowered for a TPU, in ONE kernel
call a layer over the stacked array (`delta_rule.step_in_place`: no cut of
the layer before it, no placement after it; a live slot's states read once
and written once, a standing slot's not at all; `CacheTraffic` counts it).

A SEVENTH KIND OF STATE STANDS BESIDE ROWS IN ONE LAYER: an ``"ssm+full"``
layer (`transformer.ssm_operator`, `ops/ssd.py`) runs a state-space mixer AND
full attention off one norm, so the same layer owns ``s_ssm`` ``[L, batch,
heads, state, dim]`` in FLOAT32 and ``conv_ssm`` ``[L, batch, 1, taps - 1,
channels]`` WITHOUT positions (4 MB and 30 KB a slot a layer at 32 heads of
128 with a state of 256, whatever ``max_len``) and keys and values ``k``,
``v`` WITH them (the first kind's arrays, one layer counter for all four).
Each half lives by its own kind's rules at once: the rows are written ahead
of ``pos`` harmlessly and masked by position, the state and the convolution's
inputs advance by a row's VALID tokens only, and `_check_state_rewind`
refuses a chunk window set back although the rows alone could run it again.
The fused step advances the state where it lies in one kernel call a layer
(`ssd.step_in_place`) wherever `delta_rule.step_in_place` would its own, and
`CacheTraffic` counts both.  Such a layer may not share a model with plain
``"full"`` layers (their rows would share an array under two counters:
`_check_decodable`).

AN EIGHTH KIND OF STATE IS A DECAY A CHANNEL A COLUMN: a ``"mamba"`` layer
(`transformer.mamba_operator`, `ops/selective_scan.py`: Mamba-1) carries
``s_mamba`` ``[L_mamba, batch, 1, state, channels]`` in FLOAT32 (channels
last, where the device wants its lanes) and the last ``taps - 1`` inputs of
its convolution, ``conv_mamba`` ``[L_mamba, batch, 1, taps - 1, channels]``
in the model's type: 328 KB and 31 KB a slot a layer at 5120 channels with a
state of 16, whatever ``max_len``.  It lives by the conv state's rules (a
row advances by its VALID tokens only, a standing slot keeps both arrays bit
for bit, `_check_state_rewind`, `prefix_holds` only from a donor that stands
AT the prefix).  A chunk scans by a kernel that holds the state on the chip
across the chunk's tokens (`selective_scan.chunk`), a step is an elementwise
update of the layer of the stack where it lies.  Beside its state the layer
makes a VALUE THAT LATER LAYERS CONSUME: its scan output ``m`` (before the
gate) rides down `_scan_cached`'s carry (`transformer.handed`) as an
indexer's choice does, and every ``"gmu"`` layer behind it gates it, position
by position: a gated memory unit reads no cache at all.

A ROW SET MAY HAVE MORE READERS THAN HOLDERS: a ``"cross"`` layer projects
queries of its own and attends the keys and values of the LAST FULL LAYER
before it, as that layer wrote them in the same program (its own new row
included): it holds no array, writes no column, and its layer counter in
`_scan_cached` is that full layer's.  So ``k`` / ``v`` have a leading axis
of ONE for eight readers, and `CacheTraffic` tells the layers that HOLD a
row set (its array's leading axis: capacity, the step's column writes) from
the layers that READ it (`_RowSet.layers`: a step's rows and bytes, the
blocks a kernel moves; ``shared_bytes_read``).  A step of such a model reads
the one array a reading layer each, so its one query a slot goes through
`ops/cache_attention.py` `attend_blocks` (`_shared_rows`): a slot's visible
blocks alone, where dense dots would read ``max_len`` rows of every slot
eight times.  Where an MHA/GQA block is DIFFERENTIAL (``diff_attn``) a cached
row holds a PAIR's two keys side by side (`TransformerConfig.key_dim`) and
its value is as wide: both softmax maps of a pair and its values come from
ONE pass over the blocks, the queries zero-padded each to its half of the
row (`transformer._pair_queries`) under the score scale of one head.

THE STATELESS TAIL: the layers BEHIND the last one that holds state
(`TransformerConfig.stateless_tail`: trailing ``"gmu"`` and ``"cross"``
layers) write nothing a later token reads.  A chunk program wants ONE row's
logits a lane (row ``n_valid - 1``), so `_prefill_chunk` and `_prefill_lanes`
run the layers up to the last state on all ``C`` rows and the tail on that
one row of the stream and of the memory alone (`_tail_on_one_row`: two spans
of the one layer loop): 14 of 32 layers on 1 row where they ran on 128.
Exact, and it follows from the layer kinds, not from a model's name.

A MODEL NEED HAVE NO FULL LAYER: where none holds ``max_len`` rows, the
summary arrays say it (`cache_capacity`, which then needs the model's
``summary_chunk``), and nothing stands in for a full layer.  What still
cannot be served is a model of window layers, conv layers or KDA layers
alone (`_check_decodable`).  A model with several prediction heads
(``pred_heads``) hands out every head's logits; a served token is drawn
from head 0's (`next_token_logits`).

Each array is stored ``[layers, batch, heads, width, rows]`` —
positions LAST — and every program that takes a cache extends it IN PLACE:
the whole stacked cache is state of the one layer loop
(:func:`_scan_cached`), written by ``dynamic_update_slice`` (a chunk's
columns: one slice an array a layer) or, where every slot writes one
column at a position of its own (the decode step), by ONE aliased kernel
call an array a layer (`ops/cache_write.py`: the 128-row block of each
slot that holds its column is read, changed and written back; never a
scatter, which is not updated in place under this layout) and held to
its row-major layout, so a caller that donates its cache pays no copy
at all.  Positions are last because that is the tiling a TPU gives the
array anyway: with ``head_dim`` 64 minor, 64 of 128 lanes (and 25 heads
of 32 sublanes) would be padding, so the device keeps ``max_len`` minor
whatever the logical order says, and a loop that wants another order
converts the whole cache on the way in and on the way out.

The cached programs of a latent-attention model attend in the ABSORBED
form (`ops/latent_attention.py`): the few queries of a chunk or a step
meet the cached latents directly, and wherever the program is lowered for a
TPU and the shapes are whole tiles they read them through ONE kernel call a
layer that walks the blocks a lane's chunk or a slot's one query may see,
where they lie (`attend_cache`, `_key_block`: a chunk, the lanes program and
the decode step alike; `CacheTraffic.step` and `.chunk` count what that
moves).  A model with no-drop routed experts
returns, beside its logits, what its expert layers routed (the ``load``
of `_prefill_chunk` and `_decode_step_slots`); the serve engine counts
the decode steps'.

Reference: Ray has no model runtime of its own (serving delegates to the
wrapped framework); this module is the TPU-native equivalent of what its
users bring via vLLM/TGI — sized to the in-tree transformer family.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental.layout import Layout, with_layout_constraint

from ..ops import cache_attention, delta_rule, ssd
from ..ops import eva_attention as eva
from ..ops import latent_attention as mla
from ..ops import sparse_index
from ..ops.attention import sink_softmax
from ..ops.cache_write import device_calls, write_columns
from ..ops.rotary import apply_rotary, rotary_angles
from ..ops.short_conv import conv_block, conv_inputs, short_conv
from .transformer import (ATTENTION_KINDS, READER_KINDS, SPARSE_KINDS,
                          SSM_KINDS, TransformerConfig, _attn_out, _ffn,
                          _layer, _norm, _post, _qkv, _scale_embedding,
                          _ssm_widths, _unembed, attention_scale, check_kinds,
                          cross_scope, gmu_operator, hand_on, handed,
                          head_gate, index_inputs, kda_operator,
                          latent_queries, latent_rows, latent_scope,
                          latent_weights, mamba_operator, no_load, norm_eps,
                          rope_tables, scan_layer_runs, shortcut,
                          ssm_operator)

Params = Any
# {<array>: [L, B, heads, width, max_len] for each of `cache_rows`, "pos"}
KVCache = Dict[str, jnp.ndarray]
Arrays = Dict[str, jnp.ndarray]     # a cache without its "pos"


_RING = "_win"      # suffix of a window layer's arrays: rings
_STATE = "_state"   # suffix of a conv layer's array: no positions at all
_SUMMARY = "_sum"   # suffix of a summary layer's arrays: a row a CHUNK
_INDEX = "_idx"     # suffix of an indexing layer's array: the indexer's keys
_DELTA = "_delta"   # suffix of a KDA layer's arrays: no positions either
_CONV_STATE = "conv" + _STATE
_DELTA_STATE = "s" + _DELTA         # a matrix a head, float32
_DELTA_CONV = "conv" + _DELTA       # its convolutions' last inputs
_SSM = "_ssm"       # suffix of a state-space mixer's arrays: no positions
_SSM_STATE = "s" + _SSM             # a matrix a head, float32
_SSM_CONV = "conv" + _SSM           # its convolution's last inputs
_SSM_ARRAYS = (_SSM_STATE, _SSM_CONV)
_MAMBA = "_mamba"   # suffix of a selective scan's arrays: no positions
_MAMBA_STATE = "s" + _MAMBA         # columns x channels a sequence, float32
_MAMBA_CONV = "conv" + _MAMBA       # its convolution's last inputs
#: the kinds of state that hold no positions: a sequence's whatever its length
_NO_POSITIONS = ("state", "delta", "ssm", "mamba")
_SUM_NAMES = ("k" + _SUMMARY, "v" + _SUMMARY)
#: rows a ring of latents is a whole number of (`window_ring`)
LATENT_RING_BLOCK = 128
_INDEX_ARRAY = "k" + _INDEX
#: the kinds of layer whose arrays hold a row a position for the whole
#: context, written and masked alike
_ROW_KINDS = ("full",) + SPARSE_KINDS + SSM_KINDS
#: ... and those that attend such rows under the same mask, their own or (a
#: ``"cross"`` layer) the last full layer's
_CONTEXT_KINDS = _ROW_KINDS + ("cross",)
#: the arrays that are a float32 matrix a head, whatever the model's type
_MATRIX_STATES = (_DELTA_STATE, _SSM_STATE, _MAMBA_STATE)


def cache_rows(cfg: TransformerConfig) -> Dict[str, Tuple[int, int]]:
    """What a cache of this model holds a position a layer: (heads,
    width) of each of its arrays.  Window layers have arrays of their own
    (rings, named ``*_win``) beside the full layers'; conv layers one
    (``*_state``) whose "positions" are the model's channels and whose
    width is the ``conv_kernel - 1`` inputs a sequence carries.  Each
    array has a row of its own: a kind's key-value heads
    (`TransformerConfig.kv_heads_of`), and a value's width beside a key's."""
    state = {_CONV_STATE: (1, cfg.conv_kernel - 1)} \
        if "conv" in cfg.kinds else {}
    if "kda" in cfg.kinds:      # (key dims, value dims last) a head; taps
        state.update({_DELTA_STATE: (cfg.kda_heads, cfg.kda_head_dim),
                      _DELTA_CONV: (1, cfg.kda_conv_kernel - 1)})
    if set(cfg.kinds) & set(SSM_KINDS):     # (state, dims last) a head; taps
        state.update({_SSM_STATE: (cfg.ssm_heads, cfg.ssm_state),
                      _SSM_CONV: (1, cfg.ssm_conv_kernel - 1)})
    if "mamba" in cfg.kinds:    # (columns, channels last); taps
        state.update({_MAMBA_STATE: (1, cfg.mamba_state),
                      _MAMBA_CONV: (1, cfg.mamba_conv_kernel - 1)})
    if cfg.attention == "mla":
        rows = dict(state)
        for kind in ("full", "window"):     # a kind's own latent beside
            ck = cfg.latent_of(kind)        # the shared rotary key
            if kind != "window" or "window" in cfg.kinds:
                rows[_latent_name(kind)] = (
                    1, ck.kv_lora_rank + ck.qk_rope_head_dim)
        if "index" in cfg.kinds:    # ONE key a position, no value
            rows[_INDEX_ARRAY] = (1, cfg.index_head_dim)
        return rows
    rows = {}
    for kind in ATTENTION_KINDS:
        if kind in cfg.kinds and kind not in READER_KINDS:  # (a reader
            # holds nothing: it attends the rows a full layer holds)
            hk = cfg.kv_heads_of(kind)
            row = ((hk, cfg.key_dim), (hk, cfg.value_dim))
            rows.update(zip(_kv_names(kind), row))
            if kind == "eva":   # a pooled key and value a chunk, as wide
                rows.update(zip(_SUM_NAMES, row))
    return dict(rows, **state)


def position_bytes(cfg: TransformerConfig) -> Dict[str, int]:
    """Bytes ONE layer of each state kind holds a position a sequence
    (``full``, ``ring``; ``index``, of a model with an indexer), a chunk of
    positions (``summary``, of a model that has summaries), or a sequence
    whatever its positions (``state``:
    a conv layer's ``conv_kernel - 1`` inputs of ``d_model``; ``delta``: a
    KDA layer's float32 matrix a head and its convolutions' inputs; ``ssm``:
    a state-space mixer's, likewise): what a
    decode step reads of a row it attends, by the row's kind, each array at
    its own element size."""
    out = {"full": 0, "ring": 0, "state": 0}
    for name, (heads, width) in cache_rows(cfg).items():
        kind = _state_kind(name)
        out[kind] = out.get(kind, 0) + heads * width \
            * jnp.dtype(array_dtype(cfg, name)).itemsize \
            * (_own_rows(cfg, name) or 1)
    return out


def array_dtype(cfg: TransformerConfig, name: str):
    """The element type of the cache array ``name``: the model's, but a
    delta state's and a state-space state's float32 (a state held in less
    is another result)."""
    return jnp.float32 if name in _MATRIX_STATES else cfg.dtype


def _own_rows(cfg: TransformerConfig, name: str) -> Optional[int]:
    """The last axis of an array that holds no positions (None: it does):
    a convolution's channels, a matrix state's value dims."""
    return {_CONV_STATE: cfg.d_model, _DELTA_STATE: cfg.kda_head_dim,
            _DELTA_CONV: 3 * cfg.kda_heads * cfg.kda_head_dim,
            _SSM_STATE: cfg.ssm_head_dim,
            _SSM_CONV: _ssm_widths(cfg)[1],
            _MAMBA_STATE: cfg.mamba_inner,
            _MAMBA_CONV: cfg.mamba_inner}.get(name)


def _latent_name(kind: str) -> str:
    """The latents' array of a latent layer of kind ``kind``: a window
    layer's is a ring of its own row width."""
    return "kv" + _RING if kind == "window" else "kv"


def _kv_names(kind: str) -> Tuple[str, str]:
    """The key and value arrays of a layer of attention kind ``kind``."""
    return ("k", "v") if kind in _CONTEXT_KINDS \
        else ("k" + _RING, "v" + _RING)


def window_ring(cfg: TransformerConfig, max_len: int) -> int:
    """Rows of a window layer's ring: the window and the widest chunk a
    program may write ahead of it, and no more than the context.  A ring of
    LATENTS is whole blocks of `LATENT_RING_BLOCK` rows (the kernels that
    read and write it take whole blocks, and a window need be no multiple
    of anything): the mask, not the ring's width, says what is seen."""
    rows = cfg.sliding_window + cfg.window_chunk
    if cfg.attention == "mla":
        rows = -(-rows // LATENT_RING_BLOCK) * LATENT_RING_BLOCK
    return min(max_len, rows)


def cache_arrays(cache: KVCache) -> Arrays:
    """The cache's arrays, whatever the attention kind named them."""
    return {name: a for name, a in cache.items() if name != "pos"}


def cache_capacity(cache: KVCache,
                   cfg: Optional[TransformerConfig] = None) -> int:
    """``max_len``: the positions a cache holds per row (what its full
    layers' arrays hold; a ring is shorter, a state holds none).  A cache
    with NO full layer in it has a row a ``cfg.summary_chunk`` positions in
    its summaries: they say it, and need ``cfg`` for it."""
    arrays = cache_arrays(cache)
    if _SUM_NAMES[0] in arrays and "k" not in arrays:
        return arrays[_SUM_NAMES[0]].shape[-1] * cfg.summary_chunk
    return max(a.shape[-1] for name, a in arrays.items()
               if _state_kind(name) not in _NO_POSITIONS)


def cache_bytes(cache: KVCache) -> Dict[str, int]:
    """Bytes of a cache's arrays by state kind: ``full`` (rows for the
    whole context), ``ring`` (window layers), ``state`` (conv layers) and,
    where the cache has them, ``summary`` (a row a chunk), ``index`` (an
    indexer's keys), ``delta`` (KDA layers: float32 states and the
    convolutions' inputs) and ``ssm`` (state-space mixers: the same)."""
    out = {"full": 0, "ring": 0, "state": 0}
    for name, a in cache_arrays(cache).items():
        kind = _state_kind(name)
        out[kind] = out.get(kind, 0) + int(a.nbytes)
    return out


def _chunk_sets(cfg: TransformerConfig, arrays: Arrays, kind: str, b: int,
                c: int):
    """What `ops/cache_attention.py` `kernel_shape` is asked about a layer
    of attention kind ``kind`` whose ``b`` rows feed ``c`` tokens each: the
    queries' shape and the kind's row sets (keys, values, no mask), its own
    arrays and, of a summary layer, the summaries beside them."""
    hk = cfg.kv_heads_of(kind)
    sets = [tuple(arrays[n] for n in _kv_names(kind)) + (None,)]
    if kind == "eva":
        sets.append(tuple(arrays[n] for n in _SUM_NAMES) + (None,))
    return (b, c, hk, cfg.n_heads // hk, cfg.key_dim), sets


def _blocks_moved(cfg: TransformerConfig, arrays: Arrays, kind: str, c: int,
                  size: int):
    """Where a program that feeds ``c`` tokens a row attends the MHA/GQA
    layers of attention kind ``kind`` through `ops/cache_attention.py`'s
    kernel ON THIS PROCESS'S BACKEND (what `_attend_cached` decides by the
    shapes where the program is lowered: a summary layer's step and chunks,
    a full or a window layer's chunks alone) → ``moved(first, n)``: the rows
    it moves of a set of ``size`` rows to attend the ``n`` from ``first`` on,
    the whole blocks that hold one, the host's count of the kernel's work
    list; None where dense dots read the set."""
    if (c > 1 or kind == "eva" or kind in _shared_rows(cfg)) \
            and cache_attention.engages(
                *_chunk_sets(cfg, arrays, kind, 1, c),
                kind in cfg.sink_kinds):
        return lambda first, n: cache_attention.BLOCK \
            * cache_attention.fetched_blocks(first % size, n, size)
    return None


def _shared_rows(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The kinds of layer whose rows MORE LAYERS READ THAN HOLD (a full
    layer's, where ``"cross"`` layers attend them too): a decode step of
    such a model reads them a reading layer each, so its one query a slot
    goes through the kernel that fetches a slot's visible blocks alone
    (`ops/cache_attention.py` `attend_blocks`), as a summary layer's does."""
    return ("full", "cross") if "cross" in cfg.kinds else ()


def _tiles_moved(cfg: TransformerConfig, kv, kind: str, c: int):
    """Where a program that feeds ``c`` tokens a row reads the latents ``kv``
    of the layers of kind ``kind`` through `ops/latent_attention.py`
    `attend_cache` ON THIS PROCESS'S BACKEND (`mla.engages`, what
    `_key_block` and `mla.on_the_chip` decide where the program is lowered) →
    ``moved(first, n)``: the rows it moves to attend the ``n`` from ``first``
    on, the kernel's tiles up to the last column some query sees (what
    `sparse_index.rows_seen` reads off the program's mask: the position, or
    `_ring_end`); None where XLA's forms read every row."""
    ck, size = cfg.latent_of(kind), kv.shape[-1]
    q_shape = (kv.shape[1], c, ck.n_heads, kv.shape[-2])
    block = _key_block(q_shape, ck.kv_lora_rank, size)
    if not mla.engages(q_shape, ck.kv_lora_rank, block):
        return None
    tile = mla.row_tile(q_shape, block)
    return lambda first, n: mla.fetched_rows(
        _ring_end(first, n, size) if kind == "window" else first + n, tile)


def _ring_end(first: int, n: int, size: int) -> int:
    """One past the last column of a ring of ``size`` rows that holds one of
    the ``n`` positions from ``first`` on: the last one's, unless the range
    lies astride the seam: the ring's end then."""
    last = first + n - 1
    return last % size + 1 if first // size == last // size else size


class _RowSet(NamedTuple):
    """ONE set of rows a layer kind's queries attend: an array of keys with
    the values beside it, of latents, of an indexer's keys, a state's taps."""
    kind: str       # `position_bytes`' state kind of a row of it
    sees: str       # which of its rows a query sees (`CacheTraffic._seen`)
    layers: int     # the layers that READ it (those that hold it: its array)
    size: int       # rows a slot a layer
    most: int       # ... of which a query attends no more than (a choice)
    # rows a kernel moves of it for what a step's query / a chunk's queries
    # see (`_blocks_moved`, `_tiles_moved`); None: dense dots, every row
    step: Optional[Callable[[int, int], int]]
    chunk: Optional[Callable[[int, int], int]]


#: layer kind → the sets of rows it attends, each (state kind of the row,
#: what a query at position ``t`` sees of the set, `CacheTraffic._seen`):
#: every position up to its own (``context``; under an indexer the
#: ``index_topk`` chosen of them, but ONE index key of each), the last
#: ``sliding_window`` (``window``), its own block of ``sliding_window``
#: (``block``) and a summary a ``summary_chunk`` of every block before
#: (``pooled``), a state's taps whatever the position (``taps``).  A delta
#: or a state-space STATE is no rows: `CacheTraffic.step`'s ``state_*``.
_ATTENDS = {
    **dict.fromkeys(_ROW_KINDS, (("full", "context"),)),
    "index": (("full", "context"), ("index", "context")),
    "window": (("ring", "window"),),
    "eva": (("ring", "block"), ("summary", "pooled")),
    "conv": (("state", "taps"),),
    "kda": (),
    # a selective scan's state is no rows either; a gated memory unit reads
    # no cache at all; a cross layer attends the rows a FULL layer holds
    "mamba": (),
    "gmu": (),
    "cross": (("full", "context"),),
}


class StepSums(NamedTuple):
    """What ONE fused decode step reads, moves and writes of the cache
    (`CacheTraffic.step`)."""
    # the cache rows the live slots attend, each at its position BEFORE the
    # step, its own new row included, beside what they would attend were
    # every layer a full one
    rows_read: int
    rows_if_full: int
    # the same in bytes: a row at what its layer's kind holds a position (an
    # indexer's keys among them), beside every layer's rows at the widest of
    # the model's (a model of one kind of row reads 100 %)
    bytes_read: int
    bytes_if_uniform: int
    # of both, the part that is summary rows
    summary_rows_read: int
    summary_bytes_read: int
    # the index keys an indexer scored (NOT among ``rows_read``), their bytes
    index_rows_read: int
    index_bytes_read: int
    # of ``bytes_read``, a LATENT model's ring rows
    ring_latent_bytes_read: int
    # what a delta or a state-space state costs: the (slot, layer) states
    # advanced, their bytes read AND written (not among ``bytes_read``: no
    # position is attended; a layer that holds rows beside its state has
    # those above), and what the rule's form on this backend MOVES for that
    state_rows: int
    state_bytes_moved: int
    state_bytes_fetched: int
    # the rows the attention MOVES from memory to attend ``rows_read`` of
    # them (an indexer's keys are counted by neither)
    rows_fetched: int
    # what a step WRITES, whatever its positions: a column a slot, live or
    # not, a layer of every array that holds positions, and the device calls
    # that write them (one kernel call an array a layer, or a slice a column)
    column_writes: int
    column_write_calls: int
    # of ``bytes_read``, the rows of a set that more layers READ than HOLD (a
    # full layer's rows that ``"cross"`` layers attend too), a reader each
    shared_bytes_read: int


class CacheTraffic:
    """What the programs over ONE slot cache read, move and write of it, by
    state kind: host counts from shapes and positions (``cache`` may be the
    arrays or, `jax.eval_shape`, their shapes), made where the cache's layout
    is known so that the serve engine, whose ``cache:rows`` and
    ``engine:lanes`` spans carry them, knows none of it.  ``chunk``: the rows
    a chunk program feeds a lane.

    A table with a row a SET of rows a layer kind attends (`_ATTENDS`,
    `_RowSet`): its layers, which of its rows a query sees (`_seen`: ONE rule
    for a step's rows, a chunk's rows and what a kernel moves for either),
    `position_bytes`' width of a row, and the kernel that reads it ON THIS
    PROCESS'S BACKEND, if one does: a program's choice by shape and platform,
    asked here of the same functions."""

    #: what `step` sums, in its order
    STEP_SUMS = StepSums._fields

    def __init__(self, cache: KVCache, cfg: TransformerConfig, chunk: int):
        arrays, per = cache_arrays(cache), position_bytes(cfg)
        latent = cfg.attention == "mla"
        self._window, self._pooled = cfg.sliding_window, cfg.summary_chunk
        self._chunk, self._layers = chunk, cfg.n_layers
        self._tail = cfg.stateless_tail
        self._slots = next(iter(arrays.values())).shape[1]
        self._widest = max(per.get(k, 0) for k in ("full", "ring", "summary"))
        self._latent = latent
        self._sets: Dict[str, _RowSet] = {}     # by the array that holds it
        self._row_bytes: Dict[str, int] = {}    # by state kind
        self._shared: Tuple[str, ...] = ()      # state kinds of the sets
        #   that more layers read than hold
        for kind in dict.fromkeys(cfg.kinds):
            rows_in = _latent_name(kind) if latent else _kv_names(kind)[0]
            for state, sees in _ATTENDS[kind]:
                rows = state in ("full", "ring")
                name = rows_in if rows else {
                    "summary": _SUM_NAMES[0], "state": _CONV_STATE,
                    "index": _INDEX_ARRAY}[state]
                if name in self._sets:      # (an array the row kinds share:
                    # one more kind's layers read it)
                    s = self._sets[name]
                    self._sets[name] = s._replace(
                        layers=s.layers + cfg.kinds.count(kind))
                    if self._sets[name].layers > arrays[name].shape[0]:
                        self._shared += (state,)
                    continue
                a = arrays[name]
                size = a.shape[-2 if sees == "taps" else -1]
                if latent and rows:     # a kind's own latent sizes
                    moved = [_tiles_moved(
                        cfg, a, "window" if state == "ring" else "full", c)
                        for c in (1, chunk)]
                elif rows or state == "summary":
                    moved = [_blocks_moved(cfg, arrays, kind, c, size)
                             for c in (1, chunk)]
                else:
                    moved = [None, None]
                self._sets[name] = _RowSet(
                    state, sees, cfg.kinds.count(kind), size,
                    cfg.index_topk if state == "full" and cfg.index_topk
                    else size, *moved)
                # (a state's bytes are its taps' together)
                self._row_bytes[state] = per[state] // (
                    size if sees == "taps" else 1)
        # a matrix state a head and its convolutions' inputs, read whole and
        # written whole whatever the position: (its layers, bytes a slot a
        # layer).  Where the rule's kernel (`ops/delta_rule.py`, `ops/ssd.py`)
        # engages on this process's backend a live slot's states are moved
        # once read and once written and a standing slot's not at all; in
        # XLA's form every slot's, live or not, is read twice (as it lowered
        # the delta rule: the products, then the decay and the write) and
        # written once
        self._state_layers = self._state_bytes = 0
        self._in_place = self._standing = 0
        for name, engages in ((_DELTA_STATE, delta_rule.engages), (
                _SSM_STATE, functools.partial(ssd.engages,
                                              groups=cfg.ssm_groups)),
                (_MAMBA_STATE, lambda *_: False)):  # (XLA's form alone)
            if name in arrays:
                layers = arrays[name].shape[0]
                held = layers * per[_state_kind(name)]
                self._state_layers += layers
                self._state_bytes += held
                if engages(1, arrays[name]):
                    self._in_place += 2 * held
                else:
                    self._standing += 3 * self._slots * held
        # one fed token a slot: the columns a step writes, and the device
        # calls that write them on this process's backend
        shapes = [a.shape for name, a in arrays.items()
                  if _state_kind(name) not in _NO_POSITIONS]
        self._writes = (
            sum(shape[0] * shape[1] for shape in shapes),
            sum(shape[0] * device_calls(shape) for shape in shapes))

    def _seen(self, s: _RowSet, positions, n: int):
        """(first rows, row counts), one of each a position: the range of
        the set ``s`` that SOME query of the ``n`` tokens fed from that
        position sees (it wraps where the set is a ring)."""
        window = self._window
        if s.sees == "window":
            firsts = [max(0, pos - window + 1) for pos in positions]
        elif s.sees == "block":
            firsts = [pos // window * window for pos in positions]
        else:
            return [0] * len(positions), [
                pos + n if s.sees == "context" else s.size if s.sees == "taps"
                else (pos + n - 1) // window * window // self._pooled
                for pos in positions]
        return firsts, [pos + n - first
                        for pos, first in zip(positions, firsts)]

    def step(self, positions) -> StepSums:
        """ONE fused decode step whose live slots stand at ``positions``."""
        live = len(positions)
        depth = sum(positions) + live
        read, fetched = {}, 0     # (rows attended by state kind: a set each)
        for s in self._sets.values():
            firsts, counts = self._seen(s, positions, 1)
            read[s.kind] = s.layers * (
                sum(counts) if s.most == s.size
                else sum(min(n, s.most) for n in counts))
            if s.kind != "index":
                fetched += s.layers * (
                    self._slots * s.size if s.step is None
                    else sum(map(s.step, firsts, counts)))
        nbytes = {kind: n * self._row_bytes[kind] for kind, n in read.items()}
        scored = read.pop("index", 0)
        return StepSums(
            sum(read.values()), self._layers * depth,
            sum(nbytes.values()), self._layers * depth * self._widest,
            read.get("summary", 0), nbytes.get("summary", 0),
            scored, nbytes.get("index", 0),
            nbytes.get("ring", 0) if self._latent else 0,
            live * self._state_layers, 2 * live * self._state_bytes,
            live * self._in_place + self._standing, fetched, *self._writes,
            sum(nbytes.get(kind, 0) for kind in self._shared))

    def chunk(self, pos: int, n_valid: int) -> Tuple[int, int]:
        """ONE lane of a chunk program that feeds ``n_valid`` real tokens
        from position ``pos`` → (the cache rows its attention MOVES from
        memory: every row of the lane's arrays a layer under dense dots,
        what the kernel's work list holds for the WHOLE chunk, padded rows
        too, where one engages; those of them that some REAL query of the
        chunk sees), a key and the value beside it one row, summed over the
        layers as `step` counts them.  Under an indexer's choice both are of
        the rows the queries MAY see, the most a choice reaches."""
        fetched = read = 0
        for s in self._sets.values():
            if s.kind in ("full", "ring", "summary"):
                read += s.layers * self._seen(s, (pos,), n_valid)[1][0]
                (first,), (n,) = self._seen(s, (pos,), self._chunk)
                fetched += s.layers * (
                    s.size if s.chunk is None else s.chunk(first, n))
        return fetched, read


    def tail_rows(self, lanes: int) -> int:
        """The rows on which ONE chunk program of ``lanes`` lanes runs the
        model's stateless tail (`TransformerConfig.stateless_tail`: the
        layers behind the last one that holds state): one a lane, the row
        whose logits the program hands out, of the ``lanes x chunk`` it
        feeds; 0 for a model without such a tail."""
        return lanes * (1 if self._chunk > 1 else self._chunk) \
            if self._tail else 0


def chunk_room(cfg: TransformerConfig, max_len: int) -> Tuple[int, int]:
    """(the positions a chunk program's window may cover: the cache's, and
    no more than a learned position table; the widest chunk a program may
    feed: no more than the room a ring leaves beside its window)."""
    capacity = min(max_len, cfg.max_seq_len) \
        if cfg.pos_emb == "learned" else max_len
    return capacity, min(capacity, cfg.window_chunk) \
        if {"window", "eva"} & set(cfg.kinds) else capacity


def prefix_holds(cfg: TransformerConfig, donor_pos: Optional[int],
                 depth: int, n: int, chunk: int, capacity: int) -> bool:
    """Whether a slot whose session stands at ``donor_pos`` (None: nobody
    knows) still holds what a session of ``n`` prompt tokens, seeded with the
    slot's first ``depth`` positions and fed ``chunk`` tokens a program over
    ``capacity`` positions, attends.

    A FULL layer's rows below ``depth`` are never rewritten: any donor
    serves.  A WINDOW layer's ring has moved on with the donor: whatever was
    written there (ahead of its position included) left the positions ``>=
    pos - sliding_window`` intact.  So a donor whose whole context still fits
    its window serves any prefix, and one that stands at the prefix (it has
    decoded no more than one token past it) serves a session whose chunk
    windows start at ``depth`` or later: the seeded session's first query
    needs the positions from ``depth - sliding_window + 1`` on, and a window
    set back at the capacity edge (`chunk_window`) would need earlier ones.
    A STATE without positions (a conv layer's, a KDA layer's, a state-space
    mixer's whatever rows its layer holds beside it) is the donor's at its
    LAST token and nothing of it can be masked: such a donor serves a prefix
    only while it STANDS at it (it has decoded nothing past it), and none of
    the seeded session's chunk windows, which start at ``depth`` and not at a
    multiple of the chunk, may be set back (a state cannot run tokens twice).
    A SUMMARY layer has both: the summaries of the chunks below ``depth`` are
    never rewritten (a donor writes the row of the chunk its newest token
    lies in and no earlier one), and the rows from ``depth``'s chunk on are
    the seeded session's own to write before any of its queries sees them;
    its ring holds the rows of the prefix's LAST block (what the seeded
    session's first queries attend exactly, and what its first summary is
    pooled from) only while the donor still stands in that block, and a
    prefix that ends on a block's edge needs no ring row at all; either way
    the first chunk window must not be set back before ``depth``: it would
    need the block before.  Any other donor is refused, and the prompt
    prefills from its start."""
    kinds = set(cfg.kinds)
    window = cfg.sliding_window if "window" in kinds else 0
    stateful = kinds & {"conv", "kda", "mamba", *SSM_KINDS}
    if not window and not stateful and "eva" not in kinds:
        return True
    if donor_pos is None:
        return False
    if "eva" in kinds:
        block = cfg.sliding_window
        return depth + chunk <= capacity and (
            depth % block == 0 or donor_pos // block == depth // block)
    if stateful and (donor_pos != depth or
                     depth + -(-(n - depth) // chunk) * chunk > capacity):
        return False
    return not window or donor_pos <= window or (
        donor_pos <= depth + 1 and depth + chunk <= capacity)


def _state_kind(name: str) -> str:
    """``full`` | ``ring`` | ``state`` | ``summary`` | ``index`` |
    ``delta`` | ``ssm``: what kind of state an array is."""
    for suffix, kind in ((_RING, "ring"), (_STATE, "state"),
                         (_SUMMARY, "summary"), (_INDEX, "index"),
                         (_DELTA, "delta"), (_SSM, "ssm"),
                         (_MAMBA, "mamba")):
        if name.endswith(suffix):
            return kind
    return "full"


def _init_cache(cfg: TransformerConfig, batch: int, max_len: int,
                pos: jnp.ndarray) -> KVCache:
    cache = {}
    if "eva" in cfg.kinds and max_len % cfg.summary_chunk:
        raise ValueError(f"max_len {max_len} is no whole number of chunks "
                         f"of {cfg.summary_chunk} positions")
    # (layers that hold the kind, what its arrays have for positions)
    stacks = {"full": (_ROW_KINDS, max_len),
              "index": (("index",), max_len),
              "ring": (("window", "eva"), window_ring(cfg, max_len)),
              "state": (("conv",), None), "delta": (("kda",), None),
              "ssm": (SSM_KINDS, None), "mamba": (("mamba",), None),
              "summary": (("eva",), max_len // max(1, cfg.summary_chunk))}
    for name, (heads, width) in cache_rows(cfg).items():
        kinds, rows = stacks[_state_kind(name)]
        cache[name] = jnp.zeros(
            (sum(k in kinds for k in cfg.kinds), batch, heads, width,
             _own_rows(cfg, name) or rows), array_dtype(cfg, name))
    cache["pos"] = pos
    return cache


def init_kv_cache(cfg: TransformerConfig, batch: int,
                  max_len: int) -> KVCache:
    return _init_cache(cfg, batch, max_len, jnp.zeros((), jnp.int32))


def _check_decodable(cfg: TransformerConfig) -> None:
    """A cache is one stacked array a state kind over ALL layers of the
    pattern that hold that kind, walked by one layer counter a kind: what
    cannot be served is a model cut into pipeline stages, each of which
    would own a slab of it, and what a ring cannot hold."""
    if cfg.pp_stages > 1:
        raise NotImplementedError(
            "KV-cache decode over a pipeline mesh is not supported; "
            "serve pp-sharded models stage-per-gang instead")
    if cfg.attention == "mla" and cfg.pos_emb == "learned":
        raise NotImplementedError(
            "latent attention keeps a shared key beside its latent, turned "
            "or as projected: pos_emb is 'rope' or 'none', not a learned "
            "table")
    kinds = set(cfg.kinds)
    if len(cfg.kinds) != cfg.n_layers or \
            kinds - {"full", "window", "conv", "eva", "kda", "mamba",
                     *READER_KINDS, *SPARSE_KINDS, *SSM_KINDS}:
        raise ValueError(f"layer_kinds {cfg.layer_kinds!r}: expected "
                         f"{cfg.n_layers} of 'full' | 'window' | 'conv' | "
                         f"'eva' | 'kda' | 'ssm+full' | 'mamba' | 'gmu' | "
                         f"'cross', or of 'index' | 'shared'")
    if kinds & set(SSM_KINDS) and "full" in kinds:
        raise NotImplementedError(
            "a model that mixes 'full' and 'ssm+full' layers is not served: "
            "their rows would share one array under two layer counters")
    check_kinds(cfg)
    if "conv" in kinds and cfg.conv_kernel < 2:
        raise ValueError("conv layers need conv_kernel of at least 2")
    if not kinds & {"full", "eva", "index", *SSM_KINDS}:
        raise NotImplementedError(
            "a model without a full-attention layer or a summary layer (of "
            "window layers only, of conv or KDA layers, of those) is not "
            "served: "
            "the rows a session may reach (max_len) are read off a full "
            "layer's array or a summary layer's, and neither a ring nor a "
            "state has them")
    if "eva" in kinds:
        c = cfg.summary_chunk
        if cfg.attention != "mha" or "window" in kinds or cfg.split_kv \
                or "eva" in cfg.sink_kinds:
            raise NotImplementedError(
                "summary layers are MHA/GQA layers with one ring to a "
                "model: no latent cache, no sliding-window layer beside "
                "them, no sink")
        if c < 1 or cfg.sliding_window < 1 or cfg.sliding_window % c \
                or cfg.window_chunk < 1 or cfg.window_chunk % c:
            raise ValueError(
                f"summary layers pool whole chunks: summary_chunk {c} has "
                f"to divide sliding_window {cfg.sliding_window} and "
                f"window_chunk {cfg.window_chunk} (a chunk's rows never "
                f"straddle the ring's seam)")
    if cfg.attention == "mla" and (
            cfg.split_kv or cfg.sink_kinds or cfg.value_scale != 1.0
            or cfg.rope_fraction != 1.0):
        raise NotImplementedError(
            "key-value heads by layer kind, a sink, a value scale and a "
            "rotated share of a head are MHA/GQA's; latent attention has "
            "none of them")
    for kind in kinds - {"conv", "kda", "mamba", "gmu"}:
        if cfg.attention == "mha" and cfg.n_heads % cfg.kv_heads_of(kind):
            raise ValueError(
                f"{cfg.n_heads} query heads over {cfg.kv_heads_of(kind)} "
                f"key-value heads of a {kind} layer")
    if cfg.pos_emb == "rope" and cfg.rope_dim % 2:
        raise ValueError(f"rotary over {cfg.rope_dim} dims of a head: "
                         f"pairs need an even number")
    if "window" in kinds:
        if cfg.sliding_window < 1 or cfg.window_chunk < 1:
            raise ValueError("window layers need sliding_window and "
                             "window_chunk of at least 1")


def _check_chunk(cfg: TransformerConfig, c: int) -> None:
    """What a ring cannot serve is refused, not answered wrongly: a
    program that feeds more new tokens a row than the ring is wider than
    the window would overwrite positions its own queries still see."""
    if {"window", "eva"} & set(cfg.kinds) and c > cfg.window_chunk:
        raise ValueError(
            f"a cached program of {c} new tokens a row over window layers "
            f"whose ring leaves room for window_chunk={cfg.window_chunk}: "
            f"the ring would lose positions the chunk still attends")


def _check_state_rewind(cfg: TransformerConfig, what: str) -> None:
    """What would need a conv layer's, a KDA layer's or a state-space
    mixer's state taken BACK is refused, not answered wrongly: a state has
    no position to mask and no later write that repairs it, so tokens fed
    twice (a chunk window set back at the cache's end) have already shifted
    it; the rows a layer may hold beside such a state do not help it."""
    if {"conv", "kda", "mamba", *SSM_KINDS} & set(cfg.kinds):
        raise ValueError(
            f"{what} over conv, KDA, state-space or selective-scan layers: "
            f"their state "
            f"cannot be taken back to an earlier token (models/generate.py)")


@jax.named_scope("attention")
def _ring_mask(pos, c: int, ring: int, window: int,
               block: bool = False) -> jnp.ndarray:
    """``pos`` [...] first new position a row, ``c`` new tokens a row →
    [..., c, ring] bool: ring column visible to each new token.  After the
    write the last position held is ``top = pos + c - 1`` and column j
    holds the latest position <= top that is j mod ring (negative: never
    written); token i at ``pos + i`` sees what lies at or before it and
    inside its window: the last ``window`` positions, or, with ``block``
    (a summary layer), its own block-aligned ``window`` of them."""
    pos = jnp.asarray(pos)
    top = (pos + (c - 1))[..., None]
    held = top - (top - jnp.arange(ring)) % ring              # [..., ring]
    q = (pos[..., None] + jnp.arange(c))[..., None]           # [..., c, 1]
    held = held[..., None, :]
    if block:
        return eva.block_mask(q, held, window)
    return (held >= 0) & (held <= q) & (q - held < window)


@jax.named_scope("attention")
def _eva_masks(cfg: TransformerConfig, pos, c: int,
               max_len: int) -> Dict[str, jnp.ndarray]:
    """A summary layer's two masks for ``c`` new tokens a row from ``pos``
    [...]: ``"eva"`` [..., c, ring] over its ring (the token's own block)
    and ``"summary"`` [..., c, max_len / chunk] over the summaries (the
    chunks of the blocks before it)."""
    pos = jnp.asarray(pos)
    return {"eva": _ring_mask(pos, c, window_ring(cfg, max_len),
                              cfg.sliding_window, block=True),
            "summary": eva.summary_mask(
                pos[..., None] + jnp.arange(c), max_len // cfg.summary_chunk,
                cfg.sliding_window, cfg.summary_chunk)}


def _summary_write(cfg: TransformerConfig, pos, c: int, ring: int,
                   lane=None, live=None):
    """The summaries of the chunks that the ``c`` new tokens from the scalar
    ``pos`` (batch rows ``lane`` on; every array's batch rows where ``lane``
    is None) reach, in two halves for `_write_summaries`, which pools every
    row's chunks at once: → ``(read, place)``, ``read(arrs, l, kc, vc) ->
    (keys, values)`` [b, heads, width, n, chunk] of the ``n = ceil(c /
    chunk)`` chunks from the first new token's, and ``place(arrs, l, kbar,
    vbar [b, heads, width, n]) -> {k_sum, v_sum}`` from row ``pos // chunk``
    on.

    A summary is a function of ring rows that are still held when its chunk
    completes, so the chunk a program's newest token lies in is summarised
    on EVERY program, no branch on where it ends: until the chunk is
    complete (and its whole block behind every query) the mask hides the
    row, and what was pooled over tokens written ahead of a row's ``pos``
    (a padded chunk's tail, a slot that is not active) is pooled again by
    the program that writes the true ones.  What lies before
    the new tokens in their first chunk is read from the RING AS
    WRITTEN (``arrs`` holds the rings after their write), ONE slice that
    cannot straddle the seam (``chunk`` divides the ring); the rest are the
    program's own new columns ``kc``, ``vc`` [B, heads, width, c].  A chunk
    that the new tokens only begin is the next program's.  ``live`` as
    `_full_write_chunk`'s."""
    ch = cfg.summary_chunk
    n = -(-c // ch)
    first = pos // ch
    whole = lane is None
    lane = 0 if whole else lane

    def read(arrs, l, *cols):
        def chunks(r_all, new):
            new = new if whole else new[lane:lane + 1]
            got = jax.lax.dynamic_slice(
                r_all, (l, lane, 0, 0, (first * ch) % ring),
                (1,) + new.shape[:-1] + (ch,))[0]
            if c > 1:       # the new tokens go on past the slice's end
                got = jax.lax.dynamic_update_slice(jnp.concatenate(
                    [got, jnp.zeros(got.shape[:-1] + (n * ch,), got.dtype)],
                    axis=-1), new.astype(got.dtype),
                    (0, 0, 0, pos % ch))[..., :n * ch]
            return got.reshape(got.shape[:-1] + (n, ch))

        return tuple(chunks(arrs[name], new)
                     for name, new in zip(_kv_names("eva"), cols))

    def place(arrs, l, *pooled):
        out = {}
        for name, new in zip(_SUM_NAMES, pooled):
            s_all = arrs[name]
            new = new.astype(s_all.dtype)[None]
            at = (l, lane, 0, 0, first)
            if live is not None:
                new = jnp.where(live, new, jax.lax.dynamic_slice(
                    s_all, at, new.shape))
            out[name] = jax.lax.dynamic_update_slice(s_all, new, at)
        return out

    return read, place


def _summary_write_slots(cfg: TransformerConfig, pos, ring: int):
    """`_summary_write` of ONE new token a slot, for slots that stand at
    positions of their OWN (``pos`` [S]): every slot's chunk read by its
    own slice, all slots' rows (``pos // chunk``) placed by ONE
    `ops.cache_write.write_columns` an array (a slot at the cache's end is
    clamped onto the last row, which no query ever sees)."""
    reads = [_summary_write(cfg, pos[slot], 1, ring, slot)[0]
             for slot in range(pos.shape[0])]

    def read(arrs, l, *cols):
        return tuple(jnp.concatenate(x, axis=0) for x in zip(
            *(one(arrs, l, *cols) for one in reads)))

    def place(arrs, l, *pooled):                # [S, heads, width, 1]
        return {name: write_columns(arrs[name], l, new[..., 0],
                                    pos // cfg.summary_chunk)
                for name, new in zip(_SUM_NAMES, pooled)}

    return read, place


@jax.named_scope("cache_write")
@jax.named_scope("summary")
def _write_summaries(rows, arrs: Arrays, l, kc, vc, phi, mu) -> Arrays:
    """``rows``: `_summary_write`'s two halves for each part of the batch
    (one for the whole of it, or one a batch row where rows stand at
    positions of their own) → the summary arrays with every part's chunks
    in them: all read, POOLED AT ONCE (`ops.eva_attention.pool_chunks`: a
    few operations a layer however many rows), then placed part by part."""
    got = [read(arrs, l, kc, vc) for read, _ in rows]
    pooled = eva.pool_chunks(*(jnp.concatenate(x, axis=0)
                               for x in zip(*got)), phi, mu)
    at = 0
    for (_, place), (k, _) in zip(rows, got):
        b = k.shape[0]
        arrs = dict(arrs, **place(arrs, l,
                                  *(x[at:at + b] for x in pooled)))
        at += b
    return {name: arrs[name] for name in _SUM_NAMES}


def _full_write_chunk(pos, lane=0, live=None):
    """→ ``write(c_all, l, cols [1 | B, heads, width, c])``: the chunk's
    columns as ONE slice from column ``pos`` of a full layer's array, from
    batch row ``lane`` on.  ``live`` (a traced bool; None: always) False
    leaves the array bit for bit as it was: the lane stands."""

    @jax.named_scope("cache_write")
    def write(c_all, l, cols):
        at = (l, lane, 0, 0, pos)
        if live is not None:
            cols = jnp.where(live, cols, jax.lax.dynamic_slice(
                c_all, at, (1,) + cols.shape)[0])
        return jax.lax.dynamic_update_slice(c_all, cols[None], at)

    return write


def _ring_write_chunk(pos, c: int, ring: int, lane=0, live=None):
    """→ ``write(c_all, l, cols [1 | B, heads, width, c])`` for a chunk whose
    rows all start at the scalar ``pos`` (batch rows ``lane`` on): column
    ``(pos + i) mod ring`` gets token i.  A chunk that straddles the seam
    cannot be one slice, and a branch on it would copy the ring, so EVERY
    chunk is two read-modify-write slices of ``c`` columns: one that ends no
    later than the seam, one that starts at column 0 (it rewrites what is
    there where the chunk does not wrap).  ``live`` as `_full_write_chunk`'s:
    False and no column takes a token."""
    r = pos % ring

    @jax.named_scope("cache_write")
    def write(c_all, l, cols):
        if c == 1 and live is None:
            return jax.lax.dynamic_update_slice(c_all, cols[None],
                                                (l, lane, 0, 0, r))
        if c > ring:
            raise ValueError(f"chunk of {c} over a ring of {ring} rows")
        twice = jnp.concatenate([cols, cols], axis=-1)
        o = jnp.arange(c)
        a = jnp.minimum(r, ring - c)
        # (first ring column of the slice, token at its offset 0 mod c,
        #  offsets that take a token)
        for start, k, takes in ((a, (a - r) % c, o >= r - a),
                                (0, (ring - r) % c, o + (ring - r) < c)):
            if live is not None:
                takes = takes & live
            new = jax.lax.dynamic_slice_in_dim(twice, k, c, axis=-1)[None]
            old = jax.lax.dynamic_slice(
                c_all, (l, lane, 0, 0, start), new.shape)
            c_all = jax.lax.dynamic_update_slice(
                c_all, jnp.where(takes, new, old), (l, lane, 0, 0, start))
        return c_all

    return write


def _scan_cached(cfg: TransformerConfig, params: Params, x: jnp.ndarray,
                 cache: KVCache, layer_fn, sel=(), span=None):
    """THE layer loop of every program that writes a KV cache.

    The whole stacked cache (each array ``[L, B, heads, width, rows]``)
    is loop STATE, indexed by the layer counter of its state kind, and the
    layer weights the scanned input: ``layer_fn(x, lp, arrays, l, kind, sel)
    -> (x, arrays, load, sel)`` writes its columns into layer ``l`` of its
    kind's arrays in place (``l`` counts the layers of that kind: a full
    layer's arrays and a window layer's rings are stacked apart; an
    indexing and a shared layer share the latents' array and one count).
    ``sel`` is what ONE LAYER COMPUTES FOR THE LAYERS BEHIND IT, carried
    beside the rest: of a model with an indexer ``(the chosen positions a
    query [B, C, rows] bool, the indexing layers so far)``, the second the
    layer counter of the indexer's keys; of any other model nothing.  Passing
    the cache's layers through the scan as inputs and stacking them as
    outputs instead builds a second cache per call, and leaves a donated
    cache argument nothing to alias to.  The carry is held to the
    row-major layout the cache arrives in: left to itself the compiler
    gives loop state the layout its rows are produced in (``head_dim``
    minor) and converts the whole cache before and after the loop.  The
    loop itself is the model's declared pattern (`scan_layer_runs`): the
    counter runs on from one run of layers into the next.  A ``"cross"``
    layer is handed the count of the LAST FULL layer before it: it reads that
    layer's rows and has none.  ``span`` (first layer, one past the last;
    None: all) runs that part of the loop alone, every counter standing where
    the layers before left it (`_tail_on_one_row`) and what the layers hand
    on handed back too.
    → (x, arrays, load summed over layers[, sel])."""
    arrays = cache_arrays(cache)
    row_major = Layout(major_to_minor=tuple(range(5)))

    def step(carry, lp, kind):
        xc, arrs, seen, load, sel = carry
        arrs = {n: with_layout_constraint(a, row_major)
                for n, a in arrs.items()}
        l = sum(seen[k] for k in SPARSE_KINDS if k in seen) \
            if kind in SPARSE_KINDS else seen["full"] - 1 \
            if kind == "cross" else seen[kind]
        xc, arrs, load_l, sel = layer_fn(xc, lp, arrs, l, kind, sel)
        return (xc, arrs, dict(seen, **{kind: seen[kind] + 1}),
                tuple(a + b for a, b in zip(load, load_l)), sel)

    zero = jnp.zeros((), jnp.int32)
    if span is not None:    # the layers before the span, by kind
        before = cfg.kinds[:span[0]]
        x, arrays, _, load, sel = scan_layer_runs(
            cfg, params,
            (x, arrays, {k: zero + before.count(k)
                         for k in sorted(set(cfg.kinds))},
             (zero,) * cfg.load_counts, sel),
            step, whole_expert_stacks=True, span=span)
        return x, arrays, load, sel
    x, arrays, _, load, _ = scan_layer_runs(
        cfg, params,
        (x, arrays, dict.fromkeys(sorted(set(cfg.kinds)), zero),
         (zero,) * cfg.load_counts, sel), step, whole_expert_stacks=True)
    return x, arrays, load


@jax.named_scope("cache_write")
def _as_columns(rows: jnp.ndarray, dtype) -> jnp.ndarray:
    """New tokens' K or V [B, C, hk, hd] → cache columns [B, hk, hd, C]."""
    return jnp.transpose(rows, (0, 2, 3, 1)).astype(dtype)


def _layer_of(c_all: jnp.ndarray, l) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(c_all, l, 0, keepdims=False)


@jax.named_scope("cache_write")
def _place_state(s_all: jnp.ndarray, l, state: jnp.ndarray) -> jnp.ndarray:
    """Every row's state [B, taps - 1, D] into conv layer ``l``."""
    return jax.lax.dynamic_update_slice(
        s_all, state[None, :, None].astype(s_all.dtype), (l, 0, 0, 0, 0))


@jax.named_scope("cache_write")
def _place_delta(arrs: Arrays, l, state: jnp.ndarray, conv: jnp.ndarray,
                 names=(_DELTA_STATE, _DELTA_CONV)) -> Arrays:
    """Every row's matrix state [B, heads, dim, dim] and convolution inputs
    [B, taps - 1, channels] into layer ``l`` of the arrays ``names`` (a KDA
    layer's; a state-space mixer's: ``_SSM_STATE``, ``_SSM_CONV``)."""
    s_name, c_name = names
    return dict(arrs, **{
        s_name: jax.lax.dynamic_update_slice(
            arrs[s_name], state[None].astype(jnp.float32), (l, 0, 0, 0, 0)),
        c_name: _place_state(arrs[c_name], l, conv)})


def _rotators(turn, angles: Dict[str, Any]) -> Dict[str, Any]:
    """`rope_tables`' ``{kind: (cos, sin)}`` -> ``{kind: t -> turn(t, cos,
    sin)}``: what `_attend_cached` takes as ``rotate``."""
    return {kind: functools.partial(turn, cos=cos, sin=sin)
            for kind, (cos, sin) in angles.items()}


def _key_block(q_shape: Tuple[int, ...], kv_lora: int, rows: int) -> int:
    """The block of cached rows a latent layer's queries ``[b, c, heads, ·]``
    read at a time through ONE KERNEL CALL a layer over the state array where
    it lies (`ops/latent_attention.py` `attend_cache`), whatever the number
    of queries a row, a decode step's one included: `sparse_index.key_block`
    of a cache whose ``rows`` are worth blocking, 0 where they are not or the
    shapes are no whole tiles (`kernel_shape`: a chunk's heads that fill no
    head tile).  The SHAPE answers here and the PLATFORM where the program is
    lowered (`mla.on_the_chip`): anywhere but on a TPU, and at a shape the
    kernel refuses, the layer reads through XLA's forms, which ask their own
    question of their own scores (`sparse_index.loop_block`)."""
    block = sparse_index.key_block(rows)
    return block if mla.kernel_shape(q_shape, kv_lora, block) else 0


@jax.named_scope("attention")
def _lane_of(c_all: jnp.ndarray, l, lane: int) -> jnp.ndarray:
    """Batch row ``lane`` of layer ``l``: [1, heads, width, rows], held to
    the cache's own row-major layout: a COPY of the lane's rows (63 MB a
    lane a layer of the byte cell's rings and summaries), which only the
    forms that no kernel serves still pay (`_by_lane`).  Left to itself the
    compiler cuts the row out inside the dot that reads it and hands that
    dot the WHOLE cache in the layout it would like: a copy of the cache a
    lane (v5e compiler, heads of 128)."""
    return with_layout_constraint(
        jax.lax.dynamic_slice(c_all, (l, lane, 0, 0, 0),
                              (1, 1) + c_all.shape[2:])[0],
        Layout(major_to_minor=tuple(range(4))))


@jax.named_scope("attention")
def _by_lane(live: jnp.ndarray, attend, operands) -> jnp.ndarray:
    """``attend(*operands(lane)) -> [1, ...]`` for every batch row whose
    ``live`` [B] is set, zeros for the others, stacked [B, ...]: a row that
    stands costs its operands alone (`lax.cond`), and what ``attend`` builds
    on the way (a chunk's float32 scores) is one row's at a time.  The
    DENSE path of the lanes program: a CPU's, a latent layer's loop, a layer
    with a sink, shapes that are no whole blocks; everything else attends
    through a kernel's list of the lane's blocks.  The operands are cut out
    of the batch OUTSIDE the branch: a branch handed the whole cache is
    handed it in the layout its dot would like, a copy of the cache (v5e
    compiler, key-value heads of 128)."""
    rows = [operands(lane) for lane in range(live.shape[0])]
    like = jax.eval_shape(attend, *rows[0])
    return jnp.concatenate([jax.lax.cond(
        live[lane], attend,
        lambda *_: jnp.zeros(like.shape, like.dtype), *row)
        for lane, row in enumerate(rows)], axis=0)


def _attend_cached(cfg: TransformerConfig, params: Params, x: jnp.ndarray,
                   cache: KVCache, *, rotate, write, mask, valid=None,
                   n_new=None, lanes=None, span=None, sel=None):
    """Run ``x`` [B, C, D] (C new tokens per row) through every layer
    against the cache: each attention layer writes the new tokens' columns
    (``write[kind](c_all, l, cols [B, heads, width, C]) -> c_all``) into
    each of its state kind's arrays, then attends layer ``l`` of them
    under ``mask[kind]`` [B|1, C, rows] (dense, or where
    `ops/cache_attention.py` has a kernel for the shapes and the program
    is lowered for a TPU, the 128-row blocks the mask shows a row's queries,
    where they lie); ``write`` and ``mask``
    are keyed by the layer's attention kind (``"full"``, ``"window"``,
    ``"eva"``; a summary layer has ``"summary"`` beside its own: the mask
    over its summary rows, and `_summary_write`'s halves for
    `_write_summaries`).  An ``"index"`` layer writes its indexer's keys
    too, scores the rows ``mask[kind]`` lets a query see, and attends the
    ``index_topk`` best of them, as do the ``"shared"`` layers behind it:
    the selection IS their mask, a query's own set of columns.
    A conv layer reads its state, and advances it by ``n_new`` [B] tokens
    (None: all C): the row's real tokens, none for a row that stands.
    ``rotate[kind]`` applies the caller's rotary angles at that kind's
    base (absent: the kind rotates nothing); ``valid`` [B, C] marks the
    rows a no-drop expert layer routes (None: all).  ``lanes`` [B] bool
    (None: the whole batch in one piece) makes the ATTENTION, the one part
    that reads no weight, a batch row's at a time and only where it is set
    (a kernel's list of a lane's blocks: `ops/cache_attention.py`
    `attend_chunk_blocks`, a latent layer's blocked read
    `ops/latent_attention.py` `attend_cache`; `_by_lane` elsewhere);
    everything that reads a weight still runs once over the ``B x C``
    stacked rows.  A ``"mamba"`` layer advances its state as a conv layer
    its own and hands its scan output down the loop to the ``"gmu"`` layers
    behind it; a ``"cross"`` layer writes nothing and attends layer ``l`` of
    the FULL layers' arrays, the last full layer's (`_scan_cached`).
    ``span`` runs a part of the layers alone, from what the part before
    handed on (``sel``), and hands back its own: `_tail_on_one_row`.
    → (final-norm activations, arrays, load) or, of a ``span``, (the stream
    behind it, final-normed behind the last layer; arrays; load; sel)."""
    dt = cfg.dtype
    b, c, _ = x.shape
    h, hd, kd = cfg.n_heads, cfg.head_dim, cfg.key_dim
    eps = norm_eps(cfg)
    shared = _shared_rows(cfg)
    # (a differential pair's key row is two heads wide, a score one head's)
    scaled = {} if attention_scale(cfg) is None \
        else {"scale": attention_scale(cfg)}

    def attend_mha(y, lp, arrs, l, kind, depth=None):
        kn, vn = _kv_names(kind)
        hk = cfg.kv_heads_of(kind)      # the layer's kind's, as its arrays'
        q, k_new, v_new = _qkv(cfg, y, lp, rotate.get(kind), kind)
        if kind == "cross":     # the rows as the last full layer wrote them
            k_all, v_all = arrs[kn], arrs[vn]
        else:
            k_all = write[kind](arrs[kn], l,
                                _as_columns(k_new, arrs[kn].dtype))
            v_all = write[kind](arrs[vn], l,
                                _as_columns(v_new, arrs[vn].dtype))
        sink = lp["sink"].reshape(hk, h // hk, 1) \
            if kind in cfg.sink_kinds else None

        @jax.named_scope("attention")
        def heads(q, ck, cv, m):     # the batch's rows, or one of them
            # GQA: group query heads over kv heads
            qh = q.reshape(-1, c, hk, h // hk, kd)
            scores = jnp.einsum("bskgd,bkdt->bskgt", qh,
                                ck.astype(dt)) / jnp.sqrt(float(hd))
            scores = jnp.where(m[:, :, None, None, :], scores, -1e30)
            probs = sink_softmax(scores.astype(jnp.float32), sink)
            attn = jnp.einsum("bskgt,bkdt->bskgd", probs.astype(dt),
                              cv.astype(dt))
            return attn.reshape(-1, c, h, cv.shape[-2])

        def dense(q, k_all, v_all, m, *live):
            # every row of the arrays, under the mask: a lane cut out at a
            # time (`_by_lane`), or the batch whole
            if lanes is None:
                return heads(q, _layer_of(k_all, l), _layer_of(v_all, l), m)
            return _by_lane(live[0], heads, lambda p: (
                q[p:p + 1], _lane_of(k_all, l, p), _lane_of(v_all, l, p),
                m[p:p + 1]))

        def in_place(q, k_all, v_all, m, *live):
            # a chunk's queries a lane: the blocks some query of the lane
            # sees, where they lie
            return cache_attention.attend_chunk_blocks(
                q.reshape(-1, c, hk, h // hk, kd), [(k_all, v_all, m)], l,
                *live, **scaled).reshape(-1, c, h, v_all.shape[-2])

        def one_query(q, k_all, v_all, m, *live):
            # one query a row over rows that several layers read: the blocks
            # the row sees, where they lie
            return cache_attention.attend_blocks(
                q.reshape(-1, 1, hk, h // hk, kd), [(k_all, v_all, m)], l,
                *live, **scaled).reshape(-1, 1, h, v_all.shape[-2])

        operands = (q, k_all, v_all, mask[kind]) \
            + (() if lanes is None else (lanes,))
        if c > 1 and cache_attention.kernel_shape(
                *_chunk_sets(cfg, arrs, kind, b, c), sink is not None):
            attn = mla.on_the_chip(in_place, dense, *operands)
        elif c == 1 and kind in shared and cache_attention.kernel_shape(
                *_chunk_sets(cfg, arrs, kind, b, c)):
            # (a row whose token is not real stands: its result is thrown
            # away, so nothing of its cache is fetched for it)
            live = (lanes,) if lanes is not None else \
                (valid[:, 0],) if valid is not None else ()
            attn = mla.on_the_chip(one_query, dense, *operands[:4], *live)
        else:
            attn = dense(*operands)
        return (_attn_out(cfg, y, attn, lp, depth),
                dict(arrs, **{kn: k_all, vn: v_all}))

    def attend_eva(y, lp, arrs, l, kind):
        # the new tokens into the ring, the chunks they reach pooled from
        # the ring as written, then ONE softmax over the ring's rows (the
        # token's own block) and the summaries (the blocks before it)
        kn, vn = _kv_names(kind)
        hk = cfg.kv_heads_of(kind)
        q, k_new, v_new = _qkv(cfg, y, lp, rotate.get(kind), kind)
        kc = _as_columns(k_new, arrs[kn].dtype)
        vc = _as_columns(v_new, arrs[vn].dtype)
        arrs = dict(arrs, **{kn: write[kind](arrs[kn], l, kc),
                             vn: write[kind](arrs[vn], l, vc)})
        arrs.update(_write_summaries(write["summary"], arrs, l, kc, vc,
                                     lp["adaptive_phi"],
                                     lp["adaptive_mu_k"]))
        names = (kn, vn) + _SUM_NAMES
        masks = (mask[kind], mask["summary"])
        qh = q.reshape(-1, c, hk, h // hk, hd)

        def dense(qh, k_a, v_a, k_b, v_b, m_a, m_b, *live):
            # every row of both sets, under the masks: a lane cut out at a
            # time (`_by_lane`), or the batch whole
            if lanes is None:
                return eva.attend_two(qh, *(_layer_of(a, l) for a in (
                    k_a, v_a, k_b, v_b)), m_a, m_b)
            return _by_lane(live[0], eva.attend_two, lambda p: (
                qh[p:p + 1],
                *(_lane_of(a, l, p) for a in (k_a, v_a, k_b, v_b)),
                m_a[p:p + 1], m_b[p:p + 1]))

        def in_place(qh, k_a, v_a, k_b, v_b, m_a, m_b, *live):
            # the blocks a slot's one query sees, or some query of a
            # lane's chunk, where they lie
            attend = cache_attention.attend_blocks if c == 1 \
                else cache_attention.attend_chunk_blocks
            return attend(qh, [(k_a, v_a, m_a), (k_b, v_b, m_b)], l, *live)

        # (a step's row whose token is not real stands: its result is
        # thrown away, so nothing of its cache is fetched for it)
        live = (lanes,) if lanes is not None else \
            (valid[:, 0],) if c == 1 and valid is not None else ()
        operands = (qh, *(arrs[n] for n in names), *masks, *live)
        if cache_attention.kernel_shape(*_chunk_sets(cfg, arrs, kind, b, c)):
            attn = mla.on_the_chip(in_place, dense, *operands)
        else:
            attn = dense(*operands)
        return _attn_out(cfg, y, attn.reshape(b, c, h, -1), lp), arrs

    def attend_mla(y, lp, arrs, l, kind, sel):
        # absorbed: the chunk's few queries over the cached latents, a
        # window layer's at its own sizes over its ring (`latent_of`)
        turn = rotate.get(kind, mla.no_turn)
        ck, lw, name = (cfg.latent_of(kind), latent_weights(cfg, lp, kind),
                        _latent_name(kind))
        heads, kv_lora = ck.n_heads, ck.kv_lora_rank
        q_nope, q_rope, c_q = latent_queries(ck, y, lw, turn, kind)
        new = latent_rows(ck, y, lw, turn, kind)
        kv_all = write[kind](arrs[name], l, _as_columns(
            new[:, :, None, :], arrs[name].dtype))
        arrs = dict(arrs, **{name: kv_all})
        seen = mask[kind]
        rows = kv_all.shape[-1]
        block = _key_block(q_nope.shape, kv_lora, rows)
        if kind == "index":
            # the new positions' index keys, every visible row scored, the
            # exact best chosen: this layer's mask and the shared layers'
            _, li = sel
            q_i, k_i, w = index_inputs(cfg, y, c_q, lp, rotate[kind])
            ik_all = write[kind](arrs[_INDEX_ARRAY], li, _as_columns(
                k_i[:, :, None, :], arrs[_INDEX_ARRAY].dtype))
            arrs[_INDEX_ARRAY] = ik_all
            choose = functools.partial(
                sparse_index.selection_mask, topk=cfg.index_topk,
                blocked=bool(sparse_index.loop_block(c, cfg.index_heads,
                                                     rows)))
            if lanes is None:
                chosen = choose(q_i, w, _layer_of(ik_all, li)[:, 0], seen)
            else:
                chosen = _by_lane(lanes, choose, lambda p: (
                    q_i[p:p + 1], w[p:p + 1], _lane_of(ik_all, li, p)[:, 0],
                    seen[p:p + 1]))
            sel = (chosen, li + 1)
        if kind in SPARSE_KINDS:
            seen = sel[0]
        nope = ck.qk_nope_head_dim
        scale = float(np.sqrt(nope + ck.qk_rope_head_dim))
        gate = head_gate(cfg, y, lw)    # a value a head, or None

        def loops(q_nope, q_rope, kv_all, seen, wkv_b, wo, *live):
            # XLA's forms: all rows at once, or `_attend_blocks`' loop
            whole = sparse_index.loop_block(c, heads, rows)
            if lanes is None:
                return mla.attend_absorbed(
                    q_nope, q_rope, _layer_of(kv_all, l)[:, 0], wkv_b, wo,
                    seen, whole, gate)
            q_abs = mla.absorb(q_nope, q_rope, wkv_b)
            return mla.unabsorb(_by_lane(
                live[0], functools.partial(mla.attend_latents, scale=scale,
                                           key_block=whole),
                lambda p: (q_abs[p:p + 1], _lane_of(kv_all, l, p)[:, 0],
                           seen[p:p + 1])),
                wkv_b, wo, nope, gate=gate)

        def in_place(q_nope, q_rope, kv_all, seen, wkv_b, wo, *live):
            # the visible blocks by one kernel call over the state array
            return mla.unabsorb(mla.attend_cache(
                mla.absorb(q_nope, q_rope, wkv_b, heads_major=True), kv_all,
                l, seen, live[0] if live else None, scale, kv_lora,
                block), wkv_b, wo, nope, heads_major=True, gate=gate)

        operands = (q_nope, q_rope, kv_all, seen, lw["wkv_b"], lw["wo"]) \
            + (() if lanes is None else (lanes,))
        if block:
            # (a step's row whose token is not real stands: its result is
            # thrown away, so the kernel fetches nothing of its cache)
            if lanes is None and c == 1 and valid is not None:
                operands += (valid[:, 0],)
            out = mla.on_the_chip(in_place, loops, *operands)
        else:
            out = loops(*operands)
        return out, arrs, sel

    def conv(y, lp, arrs, l, kind):
        s_all = arrs[_CONV_STATE]              # [L_conv, B, 1, taps - 1, D]
        delta, state = conv_block(
            y, lp["conv_in"], lp["conv_w"], lp["conv_out"],
            _layer_of(s_all, l)[:, 0], n_new)
        return delta, dict(arrs, **{_CONV_STATE: _place_state(
            s_all, l, state)})

    def matrix_state(operator, names, in_place):
        """A layer's operator over a float32 matrix of state a head and its
        convolutions' inputs (the arrays ``names``): layer l of both read
        whole, advanced by the rows' valid tokens, written back; where
        ``in_place`` takes the stack, one token a row, the live rows' states
        are advanced where they lie."""
        s_name, c_name = names

        def run(y, lp, arrs, l, kind=None):
            s_all, taps = arrs[s_name], _layer_of(arrs[c_name], l)[:, 0]
            if in_place(c, s_all):
                delta, s_all, taps = operator(cfg, y, lp, s_all, taps, n_new,
                                              layer=l)
                return delta, dict(arrs, **{
                    s_name: s_all,
                    c_name: _place_state(arrs[c_name], l, taps)})
            delta, state, taps = operator(cfg, y, lp, _layer_of(s_all, l),
                                          taps, n_new)
            return delta, _place_delta(arrs, l, state, taps, names)

        return run

    kda = matrix_state(kda_operator, (_DELTA_STATE, _DELTA_CONV),
                       delta_rule.kernel_shape)
    # a state-space mixer's half of an "ssm+full" layer
    ssm = matrix_state(ssm_operator, _SSM_ARRAYS, functools.partial(
        ssd.kernel_shape, groups=cfg.ssm_groups))

    def mamba(y, lp, arrs, l):
        # the state [L, B, 1, columns, channels] advanced by the rows' valid
        # tokens: one token a row in the stack where it lies, a chunk's
        # between a cut of the layer and its placement back -> the memory too
        s_all, taps = arrs[_MAMBA_STATE], \
            _layer_of(arrs[_MAMBA_CONV], l)[:, 0]
        if c == 1:
            delta, m, s_all, taps = mamba_operator(cfg, y, lp, s_all, taps,
                                                   n_new, layer=l)
        else:
            delta, m, state, taps = mamba_operator(
                cfg, y, lp, _layer_of(s_all, l)[:, 0], taps, n_new)
            with jax.named_scope("cache_write"):
                s_all = jax.lax.dynamic_update_slice(
                    s_all, state[None, :, None], (l, 0, 0, 0, 0))
        return delta, dict(arrs, **{
            _MAMBA_STATE: s_all,
            _MAMBA_CONV: _place_state(arrs[_MAMBA_CONV], l, taps)}), m

    operator = {"conv": conv, "eva": attend_eva, "kda": kda}

    def layer(xc, lp, arrs, l, kind, sel):
        y = _norm(cfg, xc, lp["attn_norm"], lp.get("attn_norm_b"))
        made = {}       # what this layer hands the layers behind it
        if kind == "mamba":
            delta, arrs, made["m"] = mamba(y, lp, arrs, l)
        elif kind == "gmu":
            delta = gmu_operator(cfg, y, lp, sel["m"])
        elif cfg.hands_down and cfg.attention == "mha":
            with cross_scope(kind):
                delta, arrs = attend_mha(y, lp, arrs, l, kind, sel["depth"])
        elif kind not in operator and cfg.attention == "mla":
            with latent_scope(cfg, kind):
                delta, arrs, sel = attend_mla(y, lp, arrs, l, kind, sel)
        else:
            delta, arrs = operator.get(kind, attend_mha)(y, lp, arrs, l,
                                                         kind)
            if kind in SSM_KINDS:   # the mixer off the same norm: the sum
                mixed, arrs = ssm(y, lp, arrs, l)
                delta = delta + mixed
        xc = xc + _post(cfg, delta, lp, "post_attn_norm")
        y2 = _norm(cfg, xc, lp["mlp_norm"], lp.get("mlp_norm_b"))
        z, _, load = _ffn(cfg, y2, lp, valid)
        if cfg.shortcut_moe:    # the routed branch leaves or rejoins here
            z, load, sel = shortcut(cfg, y2, z, lp, sel, valid)
        return (xc + _post(cfg, z, lp, "post_mlp_norm"), arrs, load,
                hand_on(sel, **made))

    if sel is None:
        # of a model with an indexer: no position chosen yet, no indexing
        # layer; of one whose layers hand values on: none made yet
        sel = (jnp.zeros((b, c, cache["kv"].shape[-1]), bool),
               jnp.zeros((), jnp.int32)) if cfg.index_topk else \
            handed(cfg, b, c) if cfg.hands_down else ()
    if span is not None:
        x, arrays, load, sel = _scan_cached(cfg, params, x, cache, layer,
                                            sel, span)
        if span[1] == cfg.n_layers:
            x = _norm(cfg, x, params["final_norm"],
                      params.get("final_norm_b"))
        return x, arrays, load, sel
    x, arrays, load = _scan_cached(cfg, params, x, cache, layer, sel)
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    return x, arrays, load


def _tail_on_one_row(cfg: TransformerConfig, params: Params, x: jnp.ndarray,
                     cache: KVCache, row: jnp.ndarray, *, mask, valid=None,
                     n_new=None, lanes=None, **kw):
    """`_attend_cached` for a program that wants ONE row's logits a batch
    row (``row`` [B]: a chunk's last real token) of a model with a STATELESS
    TAIL (`TransformerConfig.stateless_tail`): the layers up to the last one
    that holds state run on all ``C`` rows, as they must (their rows and
    states are what later tokens read); the layers behind it write nothing,
    so they run on that one row of the stream and of the memory alone,
    against the rows the layers before them just wrote.  Exact: a tail layer
    is elementwise in the position but for its reads of the cache.
    → (final-norm activations [B, D] of the rows ``row``, arrays, load)."""
    cut = cfg.n_layers - cfg.stateless_tail
    x, arrays, load, sel = _attend_cached(
        cfg, params, x, cache, mask=mask, valid=valid, n_new=n_new,
        lanes=lanes, span=(0, cut), **kw)
    b, c, _ = x.shape

    def one(t):     # [B | 1, C, ...] -> [B, 1, ...]: the row ``row``
        t = jnp.broadcast_to(t, (b,) + t.shape[1:])
        return jnp.take_along_axis(
            t, row.reshape((b, 1) + (1,) * (t.ndim - 2)), axis=1)

    x, arrays, more, _ = _attend_cached(
        cfg, params, one(x), dict(arrays, pos=cache["pos"]), rotate={},
        write={}, mask={k: one(m) for k, m in mask.items()},
        valid=None if valid is None else one(valid),
        n_new=None if n_new is None else jnp.minimum(n_new, 1), lanes=lanes,
        span=(cut, cfg.n_layers),
        sel={k: one(v) if v.ndim else v for k, v in sel.items()})
    return x[:, 0], arrays, tuple(a + b for a, b in zip(load, more))


def prefill(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig,
            cache: KVCache) -> Tuple[jnp.ndarray, KVCache]:
    """Run the prompt; → (logits of the LAST position [B, vocab], cache
    holding the prompt's K/V with pos = prompt length)."""
    _check_decodable(cfg)
    b, s = tokens.shape
    dt = cfg.stream_dtype
    if s > cache_capacity(cache, cfg):
        raise ValueError(f"prompt length {s} exceeds cache capacity "
                         f"{cache_capacity(cache, cfg)}")
    with jax.named_scope("embed"):
        x = _scale_embedding(cfg, params["embed"]["tok"][tokens].astype(dt))
        if cfg.pos_emb == "learned":
            x = x + params["embed"]["pos"][:s].astype(dt)
    angles = rope_tables(cfg, lambda base: rotary_angles(s, cfg.rope_dim,
                                                         base))
    rotate = _rotators(apply_rotary, angles)

    def columns(y, lp, kind):
        """What the cache holds of these tokens, from the same pre-norm
        projection the layer itself computes."""
        if cfg.attention == "mla":
            new = latent_rows(cfg.latent_of(kind),
                              y, latent_weights(cfg, lp, kind),
                              rotate.get(kind, mla.no_turn), kind)
            return {_latent_name(kind): new[:, :, None, :]}
        _, k, v = _qkv(cfg, y, lp, rotate.get(kind), kind)
        return dict(zip(_kv_names(kind), (k, v)))

    @jax.named_scope("cache_write")
    def place(c_all, l, cols):
        """The prompt's columns into layer ``l``: from column 0, or, of a
        prompt longer than a ring, the last ``ring`` positions, each at its
        position mod ring (a turn by a count known when tracing)."""
        rows = c_all.shape[-1]
        if s > rows:
            cols = jnp.roll(cols[..., s - rows:], (s - rows) % rows, axis=-1)
        return jax.lax.dynamic_update_slice(c_all, cols[None],
                                            (l, 0, 0, 0, 0))

    @jax.named_scope("cache_write")
    @jax.named_scope("summary")
    def summaries(arrs, l, lp, k, v):
        """Every chunk the prompt reaches, pooled from its keys and values
        ``k``, ``v`` [B, s, heads, width] as the ring's type holds them and
        placed from row 0 (the chunk the prompt ends in is pooled again by
        whatever program feeds its next token: `_summary_write`)."""
        pooled = eva.pool_chunks(
            *(eva.as_chunks(_as_columns(rows, arrs[name].dtype),
                            cfg.summary_chunk)
              for name, rows in zip(_kv_names("eva"), (k, v))),
            lp["adaptive_phi"], lp["adaptive_mu_k"])
        return {name: jax.lax.dynamic_update_slice(
            arrs[name], new.astype(arrs[name].dtype)[None], (l, 0, 0, 0, 0))
            for name, new in zip(_SUM_NAMES, pooled)}

    def from_zeros(operator, names, y, lp, arrs, l):
        """The matrix state and convolution inputs (the arrays ``names``)
        that the prompt leaves in layer l, from a zero state."""
        s_all, c_all = (arrs[n] for n in names)
        return _place_delta(arrs, l, *operator(
            cfg, y, lp, jnp.zeros(s_all.shape[1:], s_all.dtype),
            jnp.zeros(c_all.shape[1:2] + c_all.shape[3:], c_all.dtype)
        )[1:], names)

    def layer(h, lp, arrs, l, kind, sel):
        # run the layer for h, re-project for the cache
        y = _norm(cfg, h, lp["attn_norm"], lp.get("attn_norm_b"))
        if kind == "conv":     # the state the prompt's last token leaves
            _, state = short_conv(conv_inputs(y, lp["conv_in"])[0],
                                  lp["conv_w"])
            arrs = dict(arrs, **{_CONV_STATE: _place_state(
                arrs[_CONV_STATE], l, state)})
        elif kind == "kda":     # ... and its delta state, from zeros
            arrs = from_zeros(kda_operator, (_DELTA_STATE, _DELTA_CONV), y,
                              lp, arrs, l)
        elif kind == "mamba":   # ... a selective scan's, likewise
            _, _, state, taps = mamba_operator(cfg, y, lp)
            arrs = dict(arrs, **{
                _MAMBA_STATE: jax.lax.dynamic_update_slice(
                    arrs[_MAMBA_STATE], state[None, :, None],
                    (l, 0, 0, 0, 0)),
                _MAMBA_CONV: _place_state(arrs[_MAMBA_CONV], l, taps)})
        elif kind in READER_KINDS:  # (nothing of its own to hold)
            pass
        else:
            new = columns(y, lp, kind)
            arrs = dict(arrs, **{
                n: place(arrs[n], l, _as_columns(rows, arrs[n].dtype))
                for n, rows in new.items()})
            if kind == "eva":
                arrs = dict(arrs, **summaries(arrs, l, lp, *new.values()))
            if kind in SSM_KINDS:   # ... beside the rows, the mixer's state
                arrs = from_zeros(ssm_operator, _SSM_ARRAYS, y, lp, arrs, l)
        if kind == "index":     # the prompt's index keys, at this
            # indexing layer's own count
            k_i = sparse_index.index_keys(
                y, lp["wi_k"], lp["ik_norm"], lp["ik_norm_b"],
                rotate=rotate[kind], rope=cfg.rope_dim)
            arrs = dict(arrs, **{_INDEX_ARRAY: place(
                arrs[_INDEX_ARRAY], sel[1], _as_columns(
                    k_i[:, :, None, :], arrs[_INDEX_ARRAY].dtype))})
        if cfg.hands_down:      # (what the plain layers hand each other)
            h, _, sel = _layer(cfg, h, lp, angles, kind, sel)
            return h, arrs, no_load(cfg), sel
        h, _, chosen = _layer(cfg, h, lp, angles, kind,
                              sel[0] if sel else None)
        if sel:
            sel = (chosen, sel[1] + (kind == "index"))
        return h, arrs, no_load(cfg), sel

    sel = (jnp.zeros((b, s, s), bool), jnp.zeros((), jnp.int32)) \
        if cfg.index_topk else handed(cfg, b, s, rows=True) \
        if cfg.hands_down else ()
    x, arrays, _ = _scan_cached(cfg, params, x, cache, layer, sel)
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    return _last_logits(params, x[:, -1], cfg), dict(
        arrays, pos=jnp.asarray(s, jnp.int32))


@jax.named_scope("head")
def _last_logits(params: Params, x: jnp.ndarray, cfg: TransformerConfig
                 ) -> jnp.ndarray:
    """Final-norm activations [..., D] -> float32 logits [..., vocab] (of
    a model with several prediction heads: every head's, head 0's first;
    `next_token_logits`)."""
    if cfg.fp32_logits:     # accumulated and handed out float32
        return mla.times(jnp.einsum("...d,dv->...v", x, _unembed(params, cfg),
                                  preferred_element_type=jnp.float32),
                       cfg.logit_scale)
    return mla.times(jnp.einsum("...d,dv->...v", x, _unembed(
        params, cfg)).astype(jnp.float32), cfg.logit_scale)


def greedy_tokens(logits: jnp.ndarray,
                  cfg: TransformerConfig) -> jnp.ndarray:
    """The largest of the next token's logits (`next_token_logits`)."""
    return jnp.argmax(next_token_logits(logits, cfg), axis=-1)


def next_token_logits(logits: jnp.ndarray,
                      cfg: TransformerConfig) -> jnp.ndarray:
    """What the NEXT token is drawn from: the logits [..., columns] as they
    are, or, of a model with several prediction heads, head 0's
    ``vocab_size`` columns (head p's, at ``p * vocab_size`` on, predict the
    token p further on; no program here accepts more than one a step)."""
    return logits if cfg.pred_heads == 1 else logits[..., :cfg.vocab_size]


def prefill_chunk(params: Params, tokens: jnp.ndarray, cache: KVCache,
                  cfg: TransformerConfig, n_valid: Optional[jnp.ndarray] = None,
                  tail: bool = True) -> Tuple[jnp.ndarray, KVCache]:
    """Extend the cache with a CHUNK of prompt tokens [B, C] starting at
    ``cache['pos']`` → (logits of the chunk's last position, cache').

    The compile-helper-friendly prefill: one program per (B, C) shape,
    reused across a prompt of any length.  A whole-prompt flash prefill
    compiles a program proportional to the full sequence — the
    llama-1b GQA variant of that compile is a known remote-compile-
    helper killer (SURVEY §9); chunking caps the compiled program at C
    positions.  Chunk attention runs against the cache: on a TPU over the
    128-row blocks the chunk's queries see (`ops/cache_attention.py`
    `attend_chunk_blocks`, O(C·pos)), dense against the cache's max_len
    elsewhere (O(C·max_len) per chunk) — more FLOPs than causal flash,
    traded for a bounded, cacheable compile.

    ``n_valid`` (int32 scalar, TRACED, 1 <= n_valid <= C) makes the chunk
    a PADDED one: only its first ``n_valid`` tokens are real (see
    :func:`_prefill_chunk`), so a prompt's remainder is one program of
    the chunk's own shape, whatever its length.

    ``tail`` False (static; a model with a stateless tail only,
    `TransformerConfig.stateless_tail`) is the program of a chunk that is
    NOT a prompt's last: it ends behind the last layer that holds state and
    hands out no logits (zeros), so it reads neither the tail's weights nor
    the head's (`prefill_chunk_step` chooses it)."""
    logits, cache, _ = _prefill_chunk(params, tokens, cache, cfg, n_valid,
                                      tail)
    return logits, cache


def _prefill_chunk(params: Params, tokens: jnp.ndarray, cache: KVCache,
                   cfg: TransformerConfig,
                   n_valid: Optional[jnp.ndarray] = None, tail: bool = True):
    """:func:`prefill_chunk` → (logits, cache', load): beside them what
    the chunk's expert layers routed (zeros for a model without).

    With ``n_valid`` the rows from ``n_valid`` on are padding: ``pos``
    advances by ``n_valid``, the logits are row ``n_valid - 1``'s, and a
    no-drop expert layer routes the real rows only (a padded row touches
    no expert and adds nothing to ``load``).  The causal mask needs no
    word of it: row ``i`` sees positions ``<= pos + i``, so no real row
    sees a padded column, and the padded columns written at ``[pos +
    n_valid, pos + C)`` lie above the returned ``pos``, where every
    program masks its reads and the next chunk or decode step writes
    first.  A conv layer's state has no such cover: its carry-out is taken
    at row ``n_valid - 1``, not at the chunk's last row.  The WINDOW
    ``[pos, pos + C)`` has to lie inside the cache (and a learned position
    table): a slice that starts too late is clamped, silently, onto
    earlier positions (:func:`chunk_window`)."""
    _check_decodable(cfg)
    b, c = tokens.shape
    _check_chunk(cfg, c)
    dt = cfg.stream_dtype
    pos = cache["pos"]
    max_len = cache_capacity(cache, cfg)
    with jax.named_scope("embed"):
        x = _scale_embedding(cfg, params["embed"]["tok"][tokens].astype(dt))
        if cfg.pos_emb == "learned":
            x = x + jax.lax.dynamic_slice_in_dim(
                params["embed"]["pos"], pos, c, axis=0).astype(dt)
    with jax.named_scope("projections"):
        # the chunk's rows of each rotating kind's table (its own base)
        angles = rope_tables(cfg, lambda base: tuple(
            jax.lax.dynamic_slice_in_dim(t, pos, c, axis=0)
            for t in rotary_angles(max_len, cfg.rope_dim, base)))
    # mask[i, t]: cached position t visible to chunk token i (causal
    # within the chunk, everything before it fully visible)
    with jax.named_scope("attention"):
        mask = jnp.arange(max_len)[None, :] <= (pos + jnp.arange(c))[:, None]
    mask = dict.fromkeys(_CONTEXT_KINDS, mask[None])

    write = dict.fromkeys(_ROW_KINDS, _full_write_chunk(pos))
    ring = window_ring(cfg, max_len)
    if "window" in cfg.kinds:
        mask["window"] = _ring_mask(pos, c, ring, cfg.sliding_window)[None]
        write["window"] = _ring_write_chunk(pos, c, ring)
    if "eva" in cfg.kinds:
        mask.update((k, m[None])
                    for k, m in _eva_masks(cfg, pos, c, max_len).items())
        write.update(eva=_ring_write_chunk(pos, c, ring),
                     summary=[_summary_write(cfg, pos, c, ring)])
    valid = None if n_valid is None else \
        jnp.broadcast_to(jnp.arange(c) < n_valid, (b, c))
    layers = dict(
        rotate=_rotators(apply_rotary, angles),
        write=write, mask=mask, valid=valid,
        n_new=None if n_valid is None else
        jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (b,)))
    if cfg.stateless_tail and c > 1:    # the tail on the last real row alone
        step = c if n_valid is None else jnp.asarray(n_valid, pos.dtype)
        if not tail:                    # ... or, of no last chunk, not at all
            return _no_tail(cfg, params, x, cache, layers, pos + step)
        last, arrays, load = _tail_on_one_row(
            cfg, params, x, cache,
            jnp.broadcast_to(jnp.asarray(step - 1, jnp.int32), (b,)),
            **layers)
    elif n_valid is None:
        x, arrays, load = _attend_cached(cfg, params, x, cache, **layers)
        last, step = x[:, -1], c
    else:
        x, arrays, load = _attend_cached(cfg, params, x, cache, **layers)
        last = jax.lax.dynamic_index_in_dim(x, n_valid - 1, axis=1,
                                            keepdims=False)
        step = jnp.asarray(n_valid, pos.dtype)
    return _last_logits(params, last, cfg), dict(arrays,
                                                 pos=pos + step), load


def _no_tail(cfg: TransformerConfig, params, x, cache, layers, pos):
    """A chunk program that is NOT a prompt's last, of a model with a
    stateless tail: the layers up to the last one that holds state
    (``layers``: `_attend_cached`'s words), no tail, no head -> (zeros for
    logits [b, columns], cache' at ``pos``, load)."""
    _, arrays, load, _ = _attend_cached(
        cfg, params, x, cache, span=(0, cfg.n_layers - cfg.stateless_tail),
        **layers)
    return (jnp.zeros((x.shape[0], cfg.logit_size), jnp.float32),
            dict(arrays, pos=pos), load)


def chunk_window(off: int, n: int, chunk: int,
                 capacity: int) -> Tuple[int, int]:
    """THE policy by which a prompt of ``n`` tokens, ``off`` of them
    already in the cache, is cut into chunk programs of ONE width:
    → ``(start, n_valid)``, the next program's first position and how
    many of its ``chunk`` rows are real tokens (the rest is padding).

    Whole chunks while they last, then the remainder as one padded chunk.
    A window that would pass ``capacity`` (the cache's ``max_len``, and no
    more than a learned position table) starts at ``capacity - chunk``
    instead and runs the overlapped tokens ``[start, off)`` again: a
    position's columns depend only on the tokens at or before it, so the
    rewrite stores what was there.  Needs ``chunk <= capacity`` and
    ``off < n <= capacity``; :func:`prefill_chunk_step` sets the cache's
    ``pos`` to ``start`` where that is not ``off``."""
    start = min(off, capacity - chunk)
    return start, min(n - start, chunk)


def _window_of(cfg: TransformerConfig, n: int, off: int, chunk: int,
               capacity: int) -> Tuple[int, int]:
    """`chunk_window` for a model whose every state can run tokens twice:
    a window set back before ``off`` over conv layers is refused."""
    start, n_valid = chunk_window(off, n, chunk, capacity)
    if start != off:
        _check_state_rewind(cfg, "a chunk window set back at the cache's "
                                 "end (the prompt ends within one chunk "
                                 "of max_len)")
    return start, n_valid


def padded_chunk(tokens, start: int, n_valid: int, chunk: int):
    """Host tokens ``[B, n]`` (numpy) → the chunk program's ``[B, chunk]``
    int32 input: ``tokens[:, start:start + n_valid]``, zeros behind."""
    buf = np.zeros((tokens.shape[0], chunk), np.int32)
    buf[:, :n_valid] = tokens[:, start:start + n_valid]
    return buf


#: The ONE shared chunk program, a module-level jit so that every caller
#: shares one trace/compile cache (the point of chunking is a bounded,
#: REUSED program).  The cache argument is DONATED: the program extends it
#: in place and the caller's handle is dead after the call — rebind to the
#: returned cache.  Its callers: :func:`prefill_chunk_step` — the serve
#: engine's admission and failover resume of a session that prefills
#: ALONE (serve/decode_session.py `_prefill_advance`, one step between
#: decode steps; two or more at once go through :data:`prefill_lanes_jit`)
#: and :func:`prefill_chunked` (the whole prefix at once) — always passes
#: ``n_valid`` (a whole chunk passes ``chunk``, a prompt's remainder its
#: length), so a replica compiles ONE batch-1 prefill shape per model
#: config, [B, chunk], no matter how many prompts, resumes, or admissions
#: of whatever length it serves.  The benchmark's outputs check (perfbench,
#: `_verify`) walks a prompt through this handle on its own, without
#: ``n_valid``: the unpadded program of the shape it is given, which is
#: also what :func:`decode_step` traces (a chunk of one).
prefill_chunk_jit = jax.jit(prefill_chunk, static_argnames=("cfg", "tail"),
                            donate_argnames=("cache",))


def prefill_chunk_step(fn, params: Params, tokens: np.ndarray, off: int,
                       cache: KVCache, cfg: TransformerConfig, *,
                       chunk: int, capacity: int):
    """ONE step of the host walk of a prompt through the chunk program
    ``fn`` (:data:`prefill_chunk_jit`, or a wrap of it): host ``tokens``
    ``[B, n]``, ``off`` of them already in ``cache`` → ``(logits, cache',
    new offset, n_valid)``.  The window and the padding are
    :func:`chunk_window`'s and :func:`padded_chunk`'s; where the window
    starts before ``off`` (it would have passed ``capacity``) the cache's
    ``pos`` is set back to it, and the overlapped tokens run again, which
    rewrites what their columns hold."""
    start, n_valid = _window_of(cfg, tokens.shape[1], off, chunk, capacity)
    if start != off:
        cache = dict(cache, pos=np.int32(start))
    logits, cache = fn(params, padded_chunk(tokens, start, n_valid, chunk),
                       cache, cfg=cfg, n_valid=np.int32(n_valid),
                       **_tail_of(cfg, chunk, [
                           start + n_valid >= tokens.shape[1]]))
    return logits, cache, start + n_valid, n_valid


def _tail_of(cfg: TransformerConfig, chunk: int, final) -> Dict[str, bool]:
    """What a host walk tells a chunk program about its tail: nothing for a
    model that has none (the program takes no such word), and ``tail=False``
    where no row of the program is its prompt's LAST chunk (``final``: a
    bool a row that advances), so that only a prompt's last chunk pays for
    the stateless tail's and the head's weights."""
    if not cfg.stateless_tail or chunk < 2 or any(final):
        return {}
    return {"tail": False}


def prefill_chunked(params: Params, tokens: jnp.ndarray,
                    cfg: TransformerConfig, cache: KVCache,
                    *, chunk: int,
                    _jitted=None) -> Tuple[jnp.ndarray, KVCache]:
    """Whole-prefix prefill as ceil(s/chunk) programs of ONE shape:
    ``[B, chunk]`` blocks, the remainder as one more of them, padded.
    Drop-in for :func:`prefill` where compile size must stay bounded, and
    what a failover resume amounts to: a replayed prefix (prompt + tokens
    generated so far) has an *arbitrary* length, and no length compiles
    anything.  Greedy replay is deterministic: the logits of the last
    position are (numerically) those the uninterrupted session produced,
    so the argmax — the next token — matches exactly."""
    s = tokens.shape[1]
    capacity = cache_capacity(cache, cfg)
    if s > capacity:
        raise ValueError(f"prompt length {s} exceeds cache capacity "
                         f"{capacity}")
    fn = _jitted or prefill_chunk_jit
    host = np.asarray(tokens)
    chunk = min(chunk, capacity)
    logits, off = None, 0
    while off < s:
        logits, cache, off, _ = prefill_chunk_step(
            fn, params, host, off, cache, cfg, chunk=chunk,
            capacity=capacity)
    return logits, cache


def decode_step(params: Params, token: jnp.ndarray, cache: KVCache,
                cfg: TransformerConfig) -> Tuple[jnp.ndarray, KVCache]:
    """One token [B] int32 → (next-token logits [B, vocab], cache'): a
    chunk of one."""
    return prefill_chunk(params, token[:, None], cache, cfg)


def init_slot_cache(cfg: TransformerConfig, slots: int,
                    max_len: int) -> KVCache:
    """KV cache for a continuous-batching decode engine: ``slots``
    independent sessions share one batched program, so ``pos`` is a
    per-slot vector instead of the single scalar of
    :func:`init_kv_cache`."""
    return _init_cache(cfg, slots, max_len, jnp.zeros((slots,), jnp.int32))


@jax.named_scope("cache_write")
def cache_insert_slot(slot_cache: KVCache, cache: KVCache,
                      slot: jnp.ndarray) -> KVCache:
    """Write a batch-1 session cache (from :func:`prefill`) into slot
    ``slot`` of a slot-batched cache.  ``slot`` is a TRACED index —
    one jitted program serves every slot, so session admission never
    recompiles."""
    out = {name: jax.lax.dynamic_update_slice(
        a, cache[name].astype(a.dtype), (0, slot, 0, 0, 0))
        for name, a in cache_arrays(slot_cache).items()}
    out["pos"] = jax.lax.dynamic_update_slice(
        slot_cache["pos"],
        jnp.reshape(cache["pos"], (1,)).astype(jnp.int32), (slot,))
    return out


@jax.named_scope("cache_write")
def cache_gather_slot(slot_cache: KVCache, slot: jnp.ndarray,
                      upto: jnp.ndarray) -> KVCache:
    """Extract slot ``slot`` of a slot-batched cache as a batch-1 cache
    TRUNCATED to its first ``upto`` positions — the prefix-reuse
    admission primitive (inverse of :func:`cache_insert_slot`).

    A new session whose prompt shares ``upto`` tokens with a live
    slot's prompt seeds its prefill cache from this copy and chunk-
    prefills only the unshared suffix.  It copies whatever arrays the
    cache has (keys and values, or latents).  The rows at positions >=
    ``upto`` still hold the donor's LATER tokens, but they sit past the
    returned ``pos`` and every prefill/decode program masks reads to
    positions <= pos — the same stale-rows-are-invisible invariant
    paused slots rely on — and the suffix prefill overwrites them before
    ``pos`` ever reaches them.
    ``slot`` and ``upto`` are TRACED, so one compiled program serves
    every (donor slot, prefix length) pair.

    A window layer's RING is copied as it stands: it holds the donor's
    LAST positions, not its first ``upto``.  The copy is exact only while
    the donor stands at ``upto`` or its whole context still fits its
    window; a conv layer's STATE is the donor's at its LAST token, exact
    only while the donor stands at ``upto``.  The caller checks both
    (`serve/decode_session.py` ``_prefix_exact``) and refuses the reuse
    where they do not hold."""
    out = {name: jax.lax.dynamic_slice(
        a, (0, slot, 0, 0, 0), (a.shape[0], 1) + a.shape[2:])
        for name, a in cache_arrays(slot_cache).items()}
    out["pos"] = jnp.asarray(upto, jnp.int32)
    return out


@jax.named_scope("projections")
def _rotate_slots(x: jnp.ndarray, cos: jnp.ndarray,
                  sin: jnp.ndarray) -> jnp.ndarray:
    """apply_rotary for PER-SLOT positions: cos/sin are [S, 1, 1, hd//2]
    (one angle row per slot) instead of the shared [seq, hd//2] table.
    Same fp32 rotate-half math, so slot decode matches the batch-1
    path bit-for-bit."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _row_inputs(params: Params, tokens: jnp.ndarray, pos: jnp.ndarray,
                cfg: TransformerConfig, max_len: int):
    """What a program whose batch rows sit at DIFFERENT positions gives
    `_attend_cached`: ``tokens`` [S, C] fed at ``pos`` [S] .. ``pos + C - 1``
    → (embedded ``x`` [S, C, D], each row's rotary angles by kind [S, C, 1,
    ·] for `_rotate_slots`, ``mask[kind]`` [S, C, rows]: the cached positions
    (of a ring: the columns, by the position each holds once the C tokens
    are written) visible to fed token i of row s)."""
    c = tokens.shape[1]
    dt = cfg.stream_dtype
    posm = pos[:, None] + jnp.arange(c)[None, :]               # [S, C]
    with jax.named_scope("embed"):
        x = _scale_embedding(cfg, params["embed"]["tok"][tokens].astype(dt))
        if cfg.pos_emb == "learned":
            x = x + params["embed"]["pos"][posm].astype(dt)
    with jax.named_scope("projections"):
        angles = rope_tables(cfg, lambda base: tuple(
            t[posm][:, :, None, :]
            for t in rotary_angles(max_len, cfg.rope_dim, base)))
    with jax.named_scope("attention"):
        mask = dict.fromkeys(_CONTEXT_KINDS,
                             jnp.arange(max_len)[None, None, :]
                             <= posm[:, :, None])
    if "window" in cfg.kinds:
        mask["window"] = _ring_mask(pos, c, window_ring(cfg, max_len),
                                    cfg.sliding_window)
    if "eva" in cfg.kinds:
        mask.update(_eva_masks(cfg, pos, c, max_len))
    return x, angles, mask


def _forward_slots(params: Params, token: jnp.ndarray, cache: KVCache,
                   cfg: TransformerConfig, active: jnp.ndarray):
    """``token`` [S]: ONE token a slot, fed at each slot's OWN ``pos``
    → (final-norm activations [S, D], arrays, load); ``active`` [S] marks
    the slots whose tokens a no-drop expert layer routes and whose conv
    states advance (the others still compute: the batch shape is fixed,
    and their key and value columns land ahead of their ``pos``, but
    their states stay as they are).
    Slots sit at DIFFERENT positions, so no one slice holds their new
    columns.  An XLA scatter (or a ``vmap`` of the update, which lowers to
    one) is not updated in place under the cache's layout and puts the
    cache's conversions back inside the layer loop (PR 26 took it out);
    a ``dynamic_update_slice`` a slot costs the same whatever it moves
    (every tile of ``heads x width``: 3.4-16 us, ``slots x arrays``
    of them a layer).  So every array's columns go through
    `ops.cache_write.write_columns`: where the array's rows are whole
    blocks of 128, ONE kernel call an array a layer that aliases the array
    and moves only the 128-row block of each slot that holds its column
    (``slots x heads x width x 128`` elements in and out); elsewhere (a
    tiny test model's rows, any platform but the TPU) the slices, a column
    each.  Either way a column whose start lies past the end is clamped
    onto the last column, where a scatter would have dropped it.  Only a
    slot already at ``max_len`` loses its last column that way, and that
    slot is finished: nothing reads it again (a prefix match stops short
    of a prompt's last token)."""
    _check_decodable(cfg)
    _check_chunk(cfg, 1)
    pos = cache["pos"]                                         # [S]
    if token.shape != pos.shape:
        raise ValueError(
            f"a slot decode step feeds ONE token a slot: token "
            f"{token.shape} over a cache of {pos.shape[0]} slots")
    max_len = cache_capacity(cache, cfg)
    x, angles, mask = _row_inputs(params, token[:, None], pos, cfg, max_len)

    def column_writes(column):
        @jax.named_scope("cache_write")
        def write(c_all, l, cols):                # [S, heads, width, 1]
            return write_columns(c_all, l, cols[..., 0], column)
        return write

    write = dict.fromkeys(_ROW_KINDS, column_writes(pos))
    ring = window_ring(cfg, max_len)
    if "window" in cfg.kinds:
        # a ring has no end to be clamped onto: position p is column p mod
        # ring, and a column is masked by the position it holds
        write["window"] = column_writes(pos % ring)
    if "eva" in cfg.kinds:
        write["eva"] = column_writes(pos % ring)
        write["summary"] = [_summary_write_slots(cfg, pos, ring)]
    x, arrays, load = _attend_cached(
        cfg, params, x, cache,
        rotate=_rotators(_rotate_slots, angles),
        write=write, mask=mask, valid=active[:, None],
        n_new=active.astype(jnp.int32))
    return x[:, 0], arrays, load


def _prefill_lanes(params: Params, tokens: jnp.ndarray, cache: KVCache,
                   cfg: TransformerConfig, n_valid: jnp.ndarray,
                   tail: bool = True):
    """`_prefill_chunk` for up to P SESSIONS AT ONCE: ``tokens`` [P, C], a
    padded chunk a LANE, over a lane cache that is a slot cache of P rows
    (`init_slot_cache`; per-lane ``pos`` [P], each lane's first position),
    ``n_valid`` [P] int32 the real rows of each lane's chunk, 0 .. C
    → (logits [P, vocab], cache', load).

    A lane is `_prefill_chunk`'s batch-1 program in everything that is its
    own: its logits are row ``n_valid - 1``'s, its ``pos`` advances by
    ``n_valid``, its padded rows route to no expert, a conv state advances
    by its real rows, and its columns are ONE chunk-wide slice an array a
    layer (a ring's two read-modify-write slices).  A lane with ``n_valid``
    0 STANDS: it writes nothing (every array of it stays bit for bit),
    routes nothing, skips its attention, and its logits mean nothing.  What
    the lanes share is every read of a weight: the embedding, the
    projections, the dense and routed FFNs and the head run once over the
    ``P x C`` stacked rows, so P sessions' chunks cost one pass over the
    weights where P batch-1 programs cost P.  The attention, which reads
    no weight, stays a lane's: one kernel call a layer walks each live
    lane's blocks in turn (`ops/cache_attention.py` `attend_chunk_blocks`:
    the scores never leave VMEM), or, on the dense path, `_by_lane` cuts a
    lane out at a time and its float32 scores are ``[C, heads, max_len]``
    whatever P."""
    _check_decodable(cfg)
    lanes, c = tokens.shape
    _check_chunk(cfg, c)
    pos = cache["pos"]                                         # [P]
    max_len = cache_capacity(cache, cfg)
    live = n_valid > 0
    x, angles, mask = _row_inputs(params, tokens, pos, cfg, max_len)

    def lane_writes(one):
        def write(c_all, l, cols):                # [P, heads, width, C]
            for lane in range(lanes):
                c_all = one(lane)(c_all, l, cols[lane:lane + 1])
            return c_all
        return write

    write = dict.fromkeys(_ROW_KINDS, lane_writes(
        lambda p: _full_write_chunk(pos[p], p, live[p])))
    ring = window_ring(cfg, max_len)
    if "window" in cfg.kinds:
        write["window"] = lane_writes(lambda p: _ring_write_chunk(
            pos[p], c, ring, p, live[p]))
    if "eva" in cfg.kinds:
        write["eva"] = lane_writes(lambda p: _ring_write_chunk(
            pos[p], c, ring, p, live[p]))
        write["summary"] = [_summary_write(cfg, pos[p], c, ring, p, live[p])
                            for p in range(lanes)]
    layers = dict(
        rotate=_rotators(_rotate_slots, angles), write=write, mask=mask,
        valid=jnp.arange(c)[None, :] < n_valid[:, None],
        n_new=n_valid, lanes=live)
    if cfg.stateless_tail and c > 1 and not tail:   # (`prefill_chunk`'s)
        return _no_tail(cfg, params, x, cache, layers,
                        pos + n_valid.astype(pos.dtype))
    if cfg.stateless_tail and c > 1:    # the tail on the last real row alone
        last, arrays, load = _tail_on_one_row(
            cfg, params, x, cache, jnp.maximum(n_valid - 1, 0), **layers)
    else:
        x, arrays, load = _attend_cached(cfg, params, x, cache, **layers)
        last = jnp.take_along_axis(
            x, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)[:, 0]
    return _last_logits(params, last, cfg), dict(
        arrays, pos=pos + n_valid.astype(pos.dtype)), load


def prefill_lanes(params: Params, tokens: jnp.ndarray, cache: KVCache,
                  cfg: TransformerConfig, n_valid: jnp.ndarray,
                  tail: bool = True) -> Tuple[jnp.ndarray, KVCache]:
    """:func:`_prefill_lanes` → (logits [P, vocab], cache')."""
    logits, cache, _ = _prefill_lanes(params, tokens, cache, cfg, n_valid,
                                      tail)
    return logits, cache


def _lanes_program(params, tokens, cache, cfg, n_valid, tail=True):
    return prefill_lanes(params, tokens, cache, cfg, n_valid, tail)


# A trace and the compile ledger know a program by its function's name
# (``jit_prefill_chunk``), and the lanes program IS the chunk program of
# more than one session: what reads the one reads the other.
_lanes_program.__name__ = "prefill_chunk"

#: The chunk program of SEVERAL sessions (serve/decode_session.py, while two
#: or more prompts prefill): module-level and donating as
#: :data:`prefill_chunk_jit`, and under its name in a trace.
prefill_lanes_jit = jax.jit(_lanes_program, static_argnames=("cfg", "tail"),
                            donate_argnames=("cache",))


def prefill_lanes_step(fn, params: Params, prompts, cache: KVCache,
                       cfg: TransformerConfig, *, chunk: int, capacity: int):
    """ONE step of the host walk of up to P prompts through the lanes
    program ``fn`` (:data:`prefill_lanes_jit`, or a wrap of it), as
    :func:`prefill_chunk_step` walks one: ``prompts[p]`` is ``(tokens [1,
    n] on the host, off)`` for a lane that advances, None for one that
    stands → ``(logits [P, vocab], cache', moved)`` with ``moved[p]`` =
    ``(new offset, n_valid)`` or None.  Each lane's window and padding are
    `chunk_window`'s and `padded_chunk`'s, and the host passes every lane's
    first position: the cache's ``pos`` is SET to the windows' starts (a
    standing lane's to 0; nothing of it is read or written)."""
    buf = np.zeros((len(prompts), chunk), np.int32)
    starts = np.zeros(len(prompts), np.int32)
    n_valid = np.zeros(len(prompts), np.int32)
    for p, lane in enumerate(prompts):
        if lane is None:
            continue
        tokens, off = lane
        starts[p], n_valid[p] = _window_of(cfg, tokens.shape[1], off, chunk,
                                           capacity)
        buf[p] = padded_chunk(tokens, starts[p], n_valid[p], chunk)
    final = [int(starts[p] + n_valid[p]) >= lane[0].shape[1]
             for p, lane in enumerate(prompts) if lane is not None]
    if not final and _tail_of(cfg, chunk, final):
        # (no lane advances: a warm-up, which changes nothing; it warms the
        # program without a tail too)
        _, cache = fn(params, buf, dict(cache, pos=starts), cfg=cfg,
                      n_valid=n_valid, tail=False)
        final = [True]
    logits, cache = fn(params, buf, dict(cache, pos=starts), cfg=cfg,
                       n_valid=n_valid, **_tail_of(cfg, chunk, final))
    return logits, cache, [
        None if lane is None else (int(starts[p] + n_valid[p]),
                                   int(n_valid[p]))
        for p, lane in enumerate(prompts)]


def decode_step_slots(params: Params, token: jnp.ndarray, cache: KVCache,
                      active: jnp.ndarray, cfg: TransformerConfig
                      ) -> Tuple[jnp.ndarray, KVCache]:
    """One continuous-batching decode step over ALL slots at once.

    ``token`` [S] int32 (each slot's last token; free/paused slots may
    carry any value), ``cache`` a slot cache with per-slot ``pos`` [S],
    ``active`` [S] bool.  → (logits [S, vocab], cache') where ``pos``
    advances only on active slots.  Inactive slots still compute (the
    batch shape is FIXED — that is what keeps this a single compiled
    program) but their K/V write lands at their un-advanced ``pos`` and
    is overwritten by the next active step before any read, their conv
    states are not advanced, and their logits are discarded by the engine.
    """
    logits, cache, _ = _decode_step_slots(params, token, cache, active, cfg)
    return logits, cache


def _decode_step_slots(params: Params, token: jnp.ndarray, cache: KVCache,
                       active: jnp.ndarray, cfg: TransformerConfig):
    """:func:`decode_step_slots` → (logits, cache', load): beside them
    what the ACTIVE slots' tokens were routed to, summed over the expert
    layers (the serve engine's fused step reads it with the tokens)."""
    x, arrays, load = _forward_slots(params, token, cache, cfg, active)
    return _last_logits(params, x, cfg), dict(
        arrays, pos=cache["pos"] + active.astype(jnp.int32)), load


@jax.named_scope("head")
def _sample(logits: jnp.ndarray, key: jax.Array, greedy: bool,
            temperature: jnp.ndarray, top_k: Optional[int]) -> jnp.ndarray:
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[:, -1:]
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "greedy",
                                    "top_k", "total"))
def _generate_impl(params, prompt, temperature, key, *, cfg,
                   max_new_tokens, greedy, top_k, total):
    b = prompt.shape[0]
    cache = init_kv_cache(cfg, b, total)
    logits, cache = prefill(params, prompt, cfg, cache)

    # Token t_i samples from the PREVIOUS logits (prefill's for t_1), so
    # only max_new_tokens - 1 decode passes are needed — decoding after
    # the final sample would be a wasted full forward pass.
    def step(carry, _):
        logits, cache, key = carry
        key, skey = jax.random.split(key)
        tok = _sample(next_token_logits(logits, cfg), skey, greedy,
                      temperature, top_k)
        logits, cache = decode_step(params, tok, cache, cfg)
        return (logits, cache, key), tok

    (logits, _, key), toks = jax.lax.scan(
        step, (logits, cache, key), None, length=max_new_tokens - 1)
    _, skey = jax.random.split(key)
    last = _sample(next_token_logits(logits, cfg), skey, greedy,
                   temperature, top_k)
    # scan with length=0 yields a [0, B] array, so this is total for
    # every max_new_tokens >= 1
    toks = jnp.concatenate([toks, last[None]], axis=0)
    return jnp.swapaxes(toks, 0, 1)                            # [B, N]


def generate(params: Params, prompt: jnp.ndarray, *,
             cfg: TransformerConfig, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             max_len: Optional[int] = None,
             key: Optional[jax.Array] = None) -> jnp.ndarray:
    """prompt [B, S] int32 → generated tokens [B, max_new_tokens].

    Greedy when ``temperature == 0`` (default), else temperature /
    top-k sampling.  One compiled program: prefill + scanned decode.
    ``temperature`` is a TRACED input — serving different temperatures
    per request does not recompile (only the greedy/sampled switch,
    top_k, and the shape-bearing knobs are static).
    """
    b, s = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, "
                         f"got {max_new_tokens}")
    total = max_len or (s + max_new_tokens)
    if total < s + max_new_tokens - 1:
        # a short cache would silently clamp writes onto the last slot
        # (the last token is sampled, never written)
        raise ValueError(
            f"max_len={total} < prompt ({s}) + max_new_tokens "
            f"({max_new_tokens})")
    if cfg.pos_emb == "learned" and total > cfg.max_seq_len:
        # dynamic_slice would silently clamp to the last embedding row
        raise ValueError(
            f"prompt + max_new_tokens ({total}) exceeds the learned "
            f"position table ({cfg.max_seq_len})")
    if key is None:
        key = jax.random.PRNGKey(0)
    # the greedy switch must be a concrete host bool (it selects the
    # compiled program); temperature itself stays traced
    greedy = bool(float(temperature) == 0.0)
    return _generate_impl(
        params, prompt, jnp.asarray(temperature, jnp.float32), key,
        cfg=cfg, max_new_tokens=max_new_tokens,
        greedy=greedy, top_k=top_k, total=total)
