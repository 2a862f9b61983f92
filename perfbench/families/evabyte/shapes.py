"""Family ``evabyte``, the part that imports no JAX: a byte-level decoder of
identical pre-norm blocks whose attention is EVA (``attention_class``
``eva``): a position attends its own block-aligned window of ``window_size``
positions exactly and every earlier window through ONE pooled key and value
a chunk of ``chunk_size`` positions, under one softmax.  There is NO layer
that holds the whole context: a served cache is a ring of a window's rows
and a summary row a chunk.  ``num_attention_heads`` query and
``num_key_value_heads`` key-value heads of ``hidden_size /
num_attention_heads``, rotated over the whole head at ``rope_theta``; a
SwiGLU of ``intermediate_size``; RMSNorm that multiplies by ``1 + g``
(``norm_add_unit_offset``); two learned vectors a key-value head a layer for
the pooling (``adaptive_phi``, ``adaptive_mu_k``); an untied unembedding of
``num_pred_heads`` x ``vocab_size`` columns (head p predicts the byte at t +
1 + p).

A configuration of this family may be ONE PIPELINE STAGE of the published
model (``deployment``): ``num_hidden_layers`` is what the stage holds, the
embedding and the head are kept with it.  The keys are the ones the model's
``config.json`` publishes; the interface is `manifest.FAMILY_INTERFACE`; the
equations are in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple


def vocab(c: Dict[str, Any]) -> int:
    """Bytes and the model's few special ids: traffic draws below it."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Rotary angles have no table to run out of: what the model declares."""
    return c["max_position_embeddings"]


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def attention_matmuls(c: Dict[str, Any]) -> int:
    """One layer's four projections."""
    d, hd = c["hidden_size"], head_dim(c)
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * hk * hd


def layer_params(c: Dict[str, Any]) -> int:
    """The projections, the SwiGLU's three matrices, two norms, and the
    pooling's ``adaptive_phi`` and ``adaptive_mu_k`` (a key-value head
    each)."""
    d = c["hidden_size"]
    return attention_matmuls(c) + 3 * d * c["intermediate_size"] + 2 * d \
        + 2 * c["num_key_value_heads"] * head_dim(c)


def head_params(c: Dict[str, Any]) -> int:
    """The unembedding: every prediction head's columns."""
    return c["hidden_size"] * c["num_pred_heads"] * c["vocab_size"]


def _outside_layers(c: Dict[str, Any]) -> int:
    """The embedding, the head (untied) and the final norm."""
    d = c["hidden_size"]
    return c["vocab_size"] * d + head_params(c) + d


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: the stage's layers, the embedding, the head."""
    return c["num_hidden_layers"] * layer_params(c) + _outside_layers(c)


def count_params_published(c: Dict[str, Any]) -> int:
    """The whole model's, every stage's layers (``deployment``)."""
    return c["deployment"]["layers_published"] * layer_params(c) \
        + _outside_layers(c)


def attended_rows(c: Dict[str, Any], depth: int) -> Tuple[int, int]:
    """(ring rows, summary rows) that a query at position ``depth`` (0 the
    first) attends a layer: the positions of its own window up to itself,
    and a summary for every chunk of the windows before."""
    w = c["window_size"]
    return depth % w + 1, (depth // w) * (w // c["chunk_size"])


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul
    parameter (the head's every column among them) plus the attention over
    what a position sees on average: half a window of exact rows (less in a
    sequence shorter than a window) and the summaries of half the
    sequence's chunks (the pooling itself, a sixteenth of a row's cost, is
    not counted)."""
    d, h, hd = c["hidden_size"], c["num_attention_heads"], head_dim(c)
    n_matmul = c["num_hidden_layers"] * (
        attention_matmuls(c) + 3 * d * c["intermediate_size"]) \
        + head_params(c)
    seen = min(seq_len, c["window_size"]) / 2 \
        + max(0, seq_len - c["window_size"]) / 2 / c["chunk_size"]
    return 6.0 * n_matmul + 6.0 * c["num_hidden_layers"] * h * hd * 2 * seen


def cache_row_values(c: Dict[str, Any]) -> int:
    """What a ring holds a position, and a summary a chunk, a layer: a key
    and a value of every key-value head."""
    return c["num_key_value_heads"] * 2 * head_dim(c)


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      depths: Optional[Iterable[int]] = None) -> float:
    """Bytes a decode step must read: every weight once but the embedding
    table (a step gathers one row of it a slot), the head among them, and
    of the cache what the live slots ATTEND: a slot at depth ``t`` its
    window's ``t % window + 1`` ring rows and ``(t // window) * window /
    chunk`` summary rows a layer (`attended_rows`), never the dense arrays
    a program may read to get them.

    ``live_rows`` is slots x depth, which does not say how many slots nor
    where in its window each stands.  With ``depths`` (the depths the run's
    slots stood at, one an emitted token) the slots are ``live_rows /
    mean(depths)`` and each reads the mean of `attended_rows` over them.
    Without: the least that ANY slots with that many positions between them
    attend, a summary row a chunk and no more (a window's own rows are at
    least a sixteenth of its positions).  A floor either way, so that no
    reading can pass 100 %; the engine's ``cache:rows`` span has the rows
    and bytes really attended."""
    weights = c["num_hidden_layers"] * layer_params(c) + head_params(c) \
        + c["hidden_size"]
    depths = list(depths) if depths is not None else []
    if depths:
        slots = live_rows / (sum(depths) / len(depths))
        rows = slots * sum(sum(attended_rows(c, t)) for t in depths) \
            / len(depths)
    else:
        rows = live_rows / c["chunk_size"]
    return float((weights + c["num_hidden_layers"] * rows
                  * cache_row_values(c)) * bytes_per_el)


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernels of the program's paths for this family: none.
    The served path pools, attends the ring and the summaries and takes the
    joint softmax in XLA (`ray_tpu/ops/eva_attention.py`); a whole-sequence
    forward takes the dense plain form: the flash kernel has one key set
    and no block mask."""
    return {}
