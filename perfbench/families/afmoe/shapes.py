"""Family ``afmoe``, the part that imports no JAX: sandwich-RMSNorm blocks
(a norm before AND after attention and feed-forward) with gated grouped-
query attention of a head size of its own (queries ``heads x head_dim``
wide, not ``hidden_size``; RMS norms over each head's queries and keys; the
heads' output times ``sigmoid(y W_g)`` before ``W_o``), WINDOW layers
(rotary, each position sees the last ``sliding_window``) beside FULL layers
(no positional encoding at all, the whole context) in the period the file's
``layer_types`` spells out, a leading run of dense SwiGLU layers, then
layers of routed experts (sigmoid scores over ``experts_routed``, a bias
that moves the choice, weights normalised and scaled) beside a shared one,
an embedding multiplier and an untied head.

A configuration of this family may be ONE CHIP'S SHARE of an expert-parallel
deployment: ``num_experts`` is what the chip holds, ``experts_routed`` (in
``deployment``) what the router scores, ``expert_offset`` the first one
held.  Every count here is of what is held.  The keys are the ones the
model's ``config.json`` publishes; the interface is
`manifest.FAMILY_INTERFACE`; the equations are in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple


def vocab(c: Dict[str, Any]) -> int:
    """A sliced vocabulary is a smaller one: ids are drawn from the slice."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Window layers' rotary angles have no table to run out of and full
    layers carry no position: what the model declares."""
    return c["max_position_embeddings"]


def layers(c: Dict[str, Any]) -> Tuple[int, int]:
    """(leading dense layers, expert layers) as run."""
    dense = c["num_dense_layers"]
    return dense, c["num_hidden_layers"] - dense


def window_layers(c: Dict[str, Any]) -> int:
    return sum(t == "sliding_attention" for t in c["layer_types"])


def experts_routed(c: Dict[str, Any]) -> int:
    """The router's width: every expert of the layer, on whatever chip."""
    return c["deployment"]["experts_routed"]


def attention_params(c: Dict[str, Any]) -> int:
    """One layer's attention matmuls: queries, keys, values, the gate
    (as wide as the queries), output."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    return 3 * d * h * hd + 2 * d * hk * hd


def expert_params(c: Dict[str, Any]) -> int:
    """One routed (or the shared) expert: up, gate, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _norm_params(c: Dict[str, Any]) -> int:
    # four norms a layer; one scale each for a head's queries and keys
    return 4 * c["hidden_size"] + 2 * c["head_dim"]


def _outside_experts(c: Dict[str, Any]) -> int:
    """Of one expert layer, everything but its routed experts: attention
    with its gate, the shared expert, the router with its bias, norms."""
    E = experts_routed(c)
    return (attention_params(c) + c["num_shared_experts"] * expert_params(c)
            + c["hidden_size"] * E + E + _norm_params(c))


def _dense_layer(c: Dict[str, Any]) -> int:
    return (attention_params(c)
            + 3 * c["hidden_size"] * c["intermediate_size"]
            + _norm_params(c))


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: the experts this chip holds of every expert layer,
    its slice of the embedding and of the head (untied), the final norm."""
    n_dense, n_moe = layers(c)
    moe = _outside_experts(c) + c["num_experts"] * expert_params(c)
    return (n_dense * _dense_layer(c) + n_moe * moe
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per ACTIVE matmul
    parameter (of a token's ``num_experts_per_tok`` routed experts the
    share that is held here, the shared one, the router, the head) plus
    causal attention, a window layer's over at most its window."""
    d, h, hd = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    n_dense, n_moe = layers(c)
    here = c["num_experts_per_tok"] * c["num_experts"] / experts_routed(c)
    active = (attention_params(c) + d * experts_routed(c)
              + (here + c["num_shared_experts"]) * expert_params(c))
    dense = attention_params(c) + 3 * d * c["intermediate_size"]
    n_matmul = n_dense * dense + n_moe * active + c["vocab_size"] * d
    n_win = window_layers(c)
    seen = (n_dense + n_moe - n_win) * seq_len \
        + n_win * min(seq_len, 2 * c["sliding_window"])
    return 6.0 * n_matmul + 6.0 * h * hd * seen


def cache_row_values(c: Dict[str, Any]) -> int:
    """What a cache holds a position a layer: keys and values of the
    key-value heads."""
    return 2 * c["num_key_value_heads"] * c["head_dim"]


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      experts_touched: Optional[float] = None) -> float:
    """Bytes a decode step must read: every weight outside the routed
    experts once but the embedding table (a step gathers one row of it a
    slot), the head among them; of each expert layer's HELD experts
    ``experts_touched`` where the run counted them, else none (a token's
    four may all live on other chips); the full layers' rows of the live
    slots; and of the window layers' rings at most the window a slot.
    ``live_rows`` is slots x depth, which does not say how many slots: the
    rows of a window layer are counted as ``min(live_rows,
    sliding_window)``, the least that any number of slots with that many
    rows between them must read (one slot past its window reads the
    window; slots all inside it read all their rows).  A floor, so that no
    reading can pass 100 %; the engine's ``cache:rows`` span has the rows
    really attended (`cache.rows_read_share.mixed`)."""
    n_dense, n_moe = layers(c)
    if experts_touched is None:
        experts_touched = 0.0
    weights = (n_dense * _dense_layer(c) + n_moe * _outside_experts(c)
               + c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
               + n_moe * experts_touched * expert_params(c))
    n_win = window_layers(c)
    rows = (n_dense + n_moe - n_win) * live_rows \
        + n_win * min(live_rows, c["sliding_window"])
    return float((weights + rows * cache_row_values(c)) * bytes_per_el)


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernels of the program's paths for this family.  The
    served path (dense attention over the cached rows and rings, grouped
    expert matmuls) has none: both are XLA's own.  A whole-sequence forward
    runs causal flash attention on its full layers, and on its window
    layers while the sequence is no longer than the window (past it the
    window mask exists only in the plain implementation): one call a layer,
    2 matmuls forward and 5 backward over the causal half."""
    h, hd = c["num_attention_heads"], c["head_dim"]
    mm = 2.0 * batch * h * seq_len * seq_len * hd / 2.0
    rows = batch * seq_len * h * 2
    n_dense, n_moe = layers(c)
    calls = n_dense + n_moe - (window_layers(c)
                               if seq_len > c["sliding_window"] else 0)
    return {"flash_attention": {
        "fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
        "fwd_bytes": rows * 4.0 * hd, "bwd_bytes": 2.0 * rows * 4.0 * hd,
        "calls": calls}}
