"""Family ``mimo_v2_flash``, the part that imports no JAX: pre-norm RMSNorm
blocks whose attention differs BY THE LAYER'S KIND (``hybrid_layer_pattern``:
1 a window layer, 0 a full one).  Every layer has ``num_attention_heads``
query heads of ``head_dim`` whose first ``int(partial_rotary_factor x
head_dim)`` dims are rotated; a window layer has ``swa_num_key_value_heads``
key-value heads, sees the last ``sliding_window`` positions, rotates at
``swa_rope_theta`` and has a learned SINK a query head in its softmax; a
full layer has ``num_key_value_heads`` (fewer), sees the whole context,
rotates at ``rope_theta`` and has none.  Values are ``v_head_dim`` wide
(not ``head_dim``) and scaled by ``attention_value_scale``.  The
feed-forward is a dense SwiGLU where ``moe_layer_freq`` is 0 and routed
experts (sigmoid scores over ``experts_routed``, a bias that moves the
choice, weights normalised, NO shared expert) where it is 1; the head is
untied.

A configuration of this family may be ONE CHIP'S SHARE of an expert-parallel
deployment: ``n_routed_experts`` is what the chip holds, ``experts_routed``
(in ``deployment``) what the router scores, ``expert_offset`` the first one
held.  Every count here is of what is held.  The keys are the ones the
model's ``config.json`` publishes; the interface is
`manifest.FAMILY_INTERFACE`; the equations are in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

WINDOW, FULL = 1, 0     # ``hybrid_layer_pattern``'s two values


def vocab(c: Dict[str, Any]) -> int:
    """A sliced vocabulary is a smaller one: ids are drawn from the slice."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Rotary angles have no table to run out of: what the model declares."""
    return c["max_position_embeddings"]


def layer_counts(c: Dict[str, Any]) -> Tuple[int, int]:
    """(window layers, full layers) as run."""
    n_win = sum(k == WINDOW for k in c["hybrid_layer_pattern"])
    return n_win, c["num_hidden_layers"] - n_win


def expert_layers(c: Dict[str, Any]) -> int:
    return sum(c["moe_layer_freq"])


def experts_routed(c: Dict[str, Any]) -> int:
    """The router's width: every expert of the layer, on whatever chip."""
    return c["deployment"]["experts_routed"]


def rotated_dims(c: Dict[str, Any]) -> int:
    """The first dims of a head that carry a position."""
    return int(c["partial_rotary_factor"] * c["head_dim"])


def kv_heads(c: Dict[str, Any], kind: int) -> int:
    return c["swa_num_key_value_heads"] if kind == WINDOW \
        else c["num_key_value_heads"]


def attention_matmuls(c: Dict[str, Any], kind: int) -> int:
    """One layer's attention projections: queries and keys of
    ``head_dim``, values of ``v_head_dim``, the output from the values'
    width."""
    d, h, hk = c["hidden_size"], c["num_attention_heads"], kv_heads(c, kind)
    qk, v = c["head_dim"], c["v_head_dim"]
    return d * h * qk + d * hk * (qk + v) + h * v * d


def has_sink(c: Dict[str, Any], kind: int) -> bool:
    return bool(c["add_swa_attention_sink_bias"] if kind == WINDOW
                else c["add_full_attention_sink_bias"])


def attention_params(c: Dict[str, Any], kind: int) -> int:
    """The projections, and a sink a query head where the kind has one."""
    return attention_matmuls(c, kind) \
        + (c["num_attention_heads"] if has_sink(c, kind) else 0)


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: up, gate, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _outside_experts(c: Dict[str, Any]) -> int:
    """Of every layer, everything but its routed experts: attention, the
    dense feed-forward or the router with its bias, two norms."""
    d, E = c["hidden_size"], experts_routed(c)
    total = 0
    for kind, moe in zip(c["hybrid_layer_pattern"], c["moe_layer_freq"]):
        total += attention_params(c, kind) + 2 * d + (
            d * E + E if moe else 3 * d * c["intermediate_size"])
    return total


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: the experts this chip holds of every expert layer,
    its slice of the embedding and of the head (untied), the final norm."""
    return (_outside_experts(c)
            + expert_layers(c) * c["n_routed_experts"] * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per ACTIVE matmul
    parameter (of a token's ``num_experts_per_tok`` routed experts the
    share that is held here, the router, the head) plus causal attention
    over keys of ``head_dim`` and values of ``v_head_dim``, a window
    layer's over at most its window."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    here = c["num_experts_per_tok"] * c["n_routed_experts"] \
        / experts_routed(c)
    n_matmul = c["vocab_size"] * d
    for kind, moe in zip(c["hybrid_layer_pattern"], c["moe_layer_freq"]):
        n_matmul += attention_matmuls(c, kind) + (
            d * experts_routed(c) + here * expert_params(c) if moe
            else 3 * d * c["intermediate_size"])
    n_win, n_full = layer_counts(c)
    seen = n_full * seq_len + n_win * min(seq_len, 2 * c["sliding_window"])
    return 6.0 * n_matmul + 6.0 * h * (c["head_dim"] + c["v_head_dim"]) \
        / 2 * seen


def cache_row_values(c: Dict[str, Any], kind: int) -> int:
    """What a cache holds a position a layer of this kind: keys of
    ``head_dim`` and values of ``v_head_dim`` of that kind's key-value
    heads (a full layer's row is HALF a window layer's)."""
    return kv_heads(c, kind) * (c["head_dim"] + c["v_head_dim"])


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      experts_touched: Optional[float] = None) -> float:
    """Bytes a decode step must read: every weight outside the routed
    experts once but the embedding table (a step gathers one row of it a
    slot), the head among them; of each expert layer's HELD experts
    ``experts_touched`` where the run counted them, else none (a token's
    eight may all live on other chips); the full layers' rows of the live
    slots at their kind's width; and of the window layers' rings at most
    the window, at theirs.  ``live_rows`` is slots x depth, which does not
    say how many slots: a window layer's rows are counted as
    ``min(live_rows, sliding_window)``, the least that any number of slots
    with that many rows between them must read.  A floor, so that no
    reading can pass 100 %; the engine's ``cache:rows`` span has the rows
    and bytes really attended."""
    if experts_touched is None:
        experts_touched = 0.0
    weights = (_outside_experts(c)
               + c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
               + expert_layers(c) * experts_touched * expert_params(c))
    n_win, n_full = layer_counts(c)
    rows = n_full * live_rows * cache_row_values(c, FULL) \
        + n_win * min(live_rows, c["sliding_window"]) \
        * cache_row_values(c, WINDOW)
    return float((weights + rows) * bytes_per_el)


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernels of the program's paths for this family: none of
    its own.  The served path attends dense over the cached rows and rings
    in XLA and multiplies the routed experts with the repository's
    `grouped_matmul` (shared with every routed family; no roofline of its
    own yet: the experts a CHUNK touches are not counted, PERF.md section
    3).  A whole-sequence forward takes the plain attention on every layer:
    the flash kernel has one head width, no window mask and no sink."""
    return {}
