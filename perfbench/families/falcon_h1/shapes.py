"""Family ``falcon_h1``, the part that imports no JAX: a pre-RMSNorm block
whose operator is TWO mixers side by side off one norm, their outputs
summed into one residual add: a Mamba-2 state-space mixer (``mamba_n_heads``
heads of ``mamba_d_head``, ``mamba_d_ssm`` wide whatever ``mamba_expand``
says; keys and queries of ``mamba_d_state`` shared by ``mamba_n_groups``
groups of heads; a depthwise causal convolution of ``mamba_d_conv`` taps with
a bias over values, keys and queries; a ``mamba_d_state x mamba_d_head``
float32 matrix of state a head under an input-dependent step; a gate and a
norm by group) AND grouped-query attention (``num_attention_heads`` query
heads over ``num_key_value_heads`` of ``head_dim``, rotary over the whole
head); then a SwiGLU of ``intermediate_size``; fourteen fixed multipliers at
named places; an untied head.  EVERY layer is of this one kind.

A configuration of this family may be ONE STAGE of a pipeline
(``num_hidden_layers`` consecutive layers of the published depth,
``deployment``) with a slice of the vocabulary (``vocab_size``): every count
here is of what is held.  The keys are the ones the model's ``config.json``
publishes; the interface is `manifest.FAMILY_INTERFACE`; the equations are in
``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional


def vocab(c: Dict[str, Any]) -> int:
    """A sliced vocabulary is a smaller one: ids are drawn from the slice."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    return c["max_position_embeddings"]


def ssm_widths(c: Dict[str, Any]):
    """(the mixer's width, its convolution's channels: values | keys |
    queries of every group, the input projection's columns: gate | those |
    a step a head)."""
    inner = c["mamba_d_ssm"]
    if inner != c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("family falcon_h1: mamba_d_ssm is mamba_n_heads x "
                         "mamba_d_head")
    channels = inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    return inner, channels, inner + channels + c["mamba_n_heads"]


def ssm_matmul_params(c: Dict[str, Any]) -> int:
    """One mixer's two projections, in and out."""
    inner, _, columns = ssm_widths(c)
    return c["hidden_size"] * (columns + inner)


def ssm_params(c: Dict[str, Any]) -> int:
    """... with the convolution's taps and bias, the step's bias, the decay
    and the skip a head, the gated norm's weight."""
    inner, channels, _ = ssm_widths(c)
    return (ssm_matmul_params(c) + channels * (c["mamba_d_conv"] + 1)
            + 3 * c["mamba_n_heads"] + inner)


def attention_params(c: Dict[str, Any]) -> int:
    """One layer's attention: queries, keys, values, output; no bias."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * hk * hd


def ffn_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: Dict[str, Any]) -> int:
    """Both mixers, the feed-forward, the block's two norms."""
    return (ssm_params(c) + attention_params(c) + ffn_params(c)
            + 2 * c["hidden_size"])


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: the stage's layers, the embedding and the head
    (untied) over the vocabulary slice, the final norm."""
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def _matmul_params(c: Dict[str, Any]) -> int:
    return (c["num_hidden_layers"] * (ssm_matmul_params(c)
                                      + attention_params(c) + ffn_params(c))
            + c["vocab_size"] * c["hidden_size"])


def state_floats(c: Dict[str, Any]) -> int:
    """Floats of state ONE layer carries a sequence."""
    return c["mamba_n_heads"] * c["mamba_d_state"] * c["mamba_d_head"]


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul
    parameter, causal attention in its plain form, and the recurrence's 5
    operations a float of state a token (decay, the outer product added,
    the query's product summed), three times over."""
    h, hd = c["num_attention_heads"], c["head_dim"]
    L = c["num_hidden_layers"]
    return (6.0 * _matmul_params(c) + 6.0 * L * 2 * h * hd * seq_len / 2.0
            + 15.0 * L * state_floats(c))


def cache_row_values(c: Dict[str, Any]) -> int:
    """What a layer's cache holds a position: a key and a value a
    key-value head."""
    return 2 * c["num_key_value_heads"] * c["head_dim"]


def state_bytes(c: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """What ONE layer carries a sequence whatever its length: the float32
    matrix a head (4 bytes an element: the precision the file states for the
    state) and the last ``taps - 1`` inputs of the convolution at
    ``bytes_per_el``."""
    _, channels, _ = ssm_widths(c)
    return (4 * state_floats(c)
            + bytes_per_el * channels * (c["mamba_d_conv"] - 1))


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      depths: Optional[Iterable[int]] = None) -> float:
    """Bytes a decode step must MOVE, a FLOOR: every weight once but the
    embedding table (a step gathers one row of it a slot), the head among
    them; for each live slot the keys and values at its depth on EVERY
    layer; and on every layer each live slot's state ONCE READ AND ONCE
    WRITTEN (`state_bytes`: the write is the layer's mathematics, a token's
    state is a new matrix that the next token must read, as
    `kimi_linear/shapes.py` argues for its own).

    ``live_rows`` is slots x depth.  With ``depths`` (the depths the run's
    slots stood at, one an emitted token) the slots are ``live_rows /
    mean(depths)``; without, ONE slot at all the rows: the fewest states
    that so many positions can belong to."""
    L = c["num_hidden_layers"]
    weights = count_params(c) - c["vocab_size"] * c["hidden_size"]
    depths = list(depths) if depths is not None else []
    slots = live_rows / (sum(depths) / len(depths)) if depths else 1.0
    rows = L * live_rows * cache_row_values(c)
    return float((weights + rows) * bytes_per_el
                 + 2 * slots * L * state_bytes(c, bytes_per_el))


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernel this family brings: ``ssd_step``
    (`ray_tpu/ops/ssd.py` `step_in_place`), the decode step's state update,
    ONE call a layer over the stacked states where they lie.  One call at
    ``batch`` LIVE slots: a live slot's float32 state once in and once out
    (what the recurrence requires, and all the kernel should move: the
    vectors beside it, a key, a query, a value a head, are a sixtieth),
    and 5 operations a float of it.  ``calls``: the layers that call it a
    step.  (The chunk programs' scan is matrix products in XLA; the
    attention beside the mixer goes through `ops/cache_attention.py` and
    `ops/cache_write.py`, not this family's to count.)  ``seq_len`` is not
    looked at: a step feeds one token a slot."""
    floats = batch * state_floats(c)
    return {"ssd_step": {"step_flops": 5.0 * floats,
                         "step_bytes": 2.0 * 4 * floats,
                         "calls": c["num_hidden_layers"]}}
