"""Family ``lfm2_moe``, the part that imports no JAX: pre-RMSNorm blocks
whose operator is, layer by layer as the file's ``layer_types`` spells out,
a GATED SHORT CONVOLUTION (``conv``: ``[B | C | X] = y W_in``, a depthwise
causal convolution of ``conv_L_cache`` taps over ``B * X``, times ``C``,
``W_out``) or grouped-query attention (``full_attention``: RMS norms over
each head's queries and keys, rotary on both); a leading run of dense SwiGLU
layers, then layers of routed experts (sigmoid scores, a bias that moves the
choice, weights normalised and scaled) with NO shared expert; the output
head is the input embedding.

What a cache holds differs by the operator: an attention layer keys and
values a position, a conv layer the last ``conv_L_cache - 1`` inputs of its
convolution a SEQUENCE, whatever the context.  The keys are the ones the
model's ``config.json`` publishes; the interface is
`manifest.FAMILY_INTERFACE`; the equations are in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple


def vocab(c: Dict[str, Any]) -> int:
    """Nothing is padded or sliced: the traffic draws from all of it."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Rotary angles have no table to run out of and a conv layer carries
    no position: what the model declares."""
    return c["max_position_embeddings"]


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def layers(c: Dict[str, Any]) -> Tuple[int, int]:
    """(leading dense layers, expert layers) as run."""
    dense = c["num_dense_layers"]
    return dense, c["num_hidden_layers"] - dense


def operators(c: Dict[str, Any]) -> Tuple[int, int]:
    """(attention layers, conv layers) as run."""
    conv = sum(t == "conv" for t in c["layer_types"])
    return len(c["layer_types"]) - conv, conv


def conv_matmul_params(c: Dict[str, Any]) -> int:
    """A conv layer's two projections: in ``[d, 3d]``, out ``[d, d]``."""
    return 4 * c["hidden_size"] ** 2


def conv_params(c: Dict[str, Any]) -> int:
    """... and its ``conv_L_cache`` taps a channel (no bias)."""
    return conv_matmul_params(c) + c["hidden_size"] * c["conv_L_cache"]


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """Queries and output ``d x d``, keys and values of the key-value
    heads."""
    d = c["hidden_size"]
    return 2 * d * d + 2 * d * c["num_key_value_heads"] * head_dim(c)


def attention_params(c: Dict[str, Any]) -> int:
    """... and one scale each for a head's queries and keys."""
    return attention_matmul_params(c) + 2 * head_dim(c)


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: up, gate, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * c["num_experts"] + c["num_experts"]  # + bias


def _dense_ffn(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _outside_experts(c: Dict[str, Any]) -> int:
    """Everything held but the routed experts and the embedding (which is
    also the head): each layer's operator and two norms, the dense layers'
    feed-forwards, the routers with their bias, the final norm."""
    n_dense, n_moe = layers(c)
    n_attn, n_conv = operators(c)
    return (n_attn * attention_params(c) + n_conv * conv_params(c)
            + 2 * c["hidden_size"] * (n_dense + n_moe)
            + n_dense * _dense_ffn(c) + n_moe * _router_params(c)
            + c["hidden_size"])


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: every routed expert of every expert layer, the
    embedding once (tied)."""
    _, n_moe = layers(c)
    return (_outside_experts(c) + n_moe * c["num_experts"] * expert_params(c)
            + c["vocab_size"] * c["hidden_size"])


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per ACTIVE matmul
    parameter (a token meets ``num_experts_per_tok`` routed experts, the
    router, the head; the embedding's gather not) plus causal attention on
    the ATTENTION layers alone.  A conv layer's ``conv_L_cache`` multiply-
    adds a channel are not matmuls and are not counted."""
    d = c["hidden_size"]
    n_dense, n_moe = layers(c)
    n_attn, n_conv = operators(c)
    n_matmul = (n_attn * attention_matmul_params(c)
                + n_conv * conv_matmul_params(c) + n_dense * _dense_ffn(c)
                + n_moe * (c["num_experts_per_tok"] * expert_params(c)
                           + d * c["num_experts"])
                + c["vocab_size"] * d)
    return 6.0 * n_matmul + 6.0 * n_attn * d * seq_len


def cache_row_values(c: Dict[str, Any]) -> int:
    """What an ATTENTION layer's cache holds a position: keys and values of
    the key-value heads."""
    return 2 * c["num_key_value_heads"] * head_dim(c)


def state_values(c: Dict[str, Any]) -> int:
    """What a CONV layer's cache holds a sequence, whatever the context."""
    return (c["conv_L_cache"] - 1) * c["hidden_size"]


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      experts_touched: Optional[float] = None) -> float:
    """Bytes a decode step must read: every weight outside the routed
    experts once, the head (the embedding table, read whole as the head)
    among them; of each expert layer's experts ``experts_touched`` where
    the run counted them, else the ``num_experts_per_tok`` that ONE token
    must read (the floor of any batch); the key and value rows of the live
    slots on the ATTENTION layers only; and the conv layers' states.
    ``live_rows`` is slots x depth, which does not say how many slots: the
    states of ONE slot are counted, the least that any batch reads (a slot
    more is ``state_values`` more a conv layer: 8 KB here).  A floor, so
    that no reading can pass 100 %."""
    _, n_moe = layers(c)
    n_attn, n_conv = operators(c)
    if experts_touched is None:
        experts_touched = c["num_experts_per_tok"]
    weights = (_outside_experts(c) + c["vocab_size"] * c["hidden_size"]
               + n_moe * experts_touched * expert_params(c))
    cache = n_attn * live_rows * cache_row_values(c) \
        + (n_conv * state_values(c) if live_rows else 0)
    return float((weights + cache) * bytes_per_el)


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernels of the program's paths for this family, one
    call's operations and bytes and the calls a pass over ``batch x
    seq_len`` tokens makes.

    ``grouped_matmul`` (`ray_tpu/ops/grouped_matmul.py`): the routed
    experts' gate, up and down matmuls, three calls an expert layer, in the
    chunk program, the decode step (``seq_len`` 1) and a whole-sequence
    forward alike: ``batch x seq_len x num_experts_per_tok`` rows against
    ``[d, f]`` (down: ``[f, d]``, the same count), and the weights of the
    experts those rows touch, at most all and at most one a row (the
    family cannot know the routing: an upper count of the bytes, so a
    share of the roofline made from it is an upper one too and none is
    reported from it).  The backward pass is XLA's ``ragged_dot``, not the
    kernel.

    ``flash_attention``: a whole-sequence forward or a training step runs
    causal flash attention on the ATTENTION layers, one call a layer (2
    matmuls forward and 5 backward over the causal half).  The conv
    operator is plain `jax.numpy` that XLA fuses; it has no kernel."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    h, hd = c["num_attention_heads"], head_dim(c)
    _, n_moe = layers(c)
    n_attn, _ = operators(c)
    pairs = batch * seq_len * c["num_experts_per_tok"]
    touched = min(c["num_experts"], pairs)
    mm = 2.0 * batch * h * seq_len * seq_len * hd / 2.0
    rows = batch * seq_len * h * 2
    return {
        "grouped_matmul": {
            "fwd_flops": 2.0 * pairs * d * f,
            "fwd_bytes": 2.0 * (touched * d * f + pairs * (d + f)),
            "bwd_flops": 0.0, "bwd_bytes": 0.0,
            "calls": 3 * n_moe},
        "flash_attention": {
            "fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
            "fwd_bytes": rows * 4.0 * hd, "bwd_bytes": 2.0 * rows * 4.0 * hd,
            "calls": n_attn}}
