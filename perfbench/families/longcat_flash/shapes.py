"""Family ``longcat_flash``, the part that imports no JAX: a pre-RMSNorm
decoder whose PUBLISHED LAYER (``num_layers`` of them) is TWO sublayers, each
multi-head latent attention (query latent ``q_lora_rank``, key-value latent
``kv_lora_rank`` beside one rotary key, ``num_attention_heads`` heads of
``qk_nope_head_dim`` + ``qk_rope_head_dim`` | ``v_head_dim``, both latents
rescaled by ``sqrt(hidden_size / rank)``) and a dense SwiGLU of
``ffn_hidden_size``, beside ONE routed branch: a softmax router over
``deployment.experts_routed`` experts of ``expert_ffn_hidden_size`` AND
``zero_expert_num`` identity experts that compute nothing, ``moe_topk`` a
token by score plus a correction bias, the weights the raw scores times
``routed_scaling_factor``.  The branch reads the first sublayer's normed
stream and joins the stream behind the second sublayer's feed-forward.  An
untied head.

A configuration of this family may be ONE CHIP'S SHARE of an expert-parallel
deployment: ``n_routed_experts`` is what the chip holds,
``deployment.experts_routed`` what the router scores beside the identity
experts, ``deployment.expert_offset`` the first one held; ``vocab_size`` the
slice of the vocabulary held.  Every count here is of what is held.  The keys
are the ones the model's ``config.json`` publishes; the interface is
`manifest.FAMILY_INTERFACE`; the equations are in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def vocab(c: Dict[str, Any]) -> int:
    """A sliced vocabulary is a smaller one: ids are drawn from the slice."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Rotary angles have no table to run out of: what the model declares."""
    return c["max_position_embeddings"]


def sublayers(c: Dict[str, Any]) -> int:
    """Attention operators (and dense feed-forwards, and latent rows a
    position of the cache): two a published layer."""
    return 2 * c["num_layers"]


def experts_routed(c: Dict[str, Any]) -> int:
    """The experts the router scores (beside the identity experts): the
    published count, whatever share of them is held."""
    return c["deployment"]["experts_routed"]


def zero_experts(c: Dict[str, Any]) -> int:
    """The router's outputs that compute nothing."""
    return c["zero_expert_num"]


def router_outputs(c: Dict[str, Any]) -> int:
    return experts_routed(c) + zero_experts(c)


def attention_params(c: Dict[str, Any]) -> int:
    """One sublayer's attention matmuls: query down and up, key-value down
    (latent and rotary key), key-value up, output."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + v) + h * v * d)


def dense_params(c: Dict[str, Any]) -> int:
    """One sublayer's dense feed-forward: up, gate, down."""
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: up, gate, down."""
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def router_params(c: Dict[str, Any]) -> int:
    """A published layer's router: a column an output, no bias of its own."""
    return c["hidden_size"] * router_outputs(c)


def _norm_params(c: Dict[str, Any]) -> int:
    # a sublayer's: before attention and before the feed-forward; on the two
    # latents
    return 2 * c["hidden_size"] + c["q_lora_rank"] + c["kv_lora_rank"]


def outside_experts(c: Dict[str, Any]) -> int:
    """Of one PUBLISHED layer, everything but its routed experts: two
    attention operators, two dense feed-forwards, the norms, the router and
    its correction bias."""
    return 2 * (attention_params(c) + dense_params(c) + _norm_params(c)) \
        + router_params(c) + router_outputs(c)


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: every held expert of every published layer, the
    embedding and the head (untied), the final norm."""
    return (c["num_layers"] * (outside_experts(c)
                               + c["n_routed_experts"] * expert_params(c))
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def real_experts_per_token(c: Dict[str, Any]) -> float:
    """Of a token's ``moe_topk`` choices, those that land on an expert HELD
    here, the outputs chosen alike: the matmuls a token meets."""
    return c["moe_topk"] * c["n_routed_experts"] / router_outputs(c)


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per ACTIVE matmul
    parameter (a token meets both attentions, both dense feed-forwards, the
    router and the held share of its chosen experts, an identity expert
    nothing; the head; the embedding's gather not) plus causal attention in
    its plain form, query-key of ``nope + rope`` and probability-value of
    ``v`` a head over half the positions, a sublayer."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    active = 2 * (attention_params(c) + dense_params(c)) + router_params(c) \
        + real_experts_per_token(c) * expert_params(c)
    n_matmul = c["num_layers"] * active + c["vocab_size"] * d
    qkv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return 6.0 * n_matmul + 6.0 * sublayers(c) * (h * qkv // 2) * seq_len


def cache_row_values(c: Dict[str, Any]) -> int:
    """What a cache holds a position a SUBLAYER: the normed latent and the
    rotated shared key, not keys and values a head."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      experts_touched: Optional[float] = None) -> float:
    """Bytes a decode step must read, a FLOOR: every weight outside the
    routed experts once but the embedding table (a step gathers one row of it
    a slot), the head's slice among them; of each published layer's HELD
    experts ``experts_touched`` where the run counted them (``moe:load``),
    else the share held of the ``moe_topk`` outputs that ONE token chooses
    (an identity expert has nothing to read); and the latents of the live
    rows, a row a sublayer."""
    if experts_touched is None:
        experts_touched = real_experts_per_token(c)
    weights = (c["num_layers"] * (outside_experts(c)
                                  + experts_touched * expert_params(c))
               + c["vocab_size"] * c["hidden_size"] + c["hidden_size"])
    cache = sublayers(c) * live_rows * cache_row_values(c)
    return float((weights + cache) * bytes_per_el)


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernels of the program's paths that this family's sizes
    decide.  The served path brings none of its own (the grouped expert
    matmul, the latent cache's blocked read and the column write are other
    families' to count, at the shapes they brought them for).  A
    whole-sequence forward or a training step runs causal flash attention in
    the plain form, one call a SUBLAYER: query and key ``nope + rope`` wide,
    value ``v`` wide (2 matmuls forward, 5 backward, the causal half)."""
    h = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    v = c["v_head_dim"]
    mm = 2.0 * batch * h * seq_len * seq_len * ((qk + v) / 2.0) / 2.0
    rows = batch * seq_len * h * 2
    return {"flash_attention": {
        "fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
        "fwd_bytes": rows * (2.0 * qk + 2.0 * v),
        "bwd_bytes": 2.0 * rows * (2.0 * qk + 2.0 * v),
        "calls": sublayers(c)}}
