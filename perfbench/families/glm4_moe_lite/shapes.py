"""Family ``glm4_moe_lite``, the part that imports no JAX: pre-RMSNorm
blocks with multi-head latent attention (low-rank queries; one compressed
key-value latent and one rotary key a position, shared by all heads), a
leading run of dense SwiGLU layers, then layers of routed experts (sigmoid
scores, a correction bias that moves the choice, normalised and scaled
weights) beside shared experts, an untied output head.  The keys are the
ones the model's ``config.json`` publishes.  The interface is
`manifest.FAMILY_INTERFACE`; what the equations are is in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def vocab(c: Dict[str, Any]) -> int:
    """Nothing is padded: the traffic draws from the whole vocabulary."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Rotary angles have no table to run out of; the file's
    ``max_position_embeddings`` is what the model declares."""
    return c["max_position_embeddings"]


def layers(c: Dict[str, Any]):
    """(leading dense layers, expert layers) as run."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def attention_params(c: Dict[str, Any]) -> int:
    """One layer's attention matmuls: query down and up, key-value down
    (latent and rotary key), key-value up, output."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + v) + h * v * d)


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: up, gate, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _norm_params(c: Dict[str, Any]) -> int:
    # before attention and before the feed-forward; on the two latents
    return 2 * c["hidden_size"] + c["q_lora_rank"] + c["kv_lora_rank"]


def _outside_experts(c: Dict[str, Any]) -> int:
    """Of one expert layer, everything but its routed experts: attention,
    the shared experts, the router with its bias, the norms."""
    E = c["n_routed_experts"]
    return (attention_params(c) + c["n_shared_experts"] * expert_params(c)
            + c["hidden_size"] * E + E + _norm_params(c))


def _dense_layer(c: Dict[str, Any]) -> int:
    return (attention_params(c)
            + 3 * c["hidden_size"] * c["intermediate_size"]
            + _norm_params(c))


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: every routed expert of every expert layer, the
    embedding and the head (untied), the final norm."""
    n_dense, n_moe = layers(c)
    moe = _outside_experts(c) + c["n_routed_experts"] * expert_params(c)
    return (n_dense * _dense_layer(c) + n_moe * moe
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per ACTIVE matmul
    parameter (a token meets ``num_experts_per_tok`` routed experts, the
    shared ones, the router and the head; the embedding's gather not) plus
    causal attention in its plain form, query-key of ``nope + rope`` and
    probability-value of ``v`` a head over half the positions."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    n_dense, n_moe = layers(c)
    active = (attention_params(c) + d * c["n_routed_experts"]
              + (c["num_experts_per_tok"] + c["n_shared_experts"])
              * expert_params(c))
    dense = attention_params(c) + 3 * d * c["intermediate_size"]
    n_matmul = n_dense * dense + n_moe * active + c["vocab_size"] * d
    qkv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return 6.0 * n_matmul + 6.0 * (n_dense + n_moe) * (h * qkv // 2) * seq_len


def cache_row_values(c: Dict[str, Any]) -> int:
    """What a cache holds a position a layer: the normed latent and the
    rotated shared key, not keys and values a head."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      experts_touched: Optional[float] = None) -> float:
    """Bytes a decode step must read: every weight outside the routed
    experts once but the embedding table (a step gathers one row of it a
    slot), the head among them; of each expert layer's routed experts
    ``experts_touched`` where the run counted them, else the
    ``num_experts_per_tok`` that ONE token must read (the floor of any
    batch); and the latents of the live rows."""
    n_dense, n_moe = layers(c)
    if experts_touched is None:
        experts_touched = c["num_experts_per_tok"]
    weights = (n_dense * _dense_layer(c) + n_moe * _outside_experts(c)
               + c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
               + n_moe * experts_touched * expert_params(c))
    cache = (n_dense + n_moe) * live_rows * cache_row_values(c)
    return float((weights + cache) * bytes_per_el)


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernels of the program's paths for this family.  The
    served path (absorbed latent attention, grouped expert matmuls) has
    none: both are XLA's own.  A whole-sequence forward or a training step
    runs causal flash attention in the plain form, one call a layer: query
    and key ``nope + rope`` wide, value ``v`` wide (2 matmuls forward, 5
    backward, the causal half)."""
    h = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    v = c["v_head_dim"]
    mm = 2.0 * batch * h * seq_len * seq_len * ((qk + v) / 2.0) / 2.0
    rows = batch * seq_len * h * 2
    return {"flash_attention": {
        "fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
        "fwd_bytes": rows * (2.0 * qk + 2.0 * v),
        "bwd_bytes": 2.0 * rows * (2.0 * qk + 2.0 * v),
        "calls": sum(layers(c))}}
