"""Family ``rope_gqa``, the part that imports no JAX: pre-RMSNorm blocks
with rotary positions, grouped-query attention and a SwiGLU feed-forward,
an untied output head.  It exists for the CPU rehearsal alone (the block
`TransformerConfig.tiny()` builds), to show that the harness carries a
family whose keys, weights tree, position scheme and cache width are not
GPT-2's; it is no configuration of BENCHMARK.json.  The keys are the ones
such models publish (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``max_position_embeddings``).  The interface is `manifest.FAMILY_INTERFACE`.
"""

from __future__ import annotations

from typing import Any, Dict


def vocab(c: Dict[str, Any]) -> int:
    """Nothing is padded: the traffic draws from the whole vocabulary."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Rotary angles have no table to run out of; the file's
    ``max_position_embeddings`` is what a cache row is sized for."""
    return c["max_position_embeddings"]


def _dims(c: Dict[str, Any]):
    d, h, hk = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    return (d, c["num_hidden_layers"], h, hk, d // h,
            c["intermediate_size"], c["vocab_size"])


def matmul_params_per_layer(c: Dict[str, Any]) -> int:
    d, _, h, hk, hd, ff, _ = _dims(c)
    # q and o over all heads, k and v over the key-value heads; up, gate, down
    return 2 * d * h * hd + 2 * d * hk * hd + 3 * d * ff


def count_params(c: Dict[str, Any]) -> int:
    d, L, _, _, _, _, v = _dims(c)
    per_layer = matmul_params_per_layer(c) + 2 * d      # two RMSNorms
    return L * per_layer + 2 * v * d + d      # embedding, head, final norm


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul
    parameter (the head's matmul too, the embedding's gather not) plus
    causal attention over all query heads, qk and pv over half the
    positions."""
    d, L, h, _, hd, _, v = _dims(c)
    n_matmul = L * matmul_params_per_layer(c) + v * d
    return 6.0 * n_matmul + 6.0 * L * h * hd * seq_len


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2) -> float:
    """Every weight but the embedding table once (a step gathers one row
    of it per slot), and the keys and values of the live rows, which are as
    wide as the KEY-VALUE heads."""
    d, L, _, hk, hd, _, v = _dims(c)
    weights = (count_params(c) - v * d) * bytes_per_el
    cache = 2 * L * live_rows * hk * hd * bytes_per_el
    return float(weights + cache)


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """Causal flash attention, one layer's call: operations over all query
    heads (2 matmuls forward, 5 backward, the causal half); bytes: q, o and
    their gradients as wide as the query heads, k, v and theirs as wide as
    the key-value heads."""
    _, L, h, hk, hd, _, _ = _dims(c)
    mm = 2.0 * batch * h * seq_len * seq_len * hd / 2.0
    wide = batch * seq_len * h * hd * 2
    narrow = batch * seq_len * hk * hd * 2
    return {"flash_attention": {
        "fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
        "fwd_bytes": 2.0 * wide + 2.0 * narrow,
        "bwd_bytes": 4.0 * wide + 4.0 * narrow, "calls": L}}
