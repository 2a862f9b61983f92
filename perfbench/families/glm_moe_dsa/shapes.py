"""Family ``glm_moe_dsa``, the part that imports no JAX: `glm4_moe_lite`'s
block (pre-RMSNorm, multi-head latent attention, leading dense SwiGLU layers,
then routed experts under a sigmoid router with a correction bias beside a
shared expert, an untied head) with LEARNED SPARSE ATTENTION on top: by
``indexer_types`` a layer is ``"full"`` (it runs an indexer of
``index_n_heads`` heads of ``index_head_dim`` over ONE key a position and
keeps the ``index_topk`` best positions a query) or ``"shared"`` (it attends
the choice of the last ``"full"`` layer before it and holds no indexer).
Every layer attends the chosen positions only.

A configuration of this family may be ONE CHIP'S SHARE of an expert-parallel
deployment: ``n_routed_experts`` is what the chip holds,
``deployment.experts_routed`` what the router scores,
``deployment.expert_offset`` the first one held; ``vocab_size`` the slice of
the vocabulary held.  Every count here is of what is held.  The keys are the
ones the model's ``config.json`` publishes; the interface is
`manifest.FAMILY_INTERFACE`; the equations are in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

INDEXING, SHARED = "full", "shared"     # ``indexer_types``' two values


def vocab(c: Dict[str, Any]) -> int:
    """A sliced vocabulary is a smaller one: ids are drawn from the slice."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Rotary angles have no table to run out of: what the model declares."""
    return c["max_position_embeddings"]


def layers(c: Dict[str, Any]):
    """(leading dense layers, expert layers) as run."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def index_layers(c: Dict[str, Any]) -> int:
    """Layers that run an indexer of their own."""
    return sum(t == INDEXING for t in c["indexer_types"])


def experts_routed(c: Dict[str, Any]) -> int:
    """The router's width: every expert of the layer, on whatever chip."""
    return c["deployment"]["experts_routed"]


def attention_params(c: Dict[str, Any]) -> int:
    """One layer's attention matmuls: query down and up, key-value down
    (latent and rotary key), key-value up, output."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + v) + h * v * d)


def indexer_params(c: Dict[str, Any]) -> int:
    """An indexing layer's own: queries from the query latent, one key and
    the head weights from the block's input, the key's LayerNorm."""
    hi, di = c["index_n_heads"], c["index_head_dim"]
    return (c["q_lora_rank"] * hi * di + c["hidden_size"] * (di + hi)
            + 2 * di)


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: up, gate, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _norm_params(c: Dict[str, Any]) -> int:
    # before attention and before the feed-forward; on the two latents
    return 2 * c["hidden_size"] + c["q_lora_rank"] + c["kv_lora_rank"]


def _outside_experts(c: Dict[str, Any]) -> int:
    """Of one expert layer, everything but its routed experts and its
    indexer: attention, the shared experts, the router (as wide as the
    layer's experts on all chips) with its bias, the norms."""
    E = experts_routed(c)
    return (attention_params(c) + c["n_shared_experts"] * expert_params(c)
            + c["hidden_size"] * E + E + _norm_params(c))


def _dense_layer(c: Dict[str, Any]) -> int:
    return (attention_params(c)
            + 3 * c["hidden_size"] * c["intermediate_size"]
            + _norm_params(c))


def _outside_routed(c: Dict[str, Any]) -> int:
    """Every weight a decode step reads whatever it routes, but the head."""
    n_dense, n_moe = layers(c)
    return (n_dense * _dense_layer(c) + n_moe * _outside_experts(c)
            + index_layers(c) * indexer_params(c))


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: the held routed experts of every expert layer, the
    indexers, the embedding and the head (untied) over the vocabulary
    slice, the final norm."""
    _, n_moe = layers(c)
    return (_outside_routed(c)
            + n_moe * c["n_routed_experts"] * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per ACTIVE matmul
    parameter (of a token's ``num_experts_per_tok`` routed experts the share
    held here, the shared one, the router, the indexers and the head; the
    embedding's gather not) plus causal attention in its plain form over
    the positions a query ATTENDS (at most ``index_topk``) and the indexers'
    one product a head a position over half the positions."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    n_dense, n_moe = layers(c)
    held = c["num_experts_per_tok"] * c["n_routed_experts"] \
        / experts_routed(c)
    active = (attention_params(c) + d * experts_routed(c)
              + (held + c["n_shared_experts"]) * expert_params(c))
    dense = attention_params(c) + 3 * d * c["intermediate_size"]
    n_matmul = (n_dense * dense + n_moe * active
                + index_layers(c) * indexer_params(c)
                + c["vocab_size"] * d)
    qkv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    attended = min(seq_len / 2.0, c["index_topk"])
    return (6.0 * n_matmul + 6.0 * (n_dense + n_moe) * h * qkv * attended
            + 6.0 * index_layers(c) * c["index_n_heads"]
            * c["index_head_dim"] * seq_len / 2.0)


def cache_row_values(c: Dict[str, Any]) -> int:
    """What a cache holds a position a layer: the normed latent and the
    rotated shared key, not keys and values a head."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def position_values(c: Dict[str, Any]) -> int:
    """What a cache holds a position over ALL layers: a latent row a layer
    and an index key on the indexing layers alone."""
    return (c["num_hidden_layers"] * cache_row_values(c)
            + index_layers(c) * c["index_head_dim"])


def attended_values(c: Dict[str, Any], depth: float) -> float:
    """Values of cache a slot at ``depth`` must read a step: the latents of
    the ``min(depth, index_topk)`` chosen positions on every layer, and ONE
    index key of every position on the indexing layers (the indexer scores
    them all: that is its cost)."""
    return (c["num_hidden_layers"] * min(depth, c["index_topk"])
            * cache_row_values(c)
            + index_layers(c) * depth * c["index_head_dim"])


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      experts_touched: Optional[float] = None,
                      depths: Optional[Iterable[int]] = None) -> float:
    """Bytes a decode step must read, a FLOOR: every weight outside the
    routed experts once but the embedding table (a step gathers one row of
    it a slot), the indexers and the head among them; of each expert layer's
    HELD experts ``experts_touched`` where the run counted them, else the
    share held of the ``num_experts_per_tok`` that one token must read; and
    of the cache what the live slots must read (`attended_values`), never
    the dense arrays a program may read to get it.

    ``live_rows`` is slots x depth, which does not say how many slots nor
    how deep each stands, and ``min(depth, index_topk)`` is not linear in
    the depth.  With ``depths`` (the depths the run's slots stood at, one an
    emitted token) the slots are ``live_rows / mean(depths)`` and each reads
    the mean of `attended_values` over them.  Without: ONE slot at all the
    rows, the least that any slots with so many positions between them
    read."""
    _, n_moe = layers(c)
    if experts_touched is None:
        experts_touched = c["num_experts_per_tok"] * c["n_routed_experts"] \
            / experts_routed(c)
    weights = (_outside_routed(c) + c["vocab_size"] * c["hidden_size"]
               + c["hidden_size"]
               + n_moe * experts_touched * expert_params(c))
    depths = list(depths) if depths is not None else []
    if depths:
        slots = live_rows / (sum(depths) / len(depths))
        cache = slots * sum(attended_values(c, t) for t in depths) \
            / len(depths)
    else:
        cache = attended_values(c, live_rows)
    return float((weights + cache) * bytes_per_el)


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernels of the program's paths for this family.  What the
    indexer and the selection add to the served path is XLA's own (dots, a
    search over the bits of a float, a masked softmax): no kernel of this
    repository's.  The kernels the served path does call are not this
    family's to count: the grouped expert matmul (`ops/grouped_matmul.py`)
    and the decode step's column write (`ops/cache_write.py`).  A
    whole-sequence forward or a training step attends under the selection
    as a mask in plain XLA too (the flash kernel has no mask a query), so
    the table is empty."""
    return {}
