"""Family ``dots3_note``, the part that imports no JAX: a pre-RMSNorm decoder
whose layers are of TWO LATENT SHAPES by ``layer_types``.  A
``"full_attention"`` layer is multi-head latent attention (query latent
``q_lora_rank``, key-value latent ``kv_lora_rank`` beside one rotary key,
``num_attention_heads`` heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim``
| ``v_head_dim``, rotary base ``rope_theta``) under an INDEXER OF ITS OWN
(``index_n_heads`` heads of ``index_head_dim`` over one key a position, the
``index_topk`` best positions a query; no layer shares another's choice).  A
``"sliding_attention"`` layer is latent attention of its own sizes (the
``swa_*`` keys: another head count, another key-value rank, another rotary
base) over the query's own position and the ``sliding_window_size - 1``
before it, with no indexer.  Both gate each head's output by one sigmoid a
head and rescale both latents by ``sqrt(hidden_size / rank)`` after their
norms.  The feed-forward is `glm_moe_dsa`'s: ``first_k_dense_replace``
leading dense SwiGLU layers, then routed experts under a sigmoid router with
a correction bias beside shared experts; an untied head.

A configuration of this family may be ONE CHIP'S SHARE of an expert-parallel
deployment: ``n_routed_experts`` is what the chip holds,
``deployment.experts_routed`` what the router scores,
``deployment.expert_offset`` the first one held; ``vocab_size`` the slice of
the vocabulary held.  Every count here is of what is held.  The keys are the
ones the model's ``config.json`` publishes; the interface is
`manifest.FAMILY_INTERFACE`; the equations are in ``model.py``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Optional

FULL, SLIDING = "full_attention", "sliding_attention"   # ``layer_types``


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


def kind_layers(c: Dict[str, Any], kind: str) -> int:
    return sum(t == kind for t in c["layer_types"])


def latent_sizes(c: Dict[str, Any], kind: str) -> Dict[str, int]:
    """A layer kind's own latent sizes under the full layers' names."""
    pre = "swa_" if kind == SLIDING else ""
    return {"heads": c[pre + "num_attention_heads"],
            **{k: c[pre + k] for k in ("q_lora_rank", "kv_lora_rank",
                                       "qk_nope_head_dim",
                                       "qk_rope_head_dim", "v_head_dim")}}


def experts_routed(c: Dict[str, Any]) -> int:
    """The router's width: every expert of the layer, on whatever chip."""
    return c["deployment"]["experts_routed"]


def attention_params(c: Dict[str, Any], kind: str) -> int:
    """One layer's attention matmuls at its kind's sizes: query down and up,
    key-value down (latent and rotary key), key-value up, output, and the
    gate a head."""
    d, z = c["hidden_size"], latent_sizes(c, kind)
    h, nope, rope, v = (z["heads"], z["qk_nope_head_dim"],
                        z["qk_rope_head_dim"], z["v_head_dim"])
    ql, kl = z["q_lora_rank"], z["kv_lora_rank"]
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + v) + h * v * d + d * h)


def indexer_params(c: Dict[str, Any]) -> int:
    """A full layer's indexer: queries from the query latent, one key and
    the head weights from the block's input, the key's LayerNorm."""
    hi, di = c["index_n_heads"], c["index_head_dim"]
    return (c["q_lora_rank"] * hi * di + c["hidden_size"] * (di + hi)
            + 2 * di)


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: up, gate, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _norm_params(c: Dict[str, Any], kind: str) -> int:
    # before attention and before the feed-forward; on the two latents
    z = latent_sizes(c, kind)
    return 2 * c["hidden_size"] + z["q_lora_rank"] + z["kv_lora_rank"]


def _operator(c: Dict[str, Any], kind: str) -> int:
    """A layer's attention with its norms and, of a full layer, its
    indexer."""
    return attention_params(c, kind) + _norm_params(c, kind) \
        + (indexer_params(c) if kind == FULL else 0)


def _ffn_outside_experts(c: Dict[str, Any], layer: int) -> int:
    """Layer ``layer``'s feed-forward but its routed experts: the dense
    SwiGLU, or the shared experts and the router (as wide as the layer's
    experts on all chips) with its bias."""
    if layer < c["first_k_dense_replace"]:
        return 3 * c["hidden_size"] * c["intermediate_size"]
    E = experts_routed(c)
    return c["n_shared_experts"] * expert_params(c) + c["hidden_size"] * E + E


def _outside_routed(c: Dict[str, Any]) -> int:
    """Every weight a decode step reads whatever it routes, but the head."""
    return sum(_operator(c, kind) + _ffn_outside_experts(c, i)
               for i, kind in enumerate(c["layer_types"]))


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: every layer's operator at its kind's sizes, the held
    routed experts of every expert layer, the embedding and the head
    (untied) over the vocabulary slice, the final norm."""
    _, n_moe = layers(c)
    return (_outside_routed(c)
            + n_moe * c["n_routed_experts"] * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per ACTIVE matmul
    parameter (of a token's ``num_experts_per_tok`` routed experts the share
    held here, the shared one, the router, the indexers and the head; the
    embedding's gather not) plus causal attention in its plain form over the
    positions a query ATTENDS (a full layer at most ``index_topk``, a
    sliding layer at most its window) and the indexers' one product a head a
    position over half the positions."""
    d = c["hidden_size"]
    n_dense, n_moe = layers(c)
    held = c["num_experts_per_tok"] * c["n_routed_experts"] \
        / experts_routed(c)
    n_matmul = (sum(attention_params(c, t) for t in c["layer_types"])
                + kind_layers(c, FULL) * indexer_params(c)
                + n_dense * 3 * d * c["intermediate_size"]
                + n_moe * (d * experts_routed(c)
                           + (held + c["n_shared_experts"])
                           * expert_params(c))
                + c["vocab_size"] * d)
    attn = 0.0
    for kind, most in ((FULL, c["index_topk"]),
                       (SLIDING, c["sliding_window_size"])):
        z = latent_sizes(c, kind)
        attn += kind_layers(c, kind) * z["heads"] * (
            z["qk_nope_head_dim"] + z["qk_rope_head_dim"] + z["v_head_dim"]
        ) * min(seq_len / 2.0, most)
    return (6.0 * n_matmul + 6.0 * attn
            + 6.0 * kind_layers(c, FULL) * c["index_n_heads"]
            * c["index_head_dim"] * seq_len / 2.0)


def cache_row_values(c: Dict[str, Any], kind: str) -> int:
    """What a cache holds a position a layer of this kind: the normed latent
    and the rotated shared key, not keys and values a head."""
    z = latent_sizes(c, kind)
    return z["kv_lora_rank"] + z["qk_rope_head_dim"]


def attended_values(c: Dict[str, Any], depth: float) -> Dict[str, float]:
    """Values of cache a slot at ``depth`` must read a step, by state kind:
    the latents of the ``min(depth, index_topk)`` chosen positions on every
    full layer (``full``), ONE index key of every position on them (the
    indexer scores them all: ``index``), and the ``min(depth, window)`` rows
    of every sliding layer's ring (``ring``)."""
    n_full, n_win = kind_layers(c, FULL), kind_layers(c, SLIDING)
    return {"full": n_full * min(depth, c["index_topk"])
            * cache_row_values(c, FULL),
            "index": n_full * depth * c["index_head_dim"],
            "ring": n_win * min(depth, c["sliding_window_size"])
            * cache_row_values(c, SLIDING)}


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      experts_touched: Optional[float] = None,
                      depths: Optional[Iterable[int]] = None) -> float:
    """Bytes a decode step must read, a FLOOR: every weight outside the
    routed experts once but the embedding table (a step gathers one row of
    it a slot), the indexers and the head among them; of each expert layer's
    HELD experts ``experts_touched`` where the run counted them, else the
    share held of the ``num_experts_per_tok`` that one token must read; and
    of the cache what the live slots must read (`attended_values`: chosen
    latents, all visible index keys, ring rows), never the arrays a program
    may read to get it.

    ``live_rows`` is slots x depth, which does not say how many slots nor
    how deep each stands, and neither ``min(depth, index_topk)`` nor
    ``min(depth, window)`` is linear in the depth.  With ``depths`` (the
    depths the run's slots stood at, one an emitted token) the slots are
    ``live_rows / mean(depths)`` and each reads the mean of
    `attended_values` over them.  Without: ONE slot at all the rows, the
    least that any slots with so many positions between them read."""
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
        cache = slots * sum(sum(attended_values(c, t).values())
                            for t in depths) / len(depths)
    else:
        cache = sum(attended_values(c, live_rows).values())
    return float((weights + cache) * bytes_per_el)


def ring_rows(c: Dict[str, Any], chunk: int = 128, block: int = 128) -> int:
    """Rows of a sliding layer's ring of latents: the window and the widest
    chunk a program writes ahead of it, in whole blocks
    (`ray_tpu/models/generate.py` `window_ring`)."""
    return math.ceil((c["sliding_window_size"] + chunk) / block) * block


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernel this family WIDENS: ``latent_attention_cache``
    (`ray_tpu/ops/latent_attention.py` `attend_cache`) read over a RING of
    ``swa_kv_lora_rank + swa_qk_rope_head_dim`` values a row (a row of
    another width than the full layers', masked by the position a column
    holds), ONE call a sliding layer.  One DECODE STEP's call at ``batch``
    LIVE slots that stand past the window: a live slot's ``window`` ring
    rows once (what the layer must read; the kernel moves whole tiles of the
    ring), its heads' absorbed queries in and latent rows out, and two dots
    a row a head (the scores over latent and rotary key, the probabilities
    over the latent).  ``calls``: the sliding layers.  ``seq_len`` is not
    looked at: a step feeds one token a slot.  (The full layers' calls of
    the same kernel, the grouped expert matmul and the column write are not
    this family's to count.)"""
    z = latent_sizes(c, SLIDING)
    row, h = cache_row_values(c, SLIDING), z["heads"]
    rows = c["sliding_window_size"]
    return {"latent_attention_ring": {
        "step_flops": 2.0 * batch * h * rows * (row + z["kv_lora_rank"]),
        "step_bytes": 2.0 * batch * (rows * row + h * (row
                                                       + z["kv_lora_rank"])),
        "calls": kind_layers(c, SLIDING)}}
