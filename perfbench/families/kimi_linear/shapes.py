"""Family ``kimi_linear``, the part that imports no JAX: a pre-RMSNorm block
whose operator is, by ``linear_attn_config``, KIMI DELTA ATTENTION on the
``kda_layers`` (``num_heads`` heads of ``head_dim``: queries, keys and values
through a depthwise causal convolution of ``short_conv_kernel_size`` taps,
then a ``head_dim x head_dim`` float32 matrix of state a head that a gated
delta rule decays channel by channel, corrects and reads) and latent
attention WITHOUT positions on the ``full_attn_layers`` (``mla_use_nope``; no
query latent: ``q_lora_rank`` null); ``first_k_dense_replace`` leading dense
SwiGLU layers, then routed experts under a sigmoid router with a correction
bias beside shared experts; an untied head.  Layers are counted from 1 in the
two lists, as published.

A configuration of this family may be ONE CHIP'S SHARE of an expert-parallel
deployment: ``num_experts`` is what the chip holds,
``deployment.experts_routed`` what the router scores,
``deployment.expert_offset`` the first one held; ``vocab_size`` the slice of
the vocabulary held.  Every count here is of what is held.  The keys are the
ones the model's ``config.json`` publishes; the interface is
`manifest.FAMILY_INTERFACE`; the equations are in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional


def vocab(c: Dict[str, Any]) -> int:
    """A sliced vocabulary is a smaller one: ids are drawn from the slice."""
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """No position enters the model: what it declares it was trained to."""
    return c["model_max_length"]


def layers(c: Dict[str, Any]):
    """(leading dense layers, expert layers) as run."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def kinds(c: Dict[str, Any]):
    """``"kda"`` | ``"full"`` for every layer, in model order."""
    lin = c["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    n = c["num_hidden_layers"]
    if kda & full or kda | full != set(range(1, n + 1)):
        raise ValueError("linear_attn_config: every layer 1..%d is a KDA "
                         "layer or a full-attention layer, and not both" % n)
    return tuple("kda" if i in kda else "full" for i in range(1, n + 1))


def kda_layers(c: Dict[str, Any]) -> int:
    return kinds(c).count("kda")


def experts_routed(c: Dict[str, Any]) -> int:
    """The router's width: every expert of the layer, on whatever chip."""
    return c["deployment"]["experts_routed"]


def gate_rank(c: Dict[str, Any]) -> int:
    """Rank of the decay's and the output gate's two-step projections (the
    ``config.json`` does not give it: ``assumed``)."""
    return c["assumed"]["kda_gate_rank"]


def kda_matmul_params(c: Dict[str, Any]) -> int:
    """One KDA layer's projections: queries, keys, values in and the heads
    out, the two low-rank gates, the step size a head."""
    d, lin, r = c["hidden_size"], c["linear_attn_config"], gate_rank(c)
    e = lin["num_heads"] * lin["head_dim"]
    return 4 * d * e + 2 * (d * r + r * e) + d * lin["num_heads"]


def kda_params(c: Dict[str, Any]) -> int:
    """One KDA layer's operator: its projections, three convolutions, a
    decay a head with its bias a channel, the heads' norm."""
    lin = c["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    return (kda_matmul_params(c)
            + 3 * h * hd * lin["short_conv_kernel_size"] + h + h * hd + hd)


def attention_params(c: Dict[str, Any]) -> int:
    """One full layer's latent attention: queries (direct), key-value down
    (latent and shared key), key-value up, output, the latent's norm."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    kl = c["kv_lora_rank"]
    return (d * h * (nope + rope) + d * (kl + rope) + kl * h * (nope + v)
            + h * v * d + kl)


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert: up, gate, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _operators(c: Dict[str, Any]) -> int:
    """Every layer's operator and its two block norms."""
    k = kinds(c)
    return (k.count("kda") * kda_params(c)
            + k.count("full") * attention_params(c)
            + len(k) * 2 * c["hidden_size"])


def _outside_routed(c: Dict[str, Any]) -> int:
    """Every weight a decode step reads whatever it routes, but the head:
    the operators, the dense layers' SwiGLU, and of an expert layer the
    shared experts and the router (as wide as the layer's experts on all
    chips) with its bias."""
    n_dense, n_moe = layers(c)
    E, d = experts_routed(c), c["hidden_size"]
    return (_operators(c) + n_dense * 3 * d * c["intermediate_size"]
            + n_moe * (c["num_shared_experts"] * expert_params(c)
                       + d * E + E))


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: the held routed experts of every expert layer, the
    embedding and the head (untied) over the vocabulary slice, the final
    norm."""
    _, n_moe = layers(c)
    return (_outside_routed(c) + n_moe * c["num_experts"] * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def _matmul_params(c: Dict[str, Any]) -> int:
    """Matmul parameters a token meets: of its ``num_experts_per_token``
    routed experts the share held here."""
    d = c["hidden_size"]
    n_dense, n_moe = layers(c)
    k = kinds(c)
    held = c["num_experts_per_token"] * c["num_experts"] / experts_routed(c)
    return (k.count("kda") * kda_matmul_params(c)
            + k.count("full") * (attention_params(c) - c["kv_lora_rank"])
            + n_dense * 3 * d * c["intermediate_size"]
            + n_moe * (d * experts_routed(c) + (held + c["num_shared_experts"])
                       * expert_params(c))
            + c["vocab_size"] * d)


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per ACTIVE matmul
    parameter, causal attention in its plain form on the full layers, and on
    a KDA layer the recurrence's 7 operations a float of state a token
    (decay, two reads, correction, write), three times over."""
    lin = c["linear_attn_config"]
    h = c["num_attention_heads"]
    qkv = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    state = lin["num_heads"] * lin["head_dim"] ** 2
    k = kinds(c)
    return (6.0 * _matmul_params(c)
            + 6.0 * k.count("full") * h * qkv * seq_len / 2.0
            + 21.0 * k.count("kda") * state)


def cache_row_values(c: Dict[str, Any]) -> int:
    """What a full layer's cache holds a position: the normed latent and the
    shared key as projected, not keys and values a head."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def state_bytes(c: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """What ONE KDA layer carries a sequence, whatever its length: the
    float32 matrix a head (4 bytes an element: the precision the file states
    for the state) and the last ``taps - 1`` inputs of the three
    convolutions at ``bytes_per_el``."""
    lin = c["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    return (4 * h * hd * hd
            + bytes_per_el * 3 * h * hd * (lin["short_conv_kernel_size"] - 1))


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      experts_touched: Optional[float] = None,
                      depths: Optional[Iterable[int]] = None) -> float:
    """Bytes a decode step must MOVE, a FLOOR: every weight outside the
    routed experts once but the embedding table (a step gathers one row of
    it a slot), the head among them; of each expert layer's HELD experts
    ``experts_touched`` where the run counted them, else the share held of
    the ``num_experts_per_token`` that one token must read; for each live
    slot the latents at its depth on the full layers; and on the KDA layers
    each live slot's state ONCE READ AND ONCE WRITTEN (`state_bytes`).

    A WRITTEN byte is counted here, as no other family's floor does, because
    the write is the layer's mathematics and not an implementation's: a
    token's state is a new matrix (every element decayed and corrected) that
    the next token must read, so no program, however it is written, leaves
    it out; a key or a value a position is written once and is a rounding
    error beside the rows read.

    ``live_rows`` is slots x depth.  With ``depths`` (the depths the run's
    slots stood at, one an emitted token) the slots are ``live_rows /
    mean(depths)``; without, ONE slot at all the rows: the fewest states
    that so many positions can belong to."""
    _, n_moe = layers(c)
    if experts_touched is None:
        experts_touched = c["num_experts_per_token"] * c["num_experts"] \
            / experts_routed(c)
    weights = (_outside_routed(c) + c["vocab_size"] * c["hidden_size"]
               + c["hidden_size"]
               + n_moe * experts_touched * expert_params(c))
    depths = list(depths) if depths is not None else []
    slots = live_rows / (sum(depths) / len(depths)) if depths else 1.0
    k = kinds(c)
    latents = k.count("full") * live_rows * cache_row_values(c)
    return float((weights + latents) * bytes_per_el
                 + 2 * slots * k.count("kda") * state_bytes(c, bytes_per_el))


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernels of the program's paths for this family.  The gated
    delta rule is plain `jax.numpy` in all three of its forms
    (`ray_tpu/ops/delta_rule.py`: float32 multiply-adds a step, matrix
    products and one triangular solve a block of a chunk): no kernel of this
    repository's.  The kernels the served path does call are not this
    family's to count: the grouped expert matmul (`ops/grouped_matmul.py`)
    and the decode step's column write (`ops/cache_write.py`).  A
    whole-sequence forward attends latents without a rotary part through
    the reference attention (value and key heads differ in width), so the
    table is empty."""
    return {}
