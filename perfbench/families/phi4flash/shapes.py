"""Family ``phi4flash``, the part that imports no JAX: a DECODER-HYBRID-
DECODER (SambaY).  Every block is pre-LayerNorm (scale and bias, ``layer_norm_
eps``) with a SwiGLU of ``intermediate_size``; what stands in attention's
place goes by the layer (`layer_kinds`):

* ``"mamba"``: a Mamba-1 selective scan (``mamba_expand x hidden_size``
  channels, ``mamba_d_state`` state columns, a depthwise causal convolution
  of ``mamba_d_conv`` taps with a bias, a step of rank ``mamba_dt_rank``);
* ``"window"`` / ``"full"``: DIFFERENTIAL grouped-query attention
  (``num_attention_heads`` query heads of ``hidden_size / heads`` in PAIRS
  over ``num_key_value_heads`` key heads in pairs, a pair's value twice a
  head wide; two softmax maps subtracted under a norm), over the last
  ``sliding_window`` positions or all of them, with biases, NO position;
* ``"gmu"``: a gated memory unit, ``W_2 (m * silu(y W_1))`` with ``m`` the
  last ``"mamba"`` layer's scan output at the same position;
* ``"cross"``: differential attention of its OWN queries over the LAST FULL
  layer's keys and values: it holds no cache.

The first half of the layers and two more are the self-decoder (mamba layers
on the even indices, window layers on the odd ones, the last of them full);
the rest the cross-decoder (gmu on the even, cross on the odd).  The embedding
is the head (tied).  The keys are the ones the model's ``config.json``
publishes, the four Mamba sizes the published class's defaults
(``assumed.sizes``); the interface is `manifest.FAMILY_INTERFACE`; the
equations are in ``model.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional

#: lanes of the tile a key and a query are broadcast to for the scan kernel
_LANES = 128


def vocab(c: Dict[str, Any]) -> int:
    return c["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    return c["max_position_embeddings"]


def sizes(c: Dict[str, Any]) -> Dict[str, int]:
    """The mixer's sizes the ``config.json`` leaves to its class's defaults:
    ``inner`` (channels), ``state``, ``conv`` (taps), ``dt_rank``."""
    a = c["assumed"]["sizes"]
    rank = a["mamba_dt_rank"]
    return {"inner": a["mamba_expand"] * c["hidden_size"],
            "state": a["mamba_d_state"], "conv": a["mamba_d_conv"],
            "dt_rank": -(-c["hidden_size"] // 16) if rank == "auto" else rank}


def layer_kinds(c: Dict[str, Any]) -> List[str]:
    """Each layer's kind, in model order: as stated (``assumed.layer_kinds``
    of a rehearsal's tiny file) or by the published class's rule from
    ``num_hidden_layers`` and ``mb_per_layer``: layer ``l`` is a Mamba layer
    where ``l % mb_per_layer == 0``, else attention; the cross-decoder starts
    at ``L / 2 + 2``; the layer before it is the ONE full layer."""
    stated = c["assumed"].get("layer_kinds")
    if stated:
        return list(stated)
    L, per = c["num_hidden_layers"], c["mb_per_layer"]
    cross = L // 2 + 2
    return [("mamba" if l < cross else "gmu") if l % per == 0 else
            "window" if l < cross - 1 else "full" if l < cross else "cross"
            for l in range(L)]


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def attention_params(c: Dict[str, Any], kind: str) -> int:
    """One attention layer's: queries and output (with biases), the four
    ``lambda`` vectors, the pairs' norm; keys and values (with biases) unless
    it is a cross layer."""
    d, hd = c["hidden_size"], head_dim(c)
    kv = 2 * c["num_key_value_heads"] * hd
    own = 2 * d * (d + 1) + 4 * hd + 2 * hd
    return own if kind == "cross" else own + d * kv + kv


def mamba_matmul_params(c: Dict[str, Any]) -> int:
    s, d = sizes(c), c["hidden_size"]
    return (d * 2 * s["inner"] + s["inner"] * (s["dt_rank"] + 2 * s["state"])
            + s["dt_rank"] * s["inner"] + s["inner"] * d)


def mamba_params(c: Dict[str, Any]) -> int:
    """... with the convolution's taps and bias, the step's bias, the decay a
    column a channel, the skip."""
    s = sizes(c)
    return mamba_matmul_params(c) + s["inner"] * (s["conv"] + 3 + s["state"])


def gmu_params(c: Dict[str, Any]) -> int:
    return 2 * c["hidden_size"] * sizes(c)["inner"]


def ffn_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def operator_params(c: Dict[str, Any], kind: str) -> int:
    return mamba_params(c) if kind == "mamba" else gmu_params(c) \
        if kind == "gmu" else attention_params(c, kind)


def count_params(c: Dict[str, Any]) -> int:
    """Parameters held: every layer's operator, feed-forward and two norms
    (scale and bias), the embedding ONCE (it is the head), the final norm."""
    d = c["hidden_size"]
    return (sum(operator_params(c, k) + ffn_params(c) + 4 * d
                for k in layer_kinds(c))
            + c["vocab_size"] * d + 2 * d)


def _matmul_params(c: Dict[str, Any], kinds: Iterable[str]) -> int:
    d, hd = c["hidden_size"], head_dim(c)
    kv = 2 * c["num_key_value_heads"] * hd
    per = {"mamba": mamba_matmul_params(c), "gmu": gmu_params(c),
           "cross": 2 * d * d, "window": 2 * d * d + d * kv,
           "full": 2 * d * d + d * kv}
    return sum(per[k] + ffn_params(c) for k in kinds)


def state_floats(c: Dict[str, Any]) -> int:
    """Floats of state ONE mamba layer carries a sequence."""
    s = sizes(c)
    return s["state"] * s["inner"]


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul
    parameter (the head's among them), causal attention in its plain form (a
    pair's two maps of a head's width, its values of two), a window layer's
    at no more than its window, and the scan's 6 operations a float of state
    a token, three times over."""
    kinds = layer_kinds(c)
    h, hd = c["num_attention_heads"], head_dim(c)
    rows = sum(min(seq_len, 2 * c["sliding_window"]) if k == "window"
               else seq_len for k in kinds if k in ("window", "full",
                                                    "cross"))
    return (6.0 * (_matmul_params(c, kinds)
                   + c["vocab_size"] * c["hidden_size"])
            + 6.0 * h * (hd + 2 * hd) * rows / 2.0
            + 18.0 * kinds.count("mamba") * state_floats(c))


def cache_row_values(c: Dict[str, Any]) -> int:
    """What ONE layer's cache holds a position: a key and a value a
    key-value head."""
    return 2 * c["num_key_value_heads"] * head_dim(c)


def shared_row_readers(c: Dict[str, Any]) -> int:
    """The layers that read the ONE full layer's rows: itself and every
    cross layer."""
    kinds = layer_kinds(c)
    return kinds.count("full") + kinds.count("cross")


def state_bytes(c: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """What ONE mamba layer carries a sequence whatever its length: the
    float32 state and the last ``taps - 1`` inputs of the convolution at
    ``bytes_per_el``."""
    s = sizes(c)
    return 4 * state_floats(c) + bytes_per_el * s["inner"] * (s["conv"] - 1)


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2,
                      depths: Optional[Iterable[int]] = None) -> float:
    """Bytes a decode step must MOVE, a FLOOR: every weight once (the
    embedding table too: it is the head); the ONE full layer's rows at each
    live slot's depth once a READING layer (`shared_row_readers`: the layers
    attend one after the other, each its own queries, and eight passes over
    6.5 GB do not stay on the chip); a window layer's rows up to its window;
    and on every mamba layer each live slot's state ONCE READ AND ONCE
    WRITTEN (the write is the layer's mathematics).

    ``live_rows`` is slots x depth.  With ``depths`` (the depths the run's
    slots stood at, one an emitted token) the slots are ``live_rows /
    mean(depths)`` and a window layer's rows each depth's ``min(depth,
    window)``; without, ONE slot at all the rows."""
    kinds = layer_kinds(c)
    depths = list(depths) if depths is not None else []
    mean = sum(depths) / len(depths) if depths else live_rows
    slots = live_rows / mean
    window = c["sliding_window"]
    ring = sum(min(d, window) for d in depths) / len(depths) if depths \
        else min(live_rows, window)
    rows = (shared_row_readers(c) * live_rows
            + kinds.count("window") * slots * ring) * cache_row_values(c)
    return float((count_params(c) + rows) * bytes_per_el
                 + 2 * slots * kinds.count("mamba")
                 * state_bytes(c, bytes_per_el))


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """The Pallas kernel this family brings: ``selective_scan_chunk``
    (`ray_tpu/ops/selective_scan.py` `chunk`), a chunk program's scan, ONE
    call a mamba layer.  One call over ``batch`` LIVE rows of ``seq_len``
    tokens (a row that stands is not computed): its operands once, all
    float32 (the inputs ``a``, the steps ``dt`` and the output ``m`` ``[c,
    inner]``; a key and a query a token broadcast to a lane tile; the decay
    rates; the state in and out), and 6 operations a float of state a token.
    The kernel is bound by the vector and transcendental units (an ``exp`` a
    float of state a token), so its share of a MEMORY roofline is a floor's.
    ``calls``: the layers that call it a program.  (The decode step's state
    update is XLA's elementwise form; attention goes through
    `ops/cache_attention.py`, not this family's to count.)"""
    s = sizes(c)
    floats = batch * seq_len * state_floats(c)
    per_row = (3 * seq_len * s["inner"] + 2 * seq_len * s["state"] * _LANES
               + 2 * state_floats(c))
    return {"selective_scan_chunk": {
        "chunk_flops": 6.0 * floats,
        "chunk_bytes": 4.0 * (batch * per_row + state_floats(c)
                              + s["inner"]),
        "calls": layer_kinds(c).count("mamba")}}
