"""Family ``gpt2``, the part that imports no JAX (the runner and the metric
readers use it): what the traffic may draw and a cache may hold, and the
counts from shapes alone.  The shapes are GPT-2's own keys in the
configuration file.  The interface is `manifest.FAMILY_INTERFACE`.

The counts are copied from the program's arithmetic (models/transformer.py
``flops_per_token``, ``decode_flops_per_token``, ``count_params``) so that
a later change to the program cannot move the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict


def vocab(c: Dict[str, Any]) -> int:
    """Traffic draws its token ids below this: the PUBLISHED vocabulary
    (the padding rows are never asked for)."""
    return c["published"]["vocab_size"]


def positions(c: Dict[str, Any]) -> int:
    """Positions a sequence, and so a cache, may hold."""
    return c["n_positions"]


def _dims(c: Dict[str, Any]):
    d, L, h = c["n_embd"], c["n_layer"], c["n_head"]
    return d, L, h, d // h, c["n_inner"], c["vocab_size"]


def matmul_params_per_layer(c: Dict[str, Any]) -> int:
    d, _, h, hd, ff, _ = _dims(c)
    return 4 * d * h * hd + 2 * d * ff           # q, k, v, o; in, out


def count_params(c: Dict[str, Any]) -> int:
    d, L, _, _, _, v = _dims(c)
    per_layer = matmul_params_per_layer(c) + 4 * d      # two LayerNorms
    return L * per_layer + v * d + c["n_positions"] * d + 2 * d


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 per matmul
    parameter (the tied logits matmul runs once each way) plus causal
    attention, qk and pv over half the positions."""
    d, L, h, hd, _, v = _dims(c)
    n_matmul = L * matmul_params_per_layer(c) + v * d
    return 6.0 * n_matmul + 6.0 * L * h * hd * seq_len


def decode_flops_per_token(c: Dict[str, Any], context_len: int) -> float:
    d, L, h, hd, _, v = _dims(c)
    n_matmul = L * matmul_params_per_layer(c) + v * d
    return 2.0 * n_matmul + 4.0 * L * h * hd * context_len


def decode_step_bytes(c: Dict[str, Any], live_rows: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step has to read: every weight once, and the keys
    and values of the rows that hold a token (``live_rows`` summed over
    the active slots).  Writes (one row per slot) are left out."""
    d, L, h, hd, _, _ = _dims(c)
    weights = count_params(c) * bytes_per_el
    cache = 2 * L * live_rows * h * hd * bytes_per_el
    return float(weights + cache)


def flash_attention_cost(c: Dict[str, Any], batch: int, seq_len: int,
                         bytes_per_el: int = 2) -> Dict[str, float]:
    """One layer's causal flash attention over ``batch`` sequences, forward
    and the two backward kernels together.  Operations: qk and pv forward
    (2 matmuls), backward recomputes qk and makes dv, dp, dq, dk (5), all
    over the causal half.  Bytes: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    _, _, h, hd, _, _ = _dims(c)
    mm = 2.0 * batch * h * seq_len * seq_len * hd / 2.0   # one causal matmul
    tensor = batch * seq_len * h * hd * bytes_per_el
    return {"fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
            "fwd_bytes": 4.0 * tensor, "bwd_bytes": 8.0 * tensor}


def kernels(c: Dict[str, Any], batch: int, seq_len: int
            ) -> Dict[str, Dict[str, float]]:
    """Each kernel of a training step over ``batch`` sequences of
    ``seq_len``: one call's operations and bytes by pass, and ``calls``,
    the number of layers that call it in a step."""
    return {"flash_attention": dict(
        flash_attention_cost(c, batch, seq_len), calls=c["n_layer"])}
