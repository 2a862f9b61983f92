"""Mixture-of-experts FFNs: two expert layers and three ways to choose,
each by a model's router kind (`models/transformer.py` `routed_branch`,
`_ffn`), never by a switch.

**No token dropped** (`sigmoid_route` | `softmax_route`, then `routed_ffn`):
the layer of models that route by a score plus a correction bias, the score
a sigmoid over exactly the experts that exist (weights normalised over the
chosen) or a SOFTMAX over the experts AND a number of IDENTITY experts that
compute nothing (`softmax_route`: weights the raw scores, not normalised).
It is TOLD which experts it holds (the stacks it is given, from
``expert_offset``), takes the router's choice over ALL experts, and
computes its own experts' part of the result: a pair whose expert lives on
another chip joins no group here and adds nothing, and no code stands in
for the other chips or their exchange.  Token-expert pairs
are SORTED by expert and the three expert matmuls are grouped matmuls
(`ops/grouped_matmul.py`: row block i of the sorted pairs meets expert i's
weights; on a TPU this repo's kernel, which reads each touched expert's
weights once, elsewhere `jax.lax.ragged_dot`), so the cost follows the
experts the pairs touch, not the number of experts; nothing of size
tokens x experts x capacity is built and no load, however uneven, loses a
token.  A pair whose expert is an IDENTITY expert (``identity_from``)
joins no group either: it is sorted behind the last block with the other
chips' pairs, no matmul sees it, and its weight times the token's own row
is added in the float32 combine (scope ``zero_experts``).  It returns what
it routed (`Load`) for the serve engine's counters.

**Softmax router with capacity** (`route`, `moe_ffn`): the GShard/Switch
einsum formulation for the softmax presets that TRAIN over the mesh's
``ep`` axis; its only callers are those presets.  The reference has no
expert parallelism at all (SURVEY.md §2.4 row 5 — "Absent"); this is the
TPU-native deliverable for that row.  It maps onto the MXU and onto
GSPMD's all_to_all insertion:

  * router logits → top-k gate weights per token,
  * a dense one-hot *dispatch* tensor [batch, seq, experts, capacity]
    scatters tokens into per-expert buffers (einsum, no gather loops),
  * expert FFNs run batched over a leading ``expert`` axis — sharding that
    axis over the mesh's ``ep`` axis makes XLA insert the all_to_all
    dispatch/combine pair over ICI,
  * a *combine* tensor (same shape, gate-weighted) merges expert outputs
    back to token order.

Tokens beyond an expert's capacity are dropped (their combine weight is
zero and the residual connection carries them through unchanged) — the
standard Switch-Transformer overflow policy.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .grouped_matmul import grouped_matmul


class Load(NamedTuple):
    """What one `routed_ffn` call routed (int32 scalars), of the experts
    HELD: those that got at least one pair, the pairs of the fullest, and
    the pairs that landed on any of them; and the pairs of rows that count
    that chose an IDENTITY expert (the int 0 where the router has none)."""
    experts_touched: jnp.ndarray
    load_max: jnp.ndarray
    pairs: jnp.ndarray
    zero_pairs: Any = 0


@jax.named_scope("experts")
def sigmoid_route(y: jnp.ndarray, router_w: jnp.ndarray, bias: jnp.ndarray,
                  top_k: int, scaling: float = 1.0
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """y [n, d] -> (experts [n, k] int32, weights [n, k] float32).

    Scores are ``sigmoid(y W_r)`` in float32.  The ``top_k`` experts are
    chosen by score PLUS ``bias`` [E]; a chosen expert's weight is its
    score alone, normalised over the chosen and times ``scaling``: the
    bias moves the choice and never the weight."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", y.astype(jnp.float32), router_w.astype(jnp.float32)))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scaling
    return idx.astype(jnp.int32), w


@jax.named_scope("experts")
def softmax_route(y: jnp.ndarray, router_w: jnp.ndarray, bias: jnp.ndarray,
                  top_k: int, scaling: float = 1.0
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """y [n, d] -> (experts [n, k] int32, weights [n, k] float32).

    Scores are ``softmax(y W_r)`` in float32 over ALL the router's outputs
    (the experts and, behind them, the identity experts).  The ``top_k`` are
    chosen by score PLUS ``bias``; a chosen output's weight is its score
    alone times ``scaling``, NOT normalised over the chosen: the bias moves
    the choice and never the weight."""
    scores = jax.nn.softmax(jnp.einsum(
        "nd,de->ne", y.astype(jnp.float32), router_w.astype(jnp.float32)),
        axis=-1)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1) * scaling
    return idx.astype(jnp.int32), w


@jax.named_scope("experts")
def routed_ffn(y: jnp.ndarray, idx: jnp.ndarray, w: jnp.ndarray,
               w_in: jnp.ndarray, w_out: jnp.ndarray,
               w_gate: Optional[jnp.ndarray] = None,
               valid: Optional[jnp.ndarray] = None, *,
               expert_offset: int = 0,
               identity_from: Optional[int] = None
               ) -> Tuple[jnp.ndarray, Load]:
    """Every token through each of its chosen experts that is held here,
    none dropped.

    y [n, d]; idx, w [n, k] from a router over ALL the model's experts;
    the stacks hold the ``E`` experts ``expert_offset .. expert_offset + E
    - 1`` (all of them where the model is not shared out): a pair whose
    expert is another chip's joins no group and adds nothing, as a row
    that is not ``valid``.  w_in [E, d, f], w_out [E, f, d],
    w_gate [E, d, f] selects SwiGLU (None -> GELU), or each of the three as
    ``(stack [L, E, .., ..], layer)``: the whole stack of a run of layers
    and which of them this is (a slice of the stack would be copied out
    for the kernel; the kernel indexes the stack where it lies); ``valid``
    [n] bool marks the rows that count (None: all): a row that does not is
    routed nowhere, touches no expert and gets zeros.  ``identity_from``
    (None: the router has none): a pair whose expert is numbered
    ``identity_from`` or above chose an IDENTITY expert, which has no
    weights and no home: it joins no group on any chip, and ``w`` times the
    token's own row is its part of the result.  -> (out [n, d], `Load`).

    The n*k pairs are sorted by expert, so expert e's rows are one block;
    rows past the last block (the invalid ones, sorted to the end under
    the sentinel expert E) belong to no group."""
    dt = y.dtype

    def cast(a):        # [E, .., ..] or (stack, layer), in y's dtype
        return (a[0].astype(dt), a[1]) if isinstance(a, tuple) else \
            a.astype(dt)
    n_experts = (w_in[0] if isinstance(w_in, tuple) else w_in).shape[-3]
    n, k = idx.shape
    pair_expert = idx.reshape(-1) - expert_offset
    here = (pair_expert >= 0) & (pair_expert < n_experts)
    if valid is not None:
        here &= jnp.repeat(valid, k)
    pair_expert = jnp.where(here, pair_expert, n_experts)
    order = jnp.argsort(pair_expert, stable=True)                 # [n*k]
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[pair_expert].add(1)
    sizes = sizes[:n_experts]
    xs = y[order // k]                                            # [n*k, d]
    up = grouped_matmul(xs, cast(w_in), sizes)
    if w_gate is not None:
        z = jax.nn.silu(grouped_matmul(xs, cast(w_gate), sizes)) * up
    else:
        z = jax.nn.gelu(up)
    out = grouped_matmul(z, cast(w_out), sizes)                   # [n*k, d]
    # back to token order: pair j of token i sits at sorted row inv[i*k+j]
    inv = jnp.argsort(order)
    out = out[inv].reshape(n, k, -1)
    # rows of no group hold nothing defined
    out = jnp.where(here.reshape(n, k, 1), out, 0)
    out = jnp.einsum("nkd,nk->nd", out.astype(jnp.float32), w)
    zero_pairs = 0
    if identity_from is not None:
        with jax.named_scope("zero_experts"):
            identity = idx >= identity_from                       # [n, k]
            if valid is not None:
                identity &= valid[:, None]
            out = out + jnp.where(identity, w, 0.0).sum(
                -1, keepdims=True) * y.astype(jnp.float32)
            zero_pairs = identity.sum().astype(jnp.int32)
    return out.astype(dt), Load((sizes > 0).sum().astype(jnp.int32),
                                sizes.max(), sizes.sum(), zero_pairs)


def expert_capacity(seq_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Per-expert buffer size: ``ceil(tokens * k / E * factor)`` rounded up
    to a multiple of 8 (TPU sublane alignment)."""
    cap = math.ceil(seq_tokens * top_k * capacity_factor / n_experts)
    return max(8, ((cap + 7) // 8) * 8)


@jax.named_scope("experts")
def route(y: jnp.ndarray, router_w: jnp.ndarray, top_k: int,
          capacity: int):
    """Compute dispatch/combine tensors.

    y: [b, s, d] activations; router_w: [d, E].
    Returns (dispatch [b,s,E,C] bool-ish, combine [b,s,E,C] float32,
    aux_loss scalar) where aux_loss is the Switch load-balancing loss.
    """
    b, s, _ = y.shape
    n_experts = router_w.shape[-1]
    logits = jnp.einsum("bsd,de->bse", y.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)                     # [b,s,E]
    gate_k, idx_k = jax.lax.top_k(gates, top_k)                 # [b,s,k]
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(idx_k, n_experts, dtype=jnp.float32)  # [b,s,k,E]
    # Position of each (token, choice) within its expert's buffer: running
    # count over the flattened (s*k) selection order.
    flat = onehot.reshape(b, s * top_k, n_experts)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(b, s, top_k, n_experts)
    within = (pos < capacity).astype(jnp.float32) * onehot      # [b,s,k,E]
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)   # [b,s,k,E,C]
    # combine[b,s,e,c] = sum_k gate_k * 1[expert k == e] * 1[slot k == c]
    combine = jnp.einsum("bsk,bske,bskec->bsec",
                         gate_k, within, pos_oh)                # [b,s,E,C]
    dispatch = (combine > 0.0).astype(y.dtype)

    # Switch load-balancing aux loss: E * sum_e f_e * p_e where f_e is the
    # fraction of tokens routed (top-1) to e and p_e the mean gate prob.
    top1 = jax.nn.one_hot(idx_k[..., 0], n_experts, dtype=jnp.float32)
    aux = n_experts * jnp.mean(
        jnp.mean(top1, axis=(0, 1)) * jnp.mean(gates, axis=(0, 1)))
    return dispatch, combine, aux


@jax.named_scope("experts")
def moe_ffn(y: jnp.ndarray, router_w: jnp.ndarray, w_in: jnp.ndarray,
            w_out: jnp.ndarray, w_gate: Optional[jnp.ndarray] = None, *,
            top_k: int = 2, capacity_factor: float = 2.0,
            constrain=None):
    """MoE feed-forward block.

    y [b,s,d]; router_w [d,E]; w_in [E,d,f]; w_out [E,f,d];
    w_gate [E,d,f] selects SwiGLU (None → GELU).
    Returns (out [b,s,d], aux_loss).  ``constrain(x, logical_axes)`` is an
    optional sharding-constraint hook — the expert-major intermediates get
    ("expert", ...) so the `ep` mesh axis produces all_to_alls.
    """
    b, s, d = y.shape
    n_experts = w_in.shape[0]
    dt = y.dtype
    cap = expert_capacity(s, n_experts, top_k, capacity_factor)
    dispatch, combine, aux = route(y, router_w, top_k, cap)

    # dispatch: token-major → expert-major [E, b, C, d] (GSPMD all_to_all
    # happens here when `ep` shards the leading axis and batch shards b)
    xe = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(dt), y)
    if constrain is not None:
        xe = constrain(xe, ("expert", "batch", None, None))
    up = jnp.einsum("ebcd,edf->ebcf", xe, w_in.astype(dt))
    if w_gate is not None:
        gate = jnp.einsum("ebcd,edf->ebcf", xe, w_gate.astype(dt))
        z = jax.nn.silu(gate) * up
    else:
        z = jax.nn.gelu(up)
    oe = jnp.einsum("ebcf,efd->ebcd", z, w_out.astype(dt))
    if constrain is not None:
        oe = constrain(oe, ("expert", "batch", None, None))
    out = jnp.einsum("ebcd,bsec->bsd", oe, combine.astype(dt))
    return out, aux


def moe_ffn_reference(y, router_w, w_in, w_out, w_gate=None, *, top_k=2):
    """Slow per-token loop-free reference (no capacity limit): every token
    is processed by its top-k experts exactly.  Used by tests to validate
    the dispatch-einsum path (which must agree when capacity is ample)."""
    b, s, d = y.shape
    n_experts = w_in.shape[0]
    f32 = jnp.float32
    gates = jax.nn.softmax(jnp.einsum("bsd,de->bse", y.astype(f32),
                                      router_w.astype(f32)), axis=-1)
    gate_k, idx_k = jax.lax.top_k(gates, top_k)
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)
    yf = y.astype(f32)
    up = jnp.einsum("bsd,edf->bsef", yf, w_in.astype(f32))
    if w_gate is not None:
        g = jnp.einsum("bsd,edf->bsef", yf, w_gate.astype(f32))
        z = jax.nn.silu(g) * up
    else:
        z = jax.nn.gelu(up)
    all_out = jnp.einsum("bsef,efd->bsed", z, w_out.astype(f32))  # [b,s,E,d]
    weight = jnp.einsum("bsk,bske->bse", gate_k,
                        jax.nn.one_hot(idx_k, n_experts, dtype=f32))
    return jnp.einsum("bsed,bse->bsd", all_out, weight).astype(y.dtype)
