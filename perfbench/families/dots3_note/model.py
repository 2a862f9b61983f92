"""Family ``dots3_note``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

``x`` is the residual stream, ``rms`` an RMSNorm with a learned scale at
``rms_norm_eps``, ``turn_b`` rotary at base ``b`` (rotate-half pairs, as
`ray_tpu/ops/rotary.py`).  A layer is ``x = x + attention(rms(x))`` then ``x =
x + feed_forward(rms(x))``; which attention follows from ``layer_types``.

A FULL layer (``"full_attention"``), ``H = num_attention_heads``, with ``y =
rms(x)``::

    c_q     = a_q rms(y W_qa)                 a_q  = sqrt(hidden / q_lora_rank)
    q_h     = c_q W_qb,h                      nope | rope, the rope part turned
                                              at rope_theta
    [c|k_r] = y W_kva;  c = a_kv rms(c)       a_kv = sqrt(hidden / kv_lora_rank)
    k_h     = [c W_kb,h | turn(k_r)],  v_h = c W_vb,h
    qI_g    = c_q W_iq,g                      index_n_heads of index_head_dim,
                                              the first rope dims turned
    kI      = LayerNorm(y W_ik)               ONE key a position, likewise
    w       = y W_iw / sqrt(index_n_heads x index_head_dim)         float32
    I[t, j] = sum_g w[t, g] relu(qI[t, g] . kI[j])        j <= t,   float32
    S_t     = the min(t + 1, index_topk) positions j <= t of largest I[t, j],
              equal scores the earlier position first
    o_h     = sum_{j in S_t} softmax_j(q_h . k_j / sqrt(nope + rope)) v_j
    g       = sigmoid(y W_g)                  ONE value a head
    out     = concat_h(g_h o_h) W_o

EVERY full layer indexes for itself: no layer attends another's choice.

A SLIDING layer (``"sliding_attention"``) is the same with its own weights
and the ``swa_*`` sizes (heads, ranks, the part of a head that is not turned,
``swa_rope_theta``), NO indexer, and position ``t`` attends ``j`` with ``0 <=
t - j < sliding_window_size``.

The feed-forward is `glm_moe_dsa`'s: a dense SwiGLU in the leading layers,
then a sigmoid router in float32 with a correction bias for the choice only,
the chosen scores normalised and scaled, beside one shared expert; a final
``rms`` and an untied head.  The published correction bias is TRAINED (it is
what balances the experts without an auxiliary loss): `make` draws it from the
seed, gives it that training's result on the seed's own weights over
calibration tokens (`_balance`), and PLACES the experts on the chips by load
(`_place`), so that the sixteen held are a like sample of popular and idle
ones on every seed.

A configuration may hold a SHARE of the experts (``n_routed_experts`` of
``deployment.experts_routed`` from ``deployment.expert_offset``): the router
scores them all, a token's weights are those of its published choice, and
only the held experts' part of the sum is computed; nothing stands in for the
rest.  A sliced vocabulary is a smaller one.

The reference is that in float32 at ``highest``: no cache, no ring, no
absorption of the key-value up-projection, no kernel, no search over bits: the
selection is a FULL STABLE SORT of ``I[t, :]``, a window is a mask over all
positions, and every held expert is applied to every token under its weight
(zero where not chosen).  It has to fit beside the live engine at 2 x 6 k
positions, so it goes a sequence at a time, attention a head and a block of
queries at a time, the index scores a block of queries at a time, the experts
one at a time out of their stack.  ``precision="fp8"`` is the control
(`reference._round_inputs`); the router's matmul, the indexer's head weights
and its sum over heads stay float32 in it, as the configuration states them
for the program.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
FULL, SLIDING = "full_attention", "sliding_attention"   # ``layer_types``
_KINDS = {FULL: "index", SLIDING: "window"}     # -> the program's kinds
_SIZES = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim")


def _routed(c: Dict[str, Any]) -> int:
    return c["deployment"]["experts_routed"]


def latent_sizes(c: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """A layer kind's own latent sizes and rotary base under the full
    layers' names."""
    pre = "swa_" if kind == SLIDING else ""
    return {"heads": c[pre + "num_attention_heads"],
            "rope_theta": float(c[pre + "rope_theta"]),
            **{k: c[pre + k] for k in _SIZES}}


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["norm_topk_prob"], c["hidden_act"], c["attention_bias"],
            c["scoring_func"], c["topk_method"], c["moe_layer_freq"]) != (
                True, "silu", False, "sigmoid", "noaux_tc", 1):
        raise ValueError("family dots3_note: the program routes by sigmoid "
                         "scores with a correction bias and no group "
                         "limits, normalises the chosen scores, gates with "
                         "SiLU and has no bias")
    if (c["attention_gate_type"], c["swa_attention_gate_type"],
            c["apply_mla_qkv_lora_rescale"], c["rope_scaling"]) != (
                "headwise", "headwise", True, None):
        raise ValueError("family dots3_note: one sigmoid a head on both "
                         "kinds of layer, both latents rescaled, the rotary "
                         "part turned without scaling")
    full, win = latent_sizes(c, FULL), latent_sizes(c, SLIDING)
    if (full["qk_rope_head_dim"], full["v_head_dim"]) != (
            win["qk_rope_head_dim"], win["v_head_dim"]) \
            or set(c["layer_types"]) - set(_KINDS) \
            or len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("family dots3_note: a layer type a layer, "
                         "'full_attention' or 'sliding_attention', and one "
                         "rotary and one value width for both")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=full["heads"],
        d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=full["rope_theta"], window_rope_base=win["rope_theta"],
        activation="swiglu", norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        attention="mla", q_lora_rank=full["q_lora_rank"],
        kv_lora_rank=full["kv_lora_rank"],
        qk_nope_head_dim=full["qk_nope_head_dim"],
        qk_rope_head_dim=full["qk_rope_head_dim"],
        v_head_dim=full["v_head_dim"],
        window_heads=win["heads"], window_q_lora_rank=win["q_lora_rank"],
        window_kv_lora_rank=win["kv_lora_rank"],
        window_qk_nope_head_dim=win["qk_nope_head_dim"],
        sliding_window=c["sliding_window_size"],
        head_gate=True, latent_rescale=True,
        n_experts=_routed(c), experts_held=c["n_routed_experts"],
        expert_offset=c["deployment"]["expert_offset"],
        expert_top_k=c["num_experts_per_tok"], router="sigmoid",
        moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        first_dense_layers=c["first_k_dense_replace"],
        index_heads=c["index_n_heads"], index_head_dim=c["index_head_dim"],
        index_topk=c["index_topk"],
        layer_kinds=tuple(_KINDS[t] for t in c["layer_types"]),
        dtype=_DTYPES[p["compute"]], param_dtype=_DTYPES[p["params"]],
        **overrides)


def param_dtype(c: Dict[str, Any], use: str):
    return _DTYPES[c["precision"][use]["params"]]


def _normal(key: jax.Array, shape, fan_in: float, dtype, lead: int = 0):
    """``normal / sqrt(fan_in)`` of ``shape`` in ``dtype``, drawn a block
    of ``shape[lead:]`` at a time (one key a block) so that no float32 copy
    of more than one block exists: an expert stack is made an expert at a
    time, the embedding some rows at a time."""
    block = tuple(shape[lead:])

    def one(k):
        return (jax.random.normal(k, block, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    if not lead:
        return one(key)
    n = math.prod(shape[:lead])
    return jax.lax.map(one, jax.random.split(key, n)).reshape(shape)


def _rows(key, n_rows: int, width: int, fan_in: float, dtype):
    """[n_rows, width] in blocks of rows."""
    g = math.gcd(n_rows, 1210)
    return _normal(key, (n_rows // g, g, width), fan_in, dtype,
                   lead=1).reshape(n_rows, width)


def _latent(ks, c: Dict[str, Any], kind: str, n: int, dtype,
            suffix: str = "") -> Dict[str, Any]:
    """``n`` layers' latent-attention weights at the sizes of ``kind``,
    stacked, under their names + ``suffix``: the seven of latent attention
    and the gate a head."""
    d, z = c["hidden_size"], latent_sizes(c, kind)
    h, nope, rope, v = (z["heads"], z["qk_nope_head_dim"],
                        z["qk_rope_head_dim"], z["v_head_dim"])
    ql, kl = z["q_lora_rank"], z["kv_lora_rank"]
    # the up-projections from the latents are drawn at the scale the
    # rescale is published to correct: with ``sqrt(hidden / rank)`` on a
    # latent of unit size, weights of ``1 / sqrt(hidden)`` give a head's
    # query, key and value the unit variance every other projection's
    # output has (drawn at ``1 / sqrt(rank)`` they would stand 2.2-3.2
    # times over it, and the scores' softmax 5-7 times sharper than the
    # rotary part's: the file's ``assumed.weights``)
    shapes = {"wq_a": ((d, ql), d), "wq_b": ((ql, h, nope + rope), d),
              "wkv_a": ((d, kl + rope), d), "wkv_b": ((kl, h, nope + v), d),
              "wo": ((h, v, d), h * v), "wg": ((d, h), d)}
    p = {name + suffix: _normal(ks[name + suffix], (n,) + shape, fan_in,
                                dtype, lead=1)
         for name, (shape, fan_in) in shapes.items()}
    p["q_norm" + suffix] = jnp.ones((n, ql), dtype)
    p["kv_norm" + suffix] = jnp.ones((n, kl), dtype)
    return p


_WIN = "_win"       # the program's stacks over the sliding layers alone
_LATENT = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wg")


def _run(key: jax.Array, c: Dict[str, Any], types, moe: bool, dtype):
    """One run's stacked tree for the layers of ``types`` (their
    ``layer_types`` entries): latent attention with an indexer over the full
    ones alone, the sliding layers' latent attention (``*_win``) over those
    alone, norms and the feed-forward over all."""
    d = c["hidden_size"]
    L, n_full = len(types), sum(t == FULL for t in types)
    names = _LATENT + tuple(n + _WIN for n in _LATENT) + (
        "w_in", "w_gate", "w_out", "router", "router_bias", "ws_in",
        "ws_gate", "ws_out", "wi_q", "wi_k", "wi_w")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, shape, fan_in, lead=1, n=L):
        return _normal(ks[name], (n,) + shape, fan_in, dtype, lead=lead)

    p = {"attn_norm": jnp.ones((L, d), dtype),
         "mlp_norm": jnp.ones((L, d), dtype)}
    if n_full:
        hi, di, ql = c["index_n_heads"], c["index_head_dim"], c["q_lora_rank"]
        p.update(_latent(ks, c, FULL, n_full, dtype),
                 wi_q=stack("wi_q", (ql, hi, di), ql, n=n_full),
                 wi_k=stack("wi_k", (d, di), d, n=n_full),
                 wi_w=stack("wi_w", (d, hi), d, n=n_full),
                 ik_norm=jnp.ones((n_full, di), dtype),
                 ik_norm_b=jnp.zeros((n_full, di), dtype))
    if L - n_full:
        p.update(_latent(ks, c, SLIDING, L - n_full, dtype, _WIN))
    if not moe:
        f = c["intermediate_size"]
        p.update(w_in=stack("w_in", (d, f), d),
                 w_gate=stack("w_gate", (d, f), d),
                 w_out=stack("w_out", (f, d), f))
        return p
    E, held, f = _routed(c), c["n_routed_experts"], c["moe_intermediate_size"]
    fs = c["n_shared_experts"] * f
    p.update(
        router=stack("router", (d, E), d),
        # drawn, not zero, so that it changes choices (the file's
        # ``assumed``): a trained model's bias is what balanced its experts
        router_bias=(jax.random.normal(ks["router_bias"], (L, E), jnp.float32)
                     * c["assumed"]["e_score_correction_bias_std"]
                     ).astype(dtype),
        w_in=stack("w_in", (held, d, f), d, lead=2),
        w_gate=stack("w_gate", (held, d, f), d, lead=2),
        w_out=stack("w_out", (held, f, d), f, lead=2),
        ws_in=stack("ws_in", (d, fs), d),
        ws_gate=stack("ws_gate", (d, fs), d),
        ws_out=stack("ws_out", (fs, d), fs))
    return p


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    the leading dense layers one stacked run, the expert layers another,
    each with its full layers' stacks and its sliding layers' ``*_win``
    stacks.  ONE compiled program a call (`_as_one_program`)."""
    return _as_one_program(_make, c=c, dtype=dtype)(key)


def _as_one_program(fn, **fixed):
    """``fn`` with its configuration bound, compiled as one program: a layer
    at a time in Python is hundreds of small programs when called eagerly,
    and inside a caller's own `jax.jit` this is no program of its own."""
    return jax.jit(functools.partial(fn, **fixed))


def _make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = c["first_k_dense_replace"]
    types = c["layer_types"]
    k_tok, k_head, k_dense, k_moe = jax.random.split(key, 4)
    params = {
        # rows of unit scale (fan_in 1: a row is looked up, not summed): a
        # token's own embedding is the size of what a layer adds to it
        "embed": {"tok": _rows(k_tok, v, d, 1.0, dtype)},
        "dense_layers": _run(k_dense, c, types[:n_dense], False, dtype),
        "layers": _run(k_moe, c, types[n_dense:], True, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": _rows(k_head, d, v, d, dtype),
    }
    n = c["assumed"].get("expert_bias_balance_tokens", 0)
    if n:
        seen = tokens(jax.random.fold_in(key, 7), (n,), c)
        _, routers = _walk(params, seen, c, "float32", functools.partial(
            _place, k=c["num_experts_per_tok"], held=c["n_routed_experts"]))
        for name in ("router", "router_bias"):
            params["layers"][name] = jnp.stack(
                [r[name] for r in routers]).astype(dtype)
    return params


def _place(scores, lp, k: int, held: int):
    """One expert layer's router as a deployment would leave it, from the
    scores [n, E] of calibration tokens: the bias balanced (`_balance`), and
    the experts PLACED on the chips by load: ranked by the pairs they still
    draw under that bias and dealt to the ``E / held`` chips in turn, so
    that every chip's ``held`` experts are a like sample of popular and
    idle ones.  With random weights an expert's number names nothing, so
    placing is a reordering of the router's columns (and the bias with
    them)."""
    E = scores.shape[-1]
    bias = _balance(scores, lp["router_bias"].astype(F32), k)
    _, chosen = jax.lax.top_k(scores + bias, k)
    load = jnp.zeros((E,), F32).at[chosen.reshape(-1)].add(1.0)
    ranked = jnp.argsort(-load)                     # expert of rank r
    rank = jnp.arange(E)
    seat = (rank % (E // held)) * held + rank // (E // held)
    source = jnp.zeros((E,), jnp.int32).at[seat].set(ranked)
    return {"router": lp["router"][:, source], "router_bias": bias[source]}


def _balance(scores, bias, k: int, steps: int = 64, rate: float = 0.05):
    """scores [n, E] of n tokens, a starting bias [E] -> the bias after the
    balancing update of a router trained without an auxiliary loss: first
    each expert's mean score excess is taken off, then ``steps`` times the
    experts chosen under the bias are counted and an expert with more than
    its even share of the pairs loses ``rate`` (falling to 0), one with
    fewer gains it."""
    n, E = scores.shape
    even = n * k / E
    bias = bias - (scores.mean(0) - scores.mean())

    def step(i, b):
        _, chosen = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((E,), F32).at[chosen.reshape(-1)].add(1.0)
        return b + rate * (1.0 - i / steps) * jnp.sign(even - load)

    return jax.lax.fori_loop(0, steps, step, bias)


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    return jax.random.randint(key, shape, 0, c["vocab_size"], jnp.int32)


# ------------------------------------------------------ the plain reference

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale.astype(F32) \
        + bias.astype(F32)


def _rotate(x, theta, pos=None):
    """x [..., s, rope] at positions ``pos`` [s] (None: 0 .. s - 1): the
    pair (x[i], x[i + rope/2]) turned by the angle pos * theta^(-2i/rope)."""
    s, hd = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    pos = jnp.arange(s) if pos is None else pos
    ang = pos.astype(F32)[:, None] * freq[None, :]
    lo, hi = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], axis=-1)


def _rotate_first(x, theta, rope, pos=None):
    return jnp.concatenate([_rotate(x[..., :rope], theta, pos),
                            x[..., rope:]], axis=-1)


def _swiglu(r, y, w_in, w_gate, w_out, acc=None):
    """``acc + SwiGLU(y)`` for ``y`` [s, d] (``acc`` None: 0), its width
    taken 2048 at a time: a dense layer's ``[s, 12288]`` float32 products,
    and its weights in float32, are not held whole."""
    d, f = w_in.shape
    block = math.gcd(f, 2048)
    acc = jnp.zeros(y.shape, F32) if acc is None else acc
    y = r(y)

    def some_width(i, acc):
        cut = functools.partial(jax.lax.dynamic_slice_in_dim,
                                start_index=i * block, slice_size=block)
        up = jnp.einsum("sd,df->sf", y, r(cut(w_in, axis=1)))
        gate = jnp.einsum("sd,df->sf", y, r(cut(w_gate, axis=1)))
        return acc + jnp.einsum(
            "sf,fd->sd", r(gate * jax.nn.sigmoid(gate) * up),
            r(cut(w_out, axis=0)))

    if f == block:
        return some_width(0, acc)
    return jax.lax.fori_loop(0, f // block, some_width, acc)


def _query_block(s: int) -> int:
    """Queries scored at a time: a divisor of ``s``."""
    return math.gcd(s, 512)


def selection(r, y, c_q, lp, c) -> jnp.ndarray:
    """One sequence's normed input ``y`` [s, d] and (rescaled) query latents
    ``c_q`` [s, q_lora] -> [s, s] bool: ``S_t`` of each position by a full
    stable sort of its scores, a block of queries at a time."""
    s = y.shape[0]
    hi, di = c["index_n_heads"], c["index_head_dim"]
    rope, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    topk = c["index_topk"]
    k = _layer_norm(jnp.einsum("sd,dk->sk", r(y), r(lp["wi_k"])),
                    lp["ik_norm"], lp["ik_norm_b"],
                    c["assumed"]["index_key_norm_eps"])
    k = _rotate_first(k, theta, rope)                          # [s, di]
    w = jnp.einsum("sd,dh->sh", y.astype(F32), lp["wi_w"].astype(F32)) \
        / math.sqrt(hi * di)
    qb = _query_block(s)

    def some_queries(inp):
        cj, wj, t = inp                  # [qb, q_lora], [qb, hi], [qb]
        qj = _rotate_first(jnp.einsum("qr,rhk->hqk", r(cj), r(lp["wi_q"])),
                           theta, rope, t)                 # [hi, qb, di]
        dots = jnp.einsum("hqk,sk->qhs", r(qj), r(k))
        score = jnp.einsum("qhs,qh->qs", jax.nn.relu(dots), wj)
        allowed = jnp.arange(s)[None, :] <= t[:, None]
        # descending, equal scores the earlier position first, what a query
        # may not see last
        order = jnp.argsort(jnp.where(allowed, -score, jnp.inf), axis=-1,
                            stable=True)[:, :topk]
        chosen = jnp.zeros((qb, s), bool).at[
            jnp.arange(qb)[:, None], order].set(True)
        return chosen & allowed

    blocks = (c_q.reshape(s // qb, qb, -1), w.reshape(s // qb, qb, hi),
              jnp.arange(s).reshape(s // qb, qb))
    return jax.lax.map(some_queries, blocks).reshape(s, s)


def window(s: int, c: Dict[str, Any]) -> jnp.ndarray:
    """[s, s] bool: position t attends j with ``0 <= t - j <
    sliding_window_size`` (the window counts the query's own position)."""
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    return (back >= 0) & (back < c["sliding_window_size"])


def attention(r, y, lp, c, kind: str):
    """One sequence's normed input ``y`` [s, d] -> what a layer of ``kind``
    adds [s, d]; ``lp`` holds the layer's weights under the full layers'
    names (`_layer_weights`)."""
    z, d, eps = latent_sizes(c, kind), c["hidden_size"], c["rms_norm_eps"]
    theta, nope, kl = z["rope_theta"], z["qk_nope_head_dim"], z["kv_lora_rank"]
    rope = z["qk_rope_head_dim"]
    s = y.shape[0]
    c_q = math.sqrt(d / z["q_lora_rank"]) * _rms(
        jnp.einsum("sd,dr->sr", r(y), r(lp["wq_a"])), lp["q_norm"], eps)
    ckv = jnp.einsum("sd,dr->sr", r(y), r(lp["wkv_a"]))
    c_kv = math.sqrt(d / kl) * _rms(ckv[:, :kl], lp["kv_norm"], eps)
    k_r = _rotate(ckv[:, kl:], theta)                          # [s, rope]
    sees = selection(r, y, c_q, lp, c) if kind == FULL else window(s, c)
    gate = jax.nn.sigmoid(jnp.einsum("sd,dh->sh", r(y), r(lp["wg"])))
    qb = _query_block(s)

    def one_head(acc, j):
        # this head's [ql, nope + rope], [kl, nope + v] and [v, d], cut out
        # inside the loop (the stacks turned heads-first would be copies)
        wq, wkv = (jax.lax.dynamic_index_in_dim(lp[n], j, axis=1,
                                                keepdims=False)
                   for n in ("wq_b", "wkv_b"))
        wo = jax.lax.dynamic_index_in_dim(lp["wo"], j, axis=0,
                                          keepdims=False)
        q = jnp.einsum("sr,rk->sk", r(c_q), r(wq))
        q = jnp.concatenate([q[:, :nope], _rotate(q[:, nope:], theta)], -1)
        kv = jnp.einsum("sr,rk->sk", r(c_kv), r(wkv))
        k = jnp.concatenate([kv[:, :nope], k_r], axis=-1)
        v = kv[:, nope:]

        def some_queries(inp):
            qj, mj = inp
            scores = jnp.einsum("qk,tk->qt", r(qj), r(k)) \
                / math.sqrt(nope + rope)
            probs = jax.nn.softmax(jnp.where(mj, scores, -jnp.inf), axis=-1)
            return jnp.einsum("qt,tv->qv", r(probs), r(v))

        a = jax.lax.map(some_queries, (q.reshape(s // qb, qb, -1),
                                       sees.reshape(s // qb, qb, s)))
        a = a.reshape(s, -1) * jax.lax.dynamic_index_in_dim(gate, j, axis=1)
        return acc + jnp.einsum("sv,vd->sd", r(a), r(wo)), None

    out, _ = jax.lax.scan(one_head, jnp.zeros_like(y, dtype=F32),
                          jnp.arange(lp["wo"].shape[0]))
    return out


def _scores(y, lp):
    """y [s, d] normed -> the router's sigmoid scores [s, E], float32."""
    return jax.nn.sigmoid(jnp.einsum("sd,de->se", y.astype(F32),
                                     lp["router"].astype(F32)))


def expert_weights(y, lp, c):
    """y [s, d] normed -> [s, E] float32: each expert's weight for each
    token over ALL the layer's experts, zero where the token did not choose
    it."""
    s = _scores(y, lp)
    _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(F32),
                              c["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * c["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, s.shape[-1], dtype=F32)    # [s, k, E]
    return jnp.einsum("sk,ske->se", w, onehot)


def routed_part(r, y, lp, c, offset: int, held: int, layer: int,
                acc=None):
    """``acc`` (None: 0) plus the part of an expert layer's routed sum that
    the ``held`` experts from ``offset`` give for ``y`` [s, d]: every one of
    them applied to
    every token under its weight, cut out of its stack ``lp[name]`` [L,
    held, ...] inside the loop (a slice of a layer's experts would be a
    copy of them)."""
    weight = jax.lax.dynamic_slice_in_dim(expert_weights(y, lp, c), offset,
                                          held, axis=1)

    def one_expert(acc, e):
        w_in, w_gate, w_out = (jax.lax.dynamic_slice(
            lp[n], (layer, e, 0, 0), (1, 1) + lp[n].shape[2:])[0, 0]
            for n in ("w_in", "w_gate", "w_out"))
        return acc + jax.lax.dynamic_index_in_dim(
            weight, e, axis=1) * _swiglu(r, y, w_in, w_gate, w_out), None

    out, _ = jax.lax.scan(one_expert,
                          jnp.zeros_like(y) if acc is None else acc,
                          jnp.arange(held))
    return out


def _layer_weights(tree, types, at: int, routed: bool):
    """Layer ``at`` of a run's stacked tree whose layers' types are
    ``types``, under the full layers' names: a stack over one kind of layer
    alone is read at the count of that kind's layers before it (a sliding
    layer's ``*_win`` stacks, a full layer's latent attention and indexer),
    the routed experts' stacks whole (`routed_part` indexes them)."""
    kind = types[at]
    before = sum(t == kind for t in types[:at])
    own = _LATENT + ("q_norm", "kv_norm")
    lp = {}
    for name, a in tree.items():
        if name.endswith(_WIN):
            if kind == SLIDING:
                lp[name[:-len(_WIN)]] = a[before]
        elif name in own + ("wi_q", "wi_k", "wi_w", "ik_norm", "ik_norm_b"):
            if kind == FULL:
                lp[name] = a[before]
        elif routed and name in ("w_in", "w_gate", "w_out"):
            lp[name] = a
        else:
            lp[name] = a[at]
    return lp


def _walk(params, toks, c, precision: str, reroute=None):
    """One sequence's tokens [s] through the layers -> (final hidden states
    [s, d], the expert layers' routers).  With ``reroute(scores [s, E], lp)
    -> {router, router_bias}`` each expert layer's router is first set from
    the scores of these very tokens and the layer then routes by it
    (`make`'s calibration)."""
    r = _round_inputs(precision)
    routers = []
    eps = c["rms_norm_eps"]
    held, offset = c["n_routed_experts"], c["deployment"]["expert_offset"]
    n_dense, types = c["first_k_dense_replace"], c["layer_types"]
    x = params["embed"]["tok"][toks].astype(F32)

    def block(x, lp, kind, layer):
        x = x + attention(r, _rms(x, lp["attn_norm"], eps), lp, c, kind)
        y = _rms(x, lp["mlp_norm"], eps)
        if layer is None:
            return _swiglu(r, y, lp["w_in"], lp["w_gate"], lp["w_out"], x)
        if reroute is not None:
            lp = dict(lp, **reroute(_scores(y, lp), lp))
        routers.append({k: lp[k] for k in ("router", "router_bias")})
        x = _swiglu(r, y, lp["ws_in"], lp["ws_gate"], lp["ws_out"], x)
        return routed_part(r, y, lp, c, offset, held, layer, x)

    for i in range(c["num_hidden_layers"]):
        routed = i >= n_dense
        run, at, mine = ("layers", i - n_dense, types[n_dense:]) if routed \
            else ("dense_layers", i, types[:n_dense])
        lp = _layer_weights(params[run], mine, at, routed)
        layer = functools.partial(block, kind=types[i],
                                  layer=at if routed else None)
        # (a calibration pass takes no gradient, and hands its routers out)
        x = (layer if reroute is not None else jax.checkpoint(layer))(x, lp)
    return _rms(x, params["final_norm"], eps), routers


def _sequence_hidden(params, toks, c, precision: str):
    """One sequence's tokens [s] -> final hidden states [s, d]."""
    return _walk(params, toks, c, precision)[0]


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32, a sequence
    at a time."""
    return jax.lax.map(functools.partial(
        _sequence_hidden, params, c=c, precision=precision), tokens)


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, vocabulary] float32, one compiled program a call."""
    return _as_one_program(_logits, c=c, precision=precision)(params, tokens)


def _logits(params, tokens, c, precision: str) -> jnp.ndarray:
    """The head is an eighth of the vocabulary's and is taken whole (0.39 GB
    in float32): a loop over blocks of it carries the logits, and the chip's
    compiler then holds them twice (`glm_moe_dsa`'s finding, PR 46)."""
    r = _round_inputs(precision)
    with jax.default_matmul_precision("highest"):
        x = r(hidden(params, tokens, c, precision))
        return jnp.einsum("bsd,dv->bsv", x, r(params["lm_head"]))


def loss(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """Mean next-token cross entropy over positions 0..s-2; the router's
    bias is a constant and there is no auxiliary loss."""
    lg = _logits(params, tokens, c, precision)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean()


def loss_and_grad(params, tokens, c, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            functools.partial(loss, c=c, precision=precision))(
                params, tokens)
