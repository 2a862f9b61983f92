"""Family ``glm_moe_dsa``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

The block is `glm4_moe_lite`'s (``perfbench/families/glm4_moe_lite/model.py``
has its equations: pre-RMSNorm with ``rms_norm_eps``, latent attention, a
dense SwiGLU in the leading layers, then a sigmoid router in float32 with a
correction bias for the choice only, the chosen scores normalised and scaled,
beside one shared expert; untied head) with LEARNED SPARSE ATTENTION.  With
``y_t`` the block's normed input at position ``t`` and ``c_q,t = rmsnorm(y_t
W_qa)`` the query latent, a layer whose ``indexer_types`` entry is ``"full"``
computes::

    qI[t, j] = (c_q,t W_iq)[j]            j = 1..index_n_heads, index_head_dim
    kI[t]    = LayerNorm(y_t W_ik)        ONE key a position (eps 1e-6)
    rotary on the first qk_rope_head_dim dims of qI[t, j] and kI[t]
    w[t, j]  = (y_t W_w)[j] / sqrt(index_n_heads x index_head_dim)   float32
    I[t, s]  = sum_j w[t, j] relu(qI[t, j] . kI[s])      s <= t,     float32
    S_t      = the min(t + 1, index_topk) positions s <= t of largest
               I[t, s], equal scores the earlier position first

and EVERY layer's softmax runs over ``s in S_t`` only, of the same scores
``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``; a ``"shared"``
layer holds no indexer and uses the ``S_t`` of the nearest ``"full"`` layer
before it.

A configuration may hold a SHARE of the experts (``n_routed_experts`` of
``deployment.experts_routed`` from ``deployment.expert_offset``): the router
scores them all, a token's weights are those of its published choice, and
only the held experts' part of the sum is computed; nothing stands in for the
rest.  A sliced vocabulary is a smaller one.

The reference is that in float32 at ``highest``: no cache, no absorption of
the key-value up-projection, no kernel, no search over bits: the selection
is a FULL STABLE SORT of ``I[t, :]``, and every held expert is applied to
every token under its weight (zero where not chosen).  It has to fit beside
the live engine (11.4 GB) at 2 x 11 k positions, so it goes a sequence at a
time, attention a head and a block of queries at a time (``[64, s, s]``
float32 would be 32 GB), the index scores a block of queries at a time, the
experts one at a time out of their stack, the head a block of the vocabulary
at a time.  ``precision="fp8"`` is the control (`reference._round_inputs`);
the router's matmul, the indexer's head weights and its sum over heads stay
float32 in it, as the configuration states them for the program.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_KINDS = {"full": "index", "shared": "shared"}   # indexer_types -> the
#   program's layer kinds


def _routed(c: Dict[str, Any]) -> int:
    return c["deployment"]["experts_routed"]


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["n_group"], c["topk_group"], c["norm_topk_prob"], c["hidden_act"],
            c["attention_bias"], c["scoring_func"]) != (
                1, 1, True, "silu", False, "sigmoid"):
        raise ValueError("family glm_moe_dsa: the program routes by sigmoid "
                         "scores without group limits, normalises the "
                         "chosen scores, gates with SiLU and has no bias")
    if c["num_nextn_predict_layers"] \
            or c["rope_parameters"]["rope_type"] != "default":
        raise ValueError("family glm_moe_dsa: the program holds no "
                         "multi-token-prediction module and turns the "
                         "rotary part without scaling")
    n_dense = c["first_k_dense_replace"]
    if c["mlp_layer_types"] != ["dense"] * n_dense + ["sparse"] * (
            c["num_hidden_layers"] - n_dense) \
            or len(c["indexer_types"]) != c["num_hidden_layers"]:
        raise ValueError("family glm_moe_dsa: leading dense layers, then "
                         "expert layers, and an indexer type a layer")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=float(c["rope_parameters"]["rope_theta"]),
        activation="swiglu", norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        attention="mla", q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        n_experts=_routed(c), experts_held=c["n_routed_experts"],
        expert_offset=c["deployment"]["expert_offset"],
        expert_top_k=c["num_experts_per_tok"], router="sigmoid",
        moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        first_dense_layers=n_dense,
        index_heads=c["index_n_heads"], index_head_dim=c["index_head_dim"],
        index_topk=c["index_topk"],
        layer_kinds=tuple(_KINDS[t] for t in c["indexer_types"]),
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


def _run(key: jax.Array, c: Dict[str, Any], types, moe: bool, dtype):
    """One run's stacked tree for the layers of ``types`` (their
    ``indexer_types`` entries): an indexer's weights over the ``"full"``
    ones alone."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    L, n_idx = len(types), sum(t == "full" for t in types)
    names = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_in", "w_gate",
             "w_out", "router", "router_bias", "ws_in", "ws_gate", "ws_out",
             "wi_q", "wi_k", "wi_w")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, shape, fan_in, lead=1, n=L):
        return _normal(ks[name], (n,) + shape, fan_in, dtype, lead=lead)

    p = {"attn_norm": jnp.ones((L, d), dtype),
         "mlp_norm": jnp.ones((L, d), dtype),
         "q_norm": jnp.ones((L, ql), dtype),
         "kv_norm": jnp.ones((L, kl), dtype),
         "wq_a": stack("wq_a", (d, ql), d),
         "wq_b": stack("wq_b", (ql, h, nope + rope), ql),
         "wkv_a": stack("wkv_a", (d, kl + rope), d),
         "wkv_b": stack("wkv_b", (kl, h, nope + v), kl),
         "wo": stack("wo", (h, v, d), h * v)}
    if n_idx:
        hi, di = c["index_n_heads"], c["index_head_dim"]
        p.update(wi_q=stack("wi_q", (ql, hi, di), ql, n=n_idx),
                 wi_k=stack("wi_k", (d, di), d, n=n_idx),
                 wi_w=stack("wi_w", (d, hi), d, n=n_idx),
                 ik_norm=jnp.ones((n_idx, di), dtype),
                 ik_norm_b=jnp.zeros((n_idx, di), dtype))
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
    the leading dense layers one stacked run, the expert layers another, an
    indexer's weights stacked over each run's indexing layers.  ONE compiled
    program a call (`_as_one_program`)."""
    return _as_one_program(_make, c=c, dtype=dtype)(key)


def _as_one_program(fn, **fixed):
    """``fn`` with its configuration bound, compiled as one program: a layer
    at a time in Python is hundreds of small programs when called eagerly,
    and inside a caller's own `jax.jit` this is no program of its own."""
    return jax.jit(functools.partial(fn, **fixed))


def _make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = c["first_k_dense_replace"]
    types = c["indexer_types"]
    k_tok, k_head, k_dense, k_moe = jax.random.split(key, 4)
    return {
        # rows of unit scale (fan_in 1: a row is looked up, not summed): a
        # token's own embedding is the size of what a layer adds to it
        "embed": {"tok": _rows(k_tok, v, d, 1.0, dtype)},
        "dense_layers": _run(k_dense, c, types[:n_dense], False, dtype),
        "layers": _run(k_moe, c, types[n_dense:], True, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": _rows(k_head, d, v, d, dtype),
    }


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
    """One sequence's normed input ``y`` [s, d] and query latents ``c_q``
    [s, q_lora] -> [s, s] bool: ``S_t`` of each position by a full stable
    sort of its scores, a block of queries at a time."""
    s = y.shape[0]
    hi, di = c["index_n_heads"], c["index_head_dim"]
    rope, theta = c["qk_rope_head_dim"], float(c["rope_parameters"]["rope_theta"])
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


def attention(r, y, lp, c, sel):
    """One sequence's normed input ``y`` [s, d] -> (what attention adds [s,
    d], the selection its layer attended [s, s]): the layer's own if it has
    an indexer, else ``sel`` as handed in."""
    eps, theta = c["rms_norm_eps"], float(c["rope_parameters"]["rope_theta"])
    nope, kl = c["qk_nope_head_dim"], c["kv_lora_rank"]
    rope = c["qk_rope_head_dim"]
    s = y.shape[0]
    c_q = _rms(jnp.einsum("sd,dr->sr", r(y), r(lp["wq_a"])), lp["q_norm"],
               eps)
    ckv = jnp.einsum("sd,dr->sr", r(y), r(lp["wkv_a"]))
    c_kv = _rms(ckv[:, :kl], lp["kv_norm"], eps)
    k_r = _rotate(ckv[:, kl:], theta)                          # [s, rope]
    if "wi_q" in lp:
        sel = selection(r, y, c_q, lp, c)
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
                                       sel.reshape(s // qb, qb, s)))
        return acc + jnp.einsum("sv,vd->sd", r(a.reshape(s, -1)), r(wo)), None

    out, _ = jax.lax.scan(one_head, jnp.zeros_like(y, dtype=F32),
                          jnp.arange(lp["wo"].shape[0]))
    return out, sel


def expert_weights(y, lp, c):
    """y [s, d] normed -> [s, E] float32: each expert's weight for each
    token over ALL the layer's experts, zero where the token did not choose
    it."""
    s = jax.nn.sigmoid(jnp.einsum("sd,de->se", y.astype(F32),
                                  lp["router"].astype(F32)))
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
    """Layer ``at`` of a run's stacked tree whose layers' indexer types are
    ``types``: an indexer's weights by the count of indexing layers before
    it, the routed experts' stacks whole (`routed_part` indexes them)."""
    lp = {}
    for name, a in tree.items():
        if name in ("wi_q", "wi_k", "wi_w", "ik_norm", "ik_norm_b"):
            if types[at] == "full":
                lp[name] = a[sum(t == "full" for t in types[:at])]
        elif routed and name in ("w_in", "w_gate", "w_out"):
            lp[name] = a
        else:
            lp[name] = a[at]
    return lp


def _sequence_hidden(params, toks, c, precision: str):
    """One sequence's tokens [s] -> final hidden states [s, d]."""
    r = _round_inputs(precision)
    eps = c["rms_norm_eps"]
    held, offset = c["n_routed_experts"], c["deployment"]["expert_offset"]
    n_dense, types = c["first_k_dense_replace"], c["indexer_types"]
    x = params["embed"]["tok"][toks].astype(F32)
    sel = jnp.zeros((toks.shape[0],) * 2, bool)

    def block(x, sel, lp, layer):
        a, sel = attention(r, _rms(x, lp["attn_norm"], eps), lp, c, sel)
        x = x + a
        y = _rms(x, lp["mlp_norm"], eps)
        if layer is None:
            return _swiglu(r, y, lp["w_in"], lp["w_gate"], lp["w_out"],
                           x), sel
        x = _swiglu(r, y, lp["ws_in"], lp["ws_gate"], lp["ws_out"], x)
        return routed_part(r, y, lp, c, offset, held, layer, x), sel

    for i in range(c["num_hidden_layers"]):
        routed = i >= n_dense
        run, at, mine = ("layers", i - n_dense, types[n_dense:]) if routed \
            else ("dense_layers", i, types[:n_dense])
        lp = _layer_weights(params[run], mine, at, routed)
        x, sel = jax.checkpoint(functools.partial(
            block, layer=at if routed else None))(x, sel, lp)
    return _rms(x, params["final_norm"], eps)


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32, a sequence
    at a time."""
    return jax.lax.map(functools.partial(
        _sequence_hidden, params, c=c, precision=precision), tokens)


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, vocabulary] float32, one compiled program a call."""
    return _as_one_program(_logits, c=c, precision=precision)(params, tokens)


def _logits(params, tokens, c, precision: str) -> jnp.ndarray:
    """The head is an eighth of the
    vocabulary's and is taken whole (0.48 GB in float32): a loop over blocks
    of it carries the logits, and the chip's compiler then holds them twice
    (2 x 1.7 GB at 2 x 11 k positions; CPU, the described-v5e compile)."""
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
