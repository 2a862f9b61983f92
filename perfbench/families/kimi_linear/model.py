"""Family ``kimi_linear``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

Every block is pre-norm (RMSNorm, ``rms_norm_eps``): ``x += op(norm(x)); x +=
ffn(norm(x))``, and NO position enters the model anywhere.  With ``y_t`` the
block's normed input at position ``t``, ``h`` heads of ``dk = dv`` =
``linear_attn_config.head_dim``, a KDA layer computes (float32 where marked)::

    q~_t, k~_t, v~_t = y_t W_q, y_t W_k, y_t W_v          each h x dk, no bias
    u'_t[c] = silu(sum_{i=0..3} w_u[c, i] u~_{t-3+i}[c])  u in {q, k, v}: a
                depthwise causal convolution of 4 taps, zeros before position
                0, no bias, SiLU after
    q_t^h = q'_t^h / ||q'_t^h|| dk^-1/2,  k_t^h = k'_t^h / ||k'_t^h||
                (x rsqrt(sum x^2 + 1e-6))
    a_t^h = -exp(A_log[h]) softplus((y_t W_fa W_fb)^h + dt_bias^h)   float32:
                a LOG decay for every key channel
    beta_t^h = sigmoid((y_t W_b)[h])                                 float32
    S_0^h = 0 [dk, dv] float32, and for t = 1, 2, ...
        S'    = Diag(exp(a_t^h)) S_{t-1}^h
        S_t^h = S' + beta_t^h k_t^h (v_t^h - (k_t^h)^T S')^T
        o_t^h = (S_t^h)^T q_t^h
    z_t^h = (y_t W_ga W_gb)^h
    out_t = concat_h(rmsnorm(o_t^h; g_o) sigmoid(z_t^h)) W_o

and a full layer latent attention (``glm4_moe_lite``'s, whose ``model.py`` has
its equations) with two differences: ``q_t = y_t W_q`` directly (no query
latent, no query norm) and nothing is rotated: ``c_t = rmsnorm((y_t
W_kva)[:kv_lora])``, ``k^R_t = (y_t W_kva)[kv_lora:]`` as projected; head j:
``k = [c_t W_kvb^j[:, :nope]; k^R_t]``, ``v = c_t W_kvb^j[:, nope:]``, causal
softmax of ``q . k / sqrt(nope + rope)``.  The feed-forward half: the leading
layers a SwiGLU of ``intermediate_size``; the others a sigmoid router in
float32 over ``deployment.experts_routed`` experts, ``+
e_score_correction_bias`` for the choice only, ``num_experts_per_token`` a
token, renormalised, x ``routed_scaling_factor``, beside
``num_shared_experts`` shared ones.  Final RMSNorm, untied head.

The program's tree holds a KDA layer's weights in the layout it computes in
(`ray_tpu.models.transformer._init_kda`): ``kda_in`` = [W_q | W_k | W_v],
``kda_conv`` the three convolutions' taps over those channels, ``kda_lo`` =
[W_fa | W_ga | W_b].

The reference is the equations above in float32 at ``highest``: the delta
rule as the RECURRENCE token by token (never the chunkwise form the program
runs its chunks in: that form is what is checked), the convolution as a sum
of four shifted products, attention unabsorbed a head at a time, every held
expert applied to every token under its weight, no cache, no kernel; the SAME
share (the held experts, the vocabulary slice).  It goes a sequence at a
time, the experts one at a time out of their stack, so that it fits beside
the live engine.  ``precision="fp8"`` is the control
(`reference._round_inputs`); the router's matmul, the decay's and the step
size's projections and every product that reads the state stay float32 in
it, as the configuration states them for the program.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _shapes():
    from perfbench import manifest
    return manifest.family("kimi_linear").shapes


def _routed(c: Dict[str, Any]) -> int:
    return c["deployment"]["experts_routed"]


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["num_expert_group"], c["topk_group"], c["moe_renormalize"],
            c["hidden_act"], c["moe_router_activation_func"],
            c["moe_layer_freq"]) != (1, 1, True, "silu", "sigmoid", 1):
        raise ValueError("family kimi_linear: the program routes by sigmoid "
                         "scores without group limits, renormalises the "
                         "chosen scores, gates with SiLU and has an expert "
                         "layer after every leading dense one")
    if c["num_nextn_predict_layers"] or c["q_lora_rank"] \
            or not c["mla_use_nope"] or c["rope_scaling"]:
        raise ValueError("family kimi_linear: no multi-token-prediction "
                         "module, no query latent, no position in the full "
                         "layers")
    lin = c["linear_attn_config"]
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"], max_seq_len=c["model_max_length"],
        pos_emb="none", activation="swiglu", norm="rmsnorm",
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        attention="mla", q_lora_rank=0, kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        n_experts=_routed(c), experts_held=c["num_experts"],
        expert_offset=c["deployment"]["expert_offset"],
        expert_top_k=c["num_experts_per_token"], router="sigmoid",
        moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=c["num_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        first_dense_layers=c["first_k_dense_replace"],
        layer_kinds=_shapes().kinds(c), kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv_kernel=lin["short_conv_kernel_size"],
        kda_gate_rank=c["assumed"]["kda_gate_rank"],
        dtype=_DTYPES[p["compute"]], param_dtype=_DTYPES[p["params"]],
        **overrides)


def param_dtype(c: Dict[str, Any], use: str):
    return _DTYPES[c["precision"][use]["params"]]


def _normal(key: jax.Array, shape, fan_in: float, dtype, lead: int = 0):
    """``normal / sqrt(fan_in)`` of ``shape`` in ``dtype``, drawn a block
    of ``shape[lead:]`` at a time (one key a block) so that no float32 copy
    of more than one block exists."""
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
    g = math.gcd(n_rows, 1024)
    return _normal(key, (n_rows // g, g, width), fan_in, dtype,
                   lead=1).reshape(n_rows, width)


def _run(key: jax.Array, c: Dict[str, Any], kinds, moe: bool, dtype):
    """One run's stacked tree for the layers of ``kinds``: a KDA layer's
    weights over the KDA layers alone, latent attention's over the full
    ones."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    kl, lin, a = c["kv_lora_rank"], c["linear_attn_config"], c["assumed"]
    hk, hd, taps = (lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"])
    e, r = hk * hd, a["kda_gate_rank"]
    L, n_kda, n_full = len(kinds), kinds.count("kda"), kinds.count("full")
    names = ("wq", "wkv_a", "wkv_b", "wo", "w_in", "w_gate", "w_out",
             "router", "router_bias", "ws_in", "ws_gate", "ws_out", "kda_in",
             "kda_conv", "kda_lo", "kda_fb", "kda_gb", "kda_out", "kda_a_log",
             "kda_dt")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, shape, fan_in, lead=1, n=L):
        return _normal(ks[name], (n,) + shape, fan_in, dtype, lead=lead)

    p = {"attn_norm": jnp.ones((L, d), dtype),
         "mlp_norm": jnp.ones((L, d), dtype)}
    if n_full:      # W_o at a gain: what a softmax over thousands of rows
        # of random values leaves is a thirtieth of a value (``assumed``)
        p.update(wq=stack("wq", (d, h, nope + rope), d, n=n_full),
                 wkv_a=stack("wkv_a", (d, kl + rope), d, n=n_full),
                 wkv_b=stack("wkv_b", (kl, h, nope + v), kl, n=n_full),
                 wo=stack("wo", (h, v, d),
                          h * v / a["attention_out_gain"] ** 2, n=n_full),
                 kv_norm=jnp.ones((n_full, kl), dtype))
    if n_kda:
        lo, hi = a["kda_a_range"]
        dt = jnp.exp(jax.random.uniform(
            ks["kda_dt"], (n_kda, e), jnp.float32,
            math.log(a["kda_dt_range"][0]), math.log(a["kda_dt_range"][1])))
        p.update(
            kda_in=stack("kda_in", (d, 3 * e), d, n=n_kda),
            kda_conv=stack("kda_conv", (3 * e, taps), taps, n=n_kda),
            kda_lo=stack("kda_lo", (d, 2 * r + hk), d, n=n_kda),
            kda_fb=stack("kda_fb", (r, e), r, n=n_kda),
            kda_gb=stack("kda_gb", (r, e), r, n=n_kda),
            kda_out=stack("kda_out", (e, d), e, n=n_kda),
            # what decides how long a state remembers, as published: a
            # channel keeps exp(-A dt) = 0.2 to 0.999 of itself a token
            kda_a_log=jnp.log(jax.random.uniform(
                ks["kda_a_log"], (n_kda, hk), jnp.float32, lo, hi)
            ).astype(dtype),
            kda_dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            kda_norm=jnp.ones((n_kda, hd), dtype))
    if not moe:
        f = c["intermediate_size"]
        p.update(w_in=stack("w_in", (d, f), d),
                 w_gate=stack("w_gate", (d, f), d),
                 w_out=stack("w_out", (f, d), f))
        return p
    E, held, f = _routed(c), c["num_experts"], c["moe_intermediate_size"]
    fs = c["num_shared_experts"] * f
    p.update(
        router=stack("router", (d, E), d),
        # drawn, not zero, so that it changes choices (``assumed``)
        router_bias=(jax.random.normal(ks["router_bias"], (L, E), jnp.float32)
                     * a["e_score_correction_bias_std"]).astype(dtype),
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
    each operator's weights stacked over its own layers of the run.  ONE
    compiled program a call (`_as_one_program`)."""
    return _as_one_program(_make, c=c, dtype=dtype)(key)


def _as_one_program(fn, **fixed):
    """``fn`` with its configuration bound, compiled as one program: a layer
    at a time in Python is hundreds of small programs when called eagerly,
    and inside a caller's own `jax.jit` this is no program of its own."""
    return jax.jit(functools.partial(fn, **fixed))


def _make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense, kinds = c["first_k_dense_replace"], _shapes().kinds(c)
    k_tok, k_head, k_dense, k_moe = jax.random.split(key, 4)
    return {
        # rows of unit scale (fan_in 1: a row is looked up, not summed): a
        # token's own embedding is the size of what a layer adds to it
        "embed": {"tok": _rows(k_tok, v, d, 1.0, dtype)},
        "dense_layers": _run(k_dense, c, kinds[:n_dense], False, dtype),
        "layers": _run(k_moe, c, kinds[n_dense:], True, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": _rows(k_head, d, v, d, dtype),
    }


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    return jax.random.randint(key, shape, 0, c["vocab_size"], jnp.int32)


# ------------------------------------------------------ the plain reference

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _swiglu(r, y, w_in, w_gate, w_out, acc=None):
    """``acc + SwiGLU(y)`` for ``y`` [s, d] (``acc`` None: 0), its width
    taken 2048 at a time: a dense layer's float32 products, and its weights
    in float32, are not held whole."""
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


def _unit(x):
    return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)


def delta_recurrence(q, k, v, a, beta, correct: bool = True):
    """The gated delta rule token by token from a zero state: ``q``, ``k``
    [s, h, dk], ``v`` [s, h, dv], ``a`` [s, h, dk], ``beta`` [s, h], all
    float32 -> ``o`` [s, h, dv].  ``correct`` False leaves the correction
    out (``S = S' + beta k v^T``: plain gated linear attention), for the
    tests' planted fault."""
    def one(S, x):
        q, k, v, a, beta = x
        S = jnp.exp(a)[..., None] * S                   # [h, dk, dv]
        old = jnp.einsum("hk,hkv->hv", k, S) if correct else 0.0
        S = S + k[..., None] * (beta[:, None] * (v - old))[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q, S)

    h, dk = q.shape[1:]
    _, o = jax.lax.scan(one, jnp.zeros((h, dk, v.shape[-1]), F32),
                        (q, k, v, a, beta))
    return o


def kda(r, y, lp, c):
    """One sequence's normed input ``y`` [s, d] -> what a KDA layer's
    operator adds [s, d]."""
    lin, rank = c["linear_attn_config"], c["assumed"]["kda_gate_rank"]
    h, hd, taps = (lin["num_heads"], lin["head_dim"],
                   lin["short_conv_kernel_size"])
    s, e = y.shape[0], h * hd
    u = jnp.einsum("sd,de->se", r(y), r(lp["kda_in"]))
    # the convolution as a sum of shifted products, zeros before position 0
    ext = jnp.concatenate([jnp.zeros((taps - 1, 3 * e), F32), u])
    w = lp["kda_conv"].astype(F32)
    u = jax.nn.silu(sum(w[:, i] * ext[i:i + s] for i in range(taps)))
    q, k, v = (t.reshape(s, h, hd) for t in jnp.split(u, 3, axis=-1))
    q, k = _unit(q) * hd ** -0.5, _unit(k)
    y32, lo = y.astype(F32), lp["kda_lo"].astype(F32)
    f = (y32 @ lo[:, :rank]) @ lp["kda_fb"].astype(F32)        # float32
    a = -jnp.exp(lp["kda_a_log"].astype(F32))[:, None] * jax.nn.softplus(
        f.reshape(s, h, hd) + lp["kda_dt_bias"].astype(F32).reshape(h, hd))
    beta = jax.nn.sigmoid(y32 @ lo[:, 2 * rank:])
    o = delta_recurrence(q, k, v, a, beta)
    z = jnp.einsum("sr,re->se", r(jnp.einsum(
        "sd,dr->sr", r(y), r(lp["kda_lo"][:, rank:2 * rank]))),
        r(lp["kda_gb"]))
    o = _rms(o, lp["kda_norm"], c["rms_norm_eps"]) \
        * jax.nn.sigmoid(z.reshape(s, h, hd))
    return jnp.einsum("se,ed->sd", r(o.reshape(s, e)), r(lp["kda_out"]))


def _query_block(s: int) -> int:
    """Queries attended at a time: a divisor of ``s``."""
    return math.gcd(s, 512)


def attention(r, y, lp, c):
    """One sequence's normed input ``y`` [s, d] -> what a full layer's
    latent attention adds [s, d]: unabsorbed, a head and a block of queries
    at a time, nothing rotated."""
    eps, nope, kl = (c["rms_norm_eps"], c["qk_nope_head_dim"],
                     c["kv_lora_rank"])
    rope, s = c["qk_rope_head_dim"], y.shape[0]
    ckv = jnp.einsum("sd,dr->sr", r(y), r(lp["wkv_a"]))
    c_kv, k_r = _rms(ckv[:, :kl], lp["kv_norm"], eps), ckv[:, kl:]
    qb = _query_block(s)
    at = jnp.arange(s)

    def one_head(acc, j):
        wq, wkv = (jax.lax.dynamic_index_in_dim(lp[n], j, axis=1,
                                                keepdims=False)
                   for n in ("wq", "wkv_b"))
        wo = jax.lax.dynamic_index_in_dim(lp["wo"], j, axis=0,
                                          keepdims=False)
        q = jnp.einsum("sd,dk->sk", r(y), r(wq))
        kv = jnp.einsum("sr,rk->sk", r(c_kv), r(wkv))
        k = jnp.concatenate([kv[:, :nope], k_r], axis=-1)
        v = kv[:, nope:]

        def some_queries(inp):
            qj, tj = inp
            scores = jnp.einsum("qk,tk->qt", r(qj), r(k)) \
                / math.sqrt(nope + rope)
            probs = jax.nn.softmax(jnp.where(
                at[None, :] <= tj[:, None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("qt,tv->qv", r(probs), r(v))

        a = jax.lax.map(some_queries, (q.reshape(s // qb, qb, -1),
                                       at.reshape(s // qb, qb)))
        return acc + jnp.einsum("sv,vd->sd", r(a.reshape(s, -1)), r(wo)), None

    out, _ = jax.lax.scan(one_head, jnp.zeros_like(y, dtype=F32),
                          jnp.arange(lp["wo"].shape[0]))
    return out


def expert_weights(y, lp, c):
    """y [s, d] normed -> [s, E] float32: each expert's weight for each
    token over ALL the layer's experts, zero where the token did not choose
    it."""
    s = jax.nn.sigmoid(jnp.einsum("sd,de->se", y.astype(F32),
                                  lp["router"].astype(F32)))
    _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(F32),
                              c["num_experts_per_token"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * c["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, s.shape[-1], dtype=F32)    # [s, k, E]
    return jnp.einsum("sk,ske->se", w, onehot)


def routed_part(r, y, lp, c, offset: int, held: int, layer: int,
                acc=None):
    """``acc`` (None: 0) plus the part of an expert layer's routed sum that
    the ``held`` experts from ``offset`` give for ``y`` [s, d]: every one of
    them applied to every token under its weight, cut out of its stack
    ``lp[name]`` [L, held, ...] inside the loop (a slice of a layer's
    experts would be a copy of them)."""
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


_OPERATOR = {"kda": ("kda_",), "full": ("wq", "wkv_a", "wkv_b", "wo",
                                        "kv_norm")}


def _layer_weights(tree, kinds, at: int, routed: bool):
    """Layer ``at`` of a run's stacked tree whose layers are of ``kinds``:
    an operator's weights by the count of ITS layers before it, the routed
    experts' stacks whole (`routed_part` indexes them)."""
    lp = {}
    for name, a in tree.items():
        owner = next((k for k, names in _OPERATOR.items()
                      if name.startswith(names)), None)
        if owner is not None:
            if kinds[at] == owner:
                lp[name] = a[kinds[:at].count(owner)]
        elif routed and name in ("w_in", "w_gate", "w_out"):
            lp[name] = a
        else:
            lp[name] = a[at]
    return lp


def _sequence_hidden(params, toks, c, precision: str):
    """One sequence's tokens [s] -> final hidden states [s, d]."""
    r = _round_inputs(precision)
    eps = c["rms_norm_eps"]
    held, offset = c["num_experts"], c["deployment"]["expert_offset"]
    n_dense, kinds = c["first_k_dense_replace"], _shapes().kinds(c)
    x = params["embed"]["tok"][toks].astype(F32)

    def block(x, lp, kind, layer):
        op = kda if kind == "kda" else attention
        x = x + op(r, _rms(x, lp["attn_norm"], eps), lp, c)
        y = _rms(x, lp["mlp_norm"], eps)
        if layer is None:
            return _swiglu(r, y, lp["w_in"], lp["w_gate"], lp["w_out"], x)
        x = _swiglu(r, y, lp["ws_in"], lp["ws_gate"], lp["ws_out"], x)
        return routed_part(r, y, lp, c, offset, held, layer, x)

    for i, kind in enumerate(kinds):
        routed = i >= n_dense
        run, at, mine = ("layers", i - n_dense, kinds[n_dense:]) if routed \
            else ("dense_layers", i, kinds[:n_dense])
        lp = _layer_weights(params[run], mine, at, routed)
        x = jax.checkpoint(functools.partial(
            block, kind=kind, layer=at if routed else None))(x, lp)
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
