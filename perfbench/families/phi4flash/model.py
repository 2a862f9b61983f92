"""Family ``phi4flash``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

``x`` is the residual stream ``[s, d]``; ``ln`` a LayerNorm with scale and
bias (``layer_norm_eps``); NO position enters the model (order comes from the
scan, the window and the causal mask).  Every layer ``l``::

    x = x + op_l(ln_1(x));   x = x + W_down (silu(g) * u),  g = ln_2(x) W_gate,
                                                            u = ln_2(x) W_up

    -- "mamba", y = ln_1(x); E = mamba_expand d channels, N state columns
    [a | z]  = y W_in                                   E each, no bias
    a_t      = silu(b_c + sum_{k<4} w[c, k] a_{t-3+k})  depthwise, causal,
               zeros before the start
    [r|B|C]  = a W_x                                    rank | N | N
    dt       = softplus(r W_dt + b_dt)                  float32
    h_t[n,c] = exp(dt_t[c] A[n,c]) h_{t-1}[n,c] + dt_t[c] a_t[c] B_t[n]
               A = -exp(A_log), h_{-1} = 0, float32
    m_t[c]   = sum_n h_t[n,c] C_t[n] + D[c] a_t[c]
    op       = (m * silu(z)) W_out
    The LAST mamba layer's m (BEFORE its gate) is the memory of every "gmu".

    -- "window" | "full": q = y W_q + b_q (40 heads of 64: PAIRS p of heads
       2p, 2p + 1), k = y W_k + b_k (10 rows g of [k_g1 | k_g2], 64 each),
       v = y W_v + b_v (10 values of 128); pair p reads g = p // 2
    o_pi     = sum_j softmax_j(q_pi . k_gi / sqrt(64)) v_g     i = 1, 2
               j <= t, and t - j < sliding_window in a window layer
    lambda   = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
    lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)        l the layer's index of all
    d_p      = (1 - lambda_init(l)) rms_128(o_p1 - lambda o_p2)  learned scale
    op       = concat_p(d_p) W_o + b_o

    -- "cross": q of ITS OWN; k, v the LAST FULL layer's, as that layer made
       them from its own normed input; then the same with its own lambda
       vectors, norm, lambda_init(l), W_o, b_o; every j <= t

    -- "gmu": op_t = W_2 (m_t * silu(y_t W_1))

    x_0 = embed[token];  logits = ln(x_L) embed^T          (tied, no bias)

The reference is these equations in float32 at ``highest``: the scan as the
RECURRENCE token by token, the convolution as a sum of four shifted products,
attention dense a key-value pair at a time, no cache, no kernel, every layer
on every row (never the tail on one).  It goes a sequence at a time, the
feed-forward 2048 columns at a time, the head a block of the vocabulary at a
time, so that it fits beside the live engine.
``precision="fp8"`` is the control (`reference._round_inputs`: every
matmul's two inputs rounded first); the recurrence, which is no matmul, stays
float32 in it.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_ATTENTION = ("window", "full", "cross")


def _shapes():
    from perfbench import manifest
    return manifest.family("phi4flash").shapes


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if c["hidden_act"] != "silu" or c["mlp_bias"] or c["lm_head_bias"] \
            or not c["tie_word_embeddings"] \
            or c["num_key_value_heads"] % 2 or c["num_attention_heads"] % 2:
        raise ValueError("family phi4flash: the program gates with SiLU, "
                         "ties its head to the embedding, pairs its heads "
                         "and has no bias in a feed-forward or the head")
    p, s = c["precision"][use], _shapes().sizes(c)
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"] // 2,   # PAIRS of key heads
        d_ff=c["intermediate_size"], max_seq_len=c["max_position_embeddings"],
        pos_emb="none", activation="swiglu", norm="layernorm",
        norm_eps=c["layer_norm_eps"], tie_embeddings=True,
        layer_kinds=tuple(_shapes().layer_kinds(c)),
        sliding_window=c["sliding_window"],
        mamba_state=s["state"], mamba_expand=s["inner"] // c["hidden_size"],
        mamba_conv_kernel=s["conv"], mamba_dt_rank=s["dt_rank"],
        diff_attn=True, attn_bias=True,
        dtype=_DTYPES[p["compute"]], param_dtype=_DTYPES[p["params"]],
        **overrides)


def param_dtype(c: Dict[str, Any], use: str):
    return _DTYPES[c["precision"][use]["params"]]


def _normal(key: jax.Array, shape, std, dtype):
    """``normal * std`` of ``shape`` [L, rows, ...] in ``dtype``, drawn a
    layer at a time (one key a layer) so that no float32 copy of more than
    one layer's weight exists."""
    def one(k):
        return (jax.random.normal(k, tuple(shape[1:]), jnp.float32)
                * std).astype(dtype)

    return jax.lax.map(one, jax.random.split(key, shape[0]))


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    ONE run of layers, each operator's weights stacked over ITS layers alone
    (the mixer's over the mamba layers, a gated unit's over the gmu layers,
    queries and outputs over all attention layers, keys and values over
    those that hold rows).  Every matrix normal / sqrt(fan_in), the biases
    normal at ``bias_std``, what decides how long a state remembers as Mamba
    draws it (``assumed.weights``)."""
    sh = _shapes()
    d, v, ff = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    h, hk, hd = (c["num_attention_heads"], c["num_key_value_heads"] // 2,
                 sh.head_dim(c))
    s, a = sh.sizes(c), c["assumed"]["weights"]
    e, n, r, taps = s["inner"], s["state"], s["dt_rank"], s["conv"]
    kinds = sh.layer_kinds(c)
    L, Lm, Lg = len(kinds), kinds.count("mamba"), kinds.count("gmu")
    La = sum(k in _ATTENTION for k in kinds)
    Lr = La - kinds.count("cross")
    names = ("tok", "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo", "w_in",
             "w_gate", "w_out", "mamba_in", "mamba_conv", "mamba_conv_b",
             "mamba_x", "mamba_dt", "mamba_dt_b", "mamba_out", "gmu_in",
             "gmu_out", "diff_lq1", "diff_lk1", "diff_lq2", "diff_lk2")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, n_layers, shape, fan_in):
        return _normal(ks[name], (n_layers,) + shape,
                       1.0 / math.sqrt(fan_in), dtype)

    def small(name, n_layers, shape, std):
        return _normal(ks[name], (n_layers,) + shape, std, dtype)

    dt = jnp.exp(jax.random.uniform(
        ks["mamba_dt_b"], (Lm, e), jnp.float32, math.log(a["dt_range"][0]),
        math.log(a["dt_range"][1])))
    b = a["bias_std"]
    layers = {
        "attn_norm": jnp.ones((L, d), dtype),
        "attn_norm_b": jnp.zeros((L, d), dtype),
        "mlp_norm": jnp.ones((L, d), dtype),
        "mlp_norm_b": jnp.zeros((L, d), dtype),
        "w_in": stack("w_in", L, (d, ff), d),
        "w_gate": stack("w_gate", L, (d, ff), d),
        "w_out": stack("w_out", L, (ff, d), ff),
        "mamba_in": stack("mamba_in", Lm, (d, 2 * e), d),
        "mamba_conv": stack("mamba_conv", Lm, (e, taps), taps),
        "mamba_conv_b": small("mamba_conv_b", Lm, (e,), b),
        "mamba_x": stack("mamba_x", Lm, (e, r + 2 * n), e),
        "mamba_dt": stack("mamba_dt", Lm, (r, e), r),
        "mamba_dt_b": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        # a channel's column j keeps exp(-j dt) of itself a token
        "mamba_a_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[None, :, None],
            (Lm, n, e)).astype(dtype),
        "mamba_d": jnp.ones((Lm, e), dtype),
        "mamba_out": stack("mamba_out", Lm, (e, d), e),
        "gmu_in": stack("gmu_in", Lg, (d, e), d),
        "gmu_out": stack("gmu_out", Lg, (e, d), e),
        "wq": stack("wq", La, (d, h, hd), d),
        "wk": stack("wk", Lr, (d, hk, 2 * hd), d),
        "wv": stack("wv", Lr, (d, hk, 2 * hd), d),
        "wo": stack("wo", La, (h // 2, 2 * hd, d), h * hd),
        "bq": small("bq", La, (h, hd), b),
        "bk": small("bk", Lr, (hk, 2 * hd), b),
        "bv": small("bv", Lr, (hk, 2 * hd), b),
        "bo": small("bo", La, (d,), b),
        "diff_norm": jnp.ones((La, 2 * hd), dtype),
    }
    for name in ("diff_lq1", "diff_lk1", "diff_lq2", "diff_lk2"):
        layers[name] = small(name, La, (hd,), a["lambda_std"])
    return {
        "embed": {"tok": _normal(ks["tok"], (v // math.gcd(v, 1024),
                                             math.gcd(v, 1024), d),
                                 a["embedding_std"], dtype).reshape(v, d)},
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "final_norm_b": jnp.zeros((d,), dtype),
    }


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    return jax.random.randint(key, shape, 0, c["vocab_size"], jnp.int32)


# ------------------------------------------------------ the plain reference

def _ln(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale.astype(F32) \
        + bias.astype(F32)


def recurrence(a, B, C, dt, A, D):
    """The selective scan token by token from a zero state: ``a``, ``dt``
    [s, E], ``B``, ``C`` [s, N], ``A`` [N, E] (< 0), ``D`` [E], all float32
    -> ``m`` [s, E]."""
    def one(h, t):
        a, B, C, dt = t
        h = jnp.exp(dt[None, :] * A) * h + B[:, None] * (dt * a)[None, :]
        return h, (h * C[:, None]).sum(0) + D * a

    _, m = jax.lax.scan(one, jnp.zeros(A.shape, F32), (a, B, C, dt))
    return m


def mamba(r, y, lp, c):
    """One sequence's normed input ``y`` [s, d] -> (what the mixer adds [s,
    d], its memory ``m`` [s, E] before the gate)."""
    sz = _shapes().sizes(c)
    e, n, rank, taps = sz["inner"], sz["state"], sz["dt_rank"], sz["conv"]
    s = y.shape[0]
    u = jnp.einsum("sd,de->se", r(y), r(lp["mamba_in"]))
    a, z = u[:, :e], u[:, e:]
    # the convolution as a sum of shifted products, zeros before position 0
    ext = jnp.concatenate([jnp.zeros((taps - 1, e), F32), a])
    w = lp["mamba_conv"].astype(F32)
    a = jax.nn.silu(sum(w[:, i] * ext[i:i + s] for i in range(taps))
                    + lp["mamba_conv_b"].astype(F32))
    low = jnp.einsum("se,er->sr", r(a), r(lp["mamba_x"]))
    B, C = low[:, rank:rank + n], low[:, rank + n:]
    dt = jax.nn.softplus(
        jnp.einsum("sr,re->se", r(low[:, :rank]), r(lp["mamba_dt"]))
        + lp["mamba_dt_b"].astype(F32))
    m = recurrence(a, B, C, dt, -jnp.exp(lp["mamba_a_log"].astype(F32)),
                   lp["mamba_d"].astype(F32))
    return jnp.einsum("se,ed->sd", r(m * jax.nn.silu(z)),
                      r(lp["mamba_out"])), m


def rows(r, y, lp):
    """A window or full layer's keys [10, s, 2, 64] (a row's two halves) and
    values [10, s, 128] of its normed input."""
    k = jnp.einsum("sd,dgk->gsk", r(y), r(lp["wk"])) \
        + lp["bk"].astype(F32)[:, None, :]
    v = jnp.einsum("sd,dgk->gsk", r(y), r(lp["wv"])) \
        + lp["bv"].astype(F32)[:, None, :]
    g, s, wide = k.shape
    return k.reshape(g, s, 2, wide // 2), v


def attention(r, y, k, v, lp, depth: int, window, c):
    """One sequence's normed input ``y`` [s, d] against keys ``k`` [G, s, 2,
    hd] and values ``v`` [G, s, 2 hd] -> what differential attention adds [s,
    d]: a key-value pair at a time, both maps of its two query pairs."""
    h, hd = c["num_attention_heads"], _shapes().head_dim(c)
    G, s = k.shape[0], y.shape[0]
    q = jnp.einsum("sd,dhk->hsk", r(y), r(lp["wq"])) \
        + lp["bq"].astype(F32)[:, None, :]
    # head 2p + i of pair p; pair p reads key-value pair p // (pairs / G)
    q = q.reshape(G, h // 2 // G, 2, s, hd)
    t = jnp.arange(s)
    seen = t[:, None] >= t[None, :]
    if window is not None:
        seen &= t[:, None] - t[None, :] < window

    def one(q, k, v):           # [P, 2, s, hd], [s, 2, hd], [s, 2 hd]
        scores = jnp.einsum("pisk,tik->pist", r(q), r(k)) / math.sqrt(hd)
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("pist,tk->pisk", r(maps), r(v))

    o = jax.lax.map(lambda x: one(*x), (q, k, v))    # [G, P, 2, s, 2 hd]
    o = o.reshape(h // 2, 2, s, 2 * hd)
    fixed = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(lp["diff_lq1"].astype(F32)
                          * lp["diff_lk1"].astype(F32))) \
        - jnp.exp(jnp.sum(lp["diff_lq2"].astype(F32)
                          * lp["diff_lk2"].astype(F32))) + fixed
    o = o[:, 0] - lam * o[:, 1]                             # [pairs, s, 128]
    o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                          + c["layer_norm_eps"]) \
        * lp["diff_norm"].astype(F32) * (1.0 - fixed)
    return jnp.einsum("psk,pkd->sd", r(o), r(lp["wo"])) \
        + lp["bo"].astype(F32)


def gmu(r, y, m, lp):
    return jnp.einsum(
        "se,ed->sd",
        r(m * jax.nn.silu(jnp.einsum("sd,de->se", r(y), r(lp["gmu_in"])))),
        r(lp["gmu_out"]))


def feed_forward(r, y, stacks, layer: int):
    """SwiGLU of ``y`` [s, d] with layer ``layer``'s weights cut out of
    their stacks [L, d, f] / [L, f, d] 2048 columns at a time: a layer's
    float32 products, and its weights in float32, are not held whole."""
    w_in, w_gate, w_out = stacks
    _, d, f = w_in.shape
    block = math.gcd(f, 2048)
    y = r(y)

    def some_width(i, acc):
        up, gate = (jax.lax.dynamic_slice(w, (layer, 0, i * block),
                                          (1, d, block))[0]
                    for w in (w_in, w_gate))
        down = jax.lax.dynamic_slice(w_out, (layer, i * block, 0),
                                     (1, block, d))[0]
        g = jnp.einsum("sd,df->sf", y, r(gate))
        z = g * jax.nn.sigmoid(g) * jnp.einsum("sd,df->sf", y, r(up))
        return acc + jnp.einsum("sf,fd->sd", r(z), r(down))

    return jax.lax.fori_loop(0, f // block, some_width,
                             jnp.zeros(y.shape, F32))


_FFN = ("w_in", "w_gate", "w_out")
#: the stacks over the layers that hold rows, over all attention layers
_ROWS = ("wk", "wv", "bk", "bv")
_NORMS = ("attn_norm", "attn_norm_b", "mlp_norm", "mlp_norm_b")


def _layer_weights(tree, kinds, layer: int):
    """Layer ``layer``'s weights out of the stacks: each stack is over its
    own layers alone, in model order."""
    kind = kinds[layer]
    before = kinds[:layer]
    at = {"norm": layer, "mamba": before.count("mamba"),
          "gmu": before.count("gmu"),
          "attn": sum(k in _ATTENTION for k in before),
          "rows": sum(k in ("window", "full") for k in before)}
    out = {}
    for name, stack in tree.items():
        if name in _FFN:
            continue
        own = "norm" if name in _NORMS else "mamba" \
            if name.startswith("mamba_") else "gmu" \
            if name.startswith("gmu_") else "rows" if name in _ROWS \
            else "attn"
        if own == "norm" or own == kind or (
                kind in _ATTENTION and own == "attn") or (
                kind in ("window", "full") and own == "rows"):
            out[name] = stack[at[own]]
    return out


def _sequence_hidden(params, toks, c, precision: str):
    """One sequence's tokens [s] -> final hidden states [s, d]."""
    r = _round_inputs(precision)
    tree, eps = params["layers"], c["layer_norm_eps"]
    kinds = _shapes().layer_kinds(c)
    stacks = tuple(tree[n] for n in _FFN)
    x = params["embed"]["tok"][toks].astype(F32)
    m = k = v = None
    for layer, kind in enumerate(kinds):
        lp = _layer_weights(tree, kinds, layer)
        y = _ln(x, lp["attn_norm"], lp["attn_norm_b"], eps)
        if kind == "mamba":
            op, m = mamba(r, y, lp, c)
        elif kind == "gmu":
            op = gmu(r, y, m, lp)
        elif kind == "cross":
            op = attention(r, y, k, v, lp, layer, None, c)
        else:
            mine = rows(r, y, lp)
            if kind == "full":
                k, v = mine
            op = attention(r, y, *mine, lp, layer,
                           c["sliding_window"] if kind == "window" else None,
                           c)
        x = x + op
        x = x + feed_forward(
            r, _ln(x, lp["mlp_norm"], lp["mlp_norm_b"], eps), stacks, layer)
    return _ln(x, params["final_norm"], params["final_norm_b"], eps)


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32, a sequence
    at a time."""
    return jax.lax.map(functools.partial(
        _sequence_hidden, params, c=c, precision=precision), tokens)


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, vocabulary] float32: the head is the embedding, a block of its
    rows at a time (the table in float32 is never whole), the blocks set
    side by side.  Spelled out block by block, so that a caller who then
    takes a few positions of the result (`kinds/serve_common.py` `_verify`)
    compiles to the blocks' few positions and the [s, 200064] float32 array
    is never whole either: 0.6 GB of temporaries where one array written
    block by block took 4.5 (compiled for a described v5e, PR 60)."""
    r = _round_inputs(precision)
    table = params["embed"]["tok"]
    v = table.shape[0]
    blocks = max(n for n in range(1, 65) if v % n == 0)
    rows = v // blocks
    with jax.default_matmul_precision("highest"):
        x = r(hidden(params, tokens, c, precision))
        return jnp.concatenate(
            [jnp.einsum("bsd,vd->bsv", x, r(table[i * rows:(i + 1) * rows]))
             for i in range(blocks)], axis=-1)


def loss(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """Mean next-token cross entropy over positions 0..s-2."""
    lg = logits(params, tokens, c, precision)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean()


def loss_and_grad(params, tokens, c, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            functools.partial(loss, c=c, precision=precision))(
                params, tokens)
