"""Family ``falcon_h1``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

Every block is pre-norm (RMSNorm, ``rms_norm_eps``, no bias anywhere but the
convolution's), and ONE norm feeds both mixers.  With ``h_t`` the block's
normed input at position ``t`` and ``d`` = ``hidden_size`` (float32 where
marked)::

    -- state-space mixer (Mamba-2): H = mamba_n_heads heads of P =
       mamba_d_head, state N = mamba_d_state, G = mamba_n_groups groups
    p_t      = ((h_t ssm_in_multiplier) W_in) * mup        W_in [d, H P + (H P
               + 2 G N) + H]: z | x B C | dt; mup = ssm_multipliers by
               segment: z m0, x m1, B m2, C m3, dt m4
    xBC'_t[c]= silu(sum_{i=0..3} w[c, i] xBC_{t-3+i}[c] + b[c])   depthwise,
               causal, zeros before position 0
    x_t, B_t, C_t = split(xBC'_t; H P | G N | G N)     head n reads group
               n // (H / G)
    dt_t^n   = softplus(dt_t[n] + dt_bias[n])          float32, no clamp
    S_0^n = 0 [N, P] float32, and for t = 1, 2, ...
        S_t^n = exp(-exp(A_log[n]) dt_t^n) S_{t-1}^n + B_t^g (dt_t^n x_t^n)^T
        y_t^n = (S_t^n)^T C_t^g + D[n] x_t^n
    y_t      = y_t * silu(z_t)                         the gate FIRST
               (mamba_norm_before_gate false)
    y_t      = rmsnorm over each GROUP's H P / G channels, times w_norm[H P]
    m_t      = (y_t W_out) ssm_out_multiplier

    -- attention: num_attention_heads query heads over num_key_value_heads,
       head_dim wide (queries are heads x head_dim wide, not d)
    q, k, v  = (h_t attention_in_multiplier) W_q, W_k, W_v;  k = k
               key_multiplier; q, k turned (all head_dim dims, halves against
               each other, base rope_theta)
    o_t      = (softmax_causal(q k^T / sqrt(head_dim)) v) W_o
               attention_out_multiplier

    x = x + m + o                                       ONE residual add
    h2 = rmsnorm(x);  x = x + (silu((h2 W_gate) mlp_multipliers[0]) * (h2
         W_up)) W_down mlp_multipliers[1]
    x_0 = embed[token] embedding_multiplier;  logits = rmsnorm(x_L) W_head
          lm_head_multiplier

The reference is the equations above in float32 at ``highest``: the scan as
the RECURRENCE token by token (never the chunkwise form the program runs its
chunks in: that form is what is checked), the convolution as a sum of four
shifted products, attention dense, no cache, no kernel; the SAME stage (the
held layers, the vocabulary slice).  It goes a sequence at a time, the
feed-forward's width 2048 columns at a time out of its stack, so that it
fits beside the live engine.  ``precision="fp8"`` is the control
(`reference._round_inputs`: every matmul's two inputs rounded first); the
recurrence, which is no matmul, stays float32 in it, as the configuration
states the state for the program.

WEIGHTS (`make`; ``assumed.weights`` of the configuration's file).  The
published multipliers were trained WITH the weights; random weights at ``1 /
sqrt(fan_in)`` under them would leave keys of 0.011 (a uniform softmax), an
attention branch of a thousandth and logits of a hundredth, which no limit
sees.  So a weight that stands before a multiplier ``m`` is drawn at ``1 /
(m sqrt(fan_in))``: each product is a plain model's, every multiplier is
still applied by the program where it stands, and the three branches add to
the residual at the same order (``attention_out_gain`` lifts what a softmax
over hundreds of random rows leaves of a value).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
KIND = "ssm+full"


def _shapes():
    from perfbench import manifest
    return manifest.family("falcon_h1").shapes


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["hidden_act"], c["mamba_norm_before_gate"], c["mamba_rms_norm"],
            c["mamba_conv_bias"], c["rope_scaling"]) != (
                "silu", False, True, True, None) \
            or c["attention_bias"] or c["mamba_proj_bias"] or c["mlp_bias"] \
            or c["projectors_bias"] or c["attn_layer_indices"]:
        raise ValueError("family falcon_h1: the program gates with SiLU "
                         "before a norm by group, convolves with a bias, "
                         "turns every layer's attention at one base and "
                         "has no other bias")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
        d_ff=c["intermediate_size"], max_seq_len=c["max_position_embeddings"],
        pos_emb="rope", rope_base=float(c["rope_theta"]),
        activation="swiglu", norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        layer_kinds=(KIND,) * c["num_hidden_layers"],
        ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
        ssm_state=c["mamba_d_state"], ssm_groups=c["mamba_n_groups"],
        ssm_conv_kernel=c["mamba_d_conv"],
        embed_scale=c["embedding_multiplier"],
        logit_scale=c["lm_head_multiplier"],
        attn_in_scale=c["attention_in_multiplier"],
        attn_out_scale=c["attention_out_multiplier"],
        key_scale=c["key_multiplier"],
        ffn_gate_scale=c["mlp_multipliers"][0],
        ffn_out_scale=c["mlp_multipliers"][1],
        ssm_in_scale=c["ssm_in_multiplier"],
        ssm_out_scale=c["ssm_out_multiplier"],
        ssm_scales=tuple(c["ssm_multipliers"]),
        dtype=_DTYPES[p["compute"]], param_dtype=_DTYPES[p["params"]],
        **overrides)


def param_dtype(c: Dict[str, Any], use: str):
    return _DTYPES[c["precision"][use]["params"]]


def _normal(key: jax.Array, shape, std, dtype):
    """``normal * std`` of ``shape`` [L, rows, ...] in ``dtype``, drawn a
    layer at a time (one key a layer) so that no float32 copy of more than
    one layer's weight exists; ``std`` a scalar or an array that broadcasts
    against a layer's block."""
    def one(k):
        return (jax.random.normal(k, tuple(shape[1:]), jnp.float32)
                * std).astype(dtype)

    return jax.lax.map(one, jax.random.split(key, shape[0]))


def _mup(c: Dict[str, Any]) -> jnp.ndarray:
    """``ssm_multipliers`` by segment over the input projection's columns:
    z | x | B | C | dt."""
    inner = c["mamba_d_ssm"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    return jnp.concatenate([jnp.full((w,), m, F32) for w, m in zip(
        (inner, inner, gn, gn, c["mamba_n_heads"]), c["ssm_multipliers"])])


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    ONE run of layers, both mixers' weights stacked over all of them.  A
    weight before a multiplier is drawn at the multiplier's inverse (the
    module's note), attention's output at ``attention_out_gain`` besides."""
    d, L, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    h, hk, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    ff, a = c["intermediate_size"], c["assumed"]["weights"]
    inner, channels, columns = _shapes().ssm_widths(c)
    H, taps = c["mamba_n_heads"], c["mamba_d_conv"]
    names = ("tok", "wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out",
             "lm_head", "ssm_in", "ssm_conv", "ssm_conv_b", "ssm_out",
             "ssm_a_log", "ssm_dt", "ssm_d")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, shape, fan_in, over=1.0):
        return _normal(ks[name], (L,) + shape, 1.0 / (math.sqrt(fan_in)
                                                      * over), dtype)

    at_in = c["attention_in_multiplier"]
    lo, hi = a["a_range"]
    dt = jnp.exp(jax.random.uniform(
        ks["ssm_dt"], (L, H), jnp.float32, math.log(a["dt_range"][0]),
        math.log(a["dt_range"][1])))
    layers = {
        "attn_norm": jnp.ones((L, d), dtype),
        "mlp_norm": jnp.ones((L, d), dtype),
        "wq": stack("wq", (d, h, hd), d, at_in),
        "wk": stack("wk", (d, hk, hd), d, at_in * c["key_multiplier"]),
        "wv": stack("wv", (d, hk, hd), d, at_in),
        "wo": stack("wo", (h, hd, d), h * hd,
                    c["attention_out_multiplier"] / a["attention_out_gain"]),
        "w_in": stack("w_in", (d, ff), d),
        "w_gate": stack("w_gate", (d, ff), d, c["mlp_multipliers"][0]),
        "w_out": stack("w_out", (ff, d), ff, c["mlp_multipliers"][1]),
        "ssm_in": stack("ssm_in", (d, columns), d,
                        c["ssm_in_multiplier"] * _mup(c)),
        "ssm_conv": stack("ssm_conv", (channels, taps), taps),
        "ssm_conv_b": _normal(ks["ssm_conv_b"], (L, channels),
                              a["conv_bias_std"], dtype),
        "ssm_out": stack("ssm_out", (inner, d), inner,
                         c["ssm_out_multiplier"]),
        # what decides how long a state remembers, as Mamba-2 draws it: a
        # head keeps exp(-A dt) of itself a token
        "ssm_a_log": jnp.log(jax.random.uniform(
            ks["ssm_a_log"], (L, H), jnp.float32, lo, hi)).astype(dtype),
        "ssm_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "ssm_d": jnp.ones((L, H), dtype),
        "ssm_norm": jnp.ones((L, inner), dtype),
    }
    return {
        # rows of unit scale AFTER the embedding's multiplier
        "embed": {"tok": _normal(ks["tok"], (v // math.gcd(v, 1024),
                                             math.gcd(v, 1024), d),
                                 1.0 / c["embedding_multiplier"],
                                 dtype).reshape(v, d)},
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": _normal(ks["lm_head"], (d // math.gcd(d, 512),
                                           math.gcd(d, 512), v),
                           1.0 / (math.sqrt(d) * c["lm_head_multiplier"]),
                           dtype).reshape(d, v),
    }


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    return jax.random.randint(key, shape, 0, c["vocab_size"], jnp.int32)


# ------------------------------------------------------ the plain reference

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rotate(x, theta):
    """x [heads, s, hd]: the pair (x[i], x[i + hd/2]) turned by the angle
    pos * theta^(-2i/hd)."""
    s, hd = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    lo, hi = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], axis=-1)


def recurrence(x, B, C, dt, A, D):
    """The state-space recurrence token by token from a zero state: ``x``
    [s, H, P], ``B``, ``C`` [s, G, N], ``dt`` [s, H], ``A`` [H] (< 0),
    ``D`` [H], all float32 -> ``y`` [s, H, P].  Head n reads group n // (H /
    G)."""
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    per = H // G

    def one(S, t):
        x, B, C, dt = t
        Bh, Ch = (jnp.repeat(v, per, axis=0) for v in (B, C))    # [H, N]
        S = jnp.exp(dt * A)[:, None, None] * S \
            + Bh[:, :, None] * (dt[:, None] * x)[:, None, :]
        return S, jnp.einsum("hnp,hn->hp", S, Ch) + D[:, None] * x

    _, y = jax.lax.scan(one, jnp.zeros((H, N, P), F32), (x, B, C, dt))
    return y


def mixer(r, y, lp, c):
    """One sequence's normed input ``y`` [s, d] -> what the state-space
    mixer adds [s, d]."""
    H, P, N, G = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                  c["mamba_n_groups"])
    inner, channels, _ = _shapes().ssm_widths(c)
    taps, s = c["mamba_d_conv"], y.shape[0]
    p = jnp.einsum("sd,de->se", r(y * c["ssm_in_multiplier"]),
                   r(lp["ssm_in"])) * _mup(c)
    z, u, dt = p[:, :inner], p[:, inner:inner + channels], \
        p[:, inner + channels:]
    # the convolution as a sum of shifted products, zeros before position 0
    ext = jnp.concatenate([jnp.zeros((taps - 1, channels), F32), u])
    w = lp["ssm_conv"].astype(F32)
    u = jax.nn.silu(sum(w[:, i] * ext[i:i + s] for i in range(taps))
                    + lp["ssm_conv_b"].astype(F32))
    x = u[:, :inner].reshape(s, H, P)
    B, C = (t.reshape(s, G, N) for t in jnp.split(u[:, inner:], 2, axis=-1))
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"].astype(F32))
    o = recurrence(x, B, C, dt, -jnp.exp(lp["ssm_a_log"].astype(F32)),
                   lp["ssm_d"].astype(F32)).reshape(s, inner)
    o = (o * jax.nn.silu(z)).reshape(s, G, inner // G)          # gate FIRST
    o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                          + c["rms_norm_eps"])
    o = o.reshape(s, inner) * lp["ssm_norm"].astype(F32)
    return jnp.einsum("se,ed->sd", r(o), r(lp["ssm_out"])) \
        * c["ssm_out_multiplier"]


def attention(r, y, lp, c):
    """One sequence's normed input ``y`` [s, d] -> what grouped-query
    attention adds [s, d]: dense, every head at once."""
    h, hk, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    s, theta = y.shape[0], float(c["rope_theta"])
    y = r(y * c["attention_in_multiplier"])
    q = _rotate(jnp.einsum("sd,dhk->hsk", y, r(lp["wq"])), theta)
    k = _rotate(jnp.einsum("sd,dgk->gsk", y, r(lp["wk"]))
                * c["key_multiplier"], theta)
    v = jnp.einsum("sd,dgk->gsk", y, r(lp["wv"]))
    # query head j reads key-value head j // (h / hk)
    q = q.reshape(hk, h // hk, s, hd)
    scores = jnp.einsum("grsk,gtk->grst", r(q), r(k)) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    a = jnp.einsum("grst,gtk->grsk", r(jax.nn.softmax(scores, axis=-1)),
                   r(v)).reshape(h, s, hd)
    return jnp.einsum("hsk,hkd->sd", r(a), r(lp["wo"])) \
        * c["attention_out_multiplier"]


def feed_forward(r, y, stacks, layer, c):
    """SwiGLU of ``y`` [s, d] with layer ``layer``'s weights cut out of
    their stacks [L, d, f] / [L, f, d] 2048 columns at a time: a layer's
    float32 products, and its weights in float32, are not held whole."""
    w_in, w_gate, w_out = stacks
    _, d, f = w_in.shape
    block = math.gcd(f, 2048)
    m_gate, m_out = c["mlp_multipliers"]
    y = r(y)

    def some_width(i, acc):
        up, gate = (jax.lax.dynamic_slice(w, (layer, 0, i * block),
                                          (1, d, block))[0]
                    for w in (w_in, w_gate))
        down = jax.lax.dynamic_slice(w_out, (layer, i * block, 0),
                                     (1, block, d))[0]
        g = jnp.einsum("sd,df->sf", y, r(gate)) * m_gate
        z = g * jax.nn.sigmoid(g) * jnp.einsum("sd,df->sf", y, r(up))
        return acc + jnp.einsum("sf,fd->sd", r(z), r(down))

    return jax.lax.fori_loop(0, f // block, some_width,
                             jnp.zeros(y.shape, F32)) * m_out


_FFN = ("w_in", "w_gate", "w_out")


def branches(r, x, lp, stacks, layer, c):
    """What layer ``layer`` adds to one sequence's stream ``x`` [s, d]:
    (the mixer's part, attention's part, the feed-forward's part, given the
    first two)."""
    y = _rms(x, lp["attn_norm"], c["rms_norm_eps"])
    m, o = mixer(r, y, lp, c), attention(r, y, lp, c)
    x = x + m + o
    return m, o, feed_forward(
        r, _rms(x, lp["mlp_norm"], c["rms_norm_eps"]), stacks, layer, c)


def _sequence_hidden(params, toks, c, precision: str, ratios: bool = False):
    """One sequence's tokens [s] -> final hidden states [s, d]; with
    ``ratios`` the root-mean-square of each branch over the stream's it is
    added to, a layer: [L, 3]."""
    r = _round_inputs(precision)
    tree = params["layers"]
    stacks = tuple(tree[n] for n in _FFN)
    x = params["embed"]["tok"][toks].astype(F32) * c["embedding_multiplier"]

    def block(x, layer):
        lp = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
              for n, a in tree.items() if n not in _FFN}
        m, o, f = branches(r, x, lp, stacks, layer, c)
        rms = lambda t: jnp.sqrt(jnp.square(t).mean())
        return x + m + o + f, jnp.stack(
            [rms(m) / rms(x), rms(o) / rms(x), rms(f) / rms(x + m + o)])

    x, shares = jax.lax.scan(jax.checkpoint(block), x,
                             jnp.arange(c["num_hidden_layers"]))
    return shares if ratios else _rms(x, params["final_norm"],
                                      c["rms_norm_eps"])


def branch_ratios(params, tokens, c) -> jnp.ndarray:
    """tokens [b, s] -> [L, 3]: how large the mixer's, attention's and the
    feed-forward's branch are beside the stream each is added to (root mean
    squares, the sequences' mean): what ``assumed.weights`` records."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(functools.partial(
            _sequence_hidden, params, c=c, precision="float32",
            ratios=True), tokens).mean(0)


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32, a sequence
    at a time."""
    return jax.lax.map(functools.partial(
        _sequence_hidden, params, c=c, precision=precision), tokens)


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, vocabulary] float32."""
    r = _round_inputs(precision)
    with jax.default_matmul_precision("highest"):
        x = r(hidden(params, tokens, c, precision))
        return jnp.einsum("bsd,dv->bsv", x, r(params["lm_head"])) \
            * c["lm_head_multiplier"]


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
