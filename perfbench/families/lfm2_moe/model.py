"""Family ``lfm2_moe``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

The block, from the model's ``config.json`` and the family's public
modelling code (no bias anywhere, SiLU, RMSNorm with ``norm_eps`` and a
plain scale); what the keys do not state is the file's ``assumed``::

    x = E[tokens]
    h = x + Op_l(rmsnorm(x; g_op));   x' = h + FFN_l(rmsnorm(h; g_ffn))
    logits = rmsnorm(x; g_out) E^T                              tied

``Op_l``, ``layer_types[l] == "conv"``, ``L = conv_L_cache`` taps::

    [B | C | X] = y W_in                 W_in [d, 3d], parts in that order
    u = B * X
    v_t = sum_{j < L} w[:, j] * u_{t - (L-1) + j}      u_t = 0 for t < 0
    out = (C * v) W_out                  depthwise, causal, no bias

``Op_l``, ``"full_attention"``: ``h`` query and ``hk`` key-value heads of
``hidden_size / h``; RMS norm over each head's queries and keys (a scale of
the head's width); rotary on both, ``rope_theta`` over all the head's dims,
the pair (i, i + half); causal ``softmax(q k^T / sqrt(head)) v``; ``W_o``.

``FFN_l``: the first ``num_dense_layers`` layers a SwiGLU of
``intermediate_size``; the others::

    s = sigmoid(y W_r)                 float32, num_experts wide
    chosen = the num_experts_per_tok largest of s + b   (use_expert_bias)
    w = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor
    out = sum_i w_i SwiGLU_i(y)                         no shared expert

The expert bias ``b`` is TRAINED in the published model, by the update that
balances the experts without an auxiliary loss.  `make` draws it from the
seed and gives it that training's result on the seed's own weights
(`_balance`: a forward pass over tokens drawn from the seed sets each
layer's bias so that every expert meets about its even share of the pairs).
Left as drawn, random routers send most pairs to a few experts and the
bytes a decode step reads follow the seed (PERF.md, PR 32, lesson 1).

The reference is the equations above over the whole sequence in float32 at
``highest``: no cache, no state, no chunk, no kernel, no sort.  The
convolution is ``L`` shifted adds; every expert is applied to every token
under its weight (zero where not chosen) by a scan over the experts, ONE
expert's weights cut out of the stack inside the scan (a layer's slice
would be a copy of 32 experts beside the live engine); attention a block of
queries at a time; the head a block of the vocabulary at a time.  It
imports nothing of the program's model or kernel code (`model_config`
alone names the program's configuration class).  ``precision="fp8"`` is
the control (`reference._round_inputs` on every matmul's inputs); the
router's matmul stays float32 in it, as the configuration states it for
the program, and the convolution's multiply-adds are no matmul.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_KINDS = {"conv": "conv", "full_attention": "full"}
_STACKS = ("w_in", "w_gate", "w_out")


def _head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["norm_topk_prob"], c["use_expert_bias"], c["conv_bias"],
            c["assumed"]["hidden_act"]) != (True, True, False, "silu"):
        raise ValueError("family lfm2_moe: the program normalises the "
                         "chosen scores, chooses under an expert bias, "
                         "convolves without a bias and gates with SiLU")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("family lfm2_moe: layer_types names every layer "
                         "run")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=float(c["rope_theta"]), activation="swiglu",
        norm="rmsnorm", norm_eps=c["norm_eps"],
        tie_embeddings=c["assumed"]["tie_word_embeddings"], qk_norm=True,
        layer_kinds=tuple(_KINDS[t] for t in c["layer_types"]),
        conv_kernel=c["conv_L_cache"],
        n_experts=c["num_experts"], expert_top_k=c["num_experts_per_tok"],
        router="sigmoid", moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=0,
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        first_dense_layers=c["num_dense_layers"],
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


def _operators(kinds) -> Dict[str, int]:
    return {"conv": sum(k == "conv" for k in kinds),
            "full_attention": sum(k != "conv" for k in kinds)}


def _run(key: jax.Array, c: Dict[str, Any], kinds, moe: bool, dtype):
    """One run of layers as the program stacks it: what every layer has
    over all ``len(kinds)`` layers, each operator's weights over ITS layers
    of the run only."""
    d, hd = c["hidden_size"], _head_dim(c)
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    L, n = len(kinds), _operators(kinds)
    names = ("conv_in", "conv_w", "conv_out", "wq", "wk", "wv", "wo",
             "w_in", "w_gate", "w_out", "router", "router_bias")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, count, shape, fan_in, lead=1):
        return _normal(ks[name], (count,) + shape, fan_in, dtype, lead=lead)

    p = {"attn_norm": jnp.ones((L, d), dtype),
         "mlp_norm": jnp.ones((L, d), dtype)}
    if n["conv"]:
        taps = c["conv_L_cache"]
        p.update(conv_in=stack("conv_in", n["conv"], (d, 3 * d), d),
                 conv_w=stack("conv_w", n["conv"], (d, taps), taps),
                 conv_out=stack("conv_out", n["conv"], (d, d), d))
    if n["full_attention"]:
        a = n["full_attention"]
        g = c["assumed"]["qk_norm_scale"]
        p.update(q_norm=jnp.full((a, hd), g, dtype),
                 k_norm=jnp.full((a, hd), g, dtype),
                 wq=stack("wq", a, (d, h, hd), d),
                 wk=stack("wk", a, (d, hk, hd), d),
                 wv=stack("wv", a, (d, hk, hd), d),
                 wo=stack("wo", a, (h, hd, d), h * hd))
    if not moe:
        f = c["intermediate_size"]
        p.update(w_in=stack("w_in", L, (d, f), d),
                 w_gate=stack("w_gate", L, (d, f), d),
                 w_out=stack("w_out", L, (f, d), f))
        return p
    E, f = c["num_experts"], c["moe_intermediate_size"]
    p.update(
        router=stack("router", L, (d, E), d),
        # drawn, not zero, so that it changes choices (the file's
        # ``assumed``), then balanced by `make`
        router_bias=(jax.random.normal(ks["router_bias"], (L, E), jnp.float32)
                     * c["assumed"]["expert_bias_std"]).astype(dtype),
        w_in=stack("w_in", L, (E, d, f), d, lead=2),
        w_gate=stack("w_gate", L, (E, d, f), d, lead=2),
        w_out=stack("w_out", L, (E, f, d), f, lead=2))
    return p


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    the leading dense layers one stacked run, the expert layers another; in
    each, a conv layer's and an attention layer's weights stacked apart."""
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = c["num_dense_layers"]
    k_tok, k_dense, k_moe = jax.random.split(key, 3)
    g = math.gcd(v, 1024)
    params = {
        # the head too (tied): logits of order 1
        "embed": {"tok": _normal(k_tok, (v // g, g, d), d, dtype,
                                 lead=1).reshape(v, d)},
        "dense_layers": _run(k_dense, c, c["layer_types"][:n_dense], False,
                             dtype),
        "layers": _run(k_moe, c, c["layer_types"][n_dense:], True, dtype),
        "final_norm": jnp.ones((d,), dtype),
    }
    n = c["assumed"]["expert_bias_balance_tokens"]
    if n:
        seen = tokens(jax.random.fold_in(key, 7), (1, n), c)
        _, biases = _walk(params, seen, c, "float32", functools.partial(
            _balance, k=c["num_experts_per_tok"]))
        params["layers"]["router_bias"] = jnp.stack(biases).astype(dtype)
    return params


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


def _rotate(x, theta):
    """x [b, heads, s, hd]: the pair (x[i], x[i + hd/2]) turned by the
    angle pos * theta^(-2i/hd)."""
    s, hd = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    lo, hi = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], axis=-1)


def _swiglu(r, y, w_in, w_gate, w_out):
    up = jnp.einsum("bsd,df->bsf", r(y), r(w_in))
    gate = jnp.einsum("bsd,df->bsf", r(y), r(w_gate))
    return jnp.einsum("bsf,fd->bsd", r(gate * jax.nn.sigmoid(gate) * up),
                      r(w_out))


def short_conv(r, y, lp):
    """y [b, s, d] normed -> what a conv layer's operator adds: the
    convolution as shifted adds over the whole sequence."""
    d = y.shape[-1]
    mixed = jnp.einsum("bsd,de->bse", r(y), r(lp["conv_in"]))
    b, c, x = mixed[..., :d], mixed[..., d:2 * d], mixed[..., 2 * d:]
    u, w = b * x, lp["conv_w"].astype(F32)
    taps, s = w.shape[-1], y.shape[1]
    v = jnp.zeros_like(u)
    for j in range(taps):                # tap j meets the token L-1-j back
        back = taps - 1 - j
        v = v + w[:, j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :s]
    return jnp.einsum("bsd,de->bse", r(c * v), r(lp["conv_out"]))


def attention(r, y, lp, c):
    """y [b, s, d] normed -> what an attention layer's operator adds."""
    eps, hd = c["norm_eps"], _head_dim(c)
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    theta = float(c["rope_theta"])
    b, s, _ = y.shape
    q = _rotate(_rms(jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wq"])),
                     lp["q_norm"], eps), theta)
    k = _rotate(_rms(jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wk"])),
                     lp["k_norm"], eps), theta)
    v = jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wv"]))
    block = math.gcd(s, 256)
    j = jnp.arange(s)

    def one_block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, axis=2)
        qb = qb.reshape(b, hk, h // hk, block, hd)
        scores = jnp.einsum("bkgqd,bktd->bkgqt", r(qb), r(k)) \
            / math.sqrt(hd)
        see = j[None, :] <= (i0 + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,bktd->bkgqd", r(probs), r(v))

    a = jax.lax.map(one_block, jnp.arange(0, s, block))   # [n,b,hk,g,q,hd]
    a = jnp.moveaxis(a, 0, 3).reshape(b, h, s, hd)
    return jnp.einsum("bhsk,hkd->bsd", r(a), r(lp["wo"]))


def _scores(y, lp):
    return jax.nn.sigmoid(jnp.einsum("bsd,de->bse", y.astype(F32),
                                     lp["router"].astype(F32)))


def expert_weights(y, lp, c):
    """y [b, s, d] normed -> [b, s, num_experts] float32: each expert's
    weight for each token, zero where the token did not choose it."""
    s = _scores(y, lp)
    _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(F32),
                              c["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-6) \
        * c["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, s.shape[-1], dtype=F32)   # [b,s,k,E]
    return jnp.einsum("bsk,bske->bse", w, onehot)


def routed(r, y, lp, c, layer):
    """What the experts add for y [b, s, d]: ``lp`` holds the stacks of a
    whole run ``[L, E, .., ..]`` of which this is layer ``layer``; one
    expert's weights at a time are cut out of the stack."""
    weight = expert_weights(y, lp, c)

    def one_expert(acc, e):
        i, w_e = e
        w_in, w_gate, w_out = (jax.lax.dynamic_slice(
            lp[k], (layer, i, 0, 0), (1, 1) + lp[k].shape[2:])[0, 0]
            for k in _STACKS)
        return acc + w_e[..., None] * _swiglu(r, y, w_in, w_gate, w_out), \
            None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(y),
        (jnp.arange(c["num_experts"]), jnp.moveaxis(weight, -1, 0)))
    return out


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32."""
    return _walk(params, tokens, c, precision)[0]


def _walk(params, tokens, c, precision: str, rebias=None):
    """The forward pass, a layer at a time -> (final hidden states, the
    expert layers' biases).  With ``rebias(scores [n, E], bias) -> bias``
    each expert layer's bias is first set from the scores of these very
    tokens and the layer then routes by it (`make`'s calibration)."""
    r = _round_inputs(precision)
    eps, n_dense = c["norm_eps"], c["num_dense_layers"]
    biases = []
    x = params["embed"]["tok"][tokens].astype(F32)

    def block(x, lp, op, ffn):
        x = x + op(r, _rms(x, lp["attn_norm"], eps), lp)
        return x + ffn(_rms(x, lp["mlp_norm"], eps), lp)

    def dense(y, lp):
        return _swiglu(r, y, lp["w_in"], lp["w_gate"], lp["w_out"])

    def experts(y, lp, layer):
        if rebias is not None:
            lp = dict(lp, router_bias=rebias(
                _scores(y, lp).reshape(-1, lp["router"].shape[-1]),
                lp["router_bias"].astype(F32)))
            biases.append(lp["router_bias"])
        return routed(r, y, lp, c, layer)

    ops = {"conv": short_conv,
           "full_attention": functools.partial(attention, c=c)}
    own = {"conv": ("conv_in", "conv_w", "conv_out"),
           "full_attention": ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}
    # a layer at a time, in model order: each operator's weights are
    # stacked over its own layers of the run, counted here as they pass;
    # the expert stacks go in whole, with the layer's index beside them
    seen = {}
    for i, kind in enumerate(c["layer_types"]):
        moe = i >= n_dense
        run, at = ("layers", i - n_dense) if moe else ("dense_layers", i)
        nth = seen.get((run, kind), 0)
        seen[run, kind] = nth + 1
        lp = {}
        for k, a in params[run].items():
            if k in own[kind]:
                lp[k] = a[nth]
            elif not any(k in names for names in own.values()):
                lp[k] = a if moe and k in _STACKS else a[at]
        ffn = functools.partial(experts, layer=at) if moe else dense
        layer = functools.partial(block, op=ops[kind], ffn=ffn)
        # (a calibration pass takes no gradient, and hands its biases out)
        x = (layer if rebias is not None else jax.checkpoint(layer))(x, lp)
    return _rms(x, params["final_norm"], eps), biases


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, vocabulary] float32 against the input embedding, filled a
    block of the vocabulary at a time."""
    r = _round_inputs(precision)
    table = params["embed"]["tok"]
    v, d = table.shape
    block = math.gcd(v, 4096)
    with jax.default_matmul_precision("highest"):
        x = r(hidden(params, tokens, c, precision))

        def fill(i, out):
            rows = jax.lax.dynamic_slice(table, (i * block, 0), (block, d))
            return jax.lax.dynamic_update_slice(
                out, jnp.einsum("bsd,vd->bsv", x, r(rows)),
                (0, 0, i * block))

        return jax.lax.fori_loop(
            0, v // block, fill, jnp.zeros(tokens.shape + (v,), F32))


def loss(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """Mean next-token cross entropy over positions 0..s-2; the router's
    bias is a constant and there is no auxiliary loss."""
    lg = logits(params, tokens, c, precision)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean()


def loss_and_grad(params, tokens, c, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            functools.partial(loss, c=c, precision=precision))(
                params, tokens)
