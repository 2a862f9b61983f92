"""Family ``afmoe``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

The block, from the model's ``config.json`` and the family's public
modelling code (no bias anywhere, SiLU, RMSNorm with ``rms_norm_eps`` and a
plain scale); what the keys do not state is the file's ``assumed``::

    x = E[tokens] * sqrt(hidden_size)                          mup_enabled
    x = x + rmsnorm_post_attn(attn_l(rmsnorm_in(x)))           sandwich
    x = x + rmsnorm_post_mlp(ffn_l(rmsnorm_pre_mlp(x)))
    logits = rmsnorm(x) W_head                                 untied

``attn_l(y)``, ``h`` query heads and ``hk`` key-value heads of ``head_dim``
(queries are ``h x head_dim`` wide, which is not ``hidden_size``)::

    q = y W_q; k = y W_k; v = y W_v;  g = sigmoid(y W_g)   [h x head_dim]
    q, k = rmsnorm over each head's head_dim (q_norm, k_norm)
    sliding_attention layer: q, k rotated (theta, all head_dim dims, no
        scaling); position i sees j <= i with i - j < sliding_window
    full_attention layer: NOTHING rotated; i sees every j <= i
    scores q k^T / sqrt(head_dim), softmax float32, h / hk query heads a
        key-value head;  out = (attn * g) W_o

``ffn_l``: the first ``num_dense_layers`` layers a SwiGLU of
``intermediate_size``; the others::

    s = sigmoid(y W_r)                 float32, experts_routed wide
    chosen = the num_experts_per_tok largest of s + b   (b: expert bias)
    w = s[chosen] / sum s[chosen] * route_scale         (route_norm)
    out = sum_i w_i SwiGLU_i(y)  +  SwiGLU_shared(y)

The expert bias ``b`` is TRAINED in the published model, by the update
that balances the experts without an auxiliary loss.  Here `make` draws it
from the seed and, where the file's ``assumed`` names calibration tokens,
gives it that training's result on the seed's own weights (`_balance`) and
PLACES the experts on the chips by load (`_place`), as expert-parallel
serving does: a forward pass over tokens drawn from the seed sets each
layer's bias and deals its experts to the chips so that every chip's share
meets about its even share of the pairs.  Left as drawn, random weights route nine pairs in ten
to a dozen experts, and what lands on the 32 held here swings by a factor
of three from seed to seed (PERF.md, PR 32).

The scales of ``q_norm`` and ``k_norm`` are TRAINED too.  At 1, random
normed queries and keys score every row alike (standard deviation 1), a
row's attention is the mean of thousands of values, which is the same
vector for every token of a chunk, and the norm after attention scales it
to the size of the token's own embedding: the tokens of a chunk then route
alike (10 of the 32 held experts touched by a chunk of 128 where
independent choices touch 27), by a number that is the seed's luck.  `make`
sets both to ``assumed.qk_norm_scale``.

A configuration may hold a SHARE of the experts (``num_experts`` of
``deployment.experts_routed`` from ``deployment.expert_offset``): the router
scores all of them, the chosen experts that live elsewhere add nothing
here, in the program and in the reference alike, and that partial result
goes on to the next layer.  Rotary pairs (i, i + head_dim/2), as
`ray_tpu.ops.rotary` (the file's ``assumed``).

The reference is that in float32 at ``highest``: no cache, no ring, no
kernel, no sort.  Every held expert is applied to every token under its
weight (zero where not chosen) by a scan over the experts, its weights
turned to float32 an expert at a time; attention a block of queries at a
time under the mask written out from positions (at 8448 positions the
scores of all 48 heads at once would be 13.7 GB); the head a block of the
vocabulary at a time.  ``precision="fp8"`` is the control
(`reference._round_inputs`); the router's matmul stays float32 in it, as
the configuration states it for the program.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def _routed(c: Dict[str, Any]) -> int:
    return c["deployment"]["experts_routed"]


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["score_func"], c["route_norm"], c["hidden_act"], c["n_group"],
            c["topk_group"], c["rope_scaling"], c["mup_enabled"]) != (
                "sigmoid", True, "silu", 1, 1, None, True):
        raise ValueError("family afmoe: the program routes by sigmoid "
                         "scores without group limits, normalises the "
                         "chosen, gates with SiLU, scales no rotary angle "
                         "and multiplies the embedding")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError("family afmoe: layer_types names every layer run")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
        d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=float(c["rope_theta"]), rope_layers="window",
        activation="swiglu", norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        qk_norm=True, attn_gate=True, sandwich_norm=True,
        embed_scale=math.sqrt(c["hidden_size"]),
        layer_kinds=tuple(_KINDS[t] for t in c["layer_types"]),
        sliding_window=c["sliding_window"],
        window_chunk=c["deployment"]["window_chunk"],
        n_experts=_routed(c), experts_held=c["num_experts"],
        expert_offset=c["deployment"]["expert_offset"],
        expert_top_k=c["num_experts_per_tok"], router="sigmoid",
        moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=c["num_shared_experts"],
        routed_scaling_factor=c["route_scale"],
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


def _rows(key, n_rows: int, width: int, fan_in: float, dtype):
    """[n_rows, width] in blocks of rows."""
    g = math.gcd(n_rows, 1024)
    return _normal(key, (n_rows // g, g, width), fan_in, dtype,
                   lead=1).reshape(n_rows, width)


def _run(key: jax.Array, c: Dict[str, Any], L: int, moe: bool, dtype):
    d, hd = c["hidden_size"], c["head_dim"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    names = ("wq", "wk", "wv", "wg", "wo", "w_in", "w_gate", "w_out",
             "router", "router_bias", "ws_in", "ws_gate", "ws_out")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, shape, fan_in, lead=1):
        return _normal(ks[name], (L,) + shape, fan_in, dtype, lead=lead)

    p = {n: jnp.ones((L, d), dtype) for n in (
        "attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm")}
    # a head's query and key scales: scores of standard deviation
    # qk_norm_scale ** 2, so that a row attends to a few rows as a trained
    # head does and not to the mean of thousands (the file's ``assumed``)
    g = c["assumed"]["qk_norm_scale"]
    p.update(q_norm=jnp.full((L, hd), g, dtype),
             k_norm=jnp.full((L, hd), g, dtype),
             wq=stack("wq", (d, h, hd), d), wk=stack("wk", (d, hk, hd), d),
             wv=stack("wv", (d, hk, hd), d), wg=stack("wg", (d, h, hd), d),
             wo=stack("wo", (h, hd, d), h * hd))
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
        # drawn, not zero, so that it changes choices (the file's
        # ``assumed``): a trained model's bias is what balanced its experts
        router_bias=(jax.random.normal(ks["router_bias"], (L, E), jnp.float32)
                     * c["assumed"]["expert_bias_std"]).astype(dtype),
        w_in=stack("w_in", (held, d, f), d, lead=2),
        w_gate=stack("w_gate", (held, d, f), d, lead=2),
        w_out=stack("w_out", (held, f, d), f, lead=2),
        ws_in=stack("ws_in", (d, fs), d),
        ws_gate=stack("ws_gate", (d, fs), d),
        ws_out=stack("ws_out", (fs, d), fs))
    return p


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    the leading dense layers one stacked run, the expert layers another
    (window and full layers alike: a layer's kind is the configuration's,
    its weights have one shape)."""
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = c["num_dense_layers"]
    k_tok, k_head, k_dense, k_moe = jax.random.split(key, 4)
    params = {
        "embed": {"tok": _rows(k_tok, v, d, 2500.0, dtype)},   # std 0.02
        "dense_layers": _run(k_dense, c, n_dense, False, dtype),
        "layers": _run(k_moe, c, c["num_hidden_layers"] - n_dense, True,
                       dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": _rows(k_head, d, v, d, dtype),
    }
    n = c["assumed"]["expert_bias_balance_tokens"]
    if n:
        seen = tokens(jax.random.fold_in(key, 7), (1, n), c)
        _, routers = _walk(params, seen, c, "float32", functools.partial(
            _place, k=c["num_experts_per_tok"], held=c["num_experts"]))
        for name in ("router", "router_bias"):
            params["layers"][name] = jnp.stack(
                [r[name] for r in routers]).astype(dtype)
    return params


def _place(scores, lp, k: int, held: int):
    """One expert layer's router as a deployment would leave it, from the
    scores [n, E] of calibration tokens: the bias balanced (`_balance`), and
    the experts PLACED on the chips by load, as expert-parallel serving
    places them: ranked by the pairs they still draw under that bias and
    dealt to the ``E / held`` chips in turn, so that every chip's ``held``
    experts are a like sample of popular and idle ones.  With random
    weights an expert's number names nothing, so placing is a reordering of
    the router's columns (and the bias with them)."""
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


def _scores(y, lp):
    return jax.nn.sigmoid(jnp.einsum("bsd,de->bse", y.astype(F32),
                                     lp["router"].astype(F32)))


def expert_weights(y, lp, c):
    """y [b, s, d] normed -> [b, s, experts_routed] float32: each expert's
    weight for each token, zero where the token did not choose it."""
    s = _scores(y, lp)
    _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(F32),
                              c["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * c["route_scale"]
    onehot = jax.nn.one_hot(chosen, s.shape[-1], dtype=F32)   # [b,s,k,E]
    return jnp.einsum("bsk,bske->bse", w, onehot)


def routed_part(r, y, lp, c, offset: int, held: int, layer=None):
    """What the experts ``offset .. offset + held - 1`` add for y [b, s,
    d]: a chip's share of the layer.  ``lp`` holds exactly those experts'
    weights ``[held, .., ..]``, or, with ``layer``, the stacks of a whole
    run of layers ``[L, held, .., ..]`` of which this is layer ``layer``:
    one expert's weights at a time are cut out of the stack (a layer's
    slice of it would be a copy of 32 experts)."""
    weight = expert_weights(y, lp, c)[..., offset:offset + held]
    stacks = [lp[k] for k in ("w_in", "w_gate", "w_out")]

    def one_expert(acc, e):
        i, w_e = e
        w_in, w_gate, w_out = (
            jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)
            if layer is None else jax.lax.dynamic_slice(
                w, (layer, i, 0, 0), (1, 1) + w.shape[2:])[0, 0]
            for w in stacks)
        return acc + w_e[..., None] * _swiglu(r, y, w_in, w_gate, w_out), \
            None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(y),
        (jnp.arange(held), jnp.moveaxis(weight, -1, 0)))
    return out


def shared_part(r, y, lp):
    return _swiglu(r, y, lp["ws_in"], lp["ws_gate"], lp["ws_out"])


def attention(r, y, lp, c, window: bool):
    """y [b, s, d] normed -> what the attention block adds, before its
    post-norm; ``window``: a sliding_attention layer."""
    eps, hd = c["rms_norm_eps"], c["head_dim"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    b, s, _ = y.shape
    q = _rms(jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wq"])),
             lp["q_norm"], eps)
    k = _rms(jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wk"])),
             lp["k_norm"], eps)
    v = jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wv"]))
    g = jax.nn.sigmoid(jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wg"])))
    if window:
        q, k = _rotate(q, float(c["rope_theta"])), \
            _rotate(k, float(c["rope_theta"]))
    block = math.gcd(s, 256)
    j = jnp.arange(s)

    def one_block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, axis=2)
        qb = qb.reshape(b, hk, h // hk, block, hd)
        scores = jnp.einsum("bkgqd,bktd->bkgqt", r(qb), r(k)) \
            / math.sqrt(hd)
        i = i0 + jnp.arange(block)
        see = j[None, :] <= i[:, None]
        if window:
            see &= i[:, None] - j[None, :] < c["sliding_window"]
        probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqt,bktd->bkgqd", r(probs), r(v))

    a = jax.lax.map(one_block, jnp.arange(0, s, block))   # [n,b,hk,g,q,hd]
    a = jnp.moveaxis(a, 0, 3).reshape(b, h, s, hd)
    return jnp.einsum("bhsk,hkd->bsd", r(a * g), r(lp["wo"]))


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32."""
    return _walk(params, tokens, c, precision)[0]


def _walk(params, tokens, c, precision: str, reroute=None):
    """The forward pass, a layer at a time -> (final hidden states, the
    expert layers' routers).  With ``reroute(scores [n, E], lp) -> {router,
    router_bias}`` each expert layer's router is first set from the scores
    of these very tokens and the layer then routes by it (`make`'s
    calibration)."""
    r = _round_inputs(precision)
    routers = []
    eps = c["rms_norm_eps"]
    held, offset = c["num_experts"], c["deployment"]["expert_offset"]
    x = params["embed"]["tok"][tokens].astype(F32) \
        * math.sqrt(c["hidden_size"])

    def block(x, lp, window, ffn):
        y = _rms(x, lp["attn_norm"], eps)
        x = x + _rms(attention(r, y, lp, c, window),
                     lp["post_attn_norm"], eps)
        y = _rms(x, lp["mlp_norm"], eps)
        return x + _rms(ffn(y, lp), lp["post_mlp_norm"], eps)

    def dense(y, lp):
        return _swiglu(r, y, lp["w_in"], lp["w_gate"], lp["w_out"])

    def experts(y, lp, layer):
        if reroute is not None:
            lp = dict(lp, **reroute(
                _scores(y, lp).reshape(-1, lp["router"].shape[-1]), lp))
        routers.append({k: lp[k] for k in ("router", "router_bias")})
        return routed_part(r, y, lp, c, offset, held, layer) \
            + shared_part(r, y, lp)

    # a layer at a time, in model order: each layer's kind is its own.
    # The expert stacks go in whole, with the layer's index beside them
    n_dense = c["num_dense_layers"]
    stacks = ("w_in", "w_gate", "w_out")
    for i, kind in enumerate(c["layer_types"]):
        routed = i >= n_dense
        run, at = ("layers", i - n_dense) if routed else ("dense_layers", i)
        lp = {k: a if routed and k in stacks else a[at]
              for k, a in params[run].items()}
        ffn = functools.partial(experts, layer=at) if routed else dense
        layer = functools.partial(
            block, window=kind == "sliding_attention", ffn=ffn)
        # (a calibration pass takes no gradient, and hands its biases out)
        x = (layer if reroute is not None else jax.checkpoint(layer))(x, lp)
    return _rms(x, params["final_norm"], eps), routers


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, vocabulary] float32, filled a block of the vocabulary at a
    time."""
    r = _round_inputs(precision)
    head = params["lm_head"]
    d, v = head.shape
    block = math.gcd(v, 3128)
    with jax.default_matmul_precision("highest"):
        x = r(hidden(params, tokens, c, precision))

        def fill(i, out):
            w = jax.lax.dynamic_slice(head, (0, i * block), (d, block))
            return jax.lax.dynamic_update_slice(
                out, jnp.einsum("bsd,dv->bsv", x, r(w)), (0, 0, i * block))

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
