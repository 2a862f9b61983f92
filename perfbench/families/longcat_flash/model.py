"""Family ``longcat_flash``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

``rms(u; g) = u / sqrt(mean(u^2) + rms_norm_eps) * g``; ``d = hidden_size``,
``H = num_attention_heads``, ``E = deployment.experts_routed`` experts and ``Z
= zero_expert_num`` IDENTITY experts behind them in the router's outputs,
``k = moe_topk``, ``s = routed_scaling_factor``.

Latent attention ``A(y; W)`` of a normed input, one weight set a SUBLAYER::

    c_q  = rms(y W_qa; g_q) * sqrt(d / q_lora_rank)       (mla_scale_q_lora)
    q_h  = c_q W_qb,h          nope | rope, the rope part turned at rope_theta
    [c | k_r] = y W_kva;  c = rms(c; g_kv) * sqrt(d / kv_lora_rank)
                               (mla_scale_kv_lora); k_r turned, NOT rescaled
    [k_n,h | v_h] = c W_kvb,h
    p_h(t, j) = softmax_{j <= t}((q_n . k_n + q_r . k_r) / sqrt(nope + rope))
    A = concat_h(sum_j p_h(t, j) v_h(j)) W_o

Dense feed-forward ``D(u) = (silu(u W_g) * (u W_u)) W_d`` of width
``ffn_hidden_size``; an expert ``X_e`` the same at ``expert_ffn_hidden_size``.

Routed branch ``M(u)`` of a normed input::

    p = softmax(u W_r)         float32, over ALL E + Z outputs, no bias
    S = the k largest of p + b       the correction bias moves the choice only
    w_e = s p_e  for e in S          NOT renormalised over the chosen
    M(u) = sum_{e in S, e < E} w_e X_e(u)  +  (sum_{e in S, e >= E} w_e) u

ONE PUBLISHED LAYER is two sublayers and one routed branch that leaves the
stream behind the first attention and rejoins it behind the second::

    h0 = x  + A(rms(x;  a_0); W^0)
    u0 = rms(h0; b_0);   m = M(u0)                computed HERE ...
    h1 = h0 + D(u0; W^0)
    h2 = h1 + A(rms(h1; a_1); W^1)
    u1 = rms(h2; b_1)
    x' = h2 + D(u1; W^1) + m                      ... and added HERE

Token embedding unscaled, logits ``rms(x_L; g_f) W_head``, the head untied.

A configuration may hold a SHARE of the experts (``n_routed_experts`` of ``E``
from ``deployment.expert_offset``): the router scores all ``E + Z`` outputs, a
token's weights are those of its published choice, only the held experts'
part of the sum is computed and nothing stands in for the rest; an identity
expert has no weights and no home, so EVERY chip computes the identity part
for its own tokens.  A sliced vocabulary is a smaller one.

The published bias is the result of a controller that holds the experts'
loads even and the mean of REAL experts a token at its target: `make` draws
the bias from the seed, gives it that result on the seed's own weights over
calibration tokens (`_balance`) and PLACES the experts on the chips by load
(`_place`).

The reference is that in float32 at ``highest``: no cache, no absorption of
the key-value up-projection, no kernel, no sort: every held expert is applied
to every token under its weight (zero where not chosen), the identity part is
``(sum of the chosen identity weights) u``.  It has to fit beside the live
engine, so it goes a sequence at a time, attention a head and a block of
queries at a time, a feed-forward 2048 of its width at a time, the experts one
at a time out of their stack, the head a block of the vocabulary at a time,
the layers in a loop with every weight indexed INSIDE it (a sublayer's slice
of a stack, taken outside, is a copy the compiler keeps several of).
``precision="fp8"`` is the control (`reference._round_inputs`); the router's
matmul stays float32 in it, as the configuration states it for the program.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_ROUTED = ("we_in", "we_gate", "we_out")    # the program's expert stacks


def _routed(c: Dict[str, Any]) -> int:
    return c["deployment"]["experts_routed"]


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["attention_method"], c["zero_expert_type"], c["attention_bias"],
            c["mla_scale_q_lora"], c["mla_scale_kv_lora"]) != (
                "MLA", "identity", False, True, True):
        raise ValueError("family longcat_flash: latent attention without "
                         "bias, both latents rescaled, and zero experts "
                         "that hand their input on")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=2 * c["num_layers"], n_heads=c["num_attention_heads"],
        d_ff=c["ffn_hidden_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=float(c["rope_theta"]), activation="swiglu",
        norm="rmsnorm", norm_eps=c["rms_norm_eps"], tie_embeddings=False,
        attention="mla", q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        latent_rescale=True, shortcut_moe=True,
        n_experts=_routed(c), experts_held=c["n_routed_experts"],
        expert_offset=c["deployment"]["expert_offset"],
        zero_experts=c["zero_expert_num"], expert_top_k=c["moe_topk"],
        router="softmax_bias", moe_d_ff=c["expert_ffn_hidden_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
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


def _run(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The stacked tree of the ``2 x num_layers`` sublayers: attention, norms
    and the dense feed-forward over all of them, the router, its bias and
    the held experts over the routing sublayers (every second one) alone."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    F, f = c["ffn_hidden_size"], c["expert_ffn_hidden_size"]
    held, outs = c["n_routed_experts"], _routed(c) + c["zero_expert_num"]
    n, L = 2 * c["num_layers"], c["num_layers"]
    a = c["assumed"]
    names = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_in", "w_gate",
             "w_out", "router", "router_bias") + _ROUTED
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, shape, fan_in, lead=1, layers=n):
        return _normal(ks[name], (layers,) + shape, fan_in, dtype, lead=lead)

    return {
        "attn_norm": jnp.ones((n, d), dtype),
        "mlp_norm": jnp.ones((n, d), dtype),
        "q_norm": jnp.ones((n, ql), dtype),
        "kv_norm": jnp.ones((n, kl), dtype),
        "wq_a": stack("wq_a", (d, ql), d),
        # the two up-projections read a RESCALED latent: drawn / sqrt(d), a
        # head's query, key and value have the unit variance of every other
        # projection's output (the file's ``assumed.weights``)
        "wq_b": stack("wq_b", (ql, h, nope + rope), d),
        "wkv_a": stack("wkv_a", (d, kl + rope), d),
        "wkv_b": stack("wkv_b", (kl, h, nope + v), d),
        "wo": stack("wo", (h, v, d), h * v),
        "w_in": stack("w_in", (d, F), d),
        "w_gate": stack("w_gate", (d, F), d),
        "w_out": stack("w_out", (F, d), F),
        # the router's rows at a gain (``assumed.router_gain``: the chosen
        # scores' mass, and with it the routed branch's size, follows it)
        "router": stack("router", (d, outs), d / a["router_gain"] ** 2,
                        layers=L),
        # drawn, not zero, so that it changes choices; in units of the mean
        # score 1 / outputs
        "router_bias": (jax.random.normal(ks["router_bias"], (L, outs),
                                          jnp.float32)
                        * a["e_score_correction_bias_std"] / outs
                        ).astype(dtype),
        "we_in": stack("we_in", (held, d, f), d, lead=2, layers=L),
        "we_gate": stack("we_gate", (held, d, f), d, lead=2, layers=L),
        "we_out": stack("we_out", (held, f, d), f, lead=2, layers=L),
    }


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    ONE stacked run of sublayers.  ONE compiled program a call."""
    return _as_one_program(_make, c=c, dtype=dtype)(key)


def _as_one_program(fn, **fixed):
    """``fn`` with its configuration bound, compiled as one program: a layer
    at a time in Python is hundreds of small programs when called eagerly,
    and inside a caller's own `jax.jit` this is no program of its own."""
    return jax.jit(functools.partial(fn, **fixed))


def _make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, v = c["hidden_size"], c["vocab_size"]
    k_tok, k_head, k_run = jax.random.split(key, 3)
    params = {
        # rows of unit scale (fan_in 1: a row is looked up, not summed): a
        # token's own embedding is the size of what a layer adds to it
        "embed": {"tok": _rows(k_tok, v, d, 1.0, dtype)},
        "layers": _run(k_run, c, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": _rows(k_head, d, v, d, dtype),
    }
    n = c["assumed"].get("expert_bias_balance_tokens", 0)
    if n:
        seen = tokens(jax.random.fold_in(key, 7), (n,), c)
        _, routers = _walk(params, seen, c, "float32", functools.partial(
            _place, c=c))
        for name in ("router", "router_bias"):
            params["layers"][name] = routers[name].astype(dtype)
    return params


def _loads(scores, bias, k: int):
    """The pairs each output draws of tokens that choose the ``k`` largest
    of ``scores + bias`` [n, outputs]."""
    _, chosen = jax.lax.top_k(scores + bias, k)
    return jnp.zeros((scores.shape[-1],), F32).at[chosen.reshape(-1)].add(1.0)


def _place(scores, lp, c: Dict[str, Any]):
    """One routing sublayer's router as a deployment would leave it, from
    the scores [n, E + Z] of calibration tokens: the bias balanced
    (`_balance`), and the E REAL experts PLACED on the chips by load: ranked
    by the pairs they still draw under that bias and dealt to the ``E /
    held`` chips in turn, so that every chip's ``held`` experts are a like
    sample of popular and idle ones.  With random weights an expert's number
    names nothing, so placing is a reordering of the router's first E columns
    (and the bias with them); the identity outputs stay where they are."""
    E, held, k = _routed(c), c["n_routed_experts"], c["moe_topk"]
    outs = scores.shape[-1]
    bias = _balance(scores, lp["router_bias"].astype(F32), k, E,
                    c["assumed"]["real_experts_per_token"])
    ranked = jnp.argsort(-_loads(scores, bias, k)[:E])  # expert of rank r
    rank = jnp.arange(E)
    seat = (rank % (E // held)) * held + rank // (E // held)
    source = jnp.concatenate([
        jnp.zeros((E,), jnp.int32).at[seat].set(ranked.astype(jnp.int32)),
        jnp.arange(E, outs, dtype=jnp.int32)])
    return {"router": lp["router"][:, source], "router_bias": bias[source]}


def _balance(scores, bias, k: int, E: int, real: float, steps: int = 64,
             rate: float = 0.25):
    """scores [n, E + Z] of n tokens, a starting bias -> the bias after the
    published controller's work: first each output's mean score excess is
    taken off, then ``steps`` times the outputs chosen under the bias are
    counted and one with more than its target share of the pairs loses
    ``rate`` of the scores' spread (falling to 0), one with fewer gains it.
    The targets: the E real experts' loads even at ``real`` a token between
    them, the identity outputs' even at the other ``k - real`` (their bias
    is the handle that holds the mean of real experts a token)."""
    n, outs = scores.shape
    target = jnp.where(jnp.arange(outs) < E, n * real / E,
                       n * (k - real) / max(1, outs - E))
    unit = rate * scores.std()
    bias = bias - (scores.mean(0) - scores.mean())

    def step(i, b):
        return b + unit * (1.0 - i / steps) * jnp.sign(
            target - _loads(scores, b, k))

    return jax.lax.fori_loop(0, steps, step, bias)


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    return jax.random.randint(key, shape, 0, c["vocab_size"], jnp.int32)


# ------------------------------------------------------ the plain reference

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


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


def _swiglu(r, y, w_in, w_gate, w_out, layer=None):
    """``SwiGLU(y)`` for ``y`` [s, d], its width taken 2048 at a time: a
    dense feed-forward's ``[s, 12288]`` float32 products, and its weights in
    float32, are not held whole.  With ``layer`` the three are STACKS over
    the sublayers and each block is cut out of its stack inside the loop (a
    sublayer's slice of a stack would be a copy of 151 MB a matrix, and the
    compiler holds several sublayers' at once)."""
    f = w_in.shape[-1]
    block = math.gcd(f, 2048)
    y = r(y)

    def cut(a, axis, i):
        if layer is None:
            return jax.lax.dynamic_slice_in_dim(a, i * block, block, axis)
        start = [layer, 0, 0]
        start[1 + axis] = i * block
        size = [1, a.shape[1], a.shape[2]]
        size[1 + axis] = block
        return jax.lax.dynamic_slice(a, start, size)[0]

    def some_width(i, acc):
        up = jnp.einsum("sd,df->sf", y, r(cut(w_in, 1, i)))
        gate = jnp.einsum("sd,df->sf", y, r(cut(w_gate, 1, i)))
        return acc + jnp.einsum(
            "sf,fd->sd", r(gate * jax.nn.sigmoid(gate) * up),
            r(cut(w_out, 0, i)))

    zero = jnp.zeros(y.shape, F32)
    if f == block:
        return some_width(0, zero)
    return jax.lax.fori_loop(0, f // block, some_width, zero)


def _query_block(s: int) -> int:
    """Queries attended at a time: a divisor of ``s``."""
    return math.gcd(s, 512)


def _at(stack, i):
    return jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)


def attention(r, y, tree, i, c):
    """One sequence's normed input ``y`` [s, d] -> ``A(y; W^i)`` [s, d] with
    sublayer ``i``'s weights out of the run's stacks ``tree``, a head and a
    block of queries at a time."""
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    theta, nope, kl = float(c["rope_theta"]), c["qk_nope_head_dim"], \
        c["kv_lora_rank"]
    rope = c["qk_rope_head_dim"]
    s = y.shape[0]
    c_q = math.sqrt(d / c["q_lora_rank"]) * _rms(
        jnp.einsum("sd,dr->sr", r(y), r(_at(tree["wq_a"], i))),
        _at(tree["q_norm"], i), eps)
    ckv = jnp.einsum("sd,dr->sr", r(y), r(_at(tree["wkv_a"], i)))
    c_kv = math.sqrt(d / kl) * _rms(ckv[:, :kl], _at(tree["kv_norm"], i), eps)
    k_r = _rotate(ckv[:, kl:], theta)                          # [s, rope]
    qb = _query_block(s)
    at = jnp.arange(s)

    def head(name, j, axis):
        # sublayer i's head j of a stack [n, .., heads, ..] with the heads
        # on ``axis``, cut out inside the loop (a sublayer's slice, or the
        # stack turned heads-first, would be a copy)
        a = tree[name]
        start, size = [i] + [0] * (a.ndim - 1), [1] + list(a.shape[1:])
        start[axis], size[axis] = j, 1
        return jnp.squeeze(jax.lax.dynamic_slice(a, start, size), (0, axis))

    def one_head(acc, j):
        wq, wkv, wo = head("wq_b", j, 2), head("wkv_b", j, 2), \
            head("wo", j, 1)
        q = jnp.einsum("sr,rk->sk", r(c_q), r(wq))
        q = jnp.concatenate([q[:, :nope], _rotate(q[:, nope:], theta)], -1)
        kv = jnp.einsum("sr,rk->sk", r(c_kv), r(wkv))
        k = jnp.concatenate([kv[:, :nope], k_r], axis=-1)
        v = kv[:, nope:]

        def some_queries(inp):
            qj, t = inp
            scores = jnp.einsum("qk,tk->qt", r(qj), r(k)) \
                / math.sqrt(nope + rope)
            probs = jax.nn.softmax(
                jnp.where(at[None, :] <= t[:, None], scores, -jnp.inf),
                axis=-1)
            return jnp.einsum("qt,tv->qv", r(probs), r(v))

        a = jax.lax.map(some_queries, (q.reshape(s // qb, qb, -1),
                                       at.reshape(s // qb, qb)))
        return acc + jnp.einsum("sv,vd->sd", r(a.reshape(s, -1)), r(wo)), None

    out, _ = jax.lax.scan(one_head, jnp.zeros_like(y, dtype=F32),
                          jnp.arange(tree["wo"].shape[1]))
    return out


def _scores(u, lp):
    """u [s, d] normed -> the router's softmax scores [s, E + Z], float32."""
    return jax.nn.softmax(jnp.einsum("sd,de->se", u.astype(F32),
                                     lp["router"].astype(F32)), axis=-1)


def output_weights(u, lp, c):
    """u [s, d] normed -> [s, E + Z] float32: each router output's weight
    for each token (``s p_e``, not renormalised), zero where the token did
    not choose it."""
    p = _scores(u, lp)
    _, chosen = jax.lax.top_k(p + lp["router_bias"].astype(F32),
                              c["moe_topk"])
    w = jnp.take_along_axis(p, chosen, axis=-1) * c["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, p.shape[-1], dtype=F32)    # [s, k, E+Z]
    return jnp.einsum("sk,ske->se", w, onehot)


def routed_part(r, u, lp, c, offset: int, held: int, layer: int,
                identity: bool = True):
    """The part of ``M(u)`` that the ``held`` experts from ``offset`` give for
    ``u`` [s, d], every one of them applied to every token under its weight
    and cut out of its stack ``lp[name]`` [L, held, ...] inside the loop (a
    slice of a layer's experts would be a copy of them), and with
    ``identity`` the identity experts' part, which EVERY share computes
    alike: the chosen identity weights' sum times ``u`` itself."""
    weight = output_weights(u, lp, c)
    mine = jax.lax.dynamic_slice_in_dim(weight, offset, held, axis=1)

    def one_expert(acc, e):
        w_in, w_gate, w_out = (jax.lax.dynamic_slice(
            lp[n], (layer, e, 0, 0), (1, 1) + lp[n].shape[2:])[0, 0]
            for n in _ROUTED)
        return acc + jax.lax.dynamic_index_in_dim(
            mine, e, axis=1) * _swiglu(r, u, w_in, w_gate, w_out), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u, dtype=F32),
                          jnp.arange(held))
    if identity:
        out = out + weight[:, _routed(c):].sum(-1, keepdims=True) \
            * u.astype(F32)
    return out


def published_layer(r, x, tree, j, c, reroute=None):
    """``x`` [s, d] through published layer ``j`` (sublayers ``2j`` and ``2j
    + 1`` of the run's stacks ``tree``, the routing weights at ``j``) -> (x',
    the router it routed by)."""
    eps = c["rms_norm_eps"]
    held, offset = c["n_routed_experts"], c["deployment"]["expert_offset"]
    i0, i1 = 2 * j, 2 * j + 1
    dense = tuple(tree[k] for k in ("w_in", "w_gate", "w_out"))
    lp = dict({k: tree[k] for k in _ROUTED},
              router=_at(tree["router"], j),
              router_bias=_at(tree["router_bias"], j))
    h0 = x + attention(r, _rms(x, _at(tree["attn_norm"], i0), eps), tree, i0,
                       c)
    u0 = _rms(h0, _at(tree["mlp_norm"], i0), eps)
    if reroute is not None:
        lp = dict(lp, **reroute(_scores(u0, lp), lp))
    m = routed_part(r, u0, lp, c, offset, held, j)         # computed HERE
    h1 = h0 + _swiglu(r, u0, *dense, layer=i0)
    h2 = h1 + attention(r, _rms(h1, _at(tree["attn_norm"], i1), eps), tree,
                        i1, c)
    u1 = _rms(h2, _at(tree["mlp_norm"], i1), eps)
    out = h2 + _swiglu(r, u1, *dense, layer=i1) + m        # ... added HERE
    # (beside the router, the pairs each of its outputs drew of these tokens)
    return out, dict({k: lp[k] for k in ("router", "router_bias")},
                     load=_loads(_scores(u0, lp),
                                 lp["router_bias"].astype(F32),
                                 c["moe_topk"]))


def _walk(params, toks, c, precision: str, reroute=None):
    """One sequence's tokens [s] through the layers -> (final hidden states
    [s, d], the routers {router [L, d, E + Z], router_bias [L, E + Z]} the
    layers routed by and ``load`` [L, E + Z], the pairs each output drew).  A loop over the published layers with every weight
    indexed INSIDE it: one layer's slices exist at a time.  With
    ``reroute(scores [s, E + Z], lp) -> {router, router_bias}`` each router
    is first set from the scores of these very tokens and the layer then
    routes by it (`make`'s calibration)."""
    r = _round_inputs(precision)
    tree = params["layers"]
    x = params["embed"]["tok"][toks].astype(F32)
    layer = functools.partial(published_layer, r, tree=tree, c=c,
                              reroute=reroute)
    # (a calibration pass takes no gradient)
    x, routers = jax.lax.scan(
        (lambda x, j: layer(x, j=j)) if reroute is not None
        else jax.checkpoint(lambda x, j: layer(x, j=j)),
        x, jnp.arange(c["num_layers"]))
    return _rms(x, params["final_norm"], c["rms_norm_eps"]), routers


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
    """The head a block of the vocabulary at a time, the blocks set side by
    side and SPELLED OUT, so that a caller who then takes a few positions of
    the result (`kinds/serve_common.py` `_verify`) compiles to the blocks'
    few positions and neither the head in float32 nor the [s, vocabulary]
    float32 array is ever whole (`phi4flash`'s finding, PR 60)."""
    r = _round_inputs(precision)
    head = params["lm_head"]
    v = head.shape[1]
    blocks = max(n for n in range(1, 17) if v % n == 0)
    cols = v // blocks
    with jax.default_matmul_precision("highest"):
        x = r(hidden(params, tokens, c, precision))
        return jnp.concatenate(
            [jnp.einsum("bsd,dv->bsv", x, r(head[:, i * cols:(i + 1) * cols]))
             for i in range(blocks)], axis=-1)


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
