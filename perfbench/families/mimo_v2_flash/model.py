"""Family ``mimo_v2_flash``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

The block, from the model's ``config.json`` (no bias anywhere, SiLU,
RMSNorm with ``layernorm_epsilon`` and a plain scale, no query or key
norm); what the keys do not state is the file's ``assumed``::

    x = E[tokens]
    x = x + attn_l(rmsnorm(x))
    x = x + ffn_l(rmsnorm(x))
    logits = rmsnorm(x) W_head                                  untied

``attn_l(y)`` goes by the layer's KIND (``hybrid_layer_pattern``: 1 a window
layer, 0 a full one); ``h`` query heads, ``hk`` key-value heads::

    q = y W_q  [h x head_dim];  k = y W_k  [hk x head_dim]
    v = attention_value_scale * (y W_v)  [hk x v_head_dim]
    hk = swa_num_key_value_heads (window) | num_key_value_heads (full)
    the first int(partial_rotary_factor x head_dim) dims of each query and
        key head are rotated by pos * theta^(-2i/that many), theta =
        swa_rope_theta (window) | rope_theta (full); the rest carry no
        position
    z_ij = q_i . k_j / sqrt(head_dim) over j <= i, in a window layer only
        i - j < sliding_window; query head n meets key-value head
        n // (h / hk)
    full layer:    p = softmax_j(z)
    window layer:  p_ij = exp(z_ij) / (exp(b_n) + sum_j' exp(z_ij')): a
        learned SINK b_n a query head joins the denominator and takes no
        value (add_swa_attention_sink_bias)
    out = concat_n(sum_j p_ij v_j) W_o        [h x v_head_dim -> hidden]

``ffn_l``: a SwiGLU of ``intermediate_size`` where ``moe_layer_freq`` is 0;
where it is 1::

    s = sigmoid(y W_r)                 float32, experts_routed wide
    chosen = the num_experts_per_tok largest of s + b   (b: expert bias)
    w = s[chosen] / sum s[chosen]      (norm_topk_prob; scaling factor null)
    out = sum_i w_i SwiGLU_i(y)        over the chosen experts HELD here;
                                       no shared expert

The expert bias ``b`` is TRAINED in the published model, by the update
that balances the experts without an auxiliary loss.  Here `make` draws it
from the seed and gives it that training's result on the seed's own
weights (`_balance`), and PLACES the experts on the chips by load
(`_place`), as expert-parallel serving does and as family ``afmoe`` does it
(PERF.md, PR 32: left as drawn, random routers send most pairs to a few
experts and the seed decides how much work a run does).  The sinks are
TRAINED too; `make` draws them at a size at which a sink takes a real
share of a row's mass (the file's ``assumed``).

A configuration may hold a SHARE of the experts (``n_routed_experts`` of
``deployment.experts_routed`` from ``deployment.expert_offset``): the router
scores all of them, the chosen experts that live elsewhere add nothing
here, in the program and in the reference alike, and that partial result
goes on to the next layer.

The reference is that in float32 at ``highest``: no cache, no ring, no
kernel, no sort, none of the program's code.  Every held expert is applied
to every token under its weight (zero where not chosen) by a scan over the
experts, ONE expert's weights cut out of the run's stack at a time;
attention a block of queries at a time under the mask written out from
positions (at 9.2 k positions the scores of all 64 heads at once would be
21 GB); the head a block of the vocabulary at a time.  ``precision="fp8"``
is the control (`reference._round_inputs`); the router's matmul stays
float32 in it, as the configuration states it for the program.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_KINDS = {1: "window", 0: "full"}       # ``hybrid_layer_pattern``'s values


def _routed(c: Dict[str, Any]) -> int:
    return c["deployment"]["experts_routed"]


def _dense_layers(c: Dict[str, Any]) -> int:
    """The leading layers whose feed-forward is dense."""
    freq = list(c["moe_layer_freq"])
    n = freq.index(1) if 1 in freq else len(freq)
    if any(f != 1 for f in freq[n:]):
        raise ValueError("family mimo_v2_flash: dense layers lead, expert "
                         "layers follow (moe_layer_freq)")
    return n


def _sink_kinds(c: Dict[str, Any]):
    return tuple(kind for kind, key in (
        ("window", "add_swa_attention_sink_bias"),
        ("full", "add_full_attention_sink_bias")) if c[key])


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["scoring_func"], c["norm_topk_prob"], c["hidden_act"],
            c["n_group"], c["topk_group"], c["n_shared_experts"],
            c["routed_scaling_factor"], c["attention_bias"]) != (
                "sigmoid", True, "silu", 1, 1, None, None, False):
        raise ValueError("family mimo_v2_flash: the program routes by "
                         "sigmoid scores without group limits, normalises "
                         "the chosen and scales them by 1, gates with SiLU, "
                         "has no shared expert and no bias")
    if (c["swa_num_attention_heads"], c["swa_head_dim"],
            c["swa_v_head_dim"]) != (
                c["num_attention_heads"], c["head_dim"], c["v_head_dim"]):
        raise ValueError("family mimo_v2_flash: window and full layers "
                         "differ in key-value heads alone")
    if not len(c["hybrid_layer_pattern"]) == len(c["moe_layer_freq"]) \
            == c["num_hidden_layers"]:
        raise ValueError("family mimo_v2_flash: hybrid_layer_pattern and "
                         "moe_layer_freq name every layer run")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        window_kv_heads=c["swa_num_key_value_heads"],
        head_size=c["head_dim"], v_head_dim=c["v_head_dim"],
        value_scale=c["attention_value_scale"],
        d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=float(c["rope_theta"]),
        window_rope_base=float(c["swa_rope_theta"]),
        rope_fraction=c["partial_rotary_factor"],
        sink_kinds=_sink_kinds(c),
        activation="swiglu", norm="rmsnorm", norm_eps=c["layernorm_epsilon"],
        tie_embeddings=c["tie_word_embeddings"],
        layer_kinds=tuple(_KINDS[k] for k in c["hybrid_layer_pattern"]),
        sliding_window=c["sliding_window"],
        window_chunk=c["deployment"]["window_chunk"],
        n_experts=_routed(c), experts_held=c["n_routed_experts"],
        expert_offset=c["deployment"]["expert_offset"],
        expert_top_k=c["num_experts_per_tok"], router="sigmoid",
        moe_d_ff=c["moe_intermediate_size"], n_shared_experts=0,
        routed_scaling_factor=1.0, first_dense_layers=_dense_layers(c),
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


def _run(key: jax.Array, c: Dict[str, Any], kinds, moe: bool, dtype):
    """One run of layers of the kinds ``kinds`` as the program's tree has
    it: what every layer has stacked over all of them, a full layer's key
    and value projections (``wk``, ``wv``) over the full layers only, a
    window layer's (``wk_win``, ``wv_win``) and the sinks over the window
    layers only."""
    d, qk, vd = c["hidden_size"], c["head_dim"], c["v_head_dim"]
    h, L = c["num_attention_heads"], len(kinds)
    names = ("wq", "wo", "wk", "wv", "wk_win", "wv_win", "sink", "w_in",
             "w_gate", "w_out", "router", "router_bias")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, n, shape, fan_in, lead=1):
        return _normal(ks[name], (n,) + shape, fan_in, dtype, lead=lead)

    p = {"attn_norm": jnp.ones((L, d), dtype),
         "mlp_norm": jnp.ones((L, d), dtype),
         "wq": stack("wq", L, (d, h, qk), d),
         "wo": stack("wo", L, (h, vd, d), h * vd)}
    for kind, (kn, vn), hk in (
            ("full", ("wk", "wv"), c["num_key_value_heads"]),
            ("window", ("wk_win", "wv_win"), c["swa_num_key_value_heads"])):
        n = sum(k == kind for k in kinds)
        if n:
            p[kn] = stack(kn, n, (d, hk, qk), d)
            p[vn] = stack(vn, n, (d, hk, vd), d)
    n_sink = sum(k in _sink_kinds(c) for k in kinds)
    if n_sink:
        a = c["assumed"]
        p["sink"] = (a["sink_mean"] + a["sink_std"] * jax.random.normal(
            ks["sink"], (n_sink, h), jnp.float32)).astype(dtype)
    if not moe:
        f = c["intermediate_size"]
        p.update(w_in=stack("w_in", L, (d, f), d),
                 w_gate=stack("w_gate", L, (d, f), d),
                 w_out=stack("w_out", L, (f, d), f))
        return p
    E, held, f = _routed(c), c["n_routed_experts"], c["moe_intermediate_size"]
    p.update(
        router=stack("router", L, (d, E), d),
        # drawn, not zero, so that it changes choices (the file's
        # ``assumed``): a trained model's bias is what balanced its experts
        router_bias=(jax.random.normal(ks["router_bias"], (L, E), jnp.float32)
                     * c["assumed"]["expert_bias_std"]).astype(dtype),
        w_in=stack("w_in", L, (held, d, f), d, lead=2),
        w_gate=stack("w_gate", L, (held, d, f), d, lead=2),
        w_out=stack("w_out", L, (held, f, d), f, lead=2))
    return p


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    the leading dense layers one stacked run, the expert layers another; in
    each, the two kinds' key and value projections stacked apart.  ONE
    compiled program a call (`_as_one_program`)."""
    return _as_one_program(_make, c=c, dtype=dtype)(key)


def _as_one_program(fn, **fixed):
    """``fn`` with its configuration bound, compiled as one program: a
    layer at a time in Python is hundreds of small programs when called
    eagerly (a CPU test waits four times as long), and inside a caller's
    own `jax.jit` this is no program of its own."""
    return jax.jit(functools.partial(fn, **fixed))


def _make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = _dense_layers(c)
    kinds = [_KINDS[k] for k in c["hybrid_layer_pattern"]]
    k_tok, k_head, k_dense, k_moe = jax.random.split(key, 4)
    params = {
        # rows of unit scale (fan_in 1: a row is looked up, not summed): a
        # token's own embedding is the size of what a layer adds to it
        "embed": {"tok": _rows(k_tok, v, d, 1.0, dtype)},
        "dense_layers": _run(k_dense, c, kinds[:n_dense], False, dtype),
        "layers": _run(k_moe, c, kinds[n_dense:], True, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": _rows(k_head, d, v, d, dtype),
    }
    n = c["assumed"]["expert_bias_balance_tokens"]
    if n:
        seen = tokens(jax.random.fold_in(key, 7), (1, n), c)
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


def _rotate(x, theta: float, n: int):
    """x [b, heads, s, hd]: of its first ``n`` dims the pair (x[i], x[i +
    n/2]) turned by the angle pos * theta^(-2i/n); the other dims as they
    are."""
    s = x.shape[-2]
    freq = theta ** (-jnp.arange(0, n, 2, dtype=F32) / n)
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    lo, hi, rest = x[..., :n // 2], x[..., n // 2:n], x[..., n:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang), rest],
                           axis=-1)


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
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    onehot = jax.nn.one_hot(chosen, s.shape[-1], dtype=F32)   # [b,s,k,E]
    return jnp.einsum("bsk,bske->bse", w, onehot)


def routed_part(r, y, lp, c, offset: int, held: int, layer=None):
    """What the experts ``offset .. offset + held - 1`` add for y [b, s,
    d]: a chip's share of the layer.  ``lp`` holds exactly those experts'
    weights ``[held, .., ..]``, or, with ``layer``, the stacks of a whole
    run of layers ``[L, held, .., ..]`` of which this is layer ``layer``:
    one expert's weights at a time are cut out of the stack (a layer's
    slice of it would be a copy of all its experts)."""
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


def attention(r, y, lp, c, kind: str):
    """y [b, s, d] normed -> what the attention block of a layer of kind
    ``kind`` (``"window"`` | ``"full"``) adds; ``lp`` holds THAT layer's
    weights under the plain names (``wk``, ``wv``, ``sink``)."""
    qk, vd = c["head_dim"], c["v_head_dim"]
    h, hk = c["num_attention_heads"], lp["wk"].shape[-2]
    window = kind == "window"
    theta = float(c["swa_rope_theta"] if window else c["rope_theta"])
    n_rot = int(c["partial_rotary_factor"] * qk)
    b, s, _ = y.shape
    q = _rotate(jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wq"])), theta, n_rot)
    k = _rotate(jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wk"])), theta, n_rot)
    v = c["attention_value_scale"] \
        * jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wv"]))
    sink = lp["sink"].astype(F32).reshape(hk, h // hk) \
        if kind in _sink_kinds(c) else None
    block = math.gcd(s, 256)
    j = jnp.arange(s)

    def one_block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, axis=2)
        qb = qb.reshape(b, hk, h // hk, block, qk)
        z = jnp.einsum("bkgqd,bktd->bkgqt", r(qb), r(k)) / math.sqrt(qk)
        i = i0 + jnp.arange(block)
        see = j[None, :] <= i[:, None]
        if window:
            see &= i[:, None] - j[None, :] < c["sliding_window"]
        z = jnp.where(see, z, -jnp.inf)
        m = z.max(-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[None, :, :, None, None])
        e = jnp.exp(z - m)
        den = e.sum(-1, keepdims=True)
        if sink is not None:    # joins the denominator, takes no value
            den = den + jnp.exp(sink[None, :, :, None, None] - m)
        return jnp.einsum("bkgqt,bktd->bkgqd", r(e / den), r(v))

    a = jax.lax.map(one_block, jnp.arange(0, s, block))   # [n,b,hk,g,q,vd]
    a = jnp.moveaxis(a, 0, 3).reshape(b, h, s, vd)
    return jnp.einsum("bhsk,hkd->bsd", r(a), r(lp["wo"]))


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32."""
    return _walk(params, tokens, c, precision)[0]


def _layer_weights(tree, kinds, at: int, routed: bool, sinks):
    """Layer ``at`` of a run of the kinds ``kinds`` out of the run's tree,
    under the plain names: what every layer has by the layer, a kind's own
    stacks (the sinks among them, of the kinds ``sinks``) by the count of
    that kind's layers before; the routed experts' stacks whole
    (`routed_part` cuts one expert out at a time)."""
    kind = kinds[at]
    before = sum(k == kind for k in kinds[:at])
    own = {"window": {"wk_win": "wk", "wv_win": "wv"},
           "full": {"wk": "wk", "wv": "wv"}}[kind]
    lp = {}
    for name, a in tree.items():
        if name in ("wk", "wv", "wk_win", "wv_win"):
            if name in own:
                lp[own[name]] = a[before]
        elif name == "sink":
            if kind in sinks:
                lp["sink"] = a[before]
        elif routed and name in ("w_in", "w_gate", "w_out"):
            lp[name] = a
        else:
            lp[name] = a[at]
    return lp


def _walk(params, tokens, c, precision: str, reroute=None):
    """The forward pass, a layer at a time -> (final hidden states, the
    expert layers' routers).  With ``reroute(scores [n, E], lp) -> {router,
    router_bias}`` each expert layer's router is first set from the scores
    of these very tokens and the layer then routes by it (`make`'s
    calibration)."""
    r = _round_inputs(precision)
    routers = []
    eps = c["layernorm_epsilon"]
    held, offset = c["n_routed_experts"], c["deployment"]["expert_offset"]
    if len(_sink_kinds(c)) > 1:
        raise ValueError("family mimo_v2_flash: the sinks are one kind's")
    x = params["embed"]["tok"][tokens].astype(F32)

    def block(x, lp, kind, ffn):
        x = x + attention(r, _rms(x, lp["attn_norm"], eps), lp, c, kind)
        return x + ffn(_rms(x, lp["mlp_norm"], eps), lp)

    def dense(y, lp):
        return _swiglu(r, y, lp["w_in"], lp["w_gate"], lp["w_out"])

    def experts(y, lp, layer):
        if reroute is not None:
            lp = dict(lp, **reroute(
                _scores(y, lp).reshape(-1, lp["router"].shape[-1]), lp))
        routers.append({k: lp[k] for k in ("router", "router_bias")})
        return routed_part(r, y, lp, c, offset, held, layer)

    n_dense = _dense_layers(c)
    kinds = [_KINDS[k] for k in c["hybrid_layer_pattern"]]
    for i, kind in enumerate(kinds):
        routed = i >= n_dense
        run, at, mine = ("layers", i - n_dense, kinds[n_dense:]) if routed \
            else ("dense_layers", i, kinds[:n_dense])
        lp = _layer_weights(params[run], mine, at, routed, _sink_kinds(c))
        ffn = functools.partial(experts, layer=at) if routed else dense
        layer = functools.partial(block, kind=kind, ffn=ffn)
        # (a calibration pass takes no gradient, and hands its routers out)
        x = (layer if reroute is not None else jax.checkpoint(layer))(x, lp)
    return _rms(x, params["final_norm"], eps), routers


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, vocabulary] float32, filled a block of the vocabulary at a
    time."""
    return _as_one_program(_logits, c=c, precision=precision)(params, tokens)


def _logits(params, tokens, c, precision: str) -> jnp.ndarray:
    r = _round_inputs(precision)
    head = params["lm_head"]
    d, v = head.shape
    block = math.gcd(v, 2384)
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
    return _as_one_program(_loss, c=c, precision=precision)(params, tokens)


def _loss(params, tokens, c, precision: str) -> jnp.ndarray:
    lg = _logits(params, tokens, c, precision)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean()


def loss_and_grad(params, tokens, c, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(
            _loss, c=c, precision=precision)))(params, tokens)
