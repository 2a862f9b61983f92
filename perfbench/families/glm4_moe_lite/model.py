"""Family ``glm4_moe_lite``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

The block, from the model's ``config.json`` (no bias anywhere, SiLU,
RMSNorm with ``rms_norm_eps`` before attention and before the feed-forward):

Attention, every layer (``h`` heads; ``nope``, ``rope``, ``v`` the three
head sizes)::

    c_q  = rmsnorm(x W_qa)                                   q_lora_rank
    q    = c_q W_qb            per head  [q_nope | q_rope]   nope | rope
    [c_kv | k_r] = x W_kva                                   kv_lora_rank | rope
    c_kv = rmsnorm(c_kv);  k_r = rotary(k_r)   one key for all heads
    q_rope = rotary(q_rope)
    [k_nope | v] = c_kv W_kvb  per head                      nope | v
    scores = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope), causal
    out  = concat_heads(softmax(scores) v) W_o

Rotary: angles ``pos / rope_theta^(2i/rope)``; the pair turned together is
(i, i + rope/2), the halves against each other, as `ray_tpu.ops.rotary`
does (the file's ``departures``: the published weights pair (2i, 2i+1);
with random weights that is a permutation of columns).

Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU of width
``intermediate_size``; the others::

    s = sigmoid(y W_r)                     float32, n_routed_experts wide
    chosen = the num_experts_per_tok largest of s + e_score_correction_bias
             (n_group 1, topk_group 1: no group limit)
    w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    out = sum_i w_i SwiGLU_i(y)  +  SwiGLU_shared(y)

with experts of width ``moe_intermediate_size`` and the shared one
``n_shared_experts`` times as wide.  The bias moves the choice, never the
weight.  Untied embedding and head.  The multi-token-prediction module is
not part of the main model's logits and is not held.

The reference is that in float32 at ``highest``, no cache, no kernel, no
absorption of the key-value up-projection, no sort: every expert is applied
to every token under its weight (zero where not chosen) by a scan over the
experts, its weights turned to float32 an expert at a time.  It has to fit
beside the live engine's 8.7 GB at 2 x 2304 positions, so nothing larger
than the logits asked for exists at once: attention is taken a sequence at
a time (its scores are [heads, s, s] float32), the head a block of the
vocabulary at a time, the embedding's rows gathered before they are turned
to float32.
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


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["n_group"], c["topk_group"], c["norm_topk_prob"],
            c["hidden_act"], c["attention_bias"]) != (1, 1, True, "silu",
                                                      False):
        raise ValueError("family glm4_moe_lite: the program routes without "
                         "group limits, normalises the chosen scores, gates "
                         "with SiLU and has no bias")
    if c["num_nextn_predict_layers"] or c["rope_scaling"] \
            or c["partial_rotary_factor"] != 1:
        raise ValueError("family glm4_moe_lite: the program holds no "
                         "multi-token-prediction module and turns the whole "
                         "rotary part without scaling")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=float(c["rope_theta"]), activation="swiglu",
        norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        attention="mla", q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        n_experts=c["n_routed_experts"],
        expert_top_k=c["num_experts_per_tok"], router="sigmoid",
        moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        first_dense_layers=c["first_k_dense_replace"],
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
    g = math.gcd(n_rows, 1280)
    return _normal(key, (n_rows // g, g, width), fan_in, dtype,
                   lead=1).reshape(n_rows, width)


def _run(key: jax.Array, c: Dict[str, Any], L: int, moe: bool, dtype):
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    names = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_in", "w_gate",
             "w_out", "router", "router_bias", "ws_in", "ws_gate", "ws_out")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, shape, fan_in, lead=1):
        return _normal(ks[name], (L,) + shape, fan_in, dtype, lead=lead)

    p = {"attn_norm": jnp.ones((L, d), dtype),
         "mlp_norm": jnp.ones((L, d), dtype),
         "q_norm": jnp.ones((L, ql), dtype),
         "kv_norm": jnp.ones((L, kl), dtype),
         "wq_a": stack("wq_a", (d, ql), d),
         "wq_b": stack("wq_b", (ql, h, nope + rope), ql),
         "wkv_a": stack("wkv_a", (d, kl + rope), d),
         "wkv_b": stack("wkv_b", (kl, h, nope + v), kl),
         "wo": stack("wo", (h, v, d), h * v)}
    if not moe:
        f = c["intermediate_size"]
        p.update(w_in=stack("w_in", (d, f), d),
                 w_gate=stack("w_gate", (d, f), d),
                 w_out=stack("w_out", (f, d), f))
        return p
    E, f = c["n_routed_experts"], c["moe_intermediate_size"]
    fs = c["n_shared_experts"] * f
    p.update(
        router=stack("router", (d, E), d),
        # drawn, not zero, so that it changes choices (the file's
        # ``assumed``): a trained model's bias is what balanced its experts
        router_bias=(jax.random.normal(ks["router_bias"], (L, E), jnp.float32)
                     * c["assumed"]["e_score_correction_bias_std"]
                     ).astype(dtype),
        w_in=stack("w_in", (E, d, f), d, lead=2),
        w_gate=stack("w_gate", (E, d, f), d, lead=2),
        w_out=stack("w_out", (E, f, d), f, lead=2),
        ws_in=stack("ws_in", (d, fs), d),
        ws_gate=stack("ws_gate", (d, fs), d),
        ws_out=stack("ws_out", (fs, d), fs))
    return p


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    the leading dense layers one stacked run, the expert layers another."""
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = c["first_k_dense_replace"]
    k_tok, k_head, k_dense, k_moe = jax.random.split(key, 4)
    return {
        "embed": {"tok": _rows(k_tok, v, d, 2500.0, dtype)},   # std 0.02
        "dense_layers": _run(k_dense, c, n_dense, False, dtype),
        "layers": _run(k_moe, c, c["num_hidden_layers"] - n_dense, True,
                       dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": _rows(k_head, d, v, d, dtype),
    }


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    return jax.random.randint(key, shape, 0, c["vocab_size"], jnp.int32)


# ------------------------------------------------------ the plain reference

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rotate(x, theta):
    """x [b, heads, s, rope]: the pair (x[i], x[i + rope/2]) turned by the
    angle pos * theta^(-2i/rope)."""
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


def expert_weights(y, lp, c):
    """y [b, s, d] normed -> [b, s, E] float32: each expert's weight for
    each token, zero where the token did not choose it."""
    s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", y.astype(F32),
                                  lp["router"].astype(F32)))
    _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(F32),
                              c["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * c["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, s.shape[-1], dtype=F32)   # [b,s,k,E]
    return jnp.einsum("bsk,bske->bse", w, onehot)


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32."""
    r = _round_inputs(precision)
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    nope, kl = c["qk_nope_head_dim"], c["kv_lora_rank"]
    rope = c["qk_rope_head_dim"]
    s = tokens.shape[1]
    x = params["embed"]["tok"][tokens].astype(F32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def attention(x, lp):
        y = _rms(x, lp["attn_norm"], eps)
        c_q = _rms(jnp.einsum("bsd,dr->bsr", r(y), r(lp["wq_a"])),
                   lp["q_norm"], eps)
        q = jnp.einsum("bsr,rhk->bhsk", r(c_q), r(lp["wq_b"]))
        ckv = jnp.einsum("bsd,dr->bsr", r(y), r(lp["wkv_a"]))
        c_kv = _rms(ckv[..., :kl], lp["kv_norm"], eps)
        k_r = _rotate(ckv[:, None, :, kl:], theta)          # [b, 1, s, rope]
        kv = jnp.einsum("bsr,rhk->bhsk", r(c_kv), r(lp["wkv_b"]))
        q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], theta)],
                            axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, kv.shape[:3] + (rope,))],
            axis=-1)

        def one_sequence(qkv):
            q, k, v = qkv
            scores = jnp.einsum("hsk,htk->hst", r(q), r(k)) \
                / math.sqrt(nope + rope)
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                                   axis=-1)
            return jnp.einsum("hst,htv->hsv", r(probs), r(v))

        a = jax.lax.map(one_sequence, (q, k, kv[..., nope:]))
        return x + jnp.einsum("bhsv,hvd->bsd", r(a), r(lp["wo"]))

    def dense_layer(x, lp):
        x = attention(x, lp)
        y = _rms(x, lp["mlp_norm"], eps)
        return x + _swiglu(r, y, lp["w_in"], lp["w_gate"], lp["w_out"]), None

    def expert_layer(x, lp):
        x = attention(x, lp)
        y = _rms(x, lp["mlp_norm"], eps)
        weight = expert_weights(y, lp, c)                      # [b, s, E]

        def one_expert(acc, e):
            w_in, w_gate, w_out, w_e = e
            return acc + w_e[..., None] * _swiglu(r, y, w_in, w_gate,
                                                  w_out), None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(y),
            (lp["w_in"], lp["w_gate"], lp["w_out"],
             jnp.moveaxis(weight, -1, 0)))
        shared = _swiglu(r, y, lp["ws_in"], lp["ws_gate"], lp["ws_out"])
        return x + routed + shared, None

    x, _ = jax.lax.scan(jax.checkpoint(dense_layer), x,
                        params["dense_layers"])
    x, _ = jax.lax.scan(jax.checkpoint(expert_layer), x, params["layers"])
    return _rms(x, params["final_norm"], eps)


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, vocabulary] float32, filled a block of the vocabulary at a
    time: the head in float32 whole would be 1.27 GB beside the logits."""
    r = _round_inputs(precision)
    head = params["lm_head"]
    d, v = head.shape
    block = math.gcd(v, 5120)
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
