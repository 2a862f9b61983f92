"""Family ``rope_gqa``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference of a block with
RMSNorm before attention and feed-forward, rotary positions (halves
rotated against each other, angles ``pos / theta^(2i/head)``), grouped-query
attention (``num_attention_heads`` query heads share
``num_key_value_heads`` keys and values, consecutive query heads one
group), SwiGLU (``down(silu(gate(x)) * up(x))``), no bias anywhere and an
output head of its own.  Float32 at ``highest``, no kernel, no cache;
``precision="fp8"`` is the control (`reference._round_inputs`).  For the
CPU rehearsal alone (see shapes.py).
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
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=c["rope_theta"], activation="swiglu", norm="rmsnorm",
        tie_embeddings=c["tie_word_embeddings"],
        dtype=_DTYPES[p["compute"]], param_dtype=_DTYPES[p["params"]],
        **overrides)


def param_dtype(c: Dict[str, Any], use: str):
    return _DTYPES[c["precision"][use]["params"]]


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, L = c["hidden_size"], c["num_hidden_layers"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff, v = d // h, c["intermediate_size"], c["vocab_size"]
    names = ("tok", "wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out",
             "lm_head")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def normal(name, shape, fan_in):
        return (jax.random.normal(ks[name], shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    return {
        "embed": {"tok": normal("tok", (v, d), 2500.0)},     # std 0.02
        "layers": {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": normal("wq", (L, d, h, hd), d),
            "wk": normal("wk", (L, d, hk, hd), d),
            "wv": normal("wv", (L, d, hk, hd), d),
            "wo": normal("wo", (L, h, hd, d), d),
            "mlp_norm": jnp.ones((L, d), dtype),
            "w_in": normal("w_in", (L, d, ff), d),
            "w_gate": normal("w_gate", (L, d, ff), d),
            "w_out": normal("w_out", (L, ff, d), ff),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": normal("lm_head", (d, v), d),
    }


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


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32."""
    r = _round_inputs(precision)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h, hk = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // h
    b, s = tokens.shape
    x = params["embed"]["tok"].astype(F32)[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        y = _rms(x, lp["attn_norm"], eps)
        q = _rotate(jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wq"])), theta)
        k = _rotate(jnp.einsum("bsd,dgk->bgsk", r(y), r(lp["wk"])), theta)
        v = jnp.einsum("bsd,dgk->bgsk", r(y), r(lp["wv"]))
        # query head j reads key-value head j // (h / hk)
        q = q.reshape(b, hk, h // hk, s, hd)
        scores = jnp.einsum("bgrsk,bgtk->bgrst", r(q), r(k)) / math.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        a = jnp.einsum("bgrst,bgtk->bgrsk", r(probs), r(v))
        a = a.reshape(b, h, s, hd)
        x = x + jnp.einsum("bhsk,hkd->bsd", r(a), r(lp["wo"]))
        y = _rms(x, lp["mlp_norm"], eps)
        up = jnp.einsum("bsd,df->bsf", r(y), r(lp["w_in"]))
        gate = jnp.einsum("bsd,df->bsf", r(y), r(lp["w_gate"]))
        z = gate * jax.nn.sigmoid(gate) * up
        return x + jnp.einsum("bsf,fd->bsd", r(z), r(lp["w_out"])), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    return _rms(x, params["final_norm"], eps)


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    r = _round_inputs(precision)
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, c, precision)
        return jnp.einsum("bsd,dv->bsv", r(x), r(params["lm_head"]))


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
