"""Family ``gpt2``, the part that needs JAX and so runs in the chip holder
alone: the program's model configuration, weights from a key, and the plain
reference.  The sizes are GPT-2's own keys in the configuration file
(``n_embd``, ``n_layer``, ``n_head``, ``n_inner``, ``n_positions``,
``vocab_size``).  The interface is `manifest.FAMILY_INTERFACE`.

Weights are made on the device, in one jitted call, in the type they are
used in and in the layout the program takes (stacked layers: ``layers.wq``
[L, d, h, hd] ...).

The plain reference is GPT-2's forward pass, loss and gradients in
straightforward jax.numpy, float32 at ``highest`` matmul precision, with no
kernel, no cache and no batching tricks.  It follows the published model
(pre-LayerNorm blocks, learned positions, ``gelu_new``, tied output
embedding, LayerNorm epsilon from the file) except where the configuration
file lists a departure: the program has no bias on its projections, so
neither has this.  Weights come from `make`, that is from the seed; nothing
the program computed enters here.  ``precision="fp8"`` is the control
(`reference._round_inputs`).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.reference import F32, _round_inputs

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


# ------------------------------------------------ the program's configuration

def model_config(c: Dict[str, Any], use: str, **overrides):
    """The program's `TransformerConfig` at the sizes of configuration file
    ``c`` in the precision it states for ``use`` ("train" | "serve")."""
    from ray_tpu.models import TransformerConfig
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["n_embd"],
        n_layers=c["n_layer"], n_heads=c["n_head"], d_ff=c["n_inner"],
        max_seq_len=c["n_positions"], pos_emb="learned", activation="gelu",
        norm="layernorm", tie_embeddings=c["tie_word_embeddings"],
        dtype=_DTYPES[p["compute"]], param_dtype=_DTYPES[p["params"]],
        **overrides)


def param_dtype(c: Dict[str, Any], use: str):
    return _DTYPES[c["precision"][use]["params"]]


# ------------------------------------------------------- weights and tokens

def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, L, h = c["n_embd"], c["n_layer"], c["n_head"]
    hd, ff, v = d // h, c["n_inner"], c["vocab_size"]
    ks = iter(jax.random.split(key, 8))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    ones = lambda *s: jnp.ones(s, dtype)      # noqa: E731
    zeros = lambda *s: jnp.zeros(s, dtype)    # noqa: E731
    return {
        "embed": {"tok": normal((v, d), 0.02),
                  "pos": normal((c["n_positions"], d), 0.01)},
        "layers": {
            "attn_norm": ones(L, d), "attn_norm_b": zeros(L, d),
            "wq": normal((L, d, h, hd), 1 / math.sqrt(d)),
            "wk": normal((L, d, h, hd), 1 / math.sqrt(d)),
            "wv": normal((L, d, h, hd), 1 / math.sqrt(d)),
            "wo": normal((L, h, hd, d), 1 / math.sqrt(d)),
            "mlp_norm": ones(L, d), "mlp_norm_b": zeros(L, d),
            "w_in": normal((L, d, ff), 1 / math.sqrt(d)),
            "w_out": normal((L, ff, d), 1 / math.sqrt(ff)),
        },
        "final_norm": ones(d), "final_norm_b": zeros(d),
    }


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    """Token ids below the PUBLISHED vocabulary (the padding rows are never
    asked for)."""
    return jax.random.randint(key, shape, 0,
                              c["published"]["vocab_size"], jnp.int32)


# ------------------------------------------------------ the plain reference

def _ln(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale.astype(F32) \
        + bias.astype(F32)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden(params: Dict[str, Any], tokens: jnp.ndarray, c: Dict[str, Any],
           precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32."""
    r = _round_inputs(precision)
    eps = c["layer_norm_epsilon"]
    hd = c["n_embd"] // c["n_head"]
    s = tokens.shape[1]
    x = params["embed"]["tok"].astype(F32)[tokens] \
        + params["embed"]["pos"].astype(F32)[:s]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        y = _ln(x, lp["attn_norm"], lp["attn_norm_b"], eps)
        q = jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wq"]))
        k = jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wk"]))
        v = jnp.einsum("bsd,dhk->bhsk", r(y), r(lp["wv"]))
        scores = jnp.einsum("bhsk,bhtk->bhst", r(q), r(k)) / jnp.sqrt(
            jnp.float32(hd))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        a = jnp.einsum("bhst,bhtk->bshk", r(probs), r(v))
        x = x + jnp.einsum("bshk,hkd->bsd", r(a), r(lp["wo"]))
        y = _ln(x, lp["mlp_norm"], lp["mlp_norm_b"], eps)
        z = _gelu_new(jnp.einsum("bsd,df->bsf", r(y), r(lp["w_in"])))
        return x + jnp.einsum("bsf,fd->bsd", r(z), r(lp["w_out"])), None

    # one layer's weights and activations at a time: scan with checkpoint
    # changes what is kept, not what is computed
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    return _ln(x, params["final_norm"], params["final_norm_b"], eps)


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    r = _round_inputs(precision)
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, c, precision)
        return jnp.einsum("bsd,vd->bsv", r(x), r(params["embed"]["tok"]))


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
