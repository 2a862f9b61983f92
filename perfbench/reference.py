"""The plain reference: GPT-2's forward pass, loss and gradients in
straightforward jax.numpy, float32 at ``highest`` matmul precision, with no
kernel, no cache and no batching tricks, and the comparisons that decide
``correct``.

It follows the published model (pre-LayerNorm blocks, learned positions,
``gelu_new``, tied output embedding, LayerNorm epsilon from the file) except
where the configuration file lists a departure: the program has no bias on
its projections, so neither has this.  Weights come from ``weights.make``,
that is from the seed; nothing the program computed enters here.

``precision="fp8"`` is the control: the same function with every matmul's
two inputs rounded to float8_e4m3fn first, the nearest precision below the
bfloat16 the configurations state.  A sound run has to pass the limits and
this has to fail them (tests/benchmark, and tools/outputs_check.py on the
chip).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _round_inputs(precision: str):
    if precision == "float32":
        return lambda x: x.astype(F32)
    if precision == "fp8":
        def r(x):
            x = x.astype(F32)
            # straight-through: forward sees the rounded value, the
            # gradient passes as if it had not been rounded
            return x + jax.lax.stop_gradient(
                x.astype(jnp.float8_e4m3fn).astype(F32) - x)
        return r
    raise ValueError(f"unknown reference precision {precision!r}")


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


# ------------------------------------------------------------ comparisons

def tree_rel_error(got, want) -> jnp.ndarray:
    """|got - want| / |want| over all leaves together, in float32."""
    num = sum(jnp.sum(jnp.square(g.astype(F32) - w.astype(F32)))
              for g, w in zip(jax.tree_util.tree_leaves(got),
                              jax.tree_util.tree_leaves(want)))
    den = sum(jnp.sum(jnp.square(w.astype(F32)))
              for w in jax.tree_util.tree_leaves(want))
    return jnp.sqrt(num / den)


def logit_numbers(got: jnp.ndarray, want: jnp.ndarray,
                  tokens: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """``got`` and ``want`` are logits [n, vocab] at the same positions,
    ``tokens`` [n] the token emitted at each.  Returns

    logit_err   root-mean-square difference over the spread of the
                reference's logits;
    token_gap   how far, on average, the emitted token's reference logit
                lies under that position's largest, over the same spread
                (0 where the emitted token is the reference's own choice).
    """
    want = want.astype(F32)
    scale = want.std()
    err = jnp.sqrt(jnp.mean(jnp.square(got.astype(F32) - want))) / scale
    picked = jnp.take_along_axis(want, tokens[:, None], axis=-1)[:, 0]
    gap = (want.max(axis=-1) - picked).mean() / scale
    return {"logit_err": err, "token_gap": gap}
