"""Family ``evabyte``, the part that needs JAX: the program's model
configuration, weights from a key and the plain reference.

The block, from the model's ``config.json`` (no bias anywhere; what the
keys do not state is the file's ``assumed``); ``d`` = hidden_size, ``H``
heads of ``e`` = d / H, ``W`` = window_size, ``C`` = chunk_size::

    x = E[bytes]                                           float32 stream
    h = x + attn_l(rms(x));  x = h + mlp_l(rms(h))         adds in float32
    rms(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + g)  norm_add_unit_offset
    mlp(y) = (silu(y W_gate) * (y W_up)) W_down            intermediate_size
    logits = rms(x) W_head        [d, num_pred_heads x vocab_size], float32;
        columns ``vocab p .. vocab p + vocab - 1`` are prediction head p,
        which predicts the byte at t + 1 + p

``attn_l(y)`` (EVA; Zheng et al., ICLR 2023, as the model ships it)::

    q, k, v = y W_q, y W_k, y W_v          H heads of e (key-value heads:
        num_key_value_heads); q and k rotated over the whole head at the
        byte's absolute position, base rope_theta
    chunk j = positions jC .. jC + C - 1; for each key-value head with its
        learned phi, mu in R^e (adaptive_phi, adaptive_mu_k):
        a_i = softmax over the chunk's i of (k_i . phi)   (k_i ROTATED)
        kbar_j = sum_i a_i k_i + mu;   vbar_j = sum_i a_i v_i
    query t in window w = t // W sees
        L_t = { i : i // W = w, i <= t }         its own window's tokens
        R_t = { j : (j + 1) C <= w W }           the chunks of every earlier
                                                 window (all complete)
        and nothing else: no summary of its own window, no token of an
        earlier one
    s_i = q_t . k_i / sqrt(e);   r_j = q_t . kbar_j / sqrt(e)
    o_t = (sum_L exp(s_i) v_i + sum_R exp(r_j) vbar_j)
          / (sum_L exp(s_i) + sum_R exp(r_j))          ONE softmax, float32
    out = concat_heads(o_t) W_o

With ``W >= T`` no summary is ever visible and this is causal softmax
attention; with ``C = 1`` and ``mu = 0`` a summary is its token and it is
causal attention over the whole context
(tests/benchmark/test_perfbench_family_evabyte.py ties the reference to
both).

``phi``, ``mu`` and the norms' ``g`` are TRAINED in the published model;
`make` draws them from the seed at the scales the file's ``assumed`` gives,
with its reasons: a ``phi`` near zero would pool every chunk evenly and
"phi ignored" would pass any limit.

The reference is that in float32 at ``highest``: no cache, no ring, no
kernel, none of the program's code.  It walks a sequence WINDOW BY WINDOW (a
scan whose carry is the summaries so far) and inside a window a block of
queries at a time, so that 25,600 positions of the published widths fit
beside a live engine: nothing of ``[positions, positions]`` or ``[positions,
intermediate_size]`` ever exists.  ``precision="fp8"`` is the control
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


def _head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def model_config(c: Dict[str, Any], use: str, **overrides):
    from ray_tpu.models import TransformerConfig
    if (c["attention_class"], c["hidden_act"], c["attention_bias"],
            c["rope_scaling"], c["tie_word_embeddings"], c["mixedp_attn"],
            c["fp32_ln"]) != ("eva", "silu", False, None, False, True, False):
        raise ValueError("family evabyte: the program attends through chunk "
                         "summaries under a float32 softmax, gates with "
                         "SiLU, has no bias, no rotary scaling, an untied "
                         "head and norms in the stream's own type")
    p = c["precision"][use]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_size=_head_dim(c),
        d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"], pos_emb="rope",
        rope_base=float(c["rope_theta"]), activation="swiglu",
        norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        norm_unit_offset=c["norm_add_unit_offset"],
        fp32_residual=c["fp32_skip_add"], fp32_logits=c["fp32_logits"],
        pred_heads=c["num_pred_heads"], tie_embeddings=False,
        layer_kinds=("eva",) * c["num_hidden_layers"],
        sliding_window=c["window_size"], summary_chunk=c["chunk_size"],
        window_chunk=c["deployment"]["window_chunk"],
        dtype=_DTYPES[p["compute"]], param_dtype=_DTYPES[p["params"]],
        **overrides)


def param_dtype(c: Dict[str, Any], use: str):
    return _DTYPES[c["precision"][use]["params"]]


def _normal(key: jax.Array, shape, std: float, dtype):
    """``std * normal`` of ``shape`` in ``dtype``, a layer (the leading
    axis) at a time: no float32 copy of more than one layer's matrix."""
    def one(k):
        return (jax.random.normal(k, shape[1:], F32) * std).astype(dtype)
    return jax.lax.map(one, jax.random.split(key, shape[0]))


def make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree `ray_tpu.models.init_params` makes for this configuration:
    one run of identical layers.  Matrices at ``1 / sqrt(fan_in)`` but for
    the gains the file's ``assumed`` states (queries, the attention's
    output); the pooling's ``phi`` and ``mu`` and the norms' ``g`` at its
    scales."""
    return _as_one_program(_make, c=c, dtype=dtype)(key)


def _as_one_program(fn, **fixed):
    """``fn`` with its configuration bound, compiled as one program (inside
    a caller's own `jax.jit` no program of its own)."""
    return jax.jit(functools.partial(fn, **fixed))


def _make(key: jax.Array, c: Dict[str, Any], dtype) -> Dict[str, Any]:
    d, e, ff = c["hidden_size"], _head_dim(c), c["intermediate_size"]
    h, hk, L = (c["num_attention_heads"], c["num_key_value_heads"],
                c["num_hidden_layers"])
    a = c["assumed"]
    names = ("tok", "head", "attn_norm", "mlp_norm", "final_norm", "wq",
             "wk", "wv", "wo", "phi", "mu", "w_in", "w_gate", "w_out")
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def stack(name, shape, std):
        return _normal(ks[name], (L,) + shape, std, dtype)

    g = a["norm_gain_std"]
    layers = {
        "attn_norm": stack("attn_norm", (d,), g),
        "mlp_norm": stack("mlp_norm", (d,), g),
        "wq": stack("wq", (d, h, e), a["query_gain"] / math.sqrt(d)),
        "wk": stack("wk", (d, hk, e), 1.0 / math.sqrt(d)),
        "wv": stack("wv", (d, hk, e), 1.0 / math.sqrt(d)),
        "wo": stack("wo", (h, e, d),
                    a["attention_out_gain"] / math.sqrt(h * e)),
        "adaptive_phi": stack("phi", (hk, e), a["phi_std"]),
        "adaptive_mu_k": stack("mu", (hk, e), a["mu_std"]),
        "w_in": stack("w_in", (d, ff), 1.0 / math.sqrt(d)),
        "w_gate": stack("w_gate", (d, ff), 1.0 / math.sqrt(d)),
        "w_out": stack("w_out", (ff, d), 1.0 / math.sqrt(ff)),
    }
    return {
        # rows of unit scale (a row is looked up, not summed)
        "embed": {"tok": _normal(ks["tok"], (1, c["vocab_size"], d), 1.0,
                                 dtype)[0]},
        "layers": layers,
        "final_norm": _normal(ks["final_norm"], (1, d), g, dtype)[0],
        "lm_head": _normal(
            ks["head"], (1, d, c["num_pred_heads"] * c["vocab_size"]),
            1.0 / math.sqrt(d), dtype)[0],
    }


def tokens(key: jax.Array, shape, c: Dict[str, Any]) -> jax.Array:
    return jax.random.randint(key, shape, 0, c["vocab_size"], jnp.int32)


# ------------------------------------------------------ the plain reference

def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def _rotate(x, pos, theta: float):
    """x [b, s, heads, e] at the positions ``pos`` [s]: the pair (x[i], x[i
    + e/2]) turned by the angle pos * theta^(-2i/e)."""
    e = x.shape[-1]
    freq = theta ** (-jnp.arange(0, e, 2, dtype=F32) / e)
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


def pool(r, k, v, phi, mu, chunk: int):
    """Rotated keys ``k`` [b, s, hk, e] and values ``v`` [b, s, hk, e] of
    whole chunks → (kbar [b, s / chunk, hk, e], vbar alike)."""
    b, s, hk, e = k.shape
    kc = k.reshape(b, s // chunk, chunk, hk, e)
    vc = v.reshape(b, s // chunk, chunk, hk, e)
    a = jax.nn.softmax(jnp.einsum("bjihe,he->bjih", r(kc), r(phi)), axis=2)
    return (jnp.einsum("bjih,bjihe->bjhe", r(a), r(kc)) + mu.astype(F32),
            jnp.einsum("bjih,bjihe->bjhe", r(a), r(vc)))


def _block_of(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is no more than ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def attention_window(r, q, k, v, kbar, vbar, w, c):
    """One window's queries ``q`` [b, W, h, e] (rotated) over its own keys
    and values ``k``, ``v`` [b, W, hk, e] and over the summaries ``kbar``,
    ``vbar`` [b, chunks of the whole sequence, hk, e], of which those of the
    windows before window ``w`` are visible → [b, W, h, e].  A block of
    queries at a time."""
    b, W, h, e = q.shape
    hk = k.shape[2]
    block = _block_of(W, 256)
    local = jnp.arange(W)
    visible = (jnp.arange(kbar.shape[1]) + 1) * c["chunk_size"] <= w * W

    def one_block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, axis=1)
        qb = qb.reshape(b, block, hk, h // hk, e)
        s = jnp.einsum("bqkge,btke->bkgqt", r(qb), r(k)) / math.sqrt(e)
        s = jnp.where(local[None, :] <= (i0 + jnp.arange(block))[:, None],
                      s, -jnp.inf)
        z = jnp.einsum("bqkge,bjke->bkgqj", r(qb), r(kbar)) / math.sqrt(e)
        z = jnp.where(visible, z, -jnp.inf)
        m = jnp.maximum(s.max(-1, keepdims=True), z.max(-1, keepdims=True))
        es, ez = jnp.exp(s - m), jnp.exp(z - m)
        den = es.sum(-1, keepdims=True) + ez.sum(-1, keepdims=True)
        o = jnp.einsum("bkgqt,btke->bqkge", r(es / den), r(v)) \
            + jnp.einsum("bkgqj,bjke->bqkge", r(ez / den), r(vbar))
        return o.reshape(b, block, h, e)

    o = jax.lax.map(one_block, jnp.arange(0, W, block))    # [n, b, block, ..]
    return jnp.moveaxis(o, 0, 1).reshape(b, W, h, e)


def _layer(r, x, lp, c):
    """x [b, n_windows, W, d] float32 -> the block's output, alike: a scan
    over the windows that carries the summaries of the windows so far."""
    b, n_w, W, d = x.shape
    eps, theta, C = c["rms_norm_eps"], float(c["rope_theta"]), c["chunk_size"]
    hk, e = c["num_key_value_heads"], _head_dim(c)
    per = W // C

    def swiglu(y):
        up = jnp.einsum("bsd,df->bsf", r(y), r(lp["w_in"]))
        gate = jnp.einsum("bsd,df->bsf", r(y), r(lp["w_gate"]))
        return jnp.einsum("bsf,fd->bsd",
                          r(gate * jax.nn.sigmoid(gate) * up),
                          r(lp["w_out"]))

    def window(carry, inp):
        kbar, vbar = carry
        xw, w = inp
        y = _rms(xw, lp["attn_norm"], eps)
        pos = w * W + jnp.arange(W)
        q = _rotate(jnp.einsum("bsd,dhe->bshe", r(y), r(lp["wq"])), pos,
                    theta)
        k = _rotate(jnp.einsum("bsd,dhe->bshe", r(y), r(lp["wk"])), pos,
                    theta)
        v = jnp.einsum("bsd,dhe->bshe", r(y), r(lp["wv"]))
        o = attention_window(r, q, k, v, kbar, vbar, w, c)
        hid = xw + jnp.einsum("bshe,hed->bsd", r(o), r(lp["wo"]))
        out = hid + swiglu(_rms(hid, lp["mlp_norm"], eps))
        # this window's chunks join the summaries behind it
        kb, vb = pool(r, k, v, lp["adaptive_phi"], lp["adaptive_mu_k"], C)
        kbar = jax.lax.dynamic_update_slice(kbar, kb, (0, w * per, 0, 0))
        vbar = jax.lax.dynamic_update_slice(vbar, vb, (0, w * per, 0, 0))
        return (kbar, vbar), out

    zeros = jnp.zeros((b, n_w * per, hk, e), F32)
    _, out = jax.lax.scan(window, (zeros, zeros),
                          (jnp.moveaxis(x, 1, 0), jnp.arange(n_w)))
    return jnp.moveaxis(out, 0, 1)


def hidden(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """tokens [b, s] -> final hidden states [b, s, d], float32.  The
    sequence is walked in whole windows: padded behind to one (nothing
    before a position depends on what follows it)."""
    r = _round_inputs(precision)
    b, s = tokens.shape
    W = c["window_size"]
    n_w = -(-s // W)
    x = params["embed"]["tok"][jnp.pad(tokens, ((0, 0), (0, n_w * W - s)))]
    x = x.astype(F32).reshape(b, n_w, W, -1)
    x, _ = jax.lax.scan(
        lambda x, lp: (jax.checkpoint(functools.partial(_layer, r, c=c))(
            x, lp), None), x, params["layers"])
    x = x.reshape(b, n_w * W, -1)[:, :s]
    return _rms(x, params["final_norm"], c["rms_norm_eps"])


def logits(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """[b, s, num_pred_heads x vocab_size] float32: every head's columns,
    head 0's first."""
    return _as_one_program(_logits, c=c, precision=precision)(params, tokens)


def _logits(params, tokens, c, precision: str) -> jnp.ndarray:
    r = _round_inputs(precision)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bsd,dv->bsv",
                          r(hidden(params, tokens, c, precision)),
                          r(params["lm_head"]))


def loss(params, tokens, c, precision: str = "float32") -> jnp.ndarray:
    """The heads' mean of each head's mean cross entropy: head p against
    the byte 1 + p positions on, over the positions that have one."""
    return _as_one_program(_loss, c=c, precision=precision)(params, tokens)


def _loss(params, tokens, c, precision: str) -> jnp.ndarray:
    lg = _logits(params, tokens, c, precision)
    s, v = tokens.shape[1], c["vocab_size"]
    total = 0.0
    for p in range(c["num_pred_heads"]):
        logp = jax.nn.log_softmax(lg[:, :s - 1 - p, p * v:(p + 1) * v], -1)
        total -= jnp.take_along_axis(logp, tokens[:, 1 + p:, None],
                                     axis=-1).mean()
    return total / c["num_pred_heads"]


def loss_and_grad(params, tokens, c, precision: str = "float32"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(
            _loss, c=c, precision=precision)))(params, tokens)
