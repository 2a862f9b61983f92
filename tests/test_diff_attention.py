"""Differential attention as a PROPERTY of an MHA/GQA block (``diff_attn``,
`models/transformer.py`): pairs of query heads over pairs of key heads whose
two keys lie side by side in ONE cached row, the pair's two softmax maps
subtracted under a norm.  The plain form against a NumPy statement of the
equations; the cached programs (chunks over a ring that wraps, slots) and
the block kernels through the interpreter against the plain form; the layer's
index in ``lambda_init``; biases; and what the new fields cost a model that
does not use them: nothing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (decode_step_slots, forward, init_kv_cache,
                            init_params, init_slot_cache, prefill_chunk)
from ray_tpu.models.generate import (cache_insert_slot, cache_rows,
                                     prefill_chunked)
from ray_tpu.models.transformer import (TransformerConfig, _attn_out, _qkv,
                                        attention_scale, check_kinds,
                                        count_params, lambda_init)
from ray_tpu.ops.attention import multi_head_attention


def _cfg(**kw):
    base = dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4,
                n_kv_heads=1, d_ff=96, max_seq_len=64, pos_emb="none",
                activation="swiglu", norm="layernorm", tie_embeddings=True,
                remat=False, dtype=jnp.float32, param_dtype=jnp.float32,
                layer_kinds=("window", "full", "window"), sliding_window=6,
                window_chunk=4, diff_attn=True, attn_bias=True)
    base.update(kw)
    return TransformerConfig(**base)


def _params(cfg, seed=0):
    p, _ = init_params(jax.random.PRNGKey(seed), cfg)
    # biases are drawn zero: make them count
    ks = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    p["layers"] = {k: v + 0.1 * jax.random.normal(next(ks), v.shape)
                   if k in ("bq", "bk", "bv", "bo") else v
                   for k, v in p["layers"].items()}
    return p


def numpy_block(y, lp, depth, window, eps=1e-5):
    """One sequence ``y`` [s, d] through a differential block, float64: pair
    p = heads (2p, 2p + 1) reads key row p // (pairs / rows); head 2p + i
    meets the row's half i; both maps take the row's whole value."""
    y = np.asarray(y, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    s = y.shape[0]
    q = np.einsum("sd,dhk->hsk", y, w["wq"]) + w["bq"][:, None]
    k = np.einsum("sd,dgk->gsk", y, w["wk"]) + w["bk"][:, None]
    v = np.einsum("sd,dgk->gsk", y, w["wv"]) + w["bv"][:, None]
    h, hd = q.shape[0], q.shape[-1]
    t = np.arange(s)
    seen = t[:, None] >= t[None, :]
    if window:
        seen &= t[:, None] - t[None, :] < window
    fixed = 0.8 - 0.6 * np.exp(-0.3 * depth)
    lam = np.exp(w["diff_lq1"] @ w["diff_lk1"]) \
        - np.exp(w["diff_lq2"] @ w["diff_lk2"]) + fixed
    out = np.zeros((s, w["wo"].shape[-1]))
    for p in range(h // 2):
        g = p // ((h // 2) // k.shape[0])
        maps = []
        for i in range(2):
            sc = q[2 * p + i] @ k[g][:, i * hd:(i + 1) * hd].T / np.sqrt(hd)
            sc = np.where(seen, sc, -np.inf)
            e = np.exp(sc - sc.max(-1, keepdims=True))
            maps.append(e / e.sum(-1, keepdims=True) @ v[g])
        o = maps[0] - lam * maps[1]
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + eps) \
            * w["diff_norm"] * (1.0 - fixed)
        out += o @ w["wo"][p]
    return out + w["bo"]


@pytest.mark.parametrize("kind,depth", [("full", 1), ("window", 5)])
def test_the_plain_block_is_the_equations(kind, depth):
    cfg = _cfg(n_kv_heads=2, n_heads=8, d_model=128)
    p = _params(cfg)["layers"]
    at = {"window": 0, "full": 1}[kind]
    lp = {k: v[at if k in ("wk", "wv", "bk", "bv") and kind == "full"
               else at] for k, v in p.items()
          if not k.startswith(("w_", "attn_norm", "mlp_norm"))}
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 14, cfg.d_model))
    q, k, v = _qkv(cfg, y, lp, None, kind)
    assert q.shape == (1, 14, 8, 32) and k.shape == v.shape == (1, 14, 2, 32)
    attn = multi_head_attention(
        q, k, v, impl="reference", sm_scale=attention_scale(cfg),
        window=cfg.sliding_window if kind == "window" else None)
    got = _attn_out(cfg, y, attn, lp, jnp.int32(depth))
    want = numpy_block(y[0], lp, depth,
                       cfg.sliding_window if kind == "window" else 0)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_lambda_init_goes_by_the_layers_index_among_all():
    assert float(lambda_init(0)) == pytest.approx(0.2)
    assert float(lambda_init(17)) == pytest.approx(
        0.8 - 0.6 * np.exp(-5.1), rel=1e-6)
    # the same weights at another depth are another model
    cfg = _cfg()
    p = _params(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, 128)
    a = forward(p, toks, cfg)
    rolled = dataclasses.replace(
        cfg, layer_kinds=("window", "window", "full"))
    assert float(jnp.abs(forward(p, toks, rolled) - a).max()) > 1e-3


def test_a_cached_row_holds_a_pairs_two_keys_and_its_value():
    cfg = _cfg()
    rows = cache_rows(cfg)
    assert rows == {"k": (1, 32), "v": (1, 32), "k_win": (1, 32),
                    "v_win": (1, 32)}
    assert count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(_params(cfg)))


def test_chunks_over_a_ring_that_wraps_and_slots_are_the_plain_form():
    cfg = _cfg()
    p = _params(cfg, 4)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, 128)
    want = forward(p, toks, cfg)
    cache = init_kv_cache(cfg, 1, 48)
    assert cache["k_win"].shape[-1] == 10       # window 6 + chunk 4: wraps
    lg, cache = prefill_chunked(p, toks[:, :27], cfg, cache, chunk=4)
    np.testing.assert_allclose(lg[0], want[0, 26], atol=2e-5)
    slots = cache_insert_slot(init_slot_cache(cfg, 2, 48), cache,
                              jnp.int32(1))
    active = jnp.asarray([False, True])
    for t in range(27, 39):
        lg, slots = decode_step_slots(
            p, jnp.asarray([0, int(toks[0, t])]), slots, active, cfg)
        np.testing.assert_allclose(lg[1], want[0, t], atol=3e-5)


def test_the_block_kernels_through_the_interpreter_are_the_dense_forms(
        monkeypatch):
    """Whole 128-row blocks and key rows of 128: a chunk of 128 queries
    through `attend_chunk_blocks`, and, of a model whose full layer's rows a
    cross layer reads too, one query a slot through `attend_blocks`; both
    under the score scale of ONE head."""
    cfg = _cfg(d_model=128, n_heads=4, n_kv_heads=1, n_layers=2,
               layer_kinds=("full", "cross"), window_chunk=128, d_ff=64,
               head_size=64, max_seq_len=256)
    assert cfg.key_dim == 128 and attention_scale(cfg) == 64 ** -0.5
    p = _params(cfg, 5)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 140), 0, 128)
    want = forward(p, toks, cfg)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cache = init_kv_cache(cfg, 1, 256)
    lg, cache = prefill_chunk(p, toks[:, :128], cache, cfg)
    np.testing.assert_allclose(lg[0], want[0, 127], atol=1e-4)
    slots = cache_insert_slot(init_slot_cache(cfg, 2, 256), cache,
                              jnp.int32(0))
    active = jnp.asarray([True, False])
    for t in range(128, 132):
        lg, slots = decode_step_slots(
            p, jnp.asarray([int(toks[0, t]), 0]), slots, active, cfg)
        np.testing.assert_allclose(lg[0], want[0, t], atol=1e-4)


def test_the_new_fields_cost_a_model_without_them_nothing():
    """A plain GQA model's forward and chunk program are the same jaxpr
    whatever the memory fields say while no layer is of their kinds and
    ``diff_attn`` and ``attn_bias`` are off (their defaults)."""
    plain = TransformerConfig.tiny(dtype=jnp.float32)
    assert not plain.diff_attn and not plain.attn_bias \
        and not plain.hands_down and plain.stateless_tail == 0 \
        and plain.key_dim == plain.head_dim
    stated = dataclasses.replace(plain, mamba_state=16, mamba_dt_rank=4,
                                 mamba_expand=3, mamba_conv_kernel=2)
    p, _ = init_params(jax.random.PRNGKey(0), plain)
    p2, _ = init_params(jax.random.PRNGKey(0), stated)
    assert jax.tree_util.tree_structure(p) == \
        jax.tree_util.tree_structure(p2)
    toks = jnp.zeros((1, 8), jnp.int32)
    cache = init_kv_cache(plain, 1, 32)
    for cfg in (plain, stated):
        assert str(jax.make_jaxpr(lambda p, t: forward(p, t, plain))(
            p, toks)) == str(jax.make_jaxpr(
                lambda p, t: forward(p, t, cfg))(p, toks))
        assert str(jax.make_jaxpr(
            lambda p, t, c: prefill_chunk(p, t, c, plain))(
                p, toks, cache)) == str(jax.make_jaxpr(
                    lambda p, t, c: prefill_chunk(p, t, c, cfg))(
                        p, toks, cache))


@pytest.mark.parametrize("bad", [
    dict(n_heads=3, d_model=48), dict(qk_norm=True),
    dict(sink_kinds=("full",)), dict(attention="mla"),
])
def test_what_a_differential_model_is_refused_for(bad):
    with pytest.raises(ValueError, match="diff_attn"):
        check_kinds(_cfg(**bad))
