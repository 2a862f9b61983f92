"""KV-cache generation: parity with full-recompute decoding.

The decode path must produce EXACTLY the tokens that repeatedly running
the full forward over the growing sequence would (greedy), across
rope/learned positions, MHA/GQA, gelu/swiglu, and MoE (at a capacity
factor where the full-sequence forward drops no tokens — capacity
pressure is a prefill-vs-decode semantic difference by construction:
s=1 decode never hits the per-expert cap).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, forward, init_params
from ray_tpu.models.generate import (decode_step, generate, init_kv_cache,
                                     prefill)


def _greedy_reference(params, prompt, cfg, n_new):
    """Slow oracle: full forward over the growing sequence each step."""
    toks = prompt
    out = []
    for _ in range(n_new):
        logits = forward(params, toks, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


def _parity_case(cfg, seed=0, batch=2, prompt_len=7, n_new=6):
    params, _ = init_params(jax.random.PRNGKey(seed), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab_size)
    want = _greedy_reference(params, prompt, cfg, n_new)
    got = generate(params, prompt, cfg=cfg, max_new_tokens=n_new,
                   temperature=0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_greedy_parity_rope_swiglu():
    _parity_case(TransformerConfig.tiny(max_seq_len=64,
                                        attention_impl="reference",
                                        dtype=jnp.float32))


def test_greedy_parity_learned_gelu():
    cfg = TransformerConfig(vocab_size=128, d_model=32, n_layers=2,
                            n_heads=4, max_seq_len=64,
                            pos_emb="learned", activation="gelu",
                            norm="layernorm", tie_embeddings=True,
                            attention_impl="reference",
                            dtype=jnp.float32, remat=False)
    _parity_case(cfg)


def test_greedy_parity_gqa():
    cfg = TransformerConfig(vocab_size=128, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, max_seq_len=64,
                            attention_impl="reference",
                            dtype=jnp.float32, remat=False)
    _parity_case(cfg)


def test_greedy_parity_moe():
    # capacity_factor high enough that the full-sequence oracle drops no
    # tokens — the regime where decode parity is well-defined
    cfg = TransformerConfig.tiny(max_seq_len=64,
                                 attention_impl="reference",
                                 dtype=jnp.float32, n_experts=2,
                                 expert_top_k=1, capacity_factor=8.0)
    _parity_case(cfg, n_new=4)


def test_prefill_decode_cache_positions():
    cfg = TransformerConfig.tiny(max_seq_len=32,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.ones((1, 5), jnp.int32)
    cache = init_kv_cache(cfg, 1, 16)
    logits, cache = prefill(params, prompt, cfg, cache)
    assert logits.shape == (1, cfg.vocab_size)
    assert int(cache["pos"]) == 5
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits2, cache = decode_step(params, tok, cache, cfg)
    assert int(cache["pos"]) == 6 and logits2.shape == (1, cfg.vocab_size)


def test_sampling_modes_shapes_and_determinism():
    cfg = TransformerConfig.tiny(max_seq_len=64,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((2, 4), jnp.int32)
    a = generate(params, prompt, cfg=cfg, max_new_tokens=5,
                 temperature=0.8, top_k=10, key=jax.random.PRNGKey(7))
    b = generate(params, prompt, cfg=cfg, max_new_tokens=5,
                 temperature=0.8, top_k=10, key=jax.random.PRNGKey(7))
    assert a.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(a.max()) < cfg.vocab_size and int(a.min()) >= 0


def test_temperature_is_traced_no_recompile():
    """Serving different temperatures must not recompile the program."""
    from ray_tpu.models.generate import _generate_impl

    cfg = TransformerConfig.tiny(max_seq_len=64,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((1, 4), jnp.int32)
    before = _generate_impl._cache_size()
    for t in (0.5, 0.8, 1.3):
        generate(params, prompt, cfg=cfg, max_new_tokens=3,
                 temperature=t, key=jax.random.PRNGKey(0))
    assert _generate_impl._cache_size() == before + 1


def test_learned_positions_overflow_rejected():
    cfg = TransformerConfig(vocab_size=64, d_model=16, n_layers=1,
                            n_heads=2, max_seq_len=8,
                            pos_emb="learned", activation="gelu",
                            norm="layernorm", tie_embeddings=True,
                            attention_impl="reference",
                            dtype=jnp.float32, remat=False)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        generate(params, jnp.zeros((1, 6), jnp.int32), cfg=cfg,
                 max_new_tokens=4)


def test_pp_config_rejected():
    cfg = TransformerConfig.tiny(max_seq_len=32, pp_stages=2,
                                 dtype=jnp.float32)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError):
        prefill(params, jnp.ones((1, 4), jnp.int32), cfg,
                init_kv_cache(cfg, 1, 8))


def test_chunked_prefill_parity_with_whole_prefill():
    """prefill_chunked must produce the same last-position logits and
    the same cache as one whole-prompt prefill — the bounded-compile
    alternative for compile-helper-killer models (SURVEY section 9),
    including GQA and a non-divisible tail chunk."""
    from ray_tpu.models.generate import prefill_chunked

    cfg = TransformerConfig(vocab_size=128, d_model=32, n_layers=2,
                            n_heads=4, n_kv_heads=2, max_seq_len=64,
                            pos_emb="rope", attention_impl="reference",
                            dtype=jnp.float32, remat=False)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 13), 0,
                                cfg.vocab_size)
    whole_logits, whole_cache = prefill(
        params, prompt, cfg, init_kv_cache(cfg, 2, 32))
    # chunk=4 over 13 tokens: three full chunks + a padded tail of 1
    chunk_logits, chunk_cache = prefill_chunked(
        params, prompt, cfg, init_kv_cache(cfg, 2, 32), chunk=4)
    assert int(chunk_cache["pos"]) == 13 == int(whole_cache["pos"])
    np.testing.assert_allclose(np.asarray(chunk_logits),
                               np.asarray(whole_logits),
                               rtol=2e-4, atol=2e-4)
    # positions last; the columns past ``pos`` hold the tail's padding
    np.testing.assert_allclose(np.asarray(chunk_cache["k"][..., :13]),
                               np.asarray(whole_cache["k"][..., :13]),
                               rtol=2e-4, atol=2e-4)
    # and decode continues identically from a chunk-built cache
    tok = jnp.argmax(chunk_logits, axis=-1).astype(jnp.int32)
    l1, _ = decode_step(params, tok, chunk_cache, cfg)
    l2, _ = decode_step(params, tok, whole_cache, cfg)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [None, 4])
def test_decode_session_chunked_prefill_tokens_match(chunk):
    """A DecodeSessionCore — the default engine and one that prefills
    in chunks of 4 — serves the whole-prompt reference's tokens."""
    from greedy_reference import greedy_stream
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore

    cfg = TransformerConfig.tiny(max_seq_len=64,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    core = DecodeSessionCore(
        cfg, max_len=64, seed=3,
        engine=chunk and DecodeEngineConfig(prefill_chunk_tokens=chunk))
    prompt = list(range(10))
    try:
        r = core.handle({"op": "start", "prompt": prompt})
        toks = list(r["token"])
        for _ in range(5):
            toks += core.handle({"op": "next", "sid": r["sid"]})["token"]
        assert toks == greedy_stream(cfg, prompt, 6, max_len=64, seed=3)
        assert core.engine.ecfg.prefill_chunk_tokens == (chunk or 32)
    finally:
        core.engine.shutdown()


@pytest.mark.parametrize("engine", [False, True, "on"])
def test_decode_session_engine_is_a_config_or_none(engine):
    from ray_tpu.serve.decode_session import DecodeSessionCore

    cfg = TransformerConfig.tiny(max_seq_len=64,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    with pytest.raises(TypeError, match="engine"):
        DecodeSessionCore(cfg, max_len=64, seed=3, engine=engine)


def test_chunked_prefill_rejects_overlong_prompt():
    """Same loud failure as whole-prompt prefill — silent cache
    corruption via clamped dynamic_update_slice is not acceptable."""
    from ray_tpu.models.generate import prefill_chunked

    cfg = TransformerConfig.tiny(max_seq_len=64,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((1, 40), jnp.int32)
    with pytest.raises(ValueError, match="exceeds cache capacity"):
        prefill_chunked(params, prompt, cfg, init_kv_cache(cfg, 1, 32),
                        chunk=8)
