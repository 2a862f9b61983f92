"""The served KV cache is extended in place and never copied.

Counts, not times: the engine's three cache-writing programs (the fused
decode step, the shared chunk program, the slot insert) are compiled with
the engine's own donation and must alias the cache they are given to the
cache they return; and the one cached layer loop of `models/generate.py`
must serve what the unbatched whole-prompt path serves, for slots at
different positions, paused slots, reused slots and columns past the end.
CPU, tiny models.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (TransformerConfig, cache_insert_slot,
                            decode_step, decode_step_slots, init_kv_cache,
                            init_params, init_slot_cache, prefill,
                            prefill_chunk_jit)

_CONFIGS = {
    "learned": dict(pos_emb="learned", n_kv_heads=4, activation="gelu",
                    norm="layernorm"),
    "rope_gqa": dict(pos_emb="rope", n_kv_heads=2),
}


def _cfg(kind, max_seq_len):
    return TransformerConfig.tiny(max_seq_len=max_seq_len,
                                  attention_impl="reference",
                                  dtype=jnp.float32, **_CONFIGS[kind])


def _cache_bytes(cache):
    return cache["k"].nbytes + cache["v"].nbytes


def _engine(cfg, max_len, params, slots):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import ContinuousBatchingEngine
    return ContinuousBatchingEngine(cfg, max_len, params,
                                    DecodeEngineConfig(max_slots=slots))


@pytest.mark.parametrize("kind", sorted(_CONFIGS))
@pytest.mark.parametrize("program",
                         ["fused_step", "prefill_chunk", "cache_insert_slot"])
def test_engine_program_aliases_its_cache(program, kind):
    """Compiled as the engine compiles it, the program's cache output IS
    its cache input (`alias_size_in_bytes` covers the cache), what it
    allocates beside that holds no second cache, and after a call the
    cache passed in is gone."""
    slots, max_len = 4, 1024
    cfg = _cfg(kind, max_len)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    eng = _engine(cfg, max_len, params, slots)
    slot_cache = init_slot_cache(cfg, slots, max_len)
    one = init_kv_cache(cfg, 1, max_len)
    # the jitted programs, under the profiler's and the counter's shims
    if program == "fused_step":
        fn, donated = eng._step._rt_profiled_inner.__wrapped__, slot_cache
        args = (params, jnp.zeros((slots,), jnp.int32), slot_cache,
                jnp.ones((slots,), bool))
        kwargs = {"cfg": cfg}
    elif program == "prefill_chunk":
        fn, donated = eng._chunk._rt_profiled_inner.__wrapped__, one
        args, kwargs = (params, jnp.zeros((1, 2), jnp.int32), one), \
            {"cfg": cfg}
    else:
        fn, donated = eng._insert._rt_profiled_inner.__wrapped__, slot_cache
        args, kwargs = (slot_cache, one, jnp.int32(1)), {}
    ma = fn.lower(*args, **kwargs).compile().memory_analysis()
    want = _cache_bytes(donated)
    assert ma.alias_size_in_bytes >= want
    fresh = ma.output_size_in_bytes - ma.alias_size_in_bytes \
        + ma.temp_size_in_bytes
    assert fresh < want, (fresh, want)
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    assert donated["k"].is_deleted() and donated["v"].is_deleted()


def _chunked_into_slot(params, cfg, slot_cache, prompt, slot, max_len):
    """Chunked prefill (blocks of 4, then single tokens) of ``prompt`` and
    its insert into ``slot`` → (first logits [vocab], slot cache)."""
    pc = init_kv_cache(cfg, 1, max_len)
    arr = jnp.asarray([prompt], jnp.int32)
    off = 0
    while off < len(prompt):
        take = 4 if len(prompt) - off >= 4 else 1
        logits, pc = prefill_chunk_jit(params, arr[:, off:off + take], pc,
                                       cfg=cfg)
        off += take
    return logits[0], cache_insert_slot(slot_cache, pc, jnp.int32(slot))


@pytest.mark.parametrize("kind", sorted(_CONFIGS))
def test_slot_path_serves_what_the_unbatched_path_serves(kind):
    """Chunked prefill, slot insert, N slot steps against whole-prompt
    `prefill` + batch-1 `decode_step`: token for token, and logit for
    logit within the tolerance of the existing parity tests.  The two
    live slots sit at different positions, one of them in a REUSED slot
    whose rows past its `pos` still hold a longer session's K/V, and the
    paused slots neither advance nor disturb them."""
    max_len, slots, steps = 32, 4, 6
    cfg = _cfg(kind, max_len)
    params, _ = init_params(jax.random.PRNGKey(3), cfg)
    prompts = {2: [7, 11, 13, 17, 19, 23, 29], 0: [5, 3]}
    step = jax.jit(decode_step_slots, static_argnames=("cfg",))

    cache = init_slot_cache(cfg, slots, max_len)
    # a longer session lives in slot 2 first and decodes a few tokens ...
    _, cache = _chunked_into_slot(params, cfg, cache, list(range(40, 58)),
                                  2, max_len)
    only2 = jnp.asarray([False, False, True, False])
    for _ in range(3):
        _, cache = step(params, jnp.full((slots,), 9, jnp.int32), cache,
                        only2, cfg=cfg)
    assert int(cache["pos"][2]) == 21
    # ... and slot 3 is left paused at a position of its own
    _, cache = _chunked_into_slot(params, cfg, cache, [1, 2, 3], 3, max_len)
    # then slot 2 is taken over by a shorter prompt: stale rows 7 .. 20
    first = {}
    for slot, prompt in prompts.items():
        first[slot], cache = _chunked_into_slot(params, cfg, cache, prompt,
                                                slot, max_len)

    want_logits, want_toks = {}, {}
    for slot, prompt in prompts.items():
        lg, c1 = prefill(params, jnp.asarray([prompt], jnp.int32), cfg,
                         init_kv_cache(cfg, 1, max_len))
        np.testing.assert_allclose(np.asarray(first[slot]),
                                   np.asarray(lg[0]), rtol=2e-4, atol=2e-4)
        rows, toks = [], []
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        for _ in range(steps):
            toks.append(int(tok[0]))
            lg, c1 = decode_step(params, tok, c1, cfg)
            rows.append(np.asarray(lg[0]))
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
        want_logits[slot], want_toks[slot] = rows, toks

    active = jnp.asarray([True, False, True, False])
    toks = jnp.zeros((slots,), jnp.int32)
    for slot in prompts:
        toks = toks.at[slot].set(jnp.argmax(first[slot]).astype(jnp.int32))
    for i in range(steps):
        for slot in prompts:
            assert int(toks[slot]) == want_toks[slot][i]
        logits, cache = step(params, toks, cache, active, cfg=cfg)
        for slot in prompts:
            np.testing.assert_allclose(np.asarray(logits[slot]),
                                       want_logits[slot][i],
                                       rtol=2e-4, atol=2e-4)
        toks = jnp.where(active, jnp.argmax(logits, -1).astype(jnp.int32),
                         toks)
    assert np.asarray(cache["pos"]).tolist() == [2 + steps, 0, 7 + steps, 3]


@pytest.mark.parametrize("kind", sorted(_CONFIGS))
def test_columns_past_the_end_are_dropped(kind):
    """A slot two positions from the end takes its last two slot steps as
    two batch-1 steps do, and a step over a slot that is already full (its
    one column, past `max_len`, is clamped onto the last position) leaves
    every column a reader may still use."""
    max_len = 16
    cfg = _cfg(kind, max_len)
    params, _ = init_params(jax.random.PRNGKey(5), cfg)
    prompt = list(range(3, 17))                      # 14 tokens: pos 14
    lg, one = prefill(params, jnp.asarray([prompt], jnp.int32), cfg,
                      init_kv_cache(cfg, 1, max_len))
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    after = cache_insert_slot(init_slot_cache(cfg, 2, max_len), one,
                              jnp.int32(1))
    active = jnp.asarray([False, True])
    t, c1, toks = tok, one, jnp.asarray([0, int(tok[0])], jnp.int32)
    for _ in range(2):
        lg, c1 = decode_step(params, t, c1, cfg)
        t = jnp.argmax(lg, -1).astype(jnp.int32)
        lgs, after = decode_step_slots(params, toks, after, active, cfg)
        toks = jnp.where(active, jnp.argmax(lgs, -1).astype(jnp.int32),
                         toks)
        assert int(toks[1]) == int(t[0])
    assert np.asarray(after["pos"]).tolist() == [0, 16]
    np.testing.assert_allclose(np.asarray(after["k"][:, 1]),
                               np.asarray(c1["k"][:, 0]),
                               rtol=2e-4, atol=2e-4)
    # slot 1 is full now: one more step over it (paused) keeps 0 .. 14
    _, again = decode_step_slots(params, jnp.asarray([0, 5], jnp.int32),
                                 after, jnp.asarray([False, False]), cfg)
    np.testing.assert_array_equal(np.asarray(again["k"][:, 1, ..., :15]),
                                  np.asarray(after["k"][:, 1, ..., :15]))
    assert np.asarray(again["pos"]).tolist() == [0, 16]
