"""A prompt's remainder is ONE padded chunk program (`n_valid`), not one
program a token: the program against the single-token programs it replaced,
the window policy at the cache's edge, and the engine's counters.  Tier-1,
CPU, three tiny models in float32: learned positions (GPT-2-like), rotary
GQA, and latent attention with a no-drop routed-expert layer.

Tolerance: two orders of the same float32 sums, 2e-5 on logits of order 1
(what `tests/test_latent_moe.py` holds its chunk tests to).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greedy_reference import greedy_stream
from ray_tpu.models import (TransformerConfig, init_kv_cache, init_params,
                            prefill, prefill_chunk_jit, prefill_chunked)
from ray_tpu.models.generate import (_prefill_chunk, cache_arrays,
                                     chunk_window, padded_chunk)

TOL = 2e-5
CHUNK = 8
MAX_LEN = 32
MODELS = ("learned_mha", "rope_gqa", "latent_moe")


def _config(name: str) -> TransformerConfig:
    if name == "learned_mha":
        return TransformerConfig(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4,
            max_seq_len=MAX_LEN, pos_emb="learned", activation="gelu",
            norm="layernorm", tie_embeddings=True, remat=False,
            dtype=jnp.float32, attention_impl="reference")
    if name == "rope_gqa":
        return TransformerConfig.tiny(max_seq_len=MAX_LEN, dtype=jnp.float32,
                                      attention_impl="reference")
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=160,
        max_seq_len=MAX_LEN, pos_emb="rope", rope_base=1e4,
        activation="swiglu", norm="rmsnorm", norm_eps=1e-5,
        tie_embeddings=False, remat=False, attention="mla", q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=16, n_experts=8, expert_top_k=2, router="sigmoid",
        moe_d_ff=32, n_shared_experts=1, routed_scaling_factor=1.8,
        first_dense_layers=1, dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference")


@functools.lru_cache(maxsize=None)
def _model(name: str):
    cfg = _config(name)
    params, _ = init_params(jax.random.PRNGKey(5), cfg)
    if name == "latent_moe":     # a bias that changes choices
        params["layers"]["router_bias"] = 0.2 * jax.random.normal(
            jax.random.PRNGKey(7), params["layers"]["router_bias"].shape)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, MAX_LEN),
                                         1, 256), np.int32)
    return cfg, params, toks


def _single_tokens(cfg, params, toks, n, cache=None, off=0):
    """The policy this replaced, from ``off``: whole chunks, then one
    unpadded program a token."""
    cache = cache or init_kv_cache(cfg, 1, MAX_LEN)
    logits = None
    while off < n:
        take = CHUNK if n - off >= CHUNK else 1
        logits, cache = prefill_chunk_jit(params, toks[:, off:off + take],
                                          cache, cfg=cfg)
        off += take
    return logits, cache


def _padded(cfg, params, toks, n, cache=None, off=0, garbage=0):
    """`chunk_window`'s programs from ``off``; the padding rows hold
    ``garbage``, which nothing may read."""
    cache = cache or init_kv_cache(cfg, 1, MAX_LEN)
    logits, windows = None, []
    while off < n:
        start, n_valid = chunk_window(off, n, CHUNK, MAX_LEN)
        buf = padded_chunk(toks, start, n_valid, CHUNK)
        buf[:, n_valid:] = garbage
        if start != off:
            cache = dict(cache, pos=np.int32(start))
        logits, cache = prefill_chunk_jit(params, buf, cache, cfg=cfg,
                                          n_valid=np.int32(n_valid))
        windows.append((start, n_valid))
        off = start + n_valid
    return logits, cache, windows


def _columns(cache, lo, hi):
    return {k: np.asarray(a[..., lo:hi])
            for k, a in cache_arrays(cache).items()}


def _assert_same_cache(got, want, n, tol=TOL):
    assert int(got["pos"]) == int(want["pos"]) == n
    for name, cols in _columns(want, 0, n).items():
        assert float(np.abs(_columns(got, 0, n)[name] - cols).max()) < tol


# ------------------------------------------------------------ the policy

@pytest.mark.parametrize("off,n,chunk,capacity,want", [
    (0, 100, 32, 1024, (0, 32)),        # a whole chunk
    (96, 100, 32, 1024, (96, 4)),       # the remainder, padded
    (64, 96, 32, 1024, (64, 32)),       # no remainder
    (0, 5, 32, 1024, (0, 5)),           # a prompt shorter than a chunk
    (1000, 1020, 32, 1024, (992, 28)),  # seeded, window would pass the end
    (1000, 1024, 32, 1024, (992, 32)),
    (24, 29, 8, 30, (22, 7)),           # capacity no multiple of the chunk
    (0, 4, 4, 4, (0, 4)),               # chunk == capacity
])
def test_chunk_window_table(off, n, chunk, capacity, want):
    assert chunk_window(off, n, chunk, capacity) == want


@pytest.mark.parametrize("chunk,capacity", [(4, 4), (4, 9), (8, 32), (8, 30)])
def test_chunk_window_covers_every_prompt_inside_the_capacity(chunk,
                                                              capacity):
    """From any offset: every window lies inside the capacity, starts at
    or before the offset, carries real tokens only, and the walk ends at
    the prompt's end in ceil((n - off) / chunk) programs."""
    for n in range(1, capacity + 1):
        for off0 in range(n):
            off, programs = off0, 0
            while off < n:
                start, n_valid = chunk_window(off, n, chunk, capacity)
                assert 0 <= start <= off < start + n_valid <= n
                assert start + chunk <= capacity and 1 <= n_valid <= chunk
                off, programs = start + n_valid, programs + 1
            assert programs == -(-(n - off0) // chunk)


# ------------------------------------------------------------ the program

@pytest.mark.parametrize("r", range(1, CHUNK))
@pytest.mark.parametrize("name", MODELS)
def test_padded_remainder_is_the_single_token_programs(name, r):
    """One chunk and a remainder of ``r``: logits, ``pos`` and the cache's
    columns ``[0, n)`` of the padded program (whatever its padding rows
    hold) are those of ``r`` single-token programs; a whole chunk that
    passes ``n_valid = chunk`` is the unpadded chunk."""
    cfg, params, toks = _model(name)
    n = CHUNK + r
    want_logits, want = _single_tokens(cfg, params, toks, n)
    logits, got, windows = _padded(cfg, params, toks, n, garbage=200 + r)
    assert windows == [(0, CHUNK), (CHUNK, r)]
    assert float(jnp.abs(logits - want_logits).max()) < TOL
    _assert_same_cache(got, want, n)


@pytest.mark.parametrize("r", (1, 3, 7))
def test_padded_rows_touch_no_expert(r):
    """The routed layer's ``load`` (experts touched, largest expert load,
    summed over layers) of a padded chunk is that of its real rows alone."""
    cfg, params, toks = _model("latent_moe")
    chunk = jax.jit(functools.partial(_prefill_chunk, cfg=cfg))
    _, _, want = chunk(params, toks[:, :r], init_kv_cache(cfg, 1, MAX_LEN))
    buf = padded_chunk(toks, 0, r, CHUNK)
    buf[:, r:] = 99
    _, _, got = chunk(params, buf, init_kv_cache(cfg, 1, MAX_LEN),
                      n_valid=np.int32(r))
    assert [int(x) for x in got] == [int(x) for x in want]
    assert int(got[1]) <= r * cfg.expert_top_k * 2      # 2 expert layers
    _, _, full = chunk(params, buf, init_kv_cache(cfg, 1, MAX_LEN))
    assert int(full[0]) > int(got[0])     # unmasked, the padding routes too


@pytest.mark.parametrize("off,n", [(27, 31), (25, 32), (5, 20), (3, 7)])
@pytest.mark.parametrize("name", MODELS)
def test_seeded_offset_and_capacity_edge_write_only_their_positions(
        name, off, n):
    """A cache seeded to an unaligned ``off`` (a prefix donor's copy), the
    prompt ending within a chunk of the capacity or not: the windows stay
    inside the cache, columns below the first window's start keep their
    bits, and logits and columns ``[0, n)`` are the single-token walk's."""
    cfg, params, toks = _model(name)
    seeded = _single_tokens(cfg, params, toks, off)[1]
    want_logits, want = _single_tokens(
        cfg, params, toks, n, off=off,
        cache=_single_tokens(cfg, params, toks, off)[1])
    first = chunk_window(off, n, CHUNK, MAX_LEN)[0]
    before = _columns(seeded, 0, first)
    logits, got, windows = _padded(cfg, params, toks, n, cache=seeded,
                                   off=off, garbage=77)
    assert all(s + CHUNK <= MAX_LEN for s, _ in windows)
    assert (first < off) == (off + CHUNK > MAX_LEN)
    for name_, cols in _columns(got, 0, first).items():
        assert np.array_equal(cols, before[name_])
    assert float(jnp.abs(logits - want_logits).max()) < TOL
    _assert_same_cache(got, want, n)


@pytest.mark.parametrize("n", (7, 8, 23, 29, 30))
@pytest.mark.parametrize("name", MODELS)
def test_prefill_chunked_pays_one_program_for_its_remainder(name, n):
    """`prefill_chunked` walks `chunk_window`, in a cache whose capacity
    (30) is no multiple of the chunk, up to the capacity's edge (29, 30:
    the last window starts at 22 and runs tokens again): the logits and
    columns of the single-token walk AND of the whole-prompt `prefill`,
    from ceil(n / chunk) calls of ONE shape."""
    cfg, params, toks = _model(name)
    calls = []

    def counted(params, buf, cache, *, cfg, n_valid):
        calls.append((buf.shape, int(cache["pos"]), int(n_valid)))
        return prefill_chunk_jit(params, buf, cache, cfg=cfg,
                                 n_valid=n_valid)

    want_logits, want = _single_tokens(cfg, params, toks, n)
    logits, got = prefill_chunked(params, jnp.asarray(toks[:, :n]), cfg,
                                  init_kv_cache(cfg, 1, 30), chunk=CHUNK,
                                  _jitted=counted)
    assert len(calls) == -(-n // CHUNK)
    assert {c[0] for c in calls} == {(1, CHUNK)}
    assert all(pos + CHUNK <= 30 for _, pos, _ in calls)
    assert float(jnp.abs(logits - want_logits).max()) < TOL
    _assert_same_cache(got, want, n)
    whole_logits, whole = prefill(params, jnp.asarray(toks[:, :n]), cfg,
                                  init_kv_cache(cfg, 1, 30))
    assert float(jnp.abs(logits - whole_logits).max()) < TOL
    _assert_same_cache(got, whole, n)


# ------------------------------------------------------------- the engine

def _engine_core(name, chunk=CHUNK, **ecfg):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg, params, _ = _model(name)
    return DecodeSessionCore(
        cfg, max_len=MAX_LEN, params=params,
        engine=DecodeEngineConfig(prefill_chunk_tokens=chunk, max_slots=2,
                                  **ecfg))


def _reference_stream(name, prompt, want):
    """Whole-prompt prefill and batch-1 decode steps."""
    cfg, params, _ = _model(name)
    return greedy_stream(cfg, prompt, want, max_len=MAX_LEN, params=params)


def _stream(core, prompt, want, op="start", **more):
    r = core.handle({"op": op, "prompt": prompt, **more})
    toks = list(r["token"])
    while len(toks) < want:
        out = core.handle({"op": "next_chunk", "sid": r["sid"],
                           "max_tokens": want - len(toks)})
        assert "error" not in out, out
        toks += out["tokens"]
    return r["sid"], toks


@pytest.mark.parametrize("chunk", [CHUNK, 24, MAX_LEN])
@pytest.mark.parametrize("name", MODELS)
def test_engine_streams_the_same_tokens_from_one_prefill_shape(name, chunk):
    """Prompts of 7, 8, 9 and 23 tokens through chunks of 8 (chunk - 1,
    chunk, chunk + 1, 2 chunk + 7), of 24 (wider than every prompt: each
    is ONE padded program, what the width derived for a chip makes of a
    short prompt) and of the capacity: the streams of whole-prompt
    prefill + decode steps; one `prefill_chunk` shape; counters that
    read what the prompts imply."""
    toks = _model(name)[2][0]
    lengths = (CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK + 7)
    want = 5
    core = _engine_core(name, chunk, prefix_cache=False)
    try:
        for n in lengths:
            prompt = [int(t) for t in toks[:n]]
            sid, got = _stream(core, prompt, want)
            core.handle({"op": "end", "sid": sid})
            assert got == _reference_stream(name, prompt, want), n
        st = core.handle({"op": "stats"})["engine"]
        assert [s for s in st["program_shapes"]
                if s.startswith("prefill_chunk")] == [
                    f"prefill_chunk:1x{chunk}"]
        assert st["prefill_chunk_tokens"] == chunk
        assert st["prefill_chunks"] == sum(-(-n // chunk) for n in lengths)
        assert st["prefill_tails"] == sum(1 for n in lengths if n % chunk)
        assert st["prefill_pad_tokens"] == sum(-n % chunk for n in lengths)
        ph = st["phase_totals"]
        if chunk > max(lengths):     # a program a prompt, each padded
            assert st["prefill_chunks"] == len(lengths)
            assert 0 < ph["prefill_tail"] == ph["prefill"]
        else:
            assert 0 < ph["prefill_tail"] < ph["prefill"]
        (row,) = [r for r in st["device_profile"]
                  if r["program"] == "prefill_chunk"]
        assert row["tokens"] == sum(lengths) and row["shapes"] == 1
        assert st["cache_copies"] == 0
    finally:
        core.engine.shutdown()


@pytest.mark.parametrize("name", MODELS)
def test_engine_prefix_seeded_prompt_at_the_capacity_edge(name):
    """A prompt that shares 27 tokens with a live slot and ends one short
    of the capacity: seeded at 27, its one window would pass the end, so
    it starts at 24 and runs three tokens again.  Same tokens as the
    whole-prompt prefill; a resume of it (replay of 32 = the capacity)
    likewise ends where the cache does."""
    toks = _model(name)[2][0]
    donor = [int(t) for t in toks[:29]]
    prompt = donor[:27] + [int(t) % 250 + 3 for t in toks[27:31]]
    core = _engine_core(name, prefix_cache_min_tokens=4)
    try:
        sid0, _ = _stream(core, donor, 2)          # stays live: the donor
        sid, got = _stream(core, prompt, 2)
        assert got == _reference_stream(name, prompt, 2)
        st = core.handle({"op": "stats"})["engine"]
        assert st["prefix"]["applied_hits"] == 1
        assert st["prefix"]["tokens_reused"] == 27
        # donor: 3 whole chunks + a tail of 5; seeded: ONE window [24, 31)
        assert st["prefill_chunks"] == 4 + 1
        assert st["prefill_pad_tokens"] == 3 + 1
        for s in (sid0, sid):
            core.handle({"op": "end", "sid": s})
        rr = core.handle({"op": "resume", "prompt": prompt,
                          "generated": got[:1]})
        assert rr["token"] == got[1:2] and rr.get("done")
    finally:
        core.engine.shutdown()


@pytest.mark.parametrize("name", MODELS)
def test_no_prompt_length_compiles_once_the_engine_is_warm(name):
    """What the benchmark's ``compiles_in_window`` counts: after one
    session (a whole chunk, a remainder, slot insert, a step) and one
    seeded admission, prompts of every other length, one seeded at the
    capacity's edge, a resume and several prompts started together compile
    NOTHING: chunks are filled on the host, the count of real tokens is
    traced, a rewound ``pos`` is the same argument to the program, and the
    lane path was warmed by the engine at its loop's start."""
    from jax import monitoring
    toks = _model(name)[2][0]
    count = [None]            # None: not counting yet

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration" \
                and count[0] is not None:
            count[0] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    core = _engine_core(name, prefix_cache_min_tokens=4)
    try:
        warm = [int(t) for t in toks[:CHUNK + 3]]
        sid, _ = _stream(core, warm, 3)
        sid2, _ = _stream(core, warm[:6] + [3, 4, 5], 3)    # seeded at 6
        for s in (sid, sid2):
            core.handle({"op": "end", "sid": s})
        count[0] = 0
        for n in (1, 5, CHUNK, CHUNK + 1, 2 * CHUNK + 5, 30):
            prompt = [int(t) % 200 + n for t in toks[:n]]
            sid, got = _stream(core, prompt, 2)
            if n == 30:      # stays live: the donor of the edge case
                edge = prompt[:27] + [7, 8, 9, 10]
                sid3, _ = _stream(core, edge, 2)
                core.handle({"op": "end", "sid": sid3})
            core.handle({"op": "end", "sid": sid})
        rr = core.handle({"op": "resume", "prompt": warm,
                          "generated": [1, 2, 3]})
        core.handle({"op": "end", "sid": rr["sid"]})
        # several prompts AT ONCE: the lanes program, the lane cache, the
        # insert into a lane and the gather out of one were run by the
        # engine itself before it served the warm-up session
        import threading
        import time
        eng = core.engine
        before = core.handle({"op": "stats"})["engine"]

        def held(real):      # no chunk program before all three are there
            def program(*args, **kwargs):
                give_up = time.monotonic() + 60
                while not all_there and time.monotonic() < give_up:
                    if len(eng._prefilling) == 3:
                        all_there.append(True)
                    time.sleep(0.002)
                return real(*args, **kwargs)
            return program

        all_there = []

        eng._chunk, eng._chunk_lanes = held(eng._chunk), held(
            eng._chunk_lanes)
        together = [[int(t) % 190 + 7 * i + 1 for t in toks[:28 + i]]
                    for i in range(3)]
        got = {}
        callers = [threading.Thread(
            target=lambda i=i: got.update({i: _stream(core, together[i], 2)}),
            daemon=True) for i in range(3)]
        for t in callers:
            t.start()
        for t in callers:
            t.join(120)
        compiled, count[0] = count[0], None    # the reference compiles
        for i, prompt in enumerate(together):
            assert got[i][1] == _reference_stream(name, prompt, 2)
        st = core.handle({"op": "stats"})["engine"]
        assert st["prefill_chunks"] - before["prefill_chunks"] == 12
        assert st["prefill_programs"] - before["prefill_programs"] < 12
        assert st["prefix"]["applied_hits"] >= 2
        assert compiled == 0
    finally:
        core.engine.shutdown()
        from jax._src import monitoring as _m
        _m.unregister_event_duration_listener(on_duration)


def test_start_replies_with_the_first_token_however_late_its_caller_wakes():
    """`start` waits on the engine's condition for the first token.  A
    caller that wakes late (a loaded host) finds the session already in
    its slot and some steps on: the reply still carries the FIRST token,
    the later ones wait in the queue, and the stream is the reference's."""
    import threading
    import time
    name = "rope_gqa"
    prompt = [int(t) for t in _model(name)[2][0][:CHUNK + 1]]
    core = _engine_core(name, prefix_cache=False)
    cond = core.engine._cond
    wait = cond.wait
    caller = threading.current_thread()
    before = 0

    def late(timeout=None):
        woke = wait(timeout)
        if threading.current_thread() is caller:
            # as if the wake-up itself came late: two steps late
            cond.release()
            give_up = time.time() + 30
            while core.engine.steps < before + 2 and time.time() < give_up:
                time.sleep(0.01)
            cond.acquire()
        return woke

    try:
        sid, _ = _stream(core, prompt[:3], 3)       # compiles everything
        core.handle({"op": "end", "sid": sid})
        before = core.handle({"op": "stats"})["engine"]["steps"]
        cond.wait = late
        r = core.handle({"op": "start", "prompt": prompt})
        cond.wait = wait
        assert core.handle({"op": "stats"})["engine"]["steps"] >= before + 2
        toks = list(r["token"])
        while len(toks) < 5:
            toks += core.handle({"op": "next_chunk", "sid": r["sid"],
                                 "max_tokens": 5 - len(toks)})["tokens"]
        assert toks == _reference_stream(name, prompt, 5)
    finally:
        cond.wait = wait
        core.engine.shutdown()
