"""The chunk program over LANES (`models.generate.prefill_lanes`): up to P
sessions' padded chunks in one program, each lane at its own position with
its own count of real rows, against `prefill_chunk_jit` run for each session
alone.  Tier-1, CPU, float32, one tiny model of every cache kind the engine
serves: learned positions, rotary GQA, latent attention with routed experts,
window rings beside full rows, conv states beside rows, and two row shapes
by layer kind.

Tolerance: two orders of the same float32 sums, 2e-5 on logits of order 1
(what `tests/test_prefill_padded_tail.py` holds its programs to).  A lane
that stands is held bit for bit.
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (TransformerConfig, init_kv_cache, init_params,
                            init_slot_cache, prefill_chunk_jit,
                            prefill_lanes_jit)
from ray_tpu.models.generate import (_prefill_chunk, _prefill_lanes,
                                     cache_arrays, cache_gather_slot,
                                     cache_insert_slot, chunk_window,
                                     padded_chunk, prefill_chunk_step,
                                     prefill_lanes_step, window_ring)

TOL = 2e-5
CHUNK = 4
MAX_LEN = 32
LANES = 4
MODELS = ("learned_mha", "rope_gqa", "latent_moe", "window_ring",
          "conv_state", "two_row_shapes")


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
    if name == "latent_moe":
        return TransformerConfig(
            vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=160,
            max_seq_len=MAX_LEN, pos_emb="rope", rope_base=1e4,
            activation="swiglu", norm="rmsnorm", norm_eps=1e-5,
            tie_embeddings=False, remat=False, attention="mla",
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
            qk_rope_head_dim=8, v_head_dim=16, n_experts=8, expert_top_k=2,
            router="sigmoid", moe_d_ff=32, n_shared_experts=1,
            routed_scaling_factor=1.8, first_dense_layers=1,
            dtype=jnp.float32, param_dtype=jnp.float32,
            attention_impl="reference")
    if name == "window_ring":       # rings of 8 + 4 rows that prompts wrap
        return TransformerConfig(
            vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
            head_size=24, d_ff=160, max_seq_len=128, pos_emb="rope",
            rope_base=1e4, rope_layers="window", activation="swiglu",
            norm="rmsnorm", norm_eps=1e-5, tie_embeddings=False, remat=False,
            qk_norm=True, attn_gate=True, sandwich_norm=True,
            embed_scale=8.0, layer_kinds=("window",) * 4 + ("full",),
            sliding_window=8, window_chunk=CHUNK, n_experts=8,
            experts_held=2, expert_offset=4, expert_top_k=2,
            router="sigmoid", moe_d_ff=32, n_shared_experts=1,
            routed_scaling_factor=2.448, first_dense_layers=1,
            dtype=jnp.float32, param_dtype=jnp.float32,
            attention_impl="reference")
    if name == "conv_state":
        return TransformerConfig(
            vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
            head_size=16, d_ff=160, max_seq_len=128, pos_emb="rope",
            rope_base=1e6, activation="swiglu", norm="rmsnorm",
            norm_eps=1e-5, tie_embeddings=True, remat=False, qk_norm=True,
            layer_kinds=("conv", "full", "conv", "conv", "conv"),
            conv_kernel=3, n_experts=8, expert_top_k=2, router="sigmoid",
            moe_d_ff=32, first_dense_layers=1, dtype=jnp.float32,
            param_dtype=jnp.float32, attention_impl="reference")
    # key-value heads, rotary base and sink by the layer's kind: rings of
    # 4 + 8 rows beside full rows of another shape (the rehearsal's model)
    from perfbench import manifest as mf
    rehearsal = os.path.join(mf.ROOT, "perfbench", "testdata", "rehearsal")
    c = mf.Manifest(os.path.join(rehearsal, "BENCHMARK.tiny-mimo.json"),
                    os.path.join(rehearsal, "traffic")).config("tiny-mimo")
    return dataclasses.replace(
        mf.family_of(c).model.model_config(c, "serve",
                                           attention_impl="reference"),
        dtype=jnp.float32, param_dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def _model(name: str):
    cfg = _config(name)
    params, _ = init_params(jax.random.PRNGKey(5), cfg)
    if cfg.n_experts:            # a bias that changes choices
        params["layers"]["router_bias"] = 0.2 * jax.random.normal(
            jax.random.PRNGKey(7), params["layers"]["router_bias"].shape)
    if "conv" in cfg.kinds:      # a state is zeros unless the taps are not
        params["layers"]["conv_w"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(9), params["layers"]["conv_w"].shape)
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (LANES, MAX_LEN), 1, 256), np.int32)
    return cfg, params, toks


def _alone(cfg, params, prompt, off=0, cache=None):
    """One session's walk through the batch-1 chunk program → (logits of
    every program, cache)."""
    cache = cache or init_kv_cache(cfg, 1, MAX_LEN)
    out = []
    while off < prompt.shape[1]:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, params, prompt, off, cache, cfg,
            chunk=CHUNK, capacity=MAX_LEN)
        out.append(np.asarray(logits[0]))
    return out, cache


def _lane(pool, lane):
    return {n: np.asarray(a[:, lane]) for n, a in cache_arrays(pool).items()}


def _assert_lane_is(pool, lane, want, tol=TOL):
    for name, a in cache_arrays(want).items():
        got = _lane(pool, lane)[name]
        assert float(np.abs(got - np.asarray(a[:, 0])).max()) < tol, name


@pytest.mark.parametrize("name", MODELS)
def test_lanes_at_different_positions_give_what_each_session_gets_alone(name):
    """Three sessions and a lane that stands throughout: A (19 tokens) from
    the first program, B (10) joining at the second, C (30: it wraps a ring
    of 12 rows twice) seeded at position 3; each lane leaves when its prompt
    is consumed and stands from then on.  Every program's logits and, at
    the end, every array of every lane are the session's own."""
    cfg, params, toks = _model(name)
    prompts = {0: toks[0:1, :19], 1: toks[1:2, :10], 3: toks[3:4, :30]}
    joins = {0: 0, 1: 1, 3: 0}         # the program a lane first moves in
    offs = {0: 0, 1: 0, 3: 3}
    # C's first 3 positions come from elsewhere (a prefix donor): a batch-1
    # cache inserted into its lane, as the engine seeds one
    seed_logits, seed = _alone(cfg, params, toks[3:4, :3])
    want = {p: _alone(cfg, params, prompts[p], offs[p],
                      seed if p == 3 else None) for p in prompts}
    pool = init_slot_cache(cfg, LANES, MAX_LEN)
    _, seed = _alone(cfg, params, toks[3:4, :3])
    pool = jax.jit(cache_insert_slot)(pool, seed, jnp.int32(3))
    # lane 2 stands: whatever it holds stays, bit for bit
    junk = {n: jax.random.normal(jax.random.PRNGKey(3), a[:, :1].shape)
            for n, a in cache_arrays(pool).items()}
    pool = jax.jit(cache_insert_slot)(
        pool, dict(junk, pos=jnp.int32(5)), jnp.int32(2))
    stood = _lane(pool, 2)
    if "window" in cfg.kinds:
        assert window_ring(cfg, MAX_LEN) == 12
    got = {p: [] for p in prompts}
    off = dict(offs)
    for program in range(9):
        lanes = [(prompts[p], off[p]) if p in prompts
                 and program >= joins[p] and off[p] < prompts[p].shape[1]
                 else None for p in range(LANES)]
        if not any(lanes):
            break
        logits, pool, moved = prefill_lanes_step(
            prefill_lanes_jit, params, lanes, pool, cfg, chunk=CHUNK,
            capacity=MAX_LEN)
        for p, m in enumerate(moved):
            if m is not None:
                assert m[0] == min(off[p] + CHUNK, prompts[p].shape[1])
                off[p] = m[0]
                got[p].append(np.asarray(logits[p]))
    assert program == 7      # C's 27 tokens: seven programs, as alone
    for p in prompts:
        assert len(got[p]) == len(want[p][0])
        for a, b in zip(got[p], want[p][0]):
            assert float(np.abs(a - b).max()) < TOL
        _assert_lane_is(pool, p, want[p][1])
    for n, a in _lane(pool, 2).items():
        assert np.array_equal(a, stood[n]), n


@pytest.mark.parametrize("name", MODELS)
def test_a_standing_lane_keeps_its_arrays_and_its_pos_bit_for_bit(name):
    """The program itself (no host walk): ``n_valid`` 0 leaves a lane's
    every array and its ``pos`` as they were, wherever it stands (at 0, in
    the middle, within a chunk of the end, where a slice is clamped), and a
    moving lane's ``pos`` advances by its real rows."""
    cfg, params, toks = _model(name)
    pool = init_slot_cache(cfg, LANES, MAX_LEN)
    for lane in range(LANES):
        junk = {n: jax.random.normal(jax.random.PRNGKey(lane), a[:, :1].shape)
                for n, a in cache_arrays(pool).items()}
        pool = jax.jit(cache_insert_slot)(pool, dict(junk, pos=jnp.int32(0)),
                                          jnp.int32(lane))
    before = [_lane(pool, lane) for lane in range(LANES)]
    pos = np.asarray([0, 13, MAX_LEN - 2, 9], np.int32)
    n_valid = np.asarray([0, 0, 0, 3], np.int32)
    _, out = prefill_lanes_jit(params, toks[:, :CHUNK],
                               dict(pool, pos=pos), cfg=cfg, n_valid=n_valid)
    assert np.asarray(out["pos"]).tolist() == [0, 13, MAX_LEN - 2, 12]
    for lane in range(3):
        for n, a in _lane(out, lane).items():
            assert np.array_equal(a, before[lane][n]), (lane, n)
    moved = _lane(out, 3)
    assert any(not np.array_equal(a, before[3][n]) for n, a in moved.items())


def test_every_lane_standing_is_a_program_that_changes_nothing():
    """What the engine's warm-up runs: all ``n_valid`` 0."""
    cfg, params, toks = _model("window_ring")
    pool = init_slot_cache(cfg, LANES, MAX_LEN)
    _, out = prefill_lanes_jit(params, np.zeros((LANES, CHUNK), np.int32),
                               pool, cfg=cfg,
                               n_valid=np.zeros(LANES, np.int32))
    assert not np.asarray(out["pos"]).any()
    for a in cache_arrays(out).values():
        assert not np.asarray(a).any()


@pytest.mark.parametrize("name", ["latent_moe", "window_ring", "conv_state"])
def test_padded_rows_and_standing_lanes_touch_no_expert(name):
    """``load`` (experts touched, largest expert load, pairs that landed on
    an expert held here, each summed over the expert layers) of a lanes
    program is that of its lanes' real rows: the pairs add up to the
    sessions' own, and garbage in a padded row or a standing lane moves
    nothing."""
    cfg, params, toks = _model(name)
    one = jax.jit(functools.partial(_prefill_chunk, cfg=cfg))
    n_valid = np.asarray([3, 0, CHUNK, 1], np.int32)
    pairs = 0
    for lane, r in enumerate(n_valid):
        if r:
            _, _, load = one(params, padded_chunk(toks[lane:lane + 1], 0, r,
                                                  CHUNK),
                             init_kv_cache(cfg, 1, MAX_LEN),
                             n_valid=np.int32(r))
            pairs += int(load[2])
    lanes = jax.jit(functools.partial(_prefill_lanes, cfg=cfg))
    buf = np.stack([padded_chunk(toks[lane:lane + 1], 0, r, CHUNK)[0]
                    for lane, r in enumerate(n_valid)])
    _, _, want = lanes(params, buf, init_slot_cache(cfg, LANES, MAX_LEN),
                       n_valid=n_valid)
    assert int(want[2]) == pairs > 0
    for lane, r in enumerate(n_valid):
        buf[lane, r:] = 99
    _, _, got = lanes(params, buf, init_slot_cache(cfg, LANES, MAX_LEN),
                      n_valid=n_valid)
    assert [int(x) for x in got] == [int(x) for x in want]
    _, _, full = lanes(params, buf, init_slot_cache(cfg, LANES, MAX_LEN),
                       n_valid=np.full(LANES, CHUNK, np.int32))
    assert int(full[2]) > int(got[2])     # unmasked, the padding routes too


@pytest.mark.parametrize("name", ["learned_mha", "window_ring"])
def test_a_window_set_back_at_the_capacity_edge_rewrites_what_was_there(name):
    """A lane whose prompt ends within a chunk of ``max_len``: the host
    passes the window's start (`chunk_window`), the overlapped tokens run
    again, and the lane holds what the session alone holds."""
    cfg, params, toks = _model(name)
    prompt = toks[0:1, :MAX_LEN - 1]
    _, seed = _alone(cfg, params, prompt[:, :MAX_LEN - 3])
    want_logits, want = _alone(cfg, params, prompt, MAX_LEN - 3, seed)
    assert chunk_window(MAX_LEN - 3, MAX_LEN - 1, CHUNK, MAX_LEN) == (
        MAX_LEN - CHUNK, 3)
    _, seed = _alone(cfg, params, prompt[:, :MAX_LEN - 3])
    pool = jax.jit(cache_insert_slot)(
        init_slot_cache(cfg, LANES, MAX_LEN), seed, jnp.int32(1))
    logits, pool, moved = prefill_lanes_step(
        prefill_lanes_jit, params,
        [None, (prompt, MAX_LEN - 3), (toks[2:3, :6], 0), None], pool, cfg,
        chunk=CHUNK, capacity=MAX_LEN)
    assert moved == [None, (MAX_LEN - 1, 3), (4, 4), None]
    assert float(np.abs(np.asarray(logits[1]) - want_logits[-1]).max()) < TOL
    _assert_lane_is(pool, 1, want)
    out = jax.jit(cache_gather_slot)(pool, jnp.int32(1),
                                     jnp.int32(MAX_LEN - 1))
    assert int(out["pos"]) == int(want["pos"]) == MAX_LEN - 1


def test_a_state_cannot_be_set_back_in_a_lane_either():
    cfg, params, toks = _model("conv_state")
    with pytest.raises(ValueError, match="cannot be taken back"):
        prefill_lanes_step(
            prefill_lanes_jit, params,
            [(toks[0:1, :MAX_LEN - 1], MAX_LEN - 3), None], None, cfg,
            chunk=CHUNK, capacity=MAX_LEN)


def test_the_lanes_program_is_the_chunk_program_in_a_trace():
    """`perfbench/readers.py` matches ``^jit_prefill_chunk$`` and the compile
    ledger knows a program by ``jit(<name>)``: both names are the chunk
    program's."""
    cfg, params, toks = _model("rope_gqa")
    lowered = prefill_lanes_jit.lower(
        params, toks[:, :CHUNK], init_slot_cache(cfg, LANES, MAX_LEN),
        cfg=cfg, n_valid=np.ones(LANES, np.int32))
    assert lowered.compile().runtime_executable().hlo_modules()[0].name \
        == "jit_prefill_chunk"
    assert prefill_lanes_jit.__name__ == prefill_chunk_jit.__name__ \
        == "prefill_chunk"


# ------------------------------------------------- through the chunk kernel
# (`ops/cache_attention.py` `attend_chunk_blocks`, the Pallas interpreter):
# chunks of 128 rows over arrays of whole 128-row blocks, the shapes the
# chip serves, at toy widths

WIDE = 128
KERNEL_MODELS = ("byte_rings", "gqa_window_full")


def _kernel_config(name: str):
    """→ (cfg, max_len): a model whose every attention layer has a chunk
    kernel at chunks of 128."""
    if name == "byte_rings":    # rings of 256 + 128 rows, 256 summary rows
        from perfbench import manifest as mf
        rehearsal = os.path.join(mf.ROOT, "perfbench", "testdata",
                                 "rehearsal")
        c = mf.Manifest(
            os.path.join(rehearsal, "BENCHMARK.tiny-evabyte.json"),
            os.path.join(rehearsal, "traffic")).config("tiny-evabyte")
        return dataclasses.replace(
            mf.family_of(c).model.model_config(
                c, "serve", attention_impl="reference"),
            dtype=jnp.float32, param_dtype=jnp.float32, sliding_window=256,
            window_chunk=WIDE, max_seq_len=1024), 1024
    # two key-value heads of 16 under four query heads: rings of 128 + 128
    # rows on the window layers, 512 rows on the full one
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_size=16, d_ff=160, max_seq_len=512, pos_emb="rope",
        rope_base=1e4, rope_layers="window", activation="swiglu",
        norm="rmsnorm", norm_eps=1e-5, tie_embeddings=False, remat=False,
        qk_norm=True, layer_kinds=("window", "full", "window"),
        sliding_window=128, window_chunk=WIDE, dtype=jnp.float32,
        param_dtype=jnp.float32, attention_impl="reference"), 512


@pytest.mark.parametrize("name", KERNEL_MODELS)
def test_lanes_through_the_chunk_kernel_give_what_each_session_gets_alone(
        name, monkeypatch):
    """Each session ALONE through XLA's dense form (this process lowers for
    the CPU), then the lanes program and the lone chunk program through the
    kernel: A (300 tokens: two whole chunks and a padded one of 44 real
    rows), B (150) joining at the second program, C from position 130 (a
    seeded prefix) to 500, which wraps both models' rings; lane 2 stands
    throughout and stays bit for bit."""
    from ray_tpu.models import prefill_chunk, prefill_lanes
    from ray_tpu.ops import cache_attention
    cfg, max_len = _kernel_config(name)
    params, _ = init_params(jax.random.PRNGKey(5), cfg)
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (LANES, 512), 1, cfg.vocab_size), np.int32)

    def alone(fn, prompt, off=0, cache=None):
        cache = cache or init_kv_cache(cfg, 1, max_len)
        out = []
        while off < prompt.shape[1]:
            logits, cache, off, _ = prefill_chunk_step(
                fn, params, prompt, off, cache, cfg, chunk=WIDE,
                capacity=max_len)
            out.append(np.asarray(logits[0]))
        return out, cache

    prompts = {0: toks[0:1, :300], 1: toks[1:2, :150], 3: toks[3:4, :500]}
    joins, offs = {0: 0, 1: 1, 3: 0}, {0: 0, 1: 0, 3: 130}
    dense = jax.jit(prefill_chunk, static_argnames=("cfg",))
    _, seed = alone(dense, toks[3:4, :130])
    want = {p: alone(dense, prompts[p], offs[p], seed if p == 3 else None)
            for p in prompts}
    assert "cache_chunk_attention" not in dense.lower(
        params, toks[:1, :WIDE], init_kv_cache(cfg, 1, max_len),
        cfg=cfg, n_valid=np.int32(WIDE)).as_text()

    # a trace is cached by the function: fresh ones under the interpreter
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    calls = []
    real = cache_attention.attend_chunk_blocks
    monkeypatch.setattr(cache_attention, "attend_chunk_blocks",
                        lambda *a: calls.append(a[0].shape) or real(*a))
    lanes_fn = jax.jit(lambda *a, **k: prefill_lanes(*a, **k),
                       static_argnames=("cfg",))
    lone_fn = jax.jit(lambda *a, **k: prefill_chunk(*a, **k),
                      static_argnames=("cfg",))
    # the lone chunk program: A's walk once more, through the kernel
    lone, _ = alone(lone_fn, prompts[0])
    traced = len(calls)     # once a run of layers that the loop scans
    assert traced and calls[0][:2] == (1, WIDE), calls
    for a, b in zip(lone, want[0][0]):
        assert float(np.abs(a - b).max()) < 5e-5

    pool = init_slot_cache(cfg, LANES, max_len)
    pool = jax.jit(cache_insert_slot)(pool, seed, jnp.int32(3))
    junk = {n: jax.random.normal(jax.random.PRNGKey(3), a[:, :1].shape)
            for n, a in cache_arrays(pool).items()}
    pool = jax.jit(cache_insert_slot)(
        pool, dict(junk, pos=jnp.int32(5)), jnp.int32(2))
    stood = _lane(pool, 2)
    got, off = {p: [] for p in prompts}, dict(offs)
    for program in range(9):
        lanes = [(prompts[p], off[p]) if p in prompts
                 and program >= joins[p] and off[p] < prompts[p].shape[1]
                 else None for p in range(LANES)]
        if not any(lanes):
            break
        logits, pool, moved = prefill_lanes_step(
            lanes_fn, params, lanes, pool, cfg, chunk=WIDE,
            capacity=max_len)
        for p, m in enumerate(moved):
            if m is not None:
                off[p] = m[0]
                got[p].append(np.asarray(logits[p]))
    assert program == 3 and len(calls) == 2 * traced
    assert calls[-1][:2] == (LANES, WIDE)
    for p in prompts:
        assert len(got[p]) == len(want[p][0])
        for a, b in zip(got[p], want[p][0]):
            assert float(np.abs(a - b).max()) < 5e-5, p
        _assert_lane_is(pool, p, want[p][1], 5e-5)
    for n, a in _lane(pool, 2).items():
        assert np.array_equal(a, stood[n]), n
