"""Window layers over a LATENT cache (`models/transformer.py`
`TransformerConfig.latent_of`, `models/generate.py`: the ring of latents
among the cache's state kinds): a model in which one stack holds TWO LATENT
SHAPES, full layers under an indexer of their own beside sliding layers of
another head count, rank and rotary base, a gate a head and a fixed rescale
on both latents.

The pattern's weights, counts and cache arrays; every cached program
(whole-prompt prefill, chunks that straddle the ring's seam, lanes with a
lane that stands, slots at depths of their own) and the engine against the
FAMILY's plain reference, with contexts that pass BOTH the window and
``index_topk``; the latent cache kernel over a ring masked by the position a
column holds, through the interpreter; the host's counts of the rows a step
and a chunk move; three planted faults that each FAIL; the existing latent
models' lowered text, unchanged; and what is still refused.

The model is the rehearsal's ``tiny-dots3-note`` in float32 (6 layers: full,
full, sliding, sliding, full, sliding; full: 4 heads over a latent of 48, an
indexer of 2 heads of 16 that keeps 24; sliding: 2 heads over a latent of 32,
window 13, a ring of 256 rows).  The served path in bfloat16 against the
reference is tests/benchmark/test_perfbench_family_dots3_note.py's.
"""

import dataclasses
import functools
import hashlib
import importlib
import json
import os
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse
from ray_tpu.models import (CacheTraffic, TransformerConfig,
                            cache_gather_slot, cache_insert_slot,
                            decode_step_slots, forward, init_kv_cache,
                            init_params, init_slot_cache, prefill,
                            prefill_chunk_jit, prefill_lanes_jit)
from ray_tpu.models.generate import (_check_decodable, _state_kind,
                                     cache_bytes, cache_rows, position_bytes,
                                     prefill_chunk_step, prefill_lanes,
                                     prefill_lanes_step, window_ring)
from ray_tpu.models.transformer import (count_params, decode_flops_per_token,
                                        flops_per_token, stack_kinds)
from ray_tpu.ops import latent_attention as mla
from ray_tpu.ops import sparse_index
from ray_tpu.serve.decode_session import ContinuousBatchingEngine
from ray_tpu.util import device_profile

generate = importlib.import_module("ray_tpu.models.generate")

T, MAX_LEN, CHUNK, TOPK, WINDOW, RING = 300, 384, 32, 24, 13, 256
TOL = dict(atol=3e-4, rtol=0)


def _config(name):
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def world():
    c = _config("tiny-dots3-note")
    model = mf.family_of(c).model
    cfg = dataclasses.replace(model.model_config(c, "serve"),
                              dtype=jnp.float32, param_dtype=jnp.float32,
                              remat=False)
    params = jax.jit(lambda k: model.make(k, c, jnp.float32))(
        jax.random.PRNGKey(7))
    toks = model.tokens(jax.random.PRNGKey(8), (2, T), c)
    # the FAMILY's plain reference: what every program below is held to
    ref = jax.jit(lambda p, t: model.logits(p, t, c))
    return types.SimpleNamespace(
        c=c, model=model, cfg=cfg, params=params, toks=toks, ref=ref,
        want=np.asarray(ref(params, toks)),
        step=jax.jit(functools.partial(decode_step_slots, cfg=cfg)))


def _chunked(w, row, n, cache, chunk=CHUNK, params=None, cfg=None):
    off, host = 0, np.asarray(w.toks[row:row + 1, :n])
    while off < n:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, params or w.params, host, off, cache,
            cfg or w.cfg, chunk=chunk, capacity=MAX_LEN)
    return logits, cache


# ------------------------------------------------- pattern, counts, cache

def test_pattern_weights_and_counts(world):
    cfg, params = world.cfg, world.params
    assert cfg.kinds == ("index", "index", "window", "window", "index",
                         "window") and cfg.window_latent
    assert cfg.layer_segments == (
        ("dense_layers", 0, 1, "index"), ("layers", 0, 1, "index"),
        ("layers", 1, 2, "window"), ("layers", 3, 1, "index"),
        ("layers", 4, 1, "window"))
    win = cfg.latent_of("window")
    assert (win.n_heads, win.q_lora_rank, win.kv_lora_rank,
            win.qk_nope_head_dim) == (2, 32, 32, 20)
    assert cfg.latent_of("index") is cfg
    assert cfg.rope_base_of("window") == 500 and cfg.rope_base == 10000
    assert cfg.latent_scales("index") == (np.sqrt(64 / 48),) * 2
    assert cfg.latent_scales("window") == (np.sqrt(2.0),) * 2
    lay = params["layers"]
    # the full layers' latent weights and gate over the 2 full layers of
    # the run, the sliding layers' eight stacks over its 3, at their sizes
    assert lay["wq_b"].shape == (2, 48, 4, 20) and lay["wg"].shape == \
        (2, 64, 4) and lay["wi_q"].shape[0] == 2
    assert {k: v.shape for k, v in lay.items() if k.endswith("_win")} == {
        "wq_a_win": (3, 64, 32), "wq_b_win": (3, 32, 2, 28),
        "q_norm_win": (3, 32), "wkv_a_win": (3, 64, 40),
        "wkv_b_win": (3, 32, 2, 36), "kv_norm_win": (3, 32),
        "wo_win": (3, 2, 16, 64), "wg_win": (3, 64, 2)}
    assert not any(k.endswith("_win") for k in params["dense_layers"])
    assert stack_kinds(cfg, "wq_a") == ("full", "index")
    assert stack_kinds(cfg, "wq_a_win") == stack_kinds(cfg, "wg_win") == \
        ("window",)
    made = jax.eval_shape(lambda k: init_params(k, cfg)[0],
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == \
        jax.tree_util.tree_map(jnp.shape, params)
    assert count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params)) \
        == mf.family_of(world.c).shapes.count_params(world.c)
    # a step at depth t: a full layer's 4 heads meet its chosen latents (48
    # + 8, and the 48 again), a sliding layer's 2 its window's (32 + 8, 32),
    # an indexer every key
    full, ring, index = 4 * (2 * 48 + 8), 2 * (2 * 32 + 8), 2 * 16
    assert decode_flops_per_token(cfg, 200) - decode_flops_per_token(
        cfg, 100) == 2 * 3 * index * 100
    assert decode_flops_per_token(cfg, 6) - decode_flops_per_token(
        cfg, 5) == 2 * 3 * (full + index) + 2 * 3 * ring
    assert decode_flops_per_token(cfg, 12) - decode_flops_per_token(
        cfg, 11) == 2 * 3 * (full + index) + 2 * 3 * ring
    assert decode_flops_per_token(cfg, 15) - decode_flops_per_token(
        cfg, 14) == 2 * 3 * (full + index)
    assert decode_flops_per_token(cfg, 25) - decode_flops_per_token(
        cfg, 24) == 2 * 3 * index
    assert flops_per_token(cfg, 64) > 0


def test_a_cache_holds_three_arrays_of_three_row_widths(world):
    cfg = world.cfg
    assert cache_rows(cfg) == {"kv": (1, 56), "kv_win": (1, 40),
                               "k_idx": (1, 16)}
    assert [_state_kind(n) for n in cache_rows(cfg)] == ["full", "ring",
                                                         "index"]
    assert position_bytes(cfg) == {"full": 224, "ring": 160, "state": 0,
                                   "index": 64}
    # a window that is no multiple of anything: whole blocks of 128 rows
    assert window_ring(cfg, MAX_LEN) == RING == 2 * 128
    assert window_ring(cfg, 100) == 100
    assert window_ring(dataclasses.replace(cfg, sliding_window=513),
                       17408) == 768
    # (an MHA/GQA model's ring is the window and the chunk, as it was)
    assert window_ring(TransformerConfig.tiny(
        sliding_window=13, layer_kinds=("window", "full")), 1024) == 141
    cache = init_slot_cache(cfg, 3, MAX_LEN)
    assert {n: a.shape for n, a in cache.items()} == {
        "kv": (3, 3, 1, 56, MAX_LEN), "kv_win": (3, 3, 1, 40, RING),
        "k_idx": (3, 3, 1, 16, MAX_LEN), "pos": (3,)}
    assert cache_bytes(cache) == {
        "full": 3 * 3 * 56 * MAX_LEN * 4, "ring": 3 * 3 * 40 * RING * 4,
        "state": 0, "index": 3 * 3 * 16 * MAX_LEN * 4}


# ------------------------------------------- programs against the reference

def test_plain_and_whole_prompt_forms_agree_with_the_reference(world):
    w = world
    got = jax.jit(functools.partial(forward, cfg=w.cfg))(w.params, w.toks)
    np.testing.assert_allclose(got, w.want, **TOL)
    # a prompt longer than the ring: its last 256 positions, each at its
    # position mod ring, then steps past the seam
    logits, cache = jax.jit(functools.partial(prefill, cfg=w.cfg))(
        w.params, w.toks[:, :280], cache=init_kv_cache(w.cfg, 2, MAX_LEN))
    np.testing.assert_allclose(logits, w.want[:, 279], **TOL)
    slots = dict(cache, pos=jnp.full((2,), 280, jnp.int32))
    for t in range(280, 288):
        logits, slots = w.step(w.params, w.toks[:, t], slots,
                               jnp.ones((2,), bool))
        np.testing.assert_allclose(logits, w.want[:, t], **TOL)


@pytest.mark.parametrize("chunk", [32, 48])
def test_chunks_that_straddle_the_rings_seam(world, chunk):
    """Chunk programs from an empty cache to position 293: every chunk
    passes ``index_topk`` from its first on and the window from its second;
    the chunk from 224 (of 32: .. 255, then 256 ..) or 240 (of 48) writes
    across column 255 | 0 of the ring in two pieces, and the last is a
    padded remainder."""
    w = world
    cache = init_kv_cache(w.cfg, 1, MAX_LEN)
    off, host = 0, np.asarray(w.toks[0:1, :293])
    while off < 293:
        logits, cache, off, n_valid = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=chunk, capacity=MAX_LEN)
        np.testing.assert_allclose(logits[0], w.want[0, off - 1], **TOL)
    assert int(cache["pos"]) == 293 and n_valid < chunk


def test_lanes_with_a_lane_that_stands(world):
    w = world
    cache = init_slot_cache(w.cfg, 3, MAX_LEN)
    prompts = [(np.asarray(w.toks[0:1, :290]), 0), None,
               (np.asarray(w.toks[1:2, :75]), 0)]
    logits = {}
    while any(p is not None for p in prompts):
        lg, cache, moved = prefill_lanes_step(
            prefill_lanes_jit, w.params, prompts, cache, w.cfg, chunk=CHUNK,
            capacity=MAX_LEN)
        for p, m in enumerate(moved):
            if m is not None:
                logits[p] = np.asarray(lg[p])
                prompts[p] = (prompts[p][0], m[0]) \
                    if m[0] < prompts[p][0].shape[1] else None
    np.testing.assert_allclose(logits[0], w.want[0, 289], **TOL)
    np.testing.assert_allclose(logits[2], w.want[1, 74], **TOL)
    for name in ("kv", "kv_win", "k_idx"):
        assert not np.asarray(cache[name][:, 1]).any(), name


def test_slots_at_depths_of_their_own_and_one_that_stands(world):
    """Three slots filled by chunked prefills to 250 (six steps short of the
    seam), 40 and 5 (inside the window AND inside ``index_topk``), the
    second standing: the live slots' logits are the reference's, the
    standing slot's arrays stay bit for bit."""
    w = world
    slots = init_slot_cache(w.cfg, 3, MAX_LEN)
    for row, (src, n) in enumerate(((0, 250), (1, 40), (1, 5))):
        _, one = _chunked(w, src, n, init_kv_cache(w.cfg, 1, MAX_LEN))
        slots = cache_insert_slot(slots, one, jnp.int32(row))
    before = {n: np.asarray(a[:, 1]) for n, a in slots.items() if n != "pos"}
    for j in range(10):
        tok = jnp.stack([w.toks[0, 250 + j], jnp.int32(3), w.toks[1, 5 + j]])
        logits, slots = w.step(w.params, tok, slots,
                               jnp.asarray([True, False, True]))
        np.testing.assert_allclose(logits[0], w.want[0, 250 + j], **TOL)
        np.testing.assert_allclose(logits[2], w.want[1, 5 + j], **TOL)
    assert np.asarray(slots["pos"]).tolist() == [260, 40, 15]
    for name, a in before.items():
        got = np.asarray(slots[name][:, 1])
        # (its one token's column lands AHEAD of its pos, where no query
        # of its own or of a prefix's looks)
        col = 40 % a.shape[-1]
        np.testing.assert_array_equal(np.delete(got, col, axis=-1),
                                      np.delete(a, col, axis=-1))


def test_insert_and_gather_carry_all_three_arrays(world):
    w = world
    _, one = _chunked(w, 0, 40, init_kv_cache(w.cfg, 1, MAX_LEN))
    slots = cache_insert_slot(init_slot_cache(w.cfg, 2, MAX_LEN), one,
                              jnp.int32(1))
    back = cache_gather_slot(slots, jnp.int32(1), jnp.int32(40))
    for name in ("kv", "kv_win", "k_idx"):
        np.testing.assert_array_equal(back[name], one[name])
    # the copy goes on as the session would have
    logits, _ = prefill_chunk_jit(w.params, w.toks[0:1, 40:40 + CHUNK], back,
                                  cfg=w.cfg)
    np.testing.assert_allclose(logits[0], w.want[0, 40 + CHUNK - 1], **TOL)


# ----------------------------------------------- the kernel over a ring

def _ring_case(pos, c, ring, window=70):
    """A ring mask for ``c`` tokens from each lane's ``pos``."""
    return np.asarray(generate._ring_mask(jnp.asarray(pos), c, ring, window))


@pytest.mark.parametrize("c,pos", [
    (16, (450, 5, 378)),     # past the seam | inside the window | astride
    (1, (383, 384, 1000)),   # a step: the seam's two sides, far past it
])
def test_the_kernel_attends_a_ring_by_the_position_a_column_holds(
        monkeypatch, c, pos):
    """`attend_cache` (through the interpreter) over a ring of latents of
    another row width than the full layers' (a latent of 256 beside a rotary
    key of 16), under `_ring_mask`: a window that wraps the seam is columns
    at both ends of the ring, and each lane equals `attend_latents` over all
    rows at once; a lane that stands gives zeros."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    layers, lanes, h, kv_lora, rope, ring = 2, 3, 16, 256, 16, RING_K
    r, block = kv_lora + rope, sparse_index.key_block(ring)
    assert block == 128 and mla.kernel_shape((lanes, c, h, r), kv_lora,
                                             block)
    rng = np.random.default_rng(c)
    q = jnp.asarray(rng.standard_normal((lanes, c, h, r)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((layers, lanes, 1, r, ring)),
                     jnp.float32)
    mask = _ring_case(pos, c, ring)
    assert mask.shape == (lanes, c, ring)
    # the lane astride the seam sees columns at BOTH ends of the ring
    wraps = mask.any(1)
    assert any(row[0] and row[-1] and not row.all() for row in wraps)
    live = jnp.asarray([True, False, True] if c > 1 else [True] * 3)
    got = jax.jit(lambda q, kv, m, live: mla.attend_cache(
        jnp.swapaxes(q, 1, 2), kv, 1, m, live, 4.0, kv_lora, block))(
        q, kv, jnp.asarray(mask), live)
    got = np.asarray(jnp.swapaxes(got, 1, 2))
    want = np.asarray(mla.attend_latents(q, kv[1, :, 0], jnp.asarray(mask),
                                         4.0))[..., :kv_lora]
    for p in range(lanes):
        if live[p]:
            np.testing.assert_allclose(got[p], want[p], atol=2e-5)
        else:
            assert not got[p].any()


def test_the_hosts_counts_of_ring_rows_are_the_masks_own(world, monkeypatch):
    """The last column `CacheTraffic` counts a window layer's kernel by
    (`_ring_end` of what `_seen` says a step's or a chunk's queries see) is
    `sparse_index.rows_seen` of the mask the program builds, for steps and
    chunks, before, at and past the seam; and with the kernel engaged the
    counters add the two kinds' tiles."""
    cfg = dataclasses.replace(world.cfg, sliding_window=70)
    traffic = CacheTraffic(jax.eval_shape(
        lambda: init_slot_cache(cfg, 2, MAX_LEN)), cfg, CHUNK)
    ring = traffic._sets["kv_win"]
    assert ring.size == RING
    for pos in (0, 5, 69, 70, 200, 255, 256, 300, 320, 511, 512, 700):
        for n in (1, 32):
            mask = generate._ring_mask(jnp.asarray(pos), n, RING, 70)
            (first,), (rows,) = traffic._seen(ring, (pos,), n)
            assert int(sparse_index.rows_seen(mask)) == generate._ring_end(
                first, rows, RING), (pos, n)
    assert traffic._seen(traffic._sets["kv"], (41,), 32) == ([0], [73])
    # latents of whole lanes and a ring of three blocks (a ring of ONE
    # block of the kernel's is read whole by XLA's forms), so that the
    # kernel takes both kinds' arrays
    cfg = _kernel_shapes(world.cfg)
    cache = init_slot_cache(cfg, 2, MAX_LEN_K)
    assert cache["kv_win"].shape[-1] == RING_K
    dense = CacheTraffic(cache, cfg, CHUNK)
    assert dense.step([7, 300]).rows_fetched == 2 * (3 * MAX_LEN_K
                                                     + 3 * RING_K)
    assert dense.chunk(300, 20) == (3 * MAX_LEN_K + 3 * RING_K,
                                    3 * 320 + 3 * (20 + WINDOW - 1))
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    engaged = CacheTraffic(cache, cfg, CHUNK)
    step = lambda positions: engaged.step(positions).rows_fetched
    chunk = engaged.chunk
    # a slot at 7 moves a tile of each array a layer; one at 300 three
    # tiles of 128 of each; at 390 its window lies astride the ring's seam
    # (378 .. 390: the whole ring), at 400 past it (the first tile)
    assert step([7]) == 3 * 128 + 3 * 128
    assert step([300]) == 3 * 384 + 3 * 384
    assert step([390]) == 3 * 512 + 3 * RING_K
    assert step([400]) == 3 * 512 + 3 * 128
    assert step([7, 400]) == step([7]) + step([400])
    assert chunk(368, 20) == (3 * 512 + 3 * RING_K, 3 * 388 + 3 * 32)


MAX_LEN_K, RING_K = 640, 384


def _kernel_shapes(cfg):
    """``cfg`` at shapes `attend_cache` takes for both kinds of layer."""
    cfg = dataclasses.replace(cfg, kv_lora_rank=128, window_kv_lora_rank=256,
                              window_chunk=256)
    assert window_ring(cfg, MAX_LEN_K) == RING_K
    return cfg


def test_programs_with_the_kernel_are_the_programs_with_the_loop(
        world, monkeypatch):
    """The DECODE STEP and the lanes program at shapes the kernel takes for
    BOTH kinds of layer (latents of 128 and 256): the kernel through the
    interpreter against XLA's forms, logits and every array of the cache,
    with a slot past the ring's seam."""
    w = world
    cfg = _kernel_shapes(w.cfg)
    params = jax.jit(lambda k: init_params(k, cfg)[0])(jax.random.PRNGKey(5))
    calls, kernel = [], mla.attend_cache
    monkeypatch.setattr(mla, "attend_cache", lambda *a: calls.append(
        (a[0].shape, a[1].shape)) or kernel(*a))
    long = jnp.concatenate([w.toks[0], w.toks[1]])[None]     # 600 tokens
    # two slots that stand at 380 and 9 over rows of any values: both walks
    # read the same cache, so no prefill has to have written it
    rng = np.random.default_rng(3)
    start = dict({n: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
                  for n, a in init_slot_cache(cfg, 2, MAX_LEN_K).items()},
                 pos=jnp.asarray([380, 9], jnp.int32))

    def walk(interpret):
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", interpret)
        fn = jax.jit(generate.decode_step_slots, static_argnames=("cfg",))
        slots, logits = start, []
        lg, slots = fn(params, jnp.stack([long[0, 380], long[0, 9]]), slots,
                       jnp.asarray([True, True]), cfg=cfg)
        logits.append(lg)
        # a chunk of 16 from 381: across column 383 | 0 of the ring
        lanes = jax.jit(generate._lanes_program, static_argnames=("cfg",))
        lg, cache = lanes(params, jnp.stack([long[0, 381:397],
                                             long[0, 10:26]]),
                          dict(slots, pos=jnp.asarray([381, 10], jnp.int32)),
                          cfg=cfg, n_valid=jnp.asarray([16, 0], jnp.int32))
        return logits + [lg[:1]], cache

    calls.clear()
    (want, cache_w), (got, cache_g) = walk("0"), walk("1")
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, **TOL)
    for name in cache_w:
        np.testing.assert_allclose(cache_g[name], cache_w[name], **TOL)
    # both arrays went through the kernel: the full layers' and the ring
    assert {kv[-2:] for _, kv in calls} == {(128 + 8, MAX_LEN_K),
                                            (256 + 8, RING_K)}


def test_the_window_latent_scope_stands_around_parts_of_the_model(world):
    cache = init_slot_cache(world.cfg, 2, MAX_LEN)
    text = world.step.lower(world.params, jnp.zeros((2,), jnp.int32), cache,
                            jnp.ones((2,), bool)).compile().as_text()
    paths = list(device_profile.op_map(text)["instructions"].values())
    inside = [p for p in paths if "window_latent" in p.split("/")]
    assert inside and len(inside) < len(paths)
    parts = {p.split("/")[-2] for p in inside} | {
        part for p in inside for part in p.split("/")}
    assert {"projections", "attention", "cache_write"} <= parts
    # ten parts, and the scope is none of them
    assert len(device_profile.MODEL_PARTS) == 10 \
        and "window_latent" not in device_profile.MODEL_PARTS


# ------------------------------------------------------- through the engine

def _stream(core, prompt, n, out=None, key=None):
    r = core.handle({"op": "start", "prompt": prompt})
    assert "error" not in r, r
    toks = list(r["token"])
    while len(toks) < n:
        more = core.handle({"op": "next_chunk", "sid": r["sid"],
                            "max_tokens": n - len(toks)})
        assert "error" not in more, more
        toks += more["tokens"]
        if more.get("done"):
            break
    core.handle({"op": "end", "sid": r["sid"]})
    if out is not None:
        out[key] = toks[:n]
    return toks[:n]


def _forced(w, prompt, stream):
    """The REFERENCE's own choice at every generated position of ``prompt +
    stream`` (padded to the shape its program was compiled for: a causal
    model's logits do not look ahead)."""
    seq = prompt + stream[:-1]
    padded = jnp.zeros_like(w.toks).at[0, :len(seq)].set(
        jnp.asarray(seq, jnp.int32))
    logits = np.asarray(w.ref(w.params, padded))[0, :len(seq)]
    return logits[len(prompt) - 1:].argmax(-1).tolist()


def _core(w, **engine):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    return DecodeSessionCore(
        w.cfg, max_len=MAX_LEN, params=w.params,
        engine=DecodeEngineConfig(prefill_chunk_tokens=CHUNK, **engine))


def test_engine_serves_the_references_tokens(world, monkeypatch):
    """Three sessions at once through chunk programs, the lanes program and
    the fused slot step, one of them across the ring's seam (250 in, 12
    out): every token is the reference's choice at its position; the engine
    counts a full layer's chosen rows and a window layer's ring rows apart,
    each at its own row width."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    w = world
    core = _core(w, max_slots=3)
    try:
        prompts = [np.asarray(w.toks[i % 2, a:a + n]).tolist()
                   for i, (a, n) in enumerate(((0, 250), (3, 33), (11, 57)))]
        got = {}
        threads = [threading.Thread(target=_stream,
                                    args=(core, p, 12, got, i))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        for i, p in enumerate(prompts):
            assert got[i] == _forced(w, p, got[i]), i
        st = core.engine.stats()
        assert st["cache_copies"] == 0
        assert st["prefill_programs"] < st["prefill_chunks"]    # lanes ran
        cache = st["cache"]
        assert cache["bytes_ring"] == 3 * 3 * 40 * RING * 4
        assert cache["bytes_full"] == 3 * 3 * 56 * MAX_LEN * 4
        assert cache["bytes_index"] == 3 * 3 * 16 * MAX_LEN * 4
        # every step's live slots stood past index_topk AND the window: 24
        # chosen rows on 3 layers, 13 ring rows on 3
        assert cache["rows_read"] == 3 * (TOPK + WINDOW) * st["tokens"]
        assert cache["ring_latent_bytes_read"] == \
            3 * WINDOW * 160 * st["tokens"]
        assert cache["index_rows_read"] * 2 == cache["rows_if_full"]
        assert cache["bytes_read"] == 3 * TOPK * 224 * st["tokens"] \
            + cache["ring_latent_bytes_read"] + cache["index_bytes_read"]
        assert cache["bytes_if_uniform"] == cache["rows_if_full"] * 224
        span = [e for e in tracing.span_events()
                if e["name"] == "cache:rows"][-1]["args"]
        assert span["bytes_ring"] == cache["bytes_ring"]
        assert 0 < span["ring_latent_bytes_read"] < span["bytes_read"]
    finally:
        core.engine.shutdown()


def test_a_shared_prefix_from_a_donor_inside_and_past_its_window(world):
    """Prefix reuse over three arrays: a donor that still stands within the
    window serves any prefix (its ring holds every position), one that has
    decoded on past it is refused (its ring has moved on) and the prompt
    prefills from its start; either way the tokens are the reference's."""
    w = world
    # (a queue of 2: the donor stands where its CALLER left it, at most 10 +
    # the token drained + the two queued, whatever the engine thread's lead)
    core = _core(w, max_slots=2, prefix_cache=True,
                 prefix_cache_min_tokens=4, token_queue_depth=2)
    try:
        short = np.asarray(w.toks[0, :10]).tolist()
        got = _stream(core, short, 2)           # stands at 11-13: inside 13
        assert got == _forced(w, short, got)
        hits = core.engine.stats()["prefix"]["applied_hits"]
        fork = short[:8] + np.asarray(w.toks[1, 30:50]).tolist()
        got = _stream(core, fork, 6)
        assert core.engine.stats()["prefix"]["applied_hits"] == hits + 1
        assert got == _forced(w, fork, got)
        long = np.asarray(w.toks[1, :40]).tolist()
        got = _stream(core, long, 4)            # stands at 43: past it
        assert got == _forced(w, long, got)
        hits = core.engine.stats()["prefix"]["applied_hits"]
        fork = long[:30] + np.asarray(w.toks[0, 100:110]).tolist()
        got = _stream(core, fork, 4)
        assert core.engine.stats()["prefix"]["applied_hits"] == hits
        assert got == _forced(w, fork, got)
        assert core.engine.stats()["cache_copies"] == 0
    finally:
        core.engine.shutdown()


# --------------------------------------------- planted faults, and refusals

_FAULTS = {
    # (i) the mask's window edge dropped: the whole ring is attended
    "the window layers attend the whole ring": lambda mp: mp.setattr(
        generate, "_ring_mask", functools.partial(
            _ring_mask_without_its_edge, generate._ring_mask)),
    # (ii) no gate on any head
    "the head gate left out": lambda mp: mp.setattr(
        generate, "head_gate", lambda cfg, y, lp: None),
    # (iii) the key-value latent of the sliding layers as its norm left it
    "the sliding layers' latent not rescaled": lambda mp: mp.setattr(
        TransformerConfig, "latent_scales", _scales_without_the_rings),
}


def _ring_mask_without_its_edge(ring_mask, pos, c, ring, window,
                                block=False):
    return ring_mask(pos, c, ring, 10 ** 6, block)


_latent_scales = TransformerConfig.latent_scales


def _scales_without_the_rings(self, kind):
    a_q, a_kv = _latent_scales(self, kind)
    return (a_q, 1.0) if kind == "window" else (a_q, a_kv)


@pytest.mark.parametrize("fault", list(_FAULTS))
def test_three_planted_faults_each_fail(world, monkeypatch, fault):
    """Each fault in what this kind of layer ADDS, planted where the cached
    programs are traced: chunk programs to position 96 (past the window from
    the second chunk on) read far from the reference, by a tenth of its
    logits' spread and more; the sound programs read within `TOL`."""
    w = world
    _FAULTS[fault](monkeypatch)
    # (a function of its own: a program traced before the fault is not
    # found again under it)
    fn = jax.jit(lambda p, t, cache: generate.prefill_chunk(p, t, cache,
                                                            w.cfg))
    cache, worst = init_kv_cache(w.cfg, 1, MAX_LEN), 0.0
    for off in range(0, 96, CHUNK):
        logits, cache = fn(w.params, w.toks[0:1, off:off + CHUNK], cache)
        worst = max(worst, float(np.abs(
            np.asarray(logits[0]) - w.want[0, off + CHUNK - 1]).max()))
    assert worst > 0.1 * w.want.std(), (fault, worst)


def test_defaults_cost_no_instruction():
    """A latent model that states none of the new fields lowers to the text
    it lowered to: a `glm_moe_dsa` and a `kimi_linear` tiny preset (the plain
    forward, the slot step, the lanes program) hash as they did on the
    commit before the fields existed."""
    before = {
        "tiny-glm-moe-dsa": ["f819fe2f467579d7", "7fbc3b0adc68eb4a",
                             "06f9d2ce56e28d92"],
        "tiny-kimi-linear": ["0efa963107a600b1", "e8958c0856d0218c",
                             "c3be4f12e0b2f2f3"]}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    for name, want in before.items():
        c = _config(name)
        model = mf.family_of(c).model
        cfg = dataclasses.replace(model.model_config(c, "serve"),
                                  remat=False)
        params = jax.eval_shape(lambda k: model.make(k, c, jnp.bfloat16),
                                jax.random.PRNGKey(0))
        cache = jax.eval_shape(functools.partial(init_slot_cache, cfg, 3,
                                                 64))
        texts = [
            jax.jit(functools.partial(forward, cfg=cfg)).lower(
                params, i32(2, 40)).as_text(),
            jax.jit(functools.partial(decode_step_slots, cfg=cfg)).lower(
                params, i32(3), cache,
                jax.ShapeDtypeStruct((3,), jnp.bool_)).as_text(),
            jax.jit(lambda p, t, ch, n: prefill_lanes(p, t, ch, cfg, n)
                    ).lower(params, i32(3, 16), cache, i32(3)).as_text()]
        assert [hashlib.sha256(t.encode()).hexdigest()[:16]
                for t in texts] == want, name


def test_what_is_still_refused_says_so(world):
    cfg, toks = world.cfg, world.toks[:1, :8]
    for bad, match in (
            # a window layer FIRST with "shared" behind it
            (dict(layer_kinds=("window", "shared", "index", "window",
                               "index", "window")), "behind 'window'"),
            (dict(layer_kinds=("shared", "index", "window", "window",
                               "index", "window")),
             "first layer is 'shared'"),
            (dict(layer_kinds=("index", "full", "window", "window", "index",
                               "window")), "indexer"),
            (dict(index_topk=0), "indexer")):
        broken = dataclasses.replace(cfg, **bad)
        with pytest.raises(ValueError, match=match):
            init_params(jax.random.PRNGKey(0), broken)
        with pytest.raises(ValueError, match=match):
            _check_decodable(broken)
        with pytest.raises(ValueError, match=match):
            forward(world.params, toks, broken)
    # a window layer AMONG index layers, a shared one behind it: served
    _check_decodable(dataclasses.replace(cfg, layer_kinds=(
        "index", "window", "shared", "window", "index", "window")))
    for bad, error, match in (
            (dict(sliding_window=0), ValueError, "sliding_window"),
            (dict(sink_kinds=("window",)), NotImplementedError,
             "latent attention has"),
            (dict(layer_kinds=("window",) * 6, index_topk=0, index_heads=0,
                  index_head_dim=0), NotImplementedError,
             "without a full-attention layer"),
            (dict(pos_emb="learned"), NotImplementedError, "learned")):
        with pytest.raises(error, match=match):
            _check_decodable(dataclasses.replace(cfg, **bad))
    # a chunk wider than the ring leaves room for is refused, not answered
    with pytest.raises(ValueError, match="window_chunk"):
        prefill_chunk_jit(world.params, world.toks[:1, :160],
                          init_kv_cache(cfg, 1, MAX_LEN), cfg=cfg)
