"""A model with NO full layer: every layer attends its own block-aligned
window exactly and every earlier window through chunk summaries (EVA;
`ops/eva_attention.py`), over a cache whose fourth state kind is summary
rows (`models/generate.py`).

The program against the family's plain reference
(`perfbench/families/evabyte/model.py`: float32, no cache, none of the
program's code) on seeded random weights at a tiny size (window 32, chunk 4,
chunk programs of 8): `forward`; whole-prompt `prefill`; chunked prefill whose
chunks straddle a window's edge and whose prompt ends mid-chunk; the lanes
program with a lane that stands; slot decode through three windows beside a
slot that is not live; slot insert and gather; `prefix_holds`' cases.  The two limits of the equations
against `ops/attention.py`'s plain attention.  And every configuration the
benchmark already had: its `cache_rows` and its `CacheTraffic.step` as they
were.
"""

import dataclasses
import functools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse
from ray_tpu.models import (CacheTraffic, cache_gather_slot,
                            cache_insert_slot, decode_step_slots, forward,
                            init_kv_cache, init_slot_cache, prefill,
                            prefill_chunk_jit, prefill_lanes_jit,
                            prefix_holds)
from ray_tpu.models.generate import (_state_kind, cache_bytes,
                                     cache_capacity, cache_rows,
                                     greedy_tokens, position_bytes,
                                     prefill_chunk_step,
                                     prefill_lanes_step, window_ring)
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.eva_attention import eva_attention

T, MAX_LEN, CHUNK = 100, 128, 8
TOL = dict(atol=3e-4, rtol=0)


@pytest.fixture(scope="module")
def world():
    """The tiny configuration in float32, weights from the family's `make`,
    two token sequences and the reference's logits for both."""
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           "tiny-evabyte.json")) as f:
        c = json.load(f)
    model = mf.family_of(c).model
    cfg = dataclasses.replace(model.model_config(c, "serve"),
                              dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.make(jax.random.PRNGKey(7), c, jnp.float32)
    toks = model.tokens(jax.random.PRNGKey(8), (2, T), c)
    want = model.logits(params, toks, c)
    return types.SimpleNamespace(
        c=c, cfg=cfg, params=params, toks=toks, want=np.asarray(want),
        step=jax.jit(functools.partial(decode_step_slots, cfg=cfg)))


def _chunked(w, row: int, n: int, cache, off: int = 0):
    """Tokens ``off .. n - 1`` of sequence ``row`` through the chunk program
    from ``cache`` (which holds the ``off`` before) → (logits, cache)."""
    host = np.asarray(w.toks[row:row + 1, :n])
    logits = None
    while off < n:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=CHUNK, capacity=MAX_LEN)
    return logits, cache


def test_a_cache_with_no_full_layer(world):
    cfg = world.cfg
    assert set(cfg.kinds) == {"eva"} and cfg.pred_heads == 3
    assert cache_rows(cfg) == {n: (4, 16) for n in
                               ("k_win", "v_win", "k_sum", "v_sum")}
    cache = init_slot_cache(cfg, 3, MAX_LEN)
    assert cache["k_win"].shape == (2, 3, 4, 16, 32 + 8) \
        and cache["v_sum"].shape == (2, 3, 4, 16, MAX_LEN // 4)
    assert window_ring(cfg, MAX_LEN) == 40
    assert cache_capacity(cache, cfg) == MAX_LEN
    assert [_state_kind(n) for n in ("k", "k_win", "conv_state", "k_sum")] \
        == ["full", "ring", "state", "summary"]
    row = 4 * 16 * 2 * 4          # heads x width x (key, value) x float32
    assert position_bytes(cfg) == {"full": 0, "ring": row, "state": 0,
                                   "summary": row}
    assert cache_bytes(cache) == {"full": 0, "state": 0,
                                  "ring": 2 * 3 * 40 * row,
                                  "summary": 2 * 3 * 32 * row}
    with pytest.raises(ValueError, match="whole number of chunks"):
        init_kv_cache(cfg, 1, MAX_LEN + 2)
    with pytest.raises(NotImplementedError, match="full-attention layer or a summary"):
        prefill(world.params, world.toks[:1, :8], dataclasses.replace(
            cfg, layer_kinds=("window",) * 2), init_kv_cache(cfg, 1, 64))


def test_forward_is_the_references(world):
    got = forward(world.params, world.toks, world.cfg)
    assert got.shape == (2, T, 3 * 40)
    np.testing.assert_allclose(got, world.want, **TOL)


def test_whole_prompt_prefill_then_steps(world):
    """A prompt that ends past two windows' edges, mid-chunk (70 = 17 x 4 +
    2), then single tokens through the next edge."""
    cfg, w = world.cfg, world
    logits, cache = prefill(w.params, w.toks[:1, :70], cfg,
                            init_kv_cache(cfg, 1, MAX_LEN))
    np.testing.assert_allclose(logits[0], w.want[0, 69], **TOL)
    slots = cache_insert_slot(init_slot_cache(cfg, 1, MAX_LEN), cache,
                              jnp.int32(0))
    for t in range(70, T):
        logits, slots = w.step(w.params, w.toks[:1, t], slots,
                               jnp.ones((1,), bool))
        np.testing.assert_allclose(logits[0], w.want[0, t], **TOL)


def test_chunks_straddle_an_edge_and_the_prompt_ends_mid_chunk(world):
    """Chunk programs of 8 from position 27 (a whole-prompt prefill's end,
    as prefix reuse and the lanes start chunks off the grid): [27, 35)
    straddles the first window's edge and [59, 67) the second's; the prompt
    ends at 71, three tokens into a chunk program and three into a summary
    chunk.  Then single tokens to the end."""
    cfg, w = world.cfg, world
    _, cache = prefill(w.params, w.toks[:1, :27], cfg,
                       init_kv_cache(cfg, 1, MAX_LEN))
    logits, cache = _chunked(w, 0, 71, cache, off=27)
    assert int(cache["pos"]) == 71
    np.testing.assert_allclose(logits[0], w.want[0, 70], **TOL)
    slots = cache_insert_slot(init_slot_cache(cfg, 1, MAX_LEN), cache,
                              jnp.int32(0))
    for t in range(71, T):
        logits, slots = w.step(w.params, w.toks[:1, t], slots,
                               jnp.ones((1,), bool))
        np.testing.assert_allclose(logits[0], w.want[0, t], **TOL)


def test_lanes_with_a_lane_that_stands(world):
    """Three lanes: sequence 0 from its start, a lane that stands (what it
    holds stays bit for bit), sequence 1 from position 27."""
    cfg, w = world.cfg, world
    zeros = init_kv_cache(cfg, 1, MAX_LEN)
    _, seeded = prefill(w.params, w.toks[1:, :27], cfg, zeros)
    junk = {n: jnp.full(a.shape, 0.5, a.dtype)
            for n, a in init_kv_cache(cfg, 1, MAX_LEN).items() if n != "pos"}
    pool = init_slot_cache(cfg, 3, MAX_LEN)
    for lane, one in ((0, zeros), (1, dict(junk, pos=jnp.int32(5))),
                      (2, seeded)):
        pool = cache_insert_slot(pool, one, jnp.int32(lane))
    ends, offs, last = {0: 50, 2: 93}, {0: 0, 2: 27}, {}
    while any(offs[p] < ends[p] for p in ends):
        prompts = [None] * 3
        for p in ends:
            if offs[p] < ends[p]:
                prompts[p] = (np.asarray(w.toks[p // 2:p // 2 + 1, :ends[p]]),
                              offs[p])
        logits, pool, moved = prefill_lanes_step(
            prefill_lanes_jit, w.params, prompts, pool, cfg, chunk=CHUNK,
            capacity=MAX_LEN)
        for p, m in enumerate(moved):
            if m is not None:
                offs[p], last[p] = m[0], logits[p]
    np.testing.assert_allclose(last[0], w.want[0, 49], **TOL)
    np.testing.assert_allclose(last[2], w.want[1, 92], **TOL)
    for n, a in junk.items():
        assert bool(jnp.all(pool[n][:, 1] == a[:, 0])), n


def test_slots_decode_through_three_windows(world):
    """Slot 0 from position 5 to the end (the edges at 32, 64 and 96), slot
    2 joins at 61 by the slot insert, slot 1 is never live; then slot 0's
    first 45 positions gathered out seed another session (the prefix-reuse
    primitive): its ring rows of the prefix's window [32, 45) and every
    summary below 44 are the donor's."""
    cfg, w = world.cfg, world
    slots = init_slot_cache(cfg, 3, MAX_LEN)
    _, a = _chunked(w, 0, 5, init_kv_cache(cfg, 1, MAX_LEN))
    slots = cache_insert_slot(slots, a, jnp.int32(0))
    at = {0: 5}
    for _ in range(T - 5):
        if at[0] == 40:     # a second session joins mid-way
            _, b = _chunked(w, 1, 61, init_kv_cache(cfg, 1, MAX_LEN))
            slots = cache_insert_slot(slots, b, jnp.int32(2))
            at[2] = 61
        if at[0] == 46:     # the donor stands in the prefix's window
            seed = cache_gather_slot(slots, jnp.int32(0), jnp.int32(45))
        live = jnp.asarray([s in at and at[s] < T for s in range(3)])
        tok = jnp.asarray([w.toks[s // 2, min(at.get(s, 0), T - 1)]
                           for s in range(3)])
        logits, slots = w.step(w.params, tok, slots, live)
        for s in list(at):
            if at[s] < T:
                np.testing.assert_allclose(logits[s], w.want[s // 2, at[s]],
                                           **TOL)
                at[s] += 1
    assert at == {0: T, 2: T}
    logits, _ = _chunked(w, 0, 70, seed, off=45)
    np.testing.assert_allclose(logits[0], w.want[0, 69], **TOL)


def test_a_step_through_the_block_kernel_is_the_dense_step(world,
                                                           monkeypatch):
    """The fused step with `ops/cache_attention.py`'s kernel (through the
    interpreter; rings of 384 rows for windows of 256 and 128 summary rows,
    whole blocks of 128) against the step without it: slot 0 decodes from
    250 through the window's edge at 256 (its ring's live range restarts at
    one row, its first summaries appear), slot 1 stands, slot 2 decodes from
    300; the same greedy tokens, the logits inside the file's tolerance."""
    max_len = 512
    cfg = dataclasses.replace(world.cfg, sliding_window=256,
                              window_chunk=128, max_seq_len=max_len)
    assert window_ring(cfg, max_len) == 384
    toks = mf.family_of(world.c).model.tokens(
        jax.random.PRNGKey(9), (2, 300), world.c)
    slots = init_slot_cache(cfg, 3, max_len)
    first = {}
    for slot, n in ((0, 250), (2, 300)):
        logits, one = prefill(world.params, toks[slot // 2:slot // 2 + 1, :n],
                              cfg, init_kv_cache(cfg, 1, max_len))
        slots = cache_insert_slot(slots, one, jnp.int32(slot))
        first[slot] = int(greedy_tokens(logits, cfg)[0])
    live = jnp.asarray([True, False, True])

    def run(step):
        cache, tok = slots, jnp.asarray([first[0], 0, first[2]])
        out = []
        for _ in range(12):
            logits, cache = step(world.params, tok, cache, live)
            tok = jnp.where(live, greedy_tokens(logits, cfg), 0)
            out.append((np.asarray(tok), np.asarray(logits)))
        return out, cache

    def fresh():    # a trace is cached by the function: a new one a path
        return jax.jit(functools.partial(decode_step_slots, cfg=cfg))

    dense, _ = run(fresh())
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    kernel, cache = run(fresh())
    assert cache["pos"].tolist() == [262, 0, 312]
    for (t_d, l_d), (t_k, l_k) in zip(dense, kernel):
        assert t_d.tolist() == t_k.tolist()
        np.testing.assert_allclose(l_k[[0, 2]], l_d[[0, 2]], **TOL)


def test_prefix_exact_says_which_donor_still_holds_a_prefix(world):
    """Summaries below the depth: always.  Ring rows: only while the donor
    stands in the prefix's last window; none are needed where the prefix
    ends on a window's edge.  And no first chunk window set back."""
    exact = functools.partial(prefix_holds, world.cfg, chunk=CHUNK,
                              capacity=MAX_LEN)
    for pos, depth, want in (
            (50, 45, True),       # donor in the prefix's window [32, 64)
            (63, 33, True),
            (64, 45, False),      # ... has passed its end: ring moved on
            (100, 45, False),
            (100, 64, True),      # the prefix ends on an edge: no ring row
            (40, 32, True),
            (127, 124, False),    # the first chunk would be set back
            (127, 96, True)):
        assert exact(pos, depth, depth + 20) is want, (pos, depth)
    assert exact(None, 45, 60) is False     # no such donor


@pytest.mark.parametrize("limit", ["window_holds_all", "chunk_of_one"])
def test_the_two_limits_are_plain_attention(limit):
    """With ``window >= T`` no summary is ever visible; with ``chunk = 1``
    and ``mu = 0`` a summary is its token: causal softmax attention over the
    whole context either way, whatever ``phi``."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (2, 24, 4, 8))
    k = jax.random.normal(ks[1], (2, 24, 2, 8))
    v = jax.random.normal(ks[2], (2, 24, 2, 8))
    phi = jax.random.normal(ks[3], (2, 8))
    mu = jax.random.normal(ks[4], (2, 8))
    def attend(mu, **kw):     # one program a case, not one an operation
        return jax.jit(functools.partial(eva_attention, **kw))(q, k, v, phi,
                                                               mu)

    if limit == "window_holds_all":
        got = attend(mu, window=24, chunk=4)
    else:
        got = attend(0 * mu, window=8, chunk=1)
    np.testing.assert_allclose(got, jax.jit(reference_attention)(q, k, v),
                               atol=2e-5, rtol=0)
    # ... and with summaries in play it is not
    other = attend(mu, window=8, chunk=4)
    assert float(jnp.abs(other - got).max()) > 1e-2


# ----------------------------------- what the benchmark had stays as it was

def _served(config: str):
    m = mf.Manifest()
    c = m.config(config)
    return mf.family_of(c).model.model_config(c, "serve")


#: each served configuration's cache rows: {array: (heads, width)}
ROWS = {
    "gpt2-xl": {"k": (25, 64), "v": (25, 64)},
    "glm-4.7-flash": {"kv": (1, 576)},
    "trinity-large-preview": {"k": (8, 128), "v": (8, 128),
                              "k_win": (8, 128), "v_win": (8, 128)},
    "lfm2-8b-a1b": {"k": (8, 64), "v": (8, 64), "conv_state": (1, 2)},
    "mimo-v2-flash": {"k": (4, 192), "v": (4, 128),
                      "k_win": (8, 192), "v_win": (8, 128)},
}


@pytest.mark.parametrize("config", sorted(ROWS))
def test_existing_caches_and_row_counts_are_unchanged(config):
    cfg = _served(config)
    assert cache_rows(cfg) == ROWS[config]
    assert set(position_bytes(cfg)) == {"full", "ring", "state"}
    # (the served size's SHAPES: the counts read nothing else of a cache)
    traffic = CacheTraffic(jax.eval_shape(
        lambda: init_slot_cache(cfg, 4, 9216)), cfg, 128)
    batch = (0, 100, 5000, 9000)
    got = traffic.step(batch)[:6]
    # the sums as they were counted before there was a fourth state kind
    windows, convs = (cfg.kinds.count(k) for k in ("window", "conv"))
    window = cfg.sliding_window if windows else 0
    full = cfg.n_layers - windows - convs
    depth = sum(pos + 1 for pos in batch)
    seen = sum(min(pos + 1, window) for pos in batch)
    b = position_bytes(cfg)
    assert got == (
        full * depth + windows * seen
        + convs * (cfg.conv_kernel - 1) * len(batch),
        cfg.n_layers * depth,
        full * depth * b["full"] + windows * seen * b["ring"]
        + convs * b["state"] * len(batch),
        cfg.n_layers * depth * max(b["full"], b["ring"]), 0, 0)


def test_rows_of_counts_ring_and_summary_rows_apart(world):
    cfg = world.cfg
    traffic = CacheTraffic(init_slot_cache(cfg, 3, MAX_LEN), cfg, CHUNK)
    ring, pooled = (4 + 1 + 4), (0 + 8 + 24)    # a layer
    row = position_bytes(cfg)["ring"]
    assert traffic.step((3, 32, 99))[:6] == (
        2 * (ring + pooled), 2 * (4 + 33 + 100), 2 * (ring + pooled) * row,
        2 * (4 + 33 + 100) * row, 2 * pooled, 2 * pooled * row)
