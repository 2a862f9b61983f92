"""Learned sparse attention (`ops/sparse_index.py`) and the fifth kind of
cache state (`models/generate.py`): the op against a NumPy statement of its
equations (scores, ties, contexts shorter than ``index_topk``); the plain and
the absorbed form under one selection; every cached program (whole-prompt
prefill, chunks, lanes with a lane that stands, slots at depths of their own)
and the engine against the full `forward` at contexts several times
``index_topk``; stale rows past a slot's ``pos`` that are never chosen; the
prefix reuse that copies the index keys; a layer pattern whose shared layers
cross the boundary of the dense and the expert run; three planted faults that
each FAIL; and what a configuration is refused for.

The model is the rehearsal's ``tiny-glm-moe-dsa`` in float32 (an indexer of 2
heads of 16 that keeps 8 positions; layer 0 dense and indexing, 1 and 2
expert layers that share its choice, 3 indexing, 4 shared; 4 of 8 experts
held).  The plain REFERENCE's agreement is
tests/benchmark/test_perfbench_family_glm_moe_dsa.py's.
"""

import dataclasses
import functools
import importlib
import json
import os
import re
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
                            init_params, init_slot_cache, lm_loss, prefill,
                            prefill_chunk_jit, prefill_lanes_jit)
from ray_tpu.models.generate import (_state_kind, cache_bytes, cache_rows,
                                     position_bytes, prefill_chunk_step,
                                     prefill_lanes_step)
from ray_tpu.models.transformer import (count_params, decode_flops_per_token,
                                        stack_kinds)
from ray_tpu.ops import latent_attention as mla
from ray_tpu.ops import sparse_index
from ray_tpu.serve.decode_session import ContinuousBatchingEngine

T, MAX_LEN, CHUNK, TOPK = 100, 128, 8, 8
TOL = dict(atol=3e-4, rtol=0)


@pytest.fixture(scope="module")
def world():
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           "tiny-glm-moe-dsa.json")) as f:
        c = json.load(f)
    model = mf.family_of(c).model
    cfg = dataclasses.replace(model.model_config(c, "serve"),
                              dtype=jnp.float32, param_dtype=jnp.float32,
                              remat=False)
    params = jax.jit(lambda k: model.make(k, c, jnp.float32))(
        jax.random.PRNGKey(7))
    toks = model.tokens(jax.random.PRNGKey(8), (2, T), c)
    want = jax.jit(functools.partial(forward, cfg=cfg))(params, toks)
    return types.SimpleNamespace(
        c=c, cfg=cfg, params=params, toks=toks, want=np.asarray(want),
        step=jax.jit(functools.partial(decode_step_slots, cfg=cfg)))


# ------------------------------------------------------------------ the op

def _numpy_choice(scores, allowed, topk):
    """S_t by a full stable sort, a row at a time."""
    out = np.zeros(scores.shape, bool)
    for i, (row, ok) in enumerate(zip(scores, allowed)):
        order = np.argsort(np.where(ok, -row, np.inf), kind="stable")
        out[i, order[:topk]] = True
    return out & allowed


def test_scores_and_choice_are_the_numpy_statement():
    rng = np.random.default_rng(0)
    b, s, h, d, t = 2, 24, 3, 16, 60
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, d, t)).astype(np.float32)
    w = rng.standard_normal((b, s, h)).astype(np.float32)
    want = np.einsum("bsht,bsh->bst", np.maximum(
        np.einsum("bshk,bkt->bsht", q, k), 0), w)
    got = np.asarray(sparse_index.index_scores(*map(jnp.asarray, (q, w, k))))
    np.testing.assert_allclose(got, want, atol=1e-4)
    # queries at positions 36 .. 59: each sees what lies at or before it
    allowed = np.arange(t)[None, None, :] <= (36 + np.arange(s))[None, :, None]
    allowed = np.broadcast_to(allowed, (b, s, t))
    for topk in (1, 7, 16, 60, 200):
        mask = np.asarray(sparse_index.select(jnp.asarray(got), jnp.asarray(
            allowed), topk))
        np.testing.assert_array_equal(mask.reshape(-1, t), _numpy_choice(
            got.reshape(-1, t), allowed.reshape(-1, t), topk))
        assert (mask.sum(-1) == np.minimum(allowed.sum(-1), topk)).all()


@pytest.mark.parametrize("levels", [2, 3, 5])
def test_equal_scores_go_to_the_earlier_position(levels):
    """Scores of a few distinct values (zeros of both signs among them):
    most of a choice is decided among equals."""
    rng = np.random.default_rng(levels)
    scores = rng.integers(0, levels, (40, 90)).astype(np.float32) - 1.0
    scores[scores == 0] *= rng.choice([-1.0, 1.0], (scores == 0).sum())
    allowed = np.arange(90)[None, :] <= rng.integers(0, 90, (40, 1))
    for topk in (4, 16, 33):
        got = np.asarray(sparse_index.select(
            jnp.asarray(scores), jnp.asarray(allowed), topk))
        np.testing.assert_array_equal(
            got, _numpy_choice(scores, allowed, topk))


def test_a_context_within_topk_is_causal_full_attention(world):
    """For t < index_topk every layer attends all it may see: a model whose
    indexer keeps more positions than the sequence has is the model with
    every row attended."""
    w = world
    wide = dataclasses.replace(w.cfg, index_topk=T)
    wider = dataclasses.replace(w.cfg, index_topk=4 * T)
    a, b = (np.asarray(forward(w.params, w.toks, cfg))
            for cfg in (wide, wider))
    np.testing.assert_allclose(a, b, **TOL)
    # ... and the first TOPK positions of the model itself are those
    np.testing.assert_allclose(w.want[:, :TOPK], a[:, :TOPK], **TOL)
    assert np.abs(w.want[:, 4 * TOPK:] - a[:, 4 * TOPK:]).max() > 0.05


def test_blocked_reads_are_the_dense_ones():
    """Index scores and latent attention a block of cached rows at a time,
    no block past the last row a query may see."""
    rng = np.random.default_rng(3)
    b, s, h, r, t = 1, 5, 4, 24, 384
    assert sparse_index.key_block(t) == 128 and not sparse_index.key_block(
        128) and not sparse_index.key_block(100)
    q = jnp.asarray(rng.standard_normal((b, s, h, r)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((b, r, t)), jnp.float32)
    wts = jnp.asarray(rng.standard_normal((b, s, h)), jnp.float32)
    for first in (0, 120, 250):         # 1, 1-2 and 2-3 blocks are read
        mask = jnp.arange(t)[None, None, :] <= (first + jnp.arange(s))[
            None, :, None]
        np.testing.assert_allclose(
            mla.attend_latents(q, rows, mask, 3.0, key_block=128),
            mla.attend_latents(q, rows, mask, 3.0), atol=2e-5)
        seen = int(sparse_index.rows_seen(mask))
        assert seen == first + s
        dense = sparse_index.index_scores(q, wts, rows)
        blocked = np.asarray(sparse_index.index_scores(q, wts, rows, seen))
        upto = -(-seen // 128) * 128
        np.testing.assert_allclose(blocked[..., :upto], dense[..., :upto],
                                   atol=2e-5)
        assert not blocked[..., upto:].any()
        np.testing.assert_array_equal(
            sparse_index.selection_mask(q, wts, rows, mask, 16, blocked=True),
            sparse_index.selection_mask(q, wts, rows, mask, 16))


# ------------------------------------- the blocked read as one kernel call

_KERNEL_CASES = {
    # lanes' first positions, which lanes run, a query's own columns or all
    "a query's own columns": ((100, 37, 370), (1, 1, 1), True),
    "the last row seen in the middle of a block": ((150, 3, 300), (1, 1, 1),
                                                   False),
    "the last row seen in the last block": ((496, 480, 385), (1, 1, 1),
                                            False),
    "a lane that stands among lanes that run": ((100, 200, 370), (1, 0, 1),
                                                True),
    "a context within index_topk": ((0, 0, 0), (1, 1, 1), False),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_the_kernel_attends_what_the_loop_attends(monkeypatch, case, dtype,
                                                  tol):
    """`attend_cache` (through the interpreter) against `_attend_blocks` and
    against the unblocked `attend_latents`, a lane at a time: the mask a
    query's own set of columns, no block past the last row a lane sees
    (those blocks hold NaN here), a standing lane's cache never touched."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    first, runs, own = _KERNEL_CASES[case]
    layers, lanes, h, c, kv_lora, rope, t, block = 2, 3, 16, 16, 128, 16, \
        512, 128
    r, dt = kv_lora + rope, jnp.dtype(dtype)
    assert mla.kernel_shape((lanes, c, h, r), kv_lora, block)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((lanes, c, h, r)), dt)
    kv = rng.standard_normal((layers, lanes, 1, r, t)).astype(np.float32)
    mask = np.arange(t)[None, None, :] <= (
        np.asarray(first)[:, None] + np.arange(c)[None, :])[:, :, None]
    if own:
        mask &= rng.random((lanes, c, t)) < 0.4
    live = np.asarray(runs, bool)
    poisoned = kv.copy()
    for p in range(lanes):
        seen = int(sparse_index.rows_seen(jnp.asarray(mask[p]))) \
            if live[p] else 0
        poisoned[:, p, :, :, -(-seen // block) * block:] = np.nan
    poisoned[0] = np.nan                        # another layer's rows
    got = jax.jit(lambda q, kv, m, live: mla.attend_cache(
        jnp.swapaxes(q, 1, 2), kv, 1, m, live, 3.0, kv_lora, block))(
        q, jnp.asarray(poisoned, dt), jnp.asarray(mask), jnp.asarray(live))
    got = np.asarray(jnp.swapaxes(got, 1, 2).astype(jnp.float32))
    assert got.shape == (lanes, c, h, kv_lora)
    for p in range(lanes):
        if not live[p]:
            assert not got[p].any()
            continue
        one = (q[p:p + 1], jnp.asarray(kv[1, p], dt),
               jnp.asarray(mask[p:p + 1]), 3.0)
        for want in (mla.attend_latents(*one, key_block=block),
                     mla.attend_latents(*one)):
            np.testing.assert_allclose(
                got[p], np.asarray(want[0, ..., :kv_lora], np.float32),
                atol=tol)


def test_the_kernels_work_list_has_no_block_past_a_lanes_rows():
    rows = jnp.asarray([300, 0, 0, 1, 512], jnp.int32)
    lane, src, at, items = (np.asarray(x) for x in mla._cache_work(
        rows, 128, 4))
    assert int(items) == 3 + 1 + 1 + 1 + 4
    n = int(items)
    assert lane[:n].tolist() == [0, 0, 0, 1, 2, 3, 4, 4, 4, 4]
    # a lane that stands reads what the item before it read: nothing moves
    assert src[:n].tolist() == [0, 0, 0, 0, 0, 3, 4, 4, 4, 4]
    assert at[:n].tolist() == [0, 1, 2, 2, 2, 0, 0, 1, 2, 3]
    assert lane.shape == (20,) and at.max() <= 3 and src.max() <= 4


# a slot's visible rows: one, a block's edge, one past it, the cache's last
# row, the middle of a block; the last slot stands
_STEP_ROWS = (1, 128, 129, 512, 300, 77)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("chosen", [False, True],
                         ids=["every row before it", "an indexer's choice"])
@pytest.mark.parametrize("heads", [32, 64, 20])
def test_a_steps_heads_are_the_rows_of_one_tile(monkeypatch, heads, chosen,
                                                dtype, tol):
    """`attend_cache` for ONE query a slot (through the interpreter): the
    heads of a slot are the one head tile's query rows (20 are padded to 32
    and the padding dropped), the mask ONE row a slot, never ``heads``; each
    live slot equals `attend_latents` over all rows at once, no block past a
    slot's last visible row is read (they hold NaN here), and a slot that
    stands gives zeros and touches nothing of its cache (all NaN)."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    layers, kv_lora, rope, t, block = 2, 128, 16, 512, 128
    slots, r, dt = len(_STEP_ROWS), kv_lora + rope, jnp.dtype(dtype)
    assert mla.kernel_shape((slots, 1, heads, r), kv_lora, block)
    assert mla.row_tile((slots, 1, heads, r), block) == block
    rng = np.random.default_rng(heads)
    q = jnp.asarray(rng.standard_normal((slots, 1, heads, r)), dt)
    kv = rng.standard_normal((layers, slots, 1, r, t)).astype(np.float32)
    mask = np.arange(t)[None, None, :] < np.asarray(_STEP_ROWS)[:, None, None]
    if chosen:      # a choice keeps a query's own row, and some before it
        last = mask & ~np.roll(mask, -1, axis=-1)
        mask = (mask & (rng.random((slots, 1, t)) < 0.4)) | last
    live = np.arange(slots) < slots - 1
    poisoned = kv.copy()
    for p, rows in enumerate(_STEP_ROWS):
        poisoned[:, p, :, :, -(-rows // block) * block if live[p] else 0:] \
            = np.nan
    poisoned[0] = np.nan                        # another layer's rows

    def step(q, kv, m, live):
        return mla.attend_cache(jnp.swapaxes(q, 1, 2), kv, 1, m, live, 3.0,
                                kv_lora, block)
    operands = (q, jnp.asarray(poisoned, dt), jnp.asarray(mask),
                jnp.asarray(live))
    text = str(jax.make_jaxpr(step)(*operands))
    assert "pallas_call" in text and f"i8[{slots},1,{t}]" in text
    assert not re.findall(rf"i8\[{slots},(?!1,)\d+,{t}\]", text)
    got = np.asarray(jax.jit(step)(*operands).astype(jnp.float32))
    assert got.shape == (slots, heads, 1, kv_lora)
    assert not got[~live].any() and np.isfinite(got).all()
    want = mla.attend_latents(q, jnp.asarray(kv[1, :, 0], dt),
                              jnp.asarray(mask), 3.0)
    np.testing.assert_allclose(
        got[live, :, 0], np.asarray(want[live, 0, :, :kv_lora], np.float32),
        atol=tol)


@pytest.mark.parametrize("cell,heads,rows,step,chunk", [
    ("kimi-linear-48b-a3b.serve-think-closed", 32, 5632, 512, 512),
    ("glm-4.7-flash.serve-agent-closed", 20, 4096, 1024, 0),
    ("glm-5.2.serve-longdoc-closed", 64, 33792, 1024, 1024)])
def test_what_chooses_the_kernel_is_the_shape(cell, heads, rows, step, chunk):
    """`generate._key_block` at the three latent cells' shapes: a decode
    step reads through the kernel whatever its heads (they are the rows), a
    chunk of 128 where its heads fill a head tile (20 do not: the agent
    cell's chunks read through XLA's forms); no size of scores is asked.
    XLA's forms ask their own question, which the change did not move."""
    generate = importlib.import_module("ray_tpu.models.generate")
    assert generate._key_block((32, 1, heads, 192), 512, rows) == step
    assert generate._key_block((4, 128, heads, 192), 512, rows) == chunk
    assert mla.row_tile((32, 1, heads, 576), step) == step
    assert not chunk or mla.row_tile((4, 128, heads, 576), chunk) == 512
    assert not sparse_index.loop_block(1, heads, rows)
    assert bool(sparse_index.loop_block(128, heads, rows)) == (
        128 * heads * rows * 4 > 160 << 20) == (rows == 33792)
    with open(generate.__file__) as f:      # the threshold left this file
        assert "160 <<" not in f.read()


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_the_hosts_count_of_latent_rows_is_the_kernels_own_list(
        monkeypatch, program):
    """`CacheTraffic`'s ``rows_fetched`` a step and rows a chunk of a latent
    model from positions: where `attend_cache` engages on this process's backend, the
    rows of `_cache_work`'s items for the same masks (a lane that stands
    has one item that moves nothing), summed over the layers; where it does
    not, every row of every slot's (of the lane's) layer."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_layers=3, n_heads=4, d_ff=64,
        max_seq_len=1536, pos_emb="rope", attention="mla", q_lora_rank=8,
        kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=16, dtype=jnp.float32, attention_impl="reference")
    slots, rows, c = 5, 1536, 16 if program == "chunk" else 1
    cache = init_slot_cache(cfg, slots, rows)
    def count():
        traffic = CacheTraffic(cache, cfg, c)
        return traffic.chunk if program == "chunk" else \
            lambda positions: traffic.step(positions).rows_fetched

    dense = count()
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    engaged = count()
    tile = mla.row_tile((slots, c, 4, 144), sparse_index.key_block(rows))
    assert tile == 512 == sparse_index.key_block(rows)
    rng = np.random.default_rng(9)
    for _ in range(6):
        pos = rng.integers(0, rows - c, slots)
        live = rng.random(slots) < 0.7
        seen = np.where(live, pos + c, 0)
        _, _, _, items = mla._cache_work(jnp.asarray(seen, jnp.int32), tile,
                                         rows // tile)
        moved = 3 * tile * (int(items) - int((~live).sum()))
        if program == "step":
            assert engaged(pos[live].tolist()) == moved
            assert dense(pos[live].tolist()) == 3 * slots * rows
            continue
        n_valid = rng.integers(1, c + 1, slots)
        got = [engaged(int(p), int(n)) for p, n in zip(pos[live],
                                                       n_valid[live])]
        assert sum(f for f, _ in got) == moved
        assert [r for _, r in got] == [3 * int(p + n) for p, n in zip(
            pos[live], n_valid[live])]
        assert [dense(int(p), int(n)) for p, n in zip(
            pos[live], n_valid[live])] == [(3 * rows, r) for _, r in got]


_SHARE = "cache.latent_rows_fetched_share.batch"


def _span(name, end_s, **args):
    return {"name": name, "ts": (end_s - 2) * 1e6, "dur": 2e6,
            "args": dict(args, deployment="bench")}


@pytest.mark.parametrize("counted,want", [
    ("neither", None),          # a program before PR 48: no key is there
    ("steps", 100.0 * 2 * 900 / (2 * 2000)),    # this PR's parent
    ("both", 100.0 * 2 * (900 + 640) / (2 * (1024 + 768)))])
def test_the_latent_share_reads_steps_and_chunk_programs(counted, want):
    """`perfbench/metrics/cache.latent_rows_fetched_share.batch.py` on
    hand-made ring spans: rows seen over rows moved, the window's
    ``cache:rows`` and ``engine:lanes`` spans summed; its entry in the root
    manifest (found by its name: an entry is only ever appended, so none
    stays the last) lists the three cells of latent models."""
    read = mf.metric_reader(_SHARE)
    run = lambda events: types.SimpleNamespace(
        stamps={"open": 10.0, "close": 55.0}, _ring_spans=events)
    assert read(run([])) is None
    step = {"rows_fetched": 2000 if counted == "steps" else 1024,
            "rows_read": 900} if counted != "neither" else {"steps": 3}
    lanes = {"chunk_rows_fetched": 768, "chunk_rows_read": 640} \
        if counted == "both" else {"programs": 2}
    events = [_span("cache:rows", 9.5, rows_fetched=7, rows_read=1),
              _span("engine:lanes", 56.0, chunk_rows_fetched=5,
                    chunk_rows_read=5),
              {"name": "cache:rows", "ts": 20e6, "dur": 2e6},   # no args
              _span("moe:load", 20.0, rows_fetched=10 ** 12)]
    events += [_span("cache:rows", 12.0 + 2 * i, **step) for i in range(2)]
    events += [_span("engine:lanes", 13.0 + 2 * i, **lanes) for i in range(2)]
    got = read(run(events))
    assert got is None if want is None else got == pytest.approx(want)
    root = mf.Manifest()
    mine, = (m for m in root.data["per_layer"] if m["name"] == _SHARE)
    assert mine == {
        "name": _SHARE, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "kernels",
        "moves": "serve_tok_s", "workloads": [
            "kimi-linear-48b-a3b.serve-think-closed",
            "glm-4.7-flash.serve-agent-closed",
            "glm-5.2.serve-longdoc-closed"]}
    e2e = next(e for e in root.data["end_to_end"]
               if e["name"] == "serve_tok_s")
    assert set(mine["workloads"]) <= set(e2e["workloads"])


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_the_engine_counts_the_rows_its_latent_layers_moved(monkeypatch,
                                                            path):
    """One session of a tiny latent model (2 layers of 384 rows of latents
    of 128) prefills 200 tokens through chunks of 128 and decodes from 200
    past a block's edge in an engine of 2 slots: `engine.stats()` and the
    ring spans carry what the steps and the chunk programs moved and what
    their queries saw, and the reader gives the ratio of the sums."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    from ray_tpu.util import tracing
    if path == "kernel":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=384, pos_emb="rope", rope_base=1e4, activation="swiglu",
        norm="rmsnorm", tie_embeddings=False, remat=False, attention="mla",
        q_lora_rank=8, kv_lora_rank=128, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, dtype=jnp.float32,
        param_dtype=jnp.float32, attention_impl="reference")
    slots, max_len, n, out = 2, 384, 200, 60
    core = DecodeSessionCore(cfg, max_len=max_len, seed=3,
                             engine=DecodeEngineConfig(
                                 max_slots=slots, prefill_chunk_tokens=128))
    names = ("cache:rows", "engine:lanes")
    try:
        before = len([e for e in tracing.span_events()
                      if e["name"] in names])
        r = core.handle({"op": "start",
                         "prompt": [3 + i % 50 for i in range(n)]})
        assert "error" not in r, r
        got = len(r["token"])
        while got < out:
            more = core.handle({"op": "next_chunk", "sid": r["sid"],
                                "max_tokens": out - got})
            assert "error" not in more, more
            got += len(more["tokens"])
        core.handle({"op": "end", "sid": r["sid"]})
    finally:
        core.engine.shutdown()
    stats = core.engine.stats()
    steps, layers = stats["cache"]["steps"], cfg.n_layers
    assert steps >= out - 1 and stats["prefill_programs"] == 2
    # the steps stood at 200, 201, ...: a row a position up to their own
    at = range(n, n + steps)
    assert stats["cache"]["rows_read"] == layers * sum(p + 1 for p in at)
    assert stats["chunk_rows_read"] == layers * (128 + 200)
    if path == "kernel":    # blocks of 128: 2 up to 255, then 3; 1 and 2
        assert stats["cache"]["rows_fetched"] == layers * 128 * sum(
            2 if p < 256 else 3 for p in at)
        assert stats["chunk_rows_fetched"] == layers * (128 + 256)
    else:                   # every row of both slots; of the session's layer
        assert stats["cache"]["rows_fetched"] == layers * steps * slots * 384
        assert stats["chunk_rows_fetched"] == layers * 2 * 384
    spans = [e for e in tracing.span_events() if e["name"] in names][before:]
    share = mf.metric_reader(_SHARE)(types.SimpleNamespace(
        stamps={"open": 0.0, "close": 1e12}, _ring_spans=spans))
    assert share == pytest.approx(
        100.0 * (stats["cache"]["rows_read"] + stats["chunk_rows_read"])
        / (stats["cache"]["rows_fetched"] + stats["chunk_rows_fetched"]))
    assert (share > 75) == (path == "kernel")


# ------------------------------------------------- the model and its cache

def test_pattern_weights_and_counts(world):
    cfg, params = world.cfg, world.params
    assert cfg.kinds == ("index", "shared", "shared", "index", "shared")
    # the shared layers 1 and 2 are EXPERT layers behind the dense layer 0:
    # its choice crosses the boundary of the two runs
    assert cfg.layer_segments == (
        ("dense_layers", 0, 1, "index"), ("layers", 0, 2, "shared"),
        ("layers", 2, 1, "index"), ("layers", 3, 1, "shared"))
    # an indexer's weights over the indexing layers alone
    for run, n in (("dense_layers", 1), ("layers", 1)):
        assert params[run]["wi_q"].shape == (n, 24, 2, 16)
        assert params[run]["wi_k"].shape == (n, 64, 16)
        assert params[run]["wi_w"].shape == (n, 64, 2)
        assert params[run]["ik_norm"].shape == (n, 16)
    assert params["layers"]["wq_a"].shape[0] == 4
    assert stack_kinds(cfg, "wi_q") == ("index",)
    assert stack_kinds(cfg, "wq_a") == ("full", "window", "index", "shared")
    made, _ = init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree_util.tree_map(jnp.shape, made) == \
        jax.tree_util.tree_map(jnp.shape, params)
    assert count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    # a step at depth t: every layer the chosen rows, an indexer all of them
    per_pos = 4 * (2 * 16 + 8)
    assert decode_flops_per_token(cfg, 50) - decode_flops_per_token(
        cfg, 40) == 2 * 2 * 2 * 16 * 10
    assert decode_flops_per_token(cfg, 6) - decode_flops_per_token(
        cfg, 5) == 2 * per_pos * 5 + 2 * 2 * 2 * 16


def test_a_cache_has_a_fifth_kind_of_state(world):
    cfg = world.cfg
    assert cache_rows(cfg) == {"kv": (1, 24), "k_idx": (1, 16)}
    assert _state_kind("k_idx") == "index" and _state_kind("kv") == "full"
    assert position_bytes(cfg) == {"full": 24 * 4, "ring": 0, "state": 0,
                                   "index": 16 * 4}
    cache = init_slot_cache(cfg, 3, MAX_LEN)
    assert cache["kv"].shape == (5, 3, 1, 24, MAX_LEN)      # every layer
    assert cache["k_idx"].shape == (2, 3, 1, 16, MAX_LEN)   # indexing ones
    assert cache_bytes(cache) == {
        "full": 5 * 3 * 24 * MAX_LEN * 4, "ring": 0, "state": 0,
        "index": 2 * 3 * 16 * MAX_LEN * 4}


def test_rows_a_step_attends_and_what_the_choice_costs(world):
    cfg = world.cfg
    step = CacheTraffic(init_slot_cache(cfg, 3, MAX_LEN), cfg, CHUNK).step(
        (3, 7, 50))
    depth, chosen = 4 + 8 + 51, 4 + 8 + 8
    assert step[6:8] == (2 * depth, 2 * depth * 64)
    assert step[:6] == (
        5 * chosen, 5 * depth, 5 * chosen * 96 + 2 * depth * 64,
        5 * depth * 96, 0, 0)


def _chunked(w, row: int, n: int, cache, off: int = 0):
    host = np.asarray(w.toks[row:row + 1, :n])
    logits = None
    while off < n:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=CHUNK, capacity=MAX_LEN)
    return logits, cache


def test_plain_and_absorbed_forms_agree(world):
    """Whole-prompt prefill (the plain form under the selection as a mask,
    its index keys placed) then decode steps (absorbed, the choice made
    over the cached index keys) against the full forward."""
    w = world
    logits, cache = jax.jit(functools.partial(prefill, cfg=w.cfg))(
        w.params, w.toks[:, :60], cache=init_kv_cache(w.cfg, 2, MAX_LEN))
    np.testing.assert_allclose(logits, w.want[:, 59], **TOL)
    slots = dict(cache, pos=jnp.full((2,), 60, jnp.int32))
    for t in range(60, 70):
        logits, slots = w.step(w.params, w.toks[:, t], slots,
                               jnp.ones((2,), bool))
        np.testing.assert_allclose(logits, w.want[:, t], **TOL)


def test_chunks_and_a_prompt_that_ends_mid_chunk(world):
    w = world
    for row, n in ((0, 61), (1, 40)):
        logits, cache = _chunked(w, row, n, init_kv_cache(w.cfg, 1, MAX_LEN))
        np.testing.assert_allclose(logits[0], w.want[row, n - 1], **TOL)
        assert int(cache["pos"]) == n


def test_lanes_with_a_lane_that_stands(world):
    w = world
    cache = init_slot_cache(w.cfg, 3, MAX_LEN)
    prompts = [(np.asarray(w.toks[0:1, :45]), 0), None,
               (np.asarray(w.toks[1:2, :30]), 0)]
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
    np.testing.assert_allclose(logits[0], w.want[0, 44], **TOL)
    np.testing.assert_allclose(logits[2], w.want[1, 29], **TOL)
    assert not np.asarray(cache["kv"][:, 1]).any() \
        and not np.asarray(cache["k_idx"][:, 1]).any()


@pytest.mark.parametrize("program", ["lanes", "chunk", "step"])
def test_programs_with_the_kernel_are_the_programs_with_the_loop(
        world, monkeypatch, program):
    """The lanes program (a lane that stands), the batch-1 chunk program and
    the DECODE STEP (one query a slot under the indexer's choice, a slot that
    stands) at a shape the kernel takes (latents of 128, blocks of 128
    rows): the kernel through the interpreter against XLA's forms, the
    loop over blocks for the chunks (read blocked whatever the scores' size)
    and all rows at once for the step: logits and every array of the cache."""
    generate = importlib.import_module("ray_tpu.models.generate")
    w = world
    cfg = dataclasses.replace(w.cfg, kv_lora_rank=128)
    params = jax.jit(lambda k: init_params(k, cfg)[0])(jax.random.PRNGKey(5))
    if program != "step":
        monkeypatch.setattr(
            sparse_index, "loop_block",
            lambda queries, heads, rows: sparse_index.key_block(rows))
    calls, kernel = [], mla.attend_cache
    monkeypatch.setattr(mla, "attend_cache", lambda *a: calls.append(
        a[0].shape) or kernel(*a))
    chunk, max_len = 16, 384

    def steps():
        """Four steps of the program under test over three slots filled by
        chunked prefills, slot 1 standing."""
        fn = jax.jit(generate.decode_step_slots, static_argnames=("cfg",))
        slots, logits = start, {}
        for j in range(4):
            lg, slots = fn(
                params, jnp.stack([w.toks[0, 70 + j], jnp.int32(5),
                                   w.toks[1, 30 + j]]), slots,
                jnp.asarray([True, False, True]), cfg=cfg)
            logits[j] = lg[jnp.asarray([0, 2])]
        return logits, slots

    if program == "step":
        start = init_slot_cache(cfg, 3, max_len)
        for row, (src, n) in enumerate(((0, 70), (1, 21), (1, 30))):
            _, one = generate.prefill_chunked(
                params, w.toks[src:src + 1, :n], cfg,
                init_kv_cache(cfg, 1, max_len), chunk=chunk)
            start = cache_insert_slot(start, one, jnp.int32(row))

    def walk(interpret):
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", interpret)
        logits = {}
        if program == "step":
            return steps()
        if program == "chunk":
            fn = jax.jit(generate.prefill_chunk, static_argnames=("cfg",))
            logits[0], cache = generate.prefill_chunked(
                params, w.toks[0:1, :61], cfg,
                init_kv_cache(cfg, 1, max_len), chunk=chunk, _jitted=fn)
            return logits, cache
        fn = jax.jit(generate._lanes_program, static_argnames=("cfg",))
        cache = init_slot_cache(cfg, 3, max_len)
        prompts = [(np.asarray(w.toks[0:1, :45]), 0), None,
                   (np.asarray(w.toks[1:2, :30]), 0)]
        while any(p is not None for p in prompts):
            lg, cache, moved = prefill_lanes_step(
                fn, params, prompts, cache, cfg, chunk=chunk,
                capacity=max_len)
            for p, m in enumerate(moved):
                if m is not None:
                    logits[p] = lg[p]
                    prompts[p] = (prompts[p][0], m[0]) \
                        if m[0] < prompts[p][0].shape[1] else None
        return logits, cache

    (want, cache_w), (got, cache_g) = walk("0"), walk("1")
    for p in want:
        np.testing.assert_allclose(got[p], want[p], **TOL)
    for name in cache_w:
        np.testing.assert_allclose(cache_g[name], cache_w[name], **TOL)
    assert calls


def _two_slots(w, depths):
    slots = init_slot_cache(w.cfg, 2, MAX_LEN)
    insert = jax.jit(cache_insert_slot)
    for row, n in enumerate(depths):
        _, one = _chunked(w, row, n, init_kv_cache(w.cfg, 1, MAX_LEN))
        slots = insert(slots, one, jnp.int32(row))
    return slots


def test_slots_at_depths_of_their_own_and_one_that_stands(world):
    w = world
    slots = _two_slots(w, (70, 21))
    active = jnp.asarray([True, True])
    for j in range(6):
        logits, slots = w.step(
            w.params, jnp.stack([w.toks[0, 70 + j], w.toks[1, 21 + j]]),
            slots, active)
        np.testing.assert_allclose(logits[0], w.want[0, 70 + j], **TOL)
        np.testing.assert_allclose(logits[1], w.want[1, 21 + j], **TOL)
    # slot 1 stands (its token lands ahead of its pos and is never chosen)
    logits, slots = w.step(
        w.params, jnp.stack([w.toks[0, 76], jnp.int32(5)]), slots,
        jnp.asarray([True, False]))
    np.testing.assert_allclose(logits[0], w.want[0, 76], **TOL)
    assert slots["pos"].tolist() == [77, 27]
    logits, slots = w.step(w.params, jnp.stack([w.toks[0, 77], w.toks[1, 27]]),
                           slots, active)
    np.testing.assert_allclose(logits[1], w.want[1, 27], **TOL)


def test_stale_rows_past_a_slots_pos_are_never_chosen(world):
    """A slot that held a LONGER session: its rows past the new session's
    ``pos`` hold index keys that would outscore every true row (they are
    made huge here) and latents of another context; no query may choose
    them."""
    w = world
    slots = _two_slots(w, (90, 85))
    _, short = _chunked(w, 1, 21, init_kv_cache(w.cfg, 1, MAX_LEN))
    # the short session's rows into the slot the long one leaves, the long
    # one's rows from 21 on left where they are and its index keys blown up
    stale = jax.jit(cache_insert_slot)(slots, {
        name: a.at[..., 21:].set(slots[name][:, 1:2, ..., 21:] * (
            1e3 if name == "k_idx" else 1.0))
        for name, a in short.items() if name != "pos"} | {
            "pos": short["pos"]}, jnp.int32(1))
    assert float(jnp.abs(stale["k_idx"][:, 1, ..., 21:85]).max()) > 100
    for j in range(4):
        logits, stale = w.step(
            w.params, jnp.stack([w.toks[0, 90 + j], w.toks[1, 21 + j]]),
            stale, jnp.ones((2,), bool))
        np.testing.assert_allclose(logits[1], w.want[1, 21 + j], **TOL)
        np.testing.assert_allclose(logits[0], w.want[0, 90 + j], **TOL)


def test_gathered_prefix_carries_the_index_keys(world):
    """`cache_gather_slot` copies the fifth kind with the rest: a session
    seeded with a donor's first 40 positions continues as its own context
    would, the donor's later rows past its ``pos`` unseen."""
    w = world
    slots = _two_slots(w, (72, 30))
    seeded = jax.jit(cache_gather_slot)(slots, jnp.int32(0), jnp.int32(40))
    assert set(seeded) == {"kv", "k_idx", "pos"}
    np.testing.assert_array_equal(seeded["k_idx"][:, 0], slots["k_idx"][:, 0])
    host = np.concatenate([np.asarray(w.toks[0:1, :40]),
                           np.asarray(w.toks[1:2, 40:60])], axis=1)
    want = np.asarray(forward(w.params, jnp.asarray(host), w.cfg))
    off, cache = 40, seeded
    while off < 60:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=CHUNK, capacity=MAX_LEN)
    np.testing.assert_allclose(logits[0], want[0, 59], **TOL)


@pytest.mark.parametrize("dense,kinds", [
    (2, ("index", "shared", "shared", "index", "shared", "shared")),
    (1, ("index", "index", "shared", "shared")),
    (0, ("index", "shared", "shared")),
])
def test_other_patterns_serve_as_they_forward(dense, kinds):
    """Leading dense layers that SHARE (two dense layers, the second under
    the first's choice, and an expert layer under it too), an expert run
    that begins with an indexing layer, a model of one run."""
    cfg = TransformerConfig.tiny(
        vocab_size=97, d_model=32, n_layers=len(kinds), n_heads=2,
        n_kv_heads=None, attention="mla", q_lora_rank=16, kv_lora_rank=12,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, n_experts=4,
        expert_top_k=2, router="sigmoid", moe_d_ff=16, n_shared_experts=1,
        first_dense_layers=dense, d_ff=48, index_heads=2, index_head_dim=8,
        index_topk=6, layer_kinds=kinds, dtype=jnp.float32, max_seq_len=64)
    params, _ = init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, 97)
    want = forward(params, toks, cfg)
    logits, cache = prefill(params, toks[:, :30], cfg,
                            init_kv_cache(cfg, 1, 64))
    np.testing.assert_allclose(logits, want[:, 29], **TOL)
    assert cache["k_idx"].shape[0] == kinds.count("index")
    slots = dict(cache, pos=jnp.full((1,), 30, jnp.int32))
    for t in range(30, 36):
        logits, slots = decode_step_slots(params, toks[:, t], slots,
                                          jnp.ones((1,), bool), cfg)
        np.testing.assert_allclose(logits, want[:, t], **TOL)
    assert np.isfinite(float(lm_loss(params, {"tokens": toks}, cfg)))


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
    """The full forward's own choice at every generated position of
    ``prompt + stream``."""
    seq = jnp.asarray([prompt + stream[:-1]], jnp.int32)
    logits = np.asarray(forward(w.params, seq, w.cfg))[0]
    return logits[len(prompt) - 1:].argmax(-1).tolist()


def _core(w, **engine):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    return DecodeSessionCore(
        w.cfg, max_len=MAX_LEN, params=w.params,
        engine=DecodeEngineConfig(prefill_chunk_tokens=CHUNK, **engine))


def test_engine_serves_the_forwards_tokens(world, monkeypatch):
    """Four sessions at once (prompts of 4-10 times index_topk) through
    chunk programs, the lanes program and the fused slot step: every token
    is the full forward's choice at its position; the engine counts the
    rows a chosen layer attends and the index keys the choice costs."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    w = world
    core = _core(w, max_slots=3)
    try:
        prompts = [np.asarray(w.toks[i % 2, a:a + n]).tolist()
                   for i, (a, n) in enumerate(
                       ((0, 80), (3, 33), (11, 57), (20, 64)))]
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
        assert cache["bytes_index"] == 2 * 3 * 16 * MAX_LEN * 4
        assert cache["bytes_full"] == 5 * 3 * 24 * MAX_LEN * 4
        assert cache["bytes_per_position"] == 5 * 24 * 4 + 2 * 16 * 4
        # every step's live slots stood past index_topk: 8 rows a layer
        assert cache["rows_read"] == 5 * TOPK * st["tokens"]
        assert cache["rows_if_full"] > 4 * cache["rows_read"]
        assert cache["index_rows_read"] * 5 == cache["rows_if_full"] * 2
        assert cache["index_bytes_read"] == cache["index_rows_read"] * 64
        assert cache["bytes_read"] == cache["rows_read"] * 96 \
            + cache["index_bytes_read"]
        span = [e for e in tracing.span_events()
                if e["name"] == "cache:rows"][-1]["args"]
        assert span["bytes_index"] == cache["bytes_index"]
        assert span["index_rows_read"] * 5 == span["rows_if_full"] * 2
    finally:
        core.engine.shutdown()


def test_a_slot_reused_after_a_longer_session_and_a_shared_prefix(world):
    w = world
    core = _core(w, max_slots=1, prefix_cache_min_tokens=4,
                 token_queue_depth=2)
    try:
        long_ = np.asarray(w.toks[0, :90]).tolist()
        assert _stream(core, long_, 10) == _forced(
            w, long_, _stream(core, long_, 10))
        # the ONE slot again, for a session a quarter as long
        short = np.asarray(w.toks[1, :24]).tolist()
        got = _stream(core, short, 10)
        assert got == _forced(w, short, got)
        # ... and one that shares the short one's first 20 tokens: seeded
        # from the slot (index keys with the latents), the rest prefilled
        hits = core.engine.stats()["prefix"]["applied_hits"]
        fork = short[:20] + np.asarray(w.toks[0, 30:50]).tolist()
        got = _stream(core, fork, 10)
        assert core.engine.stats()["prefix"]["applied_hits"] == hits + 1
        assert got == _forced(w, fork, got)
        assert core.engine.stats()["cache_copies"] == 0
    finally:
        core.engine.shutdown()


# --------------------------------------------- planted faults, and refusals

def _without_indexer(tree):
    return {k: v for k, v in tree.items()
            if not k.startswith(("wi_", "ik_"))}


def _faults(w):
    """Three wrong programs on the same weights -> {name: logits}."""
    late = ("index", "shared", "shared", "shared", "shared")
    return {
        # every layer attends all rows
        "the selection ignored": forward(
            w.params, w.toks, dataclasses.replace(w.cfg, index_topk=4 * T)),
        # layer 4 attends layer 0's choice where layer 3's is due (layer 3
        # attends it too: it makes none of its own)
        "the shared layers attend the wrong indexing layer's choice": forward(
            dict(w.params, layers=_without_indexer(w.params["layers"])),
            w.toks, dataclasses.replace(w.cfg, layer_kinds=late)),
        "index_topk halved": forward(
            w.params, w.toks, dataclasses.replace(w.cfg,
                                                  index_topk=TOPK // 2)),
    }


def test_three_planted_faults_each_fail(world):
    w = world
    for name, got in _faults(w).items():
        got = np.asarray(got)
        # what the comparisons above hold the programs to
        assert not np.allclose(got, w.want, **TOL), name
        # ... by far: a tenth of the logits' spread at the worst position,
        # and nothing wrong before the first position that has a choice
        assert np.abs(got - w.want).max() > 0.1 * w.want.std(), name
        np.testing.assert_allclose(got[:, :TOPK // 2], w.want[:, :TOPK // 2],
                                   **TOL)


def test_what_a_configuration_is_refused_for(world):
    cfg = world.cfg
    toks = world.toks[:1, :8]
    for bad, match in (
            (dict(layer_kinds=("shared", "index", "shared", "index",
                               "shared")), "first layer is 'shared'"),
            (dict(layer_kinds=None), "indexer"),
            (dict(index_topk=0), "indexer"),
            (dict(layer_kinds=("index", "full", "shared", "index",
                               "shared")), "indexer"),
            (dict(index_head_dim=4), "indexer")):
        broken = dataclasses.replace(cfg, **bad)
        with pytest.raises(ValueError, match=match):
            init_params(jax.random.PRNGKey(0), broken)
        with pytest.raises(ValueError, match=match):
            forward(world.params, toks, broken)
        with pytest.raises(ValueError, match=match):
            prefill_chunk_jit(world.params, toks,
                              init_kv_cache(cfg, 1, MAX_LEN), cfg=broken)
    with pytest.raises(ValueError, match="indexer"):
        init_params(jax.random.PRNGKey(0), TransformerConfig.tiny(
            index_topk=4, index_heads=2, index_head_dim=8,
            layer_kinds=("index", "shared")))
