"""Every slot's new column into a slot cache by ONE kernel call an array a
layer (`ops/cache_write.py`), against the one-column slices it replaces,
bit for bit.

The kernel runs through the Pallas interpreter here (`RAY_TPU_PALLAS_
INTERPRET=1`); what the chip's compiler makes of it is `tests/test_chip_
compile.py`'s.  The five cache shapes the served cells hold, at tiny sizes
with whole 128-row blocks: rows of several heads, one row of latents, rings,
two key-value head counts with keys wider than values, rings beside summary
rows.  The two cases that keep the slices.  A decode step of a model with
no full layer either way.  And the serve engine's two counts.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import cache_write as cw

ROWS = 256


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


# name -> {array: ((layers, heads, width, rows), column of position p)}
_RING, _CHUNK = 128, 4
SHAPES = {
    "rows_of_heads": {"k": ((2, 3, 8, ROWS), lambda p: p),
                      "v": ((2, 3, 8, ROWS), lambda p: p)},
    "latents": {"kv": ((2, 1, 24, ROWS), lambda p: p)},
    "rings": {"k_win": ((2, 2, 8, _RING), lambda p: p % _RING),
              "v_win": ((2, 2, 8, _RING), lambda p: p % _RING)},
    "two_head_counts_keys_wider": {
        "k": ((1, 2, 24, ROWS), lambda p: p),
        "v": ((1, 2, 16, ROWS), lambda p: p),
        "k_win": ((1, 4, 24, _RING), lambda p: p % _RING),
        "v_win": ((1, 4, 16, _RING), lambda p: p % _RING)},
    "rings_beside_summaries": {
        "k_win": ((1, 2, 8, _RING), lambda p: p % _RING),
        "v_win": ((1, 2, 8, _RING), lambda p: p % _RING),
        "k_sum": ((1, 2, 8, 2 * ROWS // _CHUNK), lambda p: p // _CHUNK),
        "v_sum": ((1, 2, 8, 2 * ROWS // _CHUNK), lambda p: p // _CHUNK)},
}
# a block's first column, its last, the next block's second, a ring's wrap
# (and a full array's last column but one), past the end of a full array
# (the clamp; past the summaries' end too), a slot that is not active
POS = np.array([0, 127, 129, ROWS - 2, 2 * ROWS + 5, 77], np.int32)
ACTIVE = np.array([1, 1, 1, 1, 1, 0], np.int32)


def _step(write, arrays, columns, news, pos):
    """A decode step's writes: every layer of every array takes every
    slot's column (the caller moves only the active slots on, so the slot
    that is not writes its column again)."""
    out = {}
    for name, c_all in arrays.items():
        for l in range(c_all.shape[0]):
            c_all = write(c_all, l, news[name][l], columns[name](pos))
        out[name] = c_all
    return out


@pytest.mark.parametrize("case", sorted(SHAPES))
def test_kernel_is_the_slices_bit_for_bit(interpreted, case):
    slots, n = len(POS), 2
    # the served caches' bfloat16, and float32 (the CPU tests' models)
    dtype = jnp.float32 if case in ("latents", "rings") else jnp.bfloat16
    rng = np.random.default_rng(5)

    def normal(*shape):   # numpy's: no program a shape for the inputs
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)
    arrays, news, columns = {}, {}, {}
    for name, ((layers, heads, width, rows), column) in SHAPES[case].items():
        arrays[name] = normal(layers, slots, heads, width, rows)
        news[name] = normal(n, layers, slots, heads, width)
        columns[name] = column
        assert cw.kernel_shape(arrays[name].shape)

    def by(write):       # one step's program, run once a step
        step = jax.jit(lambda a, nw, pos: _step(write, a, columns, nw, pos))
        got, pos = arrays, jnp.asarray(POS)
        for i in range(n):
            got = step(got, {k: v[i] for k, v in news.items()}, pos)
            pos = pos + ACTIVE
        return got

    got = by(cw.write_columns)
    want = by(lambda c_all, l, cols, col: cw._slices(
        c_all, jnp.int32(l), cols, col))
    for name in arrays:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        assert a.dtype == b.dtype and np.array_equal(
            a.view(np.uint8), b.view(np.uint8)), name
        # ... and the slices did write: a column a slot a layer at least
        assert (b != np.asarray(arrays[name])).any(axis=(2, 3)).sum() \
            >= b.shape[0] * slots


def _writes(fn, *args):
    """(pallas calls, one-column slices) in the traced ``fn(*args)``."""
    # a trace is cached by the function, not by the environment: a fresh one
    text = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    return text.count("pallas_call"), text.count("dynamic_update_slice")


def _operands(rows, slots=3):
    """[S, heads, width] and [S], the decode step's own operands."""
    c_all = jnp.zeros((2, slots, 2, 8, rows), jnp.float32)
    cols = jnp.ones((slots, 2, 8), jnp.float32)
    col = jnp.arange(slots, dtype=jnp.int32) * 50
    return c_all, 1, cols, col


@pytest.mark.parametrize("why", ["rows_not_whole_blocks", "not_a_tpu"])
def test_the_slices_still_run_where_the_kernel_does_not(monkeypatch, why):
    """``rows % 128 != 0`` keeps the slices even under the interpreter; a
    platform that is no TPU keeps them whatever the shape.  The result is
    theirs either way."""
    if why != "not_a_tpu":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    rows = {"rows_not_whole_blocks": 96, "not_a_tpu": 128}[why]
    args = _operands(rows)
    c_all, l, cols, col = args
    assert cw.kernel_shape(c_all.shape) == (why == "not_a_tpu")
    assert cw.device_calls(c_all.shape) == 3
    if why == "not_a_tpu":
        # both branches are traced; the CPU lowers the slices alone
        text = jax.jit(cw.write_columns).lower(*args).as_text()
        assert text.count("dynamic_update_slice") == 3 \
            and "cache_column_write" not in text
    else:
        assert _writes(cw.write_columns, *args) == (0, 3)
    got = np.asarray(cw.write_columns(*args))
    want = np.zeros(c_all.shape, np.float32)
    for s in range(3):
        want[1, s, :, :, min(int(col[s]), rows - 1)] = 1.0
    assert np.array_equal(got, want)


def test_the_kernel_engages_under_the_interpreter(interpreted):
    args = _operands(128)
    assert _writes(cw.write_columns, *args) == (1, 0)
    assert cw.device_calls(args[0].shape) == 1


def test_heads_go_by_blocks_where_all_would_pass_the_budget(interpreted,
                                                            monkeypatch):
    """A block of ``heads x width x 128`` past the VMEM budget is cut into
    blocks of heads: a second grid axis, the same columns."""
    assert cw._head_block(32, 128, 2) == 32      # the byte cell: 1 MB
    assert cw._head_block(64, 512, 4) == 8       # 1 MB a head in flight
    assert cw._head_block(7, 4096, 4) == 1
    monkeypatch.setattr(cw, "_VMEM_BLOCK_BUDGET", 4 * 2 * 8 * 128 * 4)
    assert cw._head_block(6, 8, 4) == 2
    key = jax.random.PRNGKey(2)
    c_all = jax.random.normal(key, (2, 3, 6, 8, 256), jnp.float32)
    cols = jax.random.normal(key, (3, 6, 8), jnp.float32)
    col = jnp.array([3, 200, 999], jnp.int32)
    got = cw.write_columns(c_all, 0, cols, col)
    want = cw._slices(c_all, jnp.int32(0), cols, col)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------ a decode step, either way

def _tiny_eva():
    from perfbench import manifest as mf
    from perfbench.tools import rehearse
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           "tiny-evabyte.json")) as f:
        c = json.load(f)
    model = mf.family_of(c).model
    # rings of 96 + 32 = 128 rows, 512 / 4 = 128 summary rows
    cfg = dataclasses.replace(model.model_config(c, "serve"),
                              dtype=jnp.float32, param_dtype=jnp.float32,
                              sliding_window=96, window_chunk=32)
    return cfg, model.make(jax.random.PRNGKey(7), c, jnp.float32)


def test_a_step_with_no_full_layer_is_the_same_step(monkeypatch):
    """`decode_step_slots` of the tiny byte model over rings and summary
    rows of 128: slots before, on and past a window's edge and the ring's
    seam, one at the cache's end and one that is not active, four steps.
    The kernel's cache and logits are the slices' bit for bit."""
    from ray_tpu.models import decode_step_slots, init_slot_cache
    from ray_tpu.models.generate import cache_arrays
    cfg, params = _tiny_eva()
    max_len = 512
    pos = jnp.array([5, 95, 127, 300, 511], jnp.int32)
    active = jnp.array([True, True, True, False, True])
    tok = jnp.array([3, 9, 27, 81, 243], jnp.int32) % cfg.vocab_size
    start = init_slot_cache(cfg, 5, max_len)
    start = dict({n: jax.random.normal(jax.random.PRNGKey(i), a.shape,
                                       a.dtype)
                  for i, (n, a) in enumerate(cache_arrays(start).items())},
                 pos=pos)
    assert {a.shape[-1] for a in cache_arrays(start).values()} == {128}

    def run():
        step = jax.jit(lambda t, c: decode_step_slots(params, t, c, active,
                                                      cfg))
        cache, out = start, []
        for i in range(4):
            logits, cache = step((tok + i) % cfg.vocab_size, cache)
            out.append(logits)
        return out, cache

    want_logits, want = run()
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    # the WRITES are what is held bit for bit here: the step's attention
    # stays the dense form (its kernel, which these rows of whole blocks
    # would engage too, sums in another order: tests/test_eva_attention.py)
    from ray_tpu.ops import cache_attention
    monkeypatch.setattr(cache_attention, "kernel_shape", lambda *_: False)
    got_logits, got = run()
    for a, b in zip(got_logits, want_logits):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for name in want:
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name]))
    assert np.asarray(got["pos"]).tolist() == [9, 99, 131, 300, 515]


# ------------------------------------------------------ the engine's counts

def _stream(core, prompt, n):
    r = core.handle({"op": "start", "prompt": prompt})
    assert "error" not in r, r
    toks = list(r["token"])
    while len(toks) < n:
        out = core.handle({"op": "next_chunk", "sid": r["sid"],
                           "max_tokens": n - len(toks)})
        assert "error" not in out, out
        toks += out["tokens"]
        if out.get("done"):
            break
    core.handle({"op": "end", "sid": r["sid"]})
    return toks[:n]


@pytest.mark.parametrize("path", ["kernel", "slices"])
def test_engine_counts_the_columns_and_the_calls_that_wrote_them(
        monkeypatch, path):
    """Two layers of keys and values, three slots of 128 rows: a step writes
    ``slots x arrays x layers`` = 12 columns, by 4 calls where the kernel
    engages (the interpreter here, the TPU in a served cell) and by 12
    slices elsewhere; the ``cache:rows`` span carries what ``stats()
    ["cache"]`` sums, and the tokens are the greedy reference's."""
    from greedy_reference import greedy_stream
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import (ContinuousBatchingEngine,
                                              DecodeSessionCore)
    from ray_tpu.util import tracing
    if path == "kernel":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    cfg = TransformerConfig.tiny(max_seq_len=128, attention_impl="reference",
                                 dtype=jnp.float32)
    core = DecodeSessionCore(cfg, max_len=128, seed=3,
                             engine=DecodeEngineConfig(max_slots=3))
    try:
        before = len([e for e in tracing.span_events()
                      if e["name"] == "cache:rows"])
        prompt = list(range(3, 20))
        assert _stream(core, prompt, 8) == greedy_stream(
            cfg, prompt, 8, max_len=128, seed=3)
        eng = core.engine
        for _ in range(200):          # the step in flight is read too
            if eng._flight is None:
                break
            import time
            time.sleep(0.01)
        cache = eng.stats()["cache"]
    finally:
        core.engine.shutdown()
    arrays_layers = 2 * cfg.n_layers
    assert cache["steps"] >= 7
    assert cache["column_writes"] == 3 * arrays_layers * cache["steps"]
    assert cache["column_write_calls"] == cache["steps"] * arrays_layers \
        * (1 if path == "kernel" else 3)
    spans = [e["args"] for e in tracing.span_events()
             if e["name"] == "cache:rows"][before:]
    # every sum of `stats()["cache"]` is the spans' (a span leaves out what
    # was zero; the bytes are states, the last span's)
    assert {"column_writes", "column_write_calls"} <= set(spans[-1])
    states = ("bytes_full", "bytes_ring", "bytes_state")
    for key, n in cache.items():
        if key in states:
            assert spans[-1].get(key, 0) == n, key
        elif key not in ("bytes", "bytes_per_position"):
            assert sum(a.get(key, 0) for a in spans) == n, key
