"""An MHA/GQA head that differs BY THE LAYER'S KIND: window layers with 2
key-value heads, a learned sink a query head and a rotary base of their own
beside full layers with 1 key-value head, queries and keys 12 wide and values
8, a third of a head rotated, values scaled; on the CPU at a tiny size (the
rehearsal's ``tiny-mimo``: window 4, a ring of 4 + 8 rows).

The oracle is the family's plain reference (`perfbench/families/
mimo_v2_flash/model.py`: float32, no cache, no ring, none of the program's
code), against `forward` and against every cached program, at every
position; then each mechanism left out or got wrong FAILS that same
comparison.  Streams through the engine are held to `models.generate`
(tests/greedy_reference.py).
"""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greedy_reference import greedy_stream
from perfbench import manifest as mf
from perfbench import weights
from ray_tpu.models import (cache_gather_slot, cache_insert_slot,
                            decode_step_slots, forward, init_kv_cache,
                            init_params, init_slot_cache, prefill,
                            prefill_chunk_jit)
from ray_tpu.models.generate import (cache_arrays, cache_bytes,
                                     cache_capacity, cache_rows,
                                     position_bytes, window_ring)
from ray_tpu.models.transformer import (count_params, decode_flops_per_token,
                                        kind_layers, stack_kinds)
from ray_tpu.ops.attention import multi_head_attention, reference_attention

TOL = 2e-5
WINDOW, ROOM = 4, 8
REHEARSAL = os.path.join(mf.ROOT, "perfbench", "testdata", "rehearsal")


def _config():
    m = mf.Manifest(os.path.join(REHEARSAL, "BENCHMARK.tiny-mimo.json"),
                    os.path.join(REHEARSAL, "traffic"))
    return m.config("tiny-mimo")


def _f32(model, c, **kw):
    return dataclasses.replace(
        model.model_config(c, "serve", attention_impl="reference", **kw),
        dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    c = _config()
    fam = mf.family_of(c).model
    cfg = _f32(fam, c)
    key = weights.key_of(2**31 + 41)
    params = fam.make(key, c, jnp.float32)
    toks = fam.tokens(jax.random.fold_in(key, 1), (2, 72), c)
    return cfg, params, toks, fam.logits(params, toks, c), c, fam


def test_the_kinds_differ_in_heads_base_and_sink(model):
    cfg, params = model[0], model[1]
    assert cfg.kinds == ("full", "window", "window", "full", "window")
    assert (cfg.kv_heads_of("full"), cfg.kv_heads_of("window")) == (1, 2)
    assert (cfg.head_dim, cfg.value_dim, cfg.rope_dim) == (12, 8, 4)
    assert (cfg.rope_base_of("full"), cfg.rope_base_of("window")) == (
        5e6, 1e4)
    assert cfg.sink_kinds == ("window",) and cfg.split_kv
    assert cfg.layer_segments == (
        ("dense_layers", 0, 1, "full"), ("layers", 0, 2, "window"),
        ("layers", 2, 1, "full"), ("layers", 3, 1, "window"))
    # a kind's own weights are stacked over its own layers of the run
    shapes = {k: v.shape for k, v in params["layers"].items()}
    assert shapes["wk_win"] == (3, 64, 2, 12) and shapes["wk"] == (1, 64, 1,
                                                                   12)
    assert shapes["wv_win"] == (3, 64, 2, 8) and shapes["wv"] == (1, 64, 1, 8)
    assert shapes["sink"] == (3, 4) and shapes["wo"] == (4, 4, 8, 64)
    assert "wk_win" not in params["dense_layers"] \
        and "sink" not in params["dense_layers"]
    assert stack_kinds(cfg, "wk") == ("full",) \
        and stack_kinds(cfg, "sink") == ("window",) \
        and stack_kinds(cfg, "wq") == ("full", "window") \
        and stack_kinds(cfg, "mlp_norm") is None
    # the last window layer is the third of ITS stack, the fourth of the run
    assert kind_layers(cfg, "layers", ("window",), 3) == 2
    assert kind_layers(cfg, "layers", ("full",), 3) == 1
    # the program's own initialiser makes the same tree, with its axes
    mine, axes = init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree_util.tree_map(lambda a: a.shape, mine) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    # a model whose kinds share their heads keeps ONE stack (the parent's)
    same = dataclasses.replace(cfg, window_kv_heads=None)
    assert not same.split_kv and stack_kinds(same, "wk") == ("full",
                                                             "window")


def test_counts_by_hand(model):
    cfg, params = model[0], model[1]
    full = 64 * 4 * 12 + 64 * 1 * (12 + 8) + 4 * 8 * 64
    window = 64 * 4 * 12 + 64 * 2 * (12 + 8) + 4 * 8 * 64
    expert = 3 * 64 * 32
    routed = 2 * expert + 64 * 8 + 8
    held = (full + 3 * 64 * 160) + 3 * (window + 4 + routed) \
        + (full + routed) + 5 * 2 * 64 + 2 * 256 * 64 + 64
    assert count_params(cfg) == held == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    # of a token's 2 experts a quarter is held here: half an expert
    active = (full + 3 * 64 * 160) + 3 * window + full \
        + 4 * (0.5 * expert + 64 * 8) + 256 * 64
    # at depth 50 a window layer reads its window, a full layer all 50; a
    # query-key product is 12 wide, a probability-value product 8
    assert decode_flops_per_token(cfg, 50) == \
        2 * active + 2 * 4 * (12 + 8) * (2 * 50 + 3 * WINDOW)


def test_each_array_of_a_cache_has_a_row_of_its_own(model):
    cfg = model[0]
    assert cache_rows(cfg) == {"k": (1, 12), "v": (1, 8),
                               "k_win": (2, 12), "v_win": (2, 8)}
    cache = init_slot_cache(cfg, 3, 64)
    shapes = {n: a.shape for n, a in cache_arrays(cache).items()}
    assert shapes == {"k": (2, 3, 1, 12, 64), "v": (2, 3, 1, 8, 64),
                      "k_win": (3, 3, 2, 12, 12), "v_win": (3, 3, 2, 8, 12)}
    assert window_ring(cfg, 64) == WINDOW + ROOM and cache_capacity(cache) == 64
    # float32 here: a full layer's position is 1 x 20 values, a ring's 2 x 20
    assert position_bytes(cfg) == {"full": 80, "ring": 160, "state": 0}
    assert cache_bytes(cache) == {"full": 2 * 3 * 64 * 80,
                                  "ring": 3 * 3 * 12 * 160, "state": 0}
    served = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    assert position_bytes(served) == {"full": 40, "ring": 80, "state": 0}


def test_plain_attention_with_a_sink_and_values_of_another_width():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 4, 12))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 2, 12))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 2, 8))
    sink = jnp.asarray([0.5, -1.0, 2.0, 0.0])
    got = multi_head_attention(q, k, v, window=4, sink=sink)
    assert got.shape == (1, 9, 4, 8)
    for i in (0, 3, 8):
        lo = max(0, i - 3)
        for h in range(4):
            z = (q[0, i, h] @ k[0, lo:i + 1, h // 2].T) / np.sqrt(12.0)
            e = jnp.exp(z)
            want = (e / (e.sum() + jnp.exp(sink[h]))) @ v[0, lo:i + 1, h // 2]
            assert float(jnp.abs(got[0, i, h] - want).max()) < 1e-5
    # a sink far below every score is no sink
    none = multi_head_attention(q, k, v, sink=jnp.full((4,), -1e9))
    assert float(jnp.abs(none - reference_attention(q, k, v)).max()) < 1e-6
    # the flash kernel has neither: asked for by name it is refused
    for kw in (dict(sink=sink), dict()):
        with pytest.raises(NotImplementedError, match="reference"):
            multi_head_attention(q, k, v, impl="flash", **kw)


def test_forward_is_the_familys_reference(model):
    cfg, params, toks, want = model[:4]
    with jax.default_matmul_precision("highest"):
        got = forward(params, toks, cfg)
    assert float(jnp.abs(got - want).max()) < TOL


def _walk(cfg, params, toks, widths, max_len=128):
    """Batch-1 walk of ``toks`` [1, s] in chunks of ``widths``."""
    cache, off, out = init_kv_cache(cfg, 1, max_len), 0, {}
    for take in widths:
        lg, cache = prefill_chunk_jit(params, toks[:, off:off + take], cache,
                                      cfg=cfg)
        off += take
        out[off - 1] = lg[0]
    return out, cache


@pytest.mark.parametrize("widths", [
    [8] * 9, [1] + [8] * 8 + [1] * 7, [3, 8, 5, 8, 8, 1, 7, 8, 8, 8, 8],
    [1] * 30])
def test_chunks_across_the_rings_seam_are_the_reference(model, widths):
    """Sessions of up to 72 positions cross the window of 4 and wrap the
    ring of 12 rows six times; widths and offsets that are no multiple of
    the ring make chunks straddle its seam."""
    cfg, params, toks, want = model[:4]
    with jax.default_matmul_precision("highest"):
        got, cache = _walk(cfg, params, toks[:1], widths)
    assert int(cache["pos"]) == sum(widths)
    for p, lg in got.items():
        assert float(jnp.abs(lg - want[0, p]).max()) < TOL, p


def test_slot_decode_is_the_reference_at_every_position(model):
    """Two slots at different depths decode side by side, a third that is
    not active between them: every logit is the reference's full
    forward's."""
    cfg, params, toks, want = model[:4]
    slots = init_slot_cache(cfg, 3, 128)
    insert = jax.jit(cache_insert_slot)
    step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
    pos = [21, 0, 6]
    with jax.default_matmul_precision("highest"):
        _, a = _walk(cfg, params, toks[:1], [8, 8, 5])
        _, b = _walk(cfg, params, toks[1:], [6])
        slots = insert(insert(slots, a, jnp.int32(0)), b, jnp.int32(2))
        active = jnp.asarray([True, False, True])
        rows = (0, None, 1)
        for _ in range(35):
            tok = jnp.asarray([toks[0, pos[0]], 7, toks[1, pos[2]]])
            lg, slots = step(params, tok, slots, active)
            for s in (0, 2):
                assert float(jnp.abs(lg[s] - want[rows[s], pos[s]]).max()) \
                    < TOL, pos
                pos[s] += 1


@pytest.mark.parametrize("s", [3, 12, 13, 30])
def test_whole_prompt_prefill_then_decode(model, s):
    cfg, params, toks, want = model[:4]
    with jax.default_matmul_precision("highest"):
        lg, cache = jax.jit(lambda p, t, c: prefill(p, t, cfg, c))(
            params, toks[:1, :s], init_kv_cache(cfg, 1, 128))
        assert float(jnp.abs(lg[0] - want[0, s - 1]).max()) < TOL
        for p in range(s, s + 10):
            lg, cache = prefill_chunk_jit(params, toks[:1, p:p + 1], cache,
                                          cfg=cfg)
            assert float(jnp.abs(lg[0] - want[0, p]).max()) < TOL, p


def _others(cache, slot):
    """Every array's rows of every slot but ``slot``, as numpy."""
    keep = [i for i in range(cache["pos"].shape[0]) if i != slot]
    return {n: np.asarray(a)[:, keep] for n, a in cache_arrays(cache).items()}


def _same(a, b):
    return all((a[n] == b[n]).all() for n in a)


def test_what_one_slot_does_leaves_every_other_slots_arrays_bit_for_bit(
        model):
    """A padded remainder, an inactive slot, a slot insert and a gather:
    each touches its own slot's arrays (of four shapes) and nothing else."""
    cfg, params, toks, want = model[:4]
    # a padded remainder: 3 real rows of 8; what lies ahead of pos harms
    # nothing and the logits are row 2's
    cache = init_kv_cache(cfg, 1, 128)
    off = 0
    with jax.default_matmul_precision("highest"):
        for n_valid in (8, 8, 3):
            buf = np.full((1, 8), 99, np.int32)
            buf[0, :n_valid] = np.asarray(toks[0, off:off + n_valid])
            lg, cache = prefill_chunk_jit(params, buf, cache, cfg=cfg,
                                          n_valid=np.int32(n_valid))
            off += n_valid
            assert float(jnp.abs(lg[0] - want[0, off - 1]).max()) < TOL
    assert int(cache["pos"]) == 19
    slots = init_slot_cache(cfg, 3, 128)
    for n, a in cache_arrays(slots).items():       # no slot is all zeros
        slots[n] = jax.random.normal(jax.random.PRNGKey(len(n)), a.shape)
    before = _others(slots, 1)
    slots = jax.jit(cache_insert_slot)(slots, cache, jnp.int32(1))
    assert _same(before, _others(slots, 1))
    for n, a in cache_arrays(cache).items():
        assert (np.asarray(slots[n])[:, 1] == np.asarray(a)[:, 0]).all(), n
    # a step in which only slot 1 is active: the others' rows below their
    # pos stay as they were (an inactive slot writes AT its pos: set them
    # past the rows compared)
    slots["pos"] = jnp.asarray([100, 19, 100], jnp.int32)
    step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
    held = _others(slots, 1)
    with jax.default_matmul_precision("highest"):
        lg, after = step(params, jnp.asarray([5, toks[0, 19], 9]), slots,
                         jnp.asarray([False, True, False]))
    assert float(jnp.abs(lg[1] - want[0, 19]).max()) < TOL
    assert [int(p) for p in after["pos"]] == [100, 20, 100]
    now = _others(after, 1)
    col = 100 % (WINDOW + ROOM)
    for n in held:      # all but the one column an inactive slot writes
        cut = col if n.endswith("_win") else 100
        assert (np.delete(held[n], cut, -1) == np.delete(now[n], cut,
                                                         -1)).all(), n
    # a gather copies the donor's arrays and changes nothing
    seeded = jax.jit(cache_gather_slot)(after, jnp.int32(1), jnp.int32(20))
    assert int(seeded["pos"]) == 20
    for n, a in cache_arrays(seeded).items():
        assert (np.asarray(a)[:, 0] == np.asarray(after[n])[:, 1]).all(), n
    with jax.default_matmul_precision("highest"):
        for p in range(20, 30):
            lg, seeded = prefill_chunk_jit(params, toks[:1, p:p + 1], seeded,
                                           cfg=cfg)
            assert float(jnp.abs(lg[0] - want[0, p]).max()) < TOL, p


def _wrong(name, cfg, params):
    """The program with one mechanism left out or got wrong."""
    if name == "no_sink":
        layers = {k: v for k, v in params["layers"].items() if k != "sink"}
        return dataclasses.replace(cfg, sink_kinds=()), dict(params,
                                                             layers=layers)
    if name == "no_value_scale":
        return dataclasses.replace(cfg, value_scale=1.0), params
    if name == "whole_head_rotated":
        return dataclasses.replace(cfg, rope_fraction=1.0), params
    assert name == "window_heads_on_a_full_layer"
    # every layer with the window layers' 2 key-value heads: a full
    # layer's second head is drawn (a uniform model would hold one), and
    # query heads 2 and 3 meet it
    cfg = dataclasses.replace(cfg, n_kv_heads=2, window_kv_heads=None)
    out = dict(params)
    for run in ("dense_layers", "layers"):
        tree = dict(params[run])
        kinds = [k for r, first, n, k in cfg.layer_segments if r == run
                 for _ in range(n)]
        for name, win in (("wk", "wk_win"), ("wv", "wv_win")):
            full = iter(tree[name])
            window = iter(tree.pop(win, ()))
            extra = jax.random.normal(jax.random.PRNGKey(3),
                                      tree[name].shape) / 8.0
            tree[name] = jnp.stack([
                jnp.concatenate([next(full), extra[0]], axis=-2)
                if k == "full" else next(window) for k in kinds])
        out[run] = tree
    return cfg, out


@pytest.mark.parametrize("name", ["no_sink", "no_value_scale",
                                  "whole_head_rotated",
                                  "window_heads_on_a_full_layer"])
def test_each_mechanism_left_out_fails_the_comparison(model, name):
    """The comparison that passes at 2e-5 reads a thousand times that, in
    `forward` and through the cache alike, once the sink is left out, the
    values are not scaled, the whole head is rotated, or a full layer runs
    with the window layers' key-value heads."""
    cfg, params, toks, want = model[:4]
    bad_cfg, bad_params = _wrong(name, cfg, params)
    with jax.default_matmul_precision("highest"):
        got = forward(bad_params, toks, bad_cfg)
        walked, _ = _walk(bad_cfg, bad_params, toks[:1], [8, 8, 8, 1, 1])
    assert float(jnp.abs(got - want).max()) > 1000 * TOL
    assert float(jnp.abs(walked[25] - want[0, 25]).max()) > 1000 * TOL
    assert float(jnp.abs(walked[25] - got[0, 25]).max()) < TOL


def test_what_cannot_be_served_is_refused_with_a_message(model):
    cfg, params, toks = model[:3]
    odd = dataclasses.replace(cfg, rope_fraction=0.25)      # 3 dims
    with pytest.raises(ValueError, match="even"):
        prefill_chunk_jit(params, toks[:1, :4], init_kv_cache(odd, 1, 64),
                          cfg=odd)
    three = dataclasses.replace(cfg, window_kv_heads=3)
    with pytest.raises(ValueError, match="3 key-value heads"):
        prefill_chunk_jit(params, toks[:1, :4], init_kv_cache(three, 1, 64),
                          cfg=three)
    with pytest.raises(ValueError, match="window_chunk"):
        prefill_chunk_jit(params, toks[:1, :9], init_kv_cache(cfg, 1, 64),
                          cfg=cfg)
    latent = dataclasses.replace(
        cfg, attention="mla", layer_kinds=None, q_lora_rank=8,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
        first_dense_layers=1)
    with pytest.raises(NotImplementedError, match="latent"):
        prefill_chunk_jit(params, toks[:1, :4], init_kv_cache(
            dataclasses.replace(latent, sink_kinds=(), window_kv_heads=None,
                                rope_fraction=1.0, value_scale=1.0), 1, 64),
            cfg=latent)


# ------------------------------------------------------- through the engine

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


PROMPTS = [list(range(3, 40)), list(range(50, 59)), list(range(100, 130))]


def test_engine_counts_bytes_by_the_rows_kind(model, monkeypatch):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import (ContinuousBatchingEngine,
                                              DecodeSessionCore)
    from ray_tpu.util import tracing
    cfg, params = model[0], model[1]
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    core = DecodeSessionCore(cfg, max_len=96, params=params,
                             engine=DecodeEngineConfig(max_slots=2))
    try:
        eng = core.engine
        assert eng.ecfg.prefill_chunk_tokens == ROOM
        want = [greedy_stream(cfg, p, 20, max_len=96, params=params)
                for p in PROMPTS]
        assert [_stream(core, p, 20) for p in PROMPTS] == want
        st = eng.stats()
        assert st["cache_copies"] == 0
        cache = st["cache"]
        # float32: a full layer's position 80 B, a ring's row 160
        assert cache["bytes_full"] == 2 * 2 * 96 * 80
        assert cache["bytes_ring"] == 3 * 2 * 12 * 160
        assert cache["bytes"] == cache["bytes_full"] + cache["bytes_ring"]
        assert cache["bytes_per_position"] == 2 * 80   # a ring grows by none
        # every prompt is past the window: a step of a slot at depth p
        # reads p + 1 rows of 2 full layers and 4 of 3 window layers
        assert cache["steps"] == st["steps"] >= 3 * 19
        depth = cache["rows_if_full"] // 5
        assert cache["rows_read"] == 2 * depth + 3 * WINDOW * st["tokens"]
        assert cache["bytes_read"] == 2 * depth * 80 \
            + 3 * WINDOW * st["tokens"] * 160
        # ... beside five layers of rows at the widest, a ring's 160 B
        assert cache["bytes_if_uniform"] == 5 * depth * 160
        span = [e for e in tracing.span_events()
                if e["name"] == "cache:rows"][-1]["args"]
        assert span["steps"] == 1 and span["bytes_if_uniform"] % 800 == 0
        d = span["bytes_if_uniform"] // 800
        assert span["bytes_read"] == 2 * d * 80 + 3 * WINDOW * 160
        assert span["rows_read"] == 2 * d + 3 * WINDOW
    finally:
        core.engine.shutdown()


def test_a_model_of_one_kind_of_row_reads_all_its_bytes():
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = TransformerConfig.tiny(dtype=jnp.float32)
    core = DecodeSessionCore(cfg, max_len=64, seed=0,
                             engine=DecodeEngineConfig(max_slots=2))
    try:
        _stream(core, list(range(9)), 6)
        cache = core.engine.stats()["cache"]
        assert cache["bytes_read"] == cache["bytes_if_uniform"] \
            == cache["rows_read"] * 2 * 2 * 16 * 4 > 0
    finally:
        core.engine.shutdown()


def test_prefix_reuse_takes_the_new_shapes(model):
    """A shared prefix is gathered from a donor's four arrays (while the
    donor's ring still holds what the prefix needs): the streams are
    `generate`'s."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    params = model[1]
    # a window of 8 (the ring still 12 rows): the engine decodes ahead of
    # its caller by its queue's depth, and a donor of 5 tokens that may
    # run to 7 still fits it
    cfg = dataclasses.replace(model[0], sliding_window=8, window_chunk=4)
    core = DecodeSessionCore(cfg, max_len=96, params=params,
                             engine=DecodeEngineConfig(
                                 max_slots=2, prefix_cache_min_tokens=2,
                                 token_queue_depth=2))
    try:
        short = [9, 8, 7, 6]
        a = _stream(core, short + [1], 3)           # donor ends at 4 + 1 + 2
        assert a == greedy_stream(cfg, short + [1], 3, max_len=96,
                                  params=params)
        b = _stream(core, short + [2, 3], 6)
        assert core.engine.stats()["prefix"]["applied_hits"] == 1
        assert b == greedy_stream(cfg, short + [2, 3], 6, max_len=96,
                                  params=params)
        system = list(range(40, 60))                 # past the window
        c = _stream(core, system + [1], 12)
        d = _stream(core, system + [2, 3], 6)
        assert core.engine.stats()["prefix"]["applied_hits"] == 1  # refused
        assert (c, d) == tuple(greedy_stream(
            cfg, system + tail, n, max_len=96, params=params)
            for tail, n in (([1], 12), ([2, 3], 6)))
        assert core.engine.stats()["cache_copies"] == 0
    finally:
        core.engine.shutdown()
