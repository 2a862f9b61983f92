"""Layers that hold NO cache of their own (`models/generate.py`): a
``"cross"`` layer attends the rows the last full layer wrote, a ``"gmu"``
layer gates the scan output the last ``"mamba"`` layer made; the EIGHTH kind
of cache state (a selective scan's, ``s_mamba`` / ``conv_mamba``); and the
STATELESS TAIL, which the chunk programs run on one row a lane.  Every
cached program (whole-prompt prefill, chunks that wrap the ring and pad,
lanes with a lane that stands, slots at depths of their own) against the
FAMILY's plain reference logits; the cache's shapes; `CacheTraffic`'s holders
and readers; the tail on one row against the tail on every row; what a
configuration is refused for; what a prefix donor must be.

The model is the rehearsal's ``tiny-phi4flash`` in float32: 8 layers ``mamba,
window, mamba, full, gmu, cross, gmu, cross``, window 8, chunks of 4."""

import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse
from ray_tpu.models import (CacheTraffic, cache_insert_slot,
                            decode_step_slots, forward, init_kv_cache,
                            init_slot_cache, prefill, prefix_holds)
from ray_tpu.models.generate import (_check_decodable, _check_state_rewind,
                                     _state_kind, array_dtype, cache_bytes,
                                     cache_capacity, position_bytes,
                                     prefill_chunk, prefill_chunked,
                                     prefill_lanes_jit, prefill_lanes_step)
from ray_tpu.models.transformer import (TransformerConfig, check_kinds,
                                        count_params, stack_kinds)

G = importlib.import_module("ray_tpu.models.generate")    # (the package
#   names a function so)
MAX_LEN, CHUNK, T = 64, 4, 46
TOL = dict(atol=2e-4, rtol=0)


@pytest.fixture(scope="module")
def model():
    m = mf.Manifest(os.path.join(mf.ROOT, rehearse.REHEARSAL,
                                 "BENCHMARK.tiny-phi4flash.json"),
                    os.path.join(mf.ROOT, rehearse.REHEARSAL, "traffic"))
    c = m.config("tiny-phi4flash")
    c = dict(c, precision={"serve": {"params": "float32",
                                     "compute": "float32"}})
    fam = mf.family_of(c).model
    cfg = fam.model_config(c, "serve", window_chunk=CHUNK)
    params = fam.make(jax.random.PRNGKey(3), c, jnp.float32)
    toks = fam.tokens(jax.random.PRNGKey(4), (2, T), c)
    return cfg, params, toks, fam.logits(params, toks, c)


def test_the_plain_forward_is_the_references(model):
    cfg, params, toks, want = model
    np.testing.assert_allclose(forward(params, toks, cfg), want, **TOL)
    assert count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert cfg.kinds == ("mamba", "window", "mamba", "full", "gmu", "cross",
                         "gmu", "cross") and cfg.stateless_tail == 4


def test_the_cache_holds_one_layer_of_rows_and_nothing_for_the_tail(model):
    cfg = model[0]
    cache = init_slot_cache(cfg, 3, MAX_LEN)
    shapes = {k: v.shape for k, v in cache.items() if k != "pos"}
    assert shapes == {
        "k": (1, 3, 1, 32, MAX_LEN), "v": (1, 3, 1, 32, MAX_LEN),
        "k_win": (1, 3, 1, 32, 12), "v_win": (1, 3, 1, 32, 12),
        "s_mamba": (2, 3, 1, 8, 128), "conv_mamba": (2, 3, 1, 3, 128)}
    assert array_dtype(cfg, "s_mamba") == jnp.float32
    assert cache["s_mamba"].dtype == jnp.float32
    assert _state_kind("s_mamba") == _state_kind("conv_mamba") == "mamba"
    assert cache_capacity(cache, cfg) == MAX_LEN
    assert position_bytes(cfg)["mamba"] == 8 * 128 * 4 + 3 * 128 * 4
    assert cache_bytes(cache)["mamba"] == 2 * 3 * position_bytes(cfg)["mamba"]
    assert stack_kinds(cfg, "wq") == ("full", "window", "cross")
    assert stack_kinds(cfg, "wk") == stack_kinds(cfg, "bv") == (
        "full", "window")
    assert stack_kinds(cfg, "mamba_x") == ("mamba",)
    assert stack_kinds(cfg, "gmu_in") == ("gmu",)


def test_traffic_tells_a_row_sets_holders_from_its_readers(model):
    cfg = model[0]
    cache = init_slot_cache(cfg, 3, MAX_LEN)
    traffic = CacheTraffic(cache, cfg, CHUNK)
    full = traffic._sets["k"]
    assert cache["k"].shape[0] == 1 and full.layers == 3    # 1 holds, 3 read
    assert traffic._sets["k_win"].layers == 1
    sums = traffic.step([10, 30])._asdict()
    row, ring = position_bytes(cfg)["full"], position_bytes(cfg)["ring"]
    # a slot at position p attends p + 1 rows on EACH reading layer, and no
    # more than the window on the window layer
    assert sums["rows_read"] == 3 * (11 + 31) + 2 * 8
    assert sums["shared_bytes_read"] == 3 * (11 + 31) * row
    assert sums["bytes_read"] == sums["shared_bytes_read"] + 2 * 8 * ring
    # two scans' states a live slot, read and written
    assert sums["state_rows"] == 2 * 2
    assert sums["state_bytes_moved"] == 2 * 2 * 2 * position_bytes(
        cfg)["mamba"]
    # what a step WRITES is by the holders: one column a slot an array
    assert sums["column_writes"] == 4 * 3
    assert traffic.tail_rows(4) == 4 and traffic.tail_rows(1) == 1
    plain = TransformerConfig.tiny(dtype=jnp.float32)
    other = CacheTraffic(init_slot_cache(plain, 2, 32), plain, 8)
    assert other.tail_rows(4) == 0
    assert other.step([3])._asdict()["shared_bytes_read"] == 0
    assert other._sets["k"].layers == plain.n_layers


def test_whole_prompt_prefill_then_slots(model):
    cfg, params, toks, want = model
    lg, cache = prefill(params, toks[:1, :21], cfg,
                        init_kv_cache(cfg, 1, MAX_LEN))
    np.testing.assert_allclose(lg[0], want[0, 20], **TOL)
    slots = cache_insert_slot(init_slot_cache(cfg, 2, MAX_LEN), cache,
                              jnp.int32(1))
    for t in range(21, 30):
        lg, slots = decode_step_slots(
            params, jnp.asarray([5, int(toks[0, t])]), slots,
            jnp.asarray([False, True]), cfg)
        np.testing.assert_allclose(lg[1], want[0, t], **TOL)


def test_chunks_that_wrap_and_pad_then_slots_at_depths_of_their_own(model):
    """Chunked prefill (chunks of 4 over a ring of 12 that wraps, a padded
    last chunk, the tail on ONE row) and then decoding through the cache,
    two slots at depths of their own and one that stands, against the
    reference's one full forward."""
    cfg, params, toks, want = model
    slots = init_slot_cache(cfg, 3, MAX_LEN)
    depth = (27, 18)
    for i, n in enumerate(depth):
        lg, cache = prefill_chunked(params, toks[i:i + 1, :n], cfg,
                                    init_kv_cache(cfg, 1, MAX_LEN),
                                    chunk=CHUNK)
        np.testing.assert_allclose(lg[0], want[i, n - 1], **TOL)
        slots = cache_insert_slot(slots, cache, jnp.int32(2 * i))
    before = {k: np.asarray(v[:, 1]) for k, v in slots.items() if k != "pos"}
    active = jnp.asarray([True, False, True])
    for j in range(12):
        tok = jnp.asarray([int(toks[0, depth[0] + j]), 9,
                           int(toks[1, depth[1] + j])])
        lg, slots = decode_step_slots(params, tok, slots, active, cfg)
        np.testing.assert_allclose(lg[0], want[0, depth[0] + j], **TOL)
        np.testing.assert_allclose(lg[2], want[1, depth[1] + j], **TOL)
    # the slot that stood kept its states bit for bit (its rows may be
    # written ahead of its position: harmless)
    for name in ("s_mamba", "conv_mamba"):
        assert np.array_equal(np.asarray(slots[name][:, 1]), before[name])
    assert list(np.asarray(slots["pos"])) == [39, 0, 30]


def test_lanes_with_one_that_stands(model):
    cfg, params, toks, want = model
    lanes = init_slot_cache(cfg, 3, MAX_LEN)
    prompts = [(np.asarray(toks[:1, :22]), 0), None,
               (np.asarray(toks[1:, :9]), 0)]
    ends = {0: 21, 2: 8}
    while any(p is not None for p in prompts):
        logits, lanes, moved = prefill_lanes_step(
            prefill_lanes_jit, params, prompts, lanes, cfg, chunk=CHUNK,
            capacity=MAX_LEN)
        for i, (p, mv) in enumerate(zip(prompts, moved)):
            if p is not None and mv[0] >= p[0].shape[1]:
                np.testing.assert_allclose(
                    logits[i], want[i // 2, ends[i]], **TOL)
                prompts[i] = None
            elif p is not None:
                prompts[i] = (p[0], mv[0])
    assert not np.asarray(lanes["s_mamba"][:, 1]).any()     # it stood


def test_the_tail_on_one_row_gives_the_logits_of_the_tail_on_every_row(
        model, monkeypatch):
    cfg, params, toks, _ = model
    cache = init_kv_cache(cfg, 1, MAX_LEN)
    n_valid = jnp.int32(3)
    cut = prefill_chunk(params, toks[:1, :CHUNK], cache, cfg, n_valid)
    seen = []
    whole = G._attend_cached

    def spy(*a, **kw):
        seen.append((a[2].shape[1], kw.get("span")))
        return whole(*a, **kw)
    monkeypatch.setattr(G, "_attend_cached", spy)
    prefill_chunk(params, toks[:1, :CHUNK], cache, cfg, n_valid)
    assert seen == [(CHUNK, (0, 4)), (1, (4, 8))]
    monkeypatch.setattr(TransformerConfig, "stateless_tail",
                        property(lambda self: 0))
    del seen[:]
    full = prefill_chunk(params, toks[:1, :CHUNK], cache, cfg, n_valid)
    assert seen == [(CHUNK, None)]
    np.testing.assert_allclose(cut[0], full[0], atol=1e-5)
    for name in cut[1]:
        np.testing.assert_allclose(cut[1][name], full[1][name], atol=1e-6)


@pytest.mark.parametrize("kinds,what", [
    (("gmu", "mamba", "full"), "'gmu' layer reads"),
    (("mamba", "cross", "full"), "'cross' layer reads"),
    (("mamba", "window", "gmu"), None),         # nothing holds max_len rows
])
def test_what_is_refused(model, kinds, what):
    cfg = dataclasses.replace(model[0], n_layers=3, layer_kinds=kinds)
    if what is None:
        check_kinds(cfg)
        with pytest.raises(NotImplementedError, match="full-attention"):
            _check_decodable(cfg)
    else:
        with pytest.raises(ValueError, match=what):
            check_kinds(cfg)
    with pytest.raises(ValueError, match="turns nothing"):
        check_kinds(dataclasses.replace(model[0], pos_emb="rope"))
    with pytest.raises(ValueError, match="mamba_state"):
        check_kinds(dataclasses.replace(model[0], mamba_state=0))


def test_a_state_cannot_be_taken_back_and_a_donor_stands_at_the_prefix(
        model):
    cfg = model[0]
    with pytest.raises(ValueError, match="selective-scan"):
        _check_state_rewind(cfg, "a chunk window set back")
    # the donor stands AT the prefix, and no chunk window is set back
    assert prefix_holds(cfg, 20, 20, 30, CHUNK, MAX_LEN)
    assert not prefix_holds(cfg, 21, 20, 30, CHUNK, MAX_LEN)
    assert not prefix_holds(cfg, None, 20, 30, CHUNK, MAX_LEN)
    assert not prefix_holds(cfg, 21, 21, MAX_LEN - 1, CHUNK, MAX_LEN)
    # a prompt that ends within a chunk of max_len is refused, not rewound
    params, toks = model[1], model[2]
    with pytest.raises(ValueError, match="selective-scan"):
        prefill_chunked(params, jnp.tile(toks[:1], (1, 2))[:, :MAX_LEN - 3],
                        cfg, init_kv_cache(cfg, 1, MAX_LEN - 2), chunk=CHUNK)


def test_a_chunk_that_is_not_its_prompts_last_runs_no_tail_and_no_head(
        model):
    """``tail=False``: the program ends behind the last layer that holds
    state: the cache it leaves is the whole program's to the bit, its logits
    are zeros; the host walks choose it for every chunk but a prompt's last,
    and a warm-up of the lanes program (no lane advances) warms both."""
    cfg, params, toks, want = model
    cache = init_kv_cache(cfg, 1, MAX_LEN)
    whole = prefill_chunk(params, toks[:1, :CHUNK], cache, cfg, jnp.int32(4))
    short = prefill_chunk(params, toks[:1, :CHUNK], cache, cfg, jnp.int32(4),
                          tail=False)
    assert not np.asarray(short[0]).any() and np.asarray(whole[0]).any()
    for name in whole[1]:
        assert np.array_equal(np.asarray(whole[1][name]),
                              np.asarray(short[1][name])), name
    tails = []

    def spy(fn):
        def call(*a, **kw):
            tails.append(kw.get("tail", True))
            return fn(*a, **kw)
        return call
    lg, _ = prefill_chunked(params, toks[:1, :14], cfg, cache, chunk=CHUNK,
                            _jitted=spy(G.prefill_chunk_jit))
    assert tails == [False, False, False, True]
    np.testing.assert_allclose(lg[0], want[0, 13], **TOL)
    del tails[:]
    lanes = init_slot_cache(cfg, 2, MAX_LEN)
    _, lanes, _ = prefill_lanes_step(      # the engine's warm-up: both
        spy(prefill_lanes_jit), params, [None, None], lanes, cfg,
        chunk=CHUNK, capacity=MAX_LEN)
    assert tails == [False, True]
    del tails[:]
    prompts = [(np.asarray(toks[:1, :9]), 0), (np.asarray(toks[1:, :4]), 0)]
    logits, lanes, moved = prefill_lanes_step(
        spy(prefill_lanes_jit), params, prompts, lanes, cfg, chunk=CHUNK,
        capacity=MAX_LEN)
    assert tails == [True]          # lane 1 ends here: every lane's tail
    np.testing.assert_allclose(logits[1], want[1, 3], **TOL)
    # a model without such a tail is never told of one
    plain = TransformerConfig.tiny(dtype=jnp.float32)
    assert G._tail_of(plain, CHUNK, [False]) == {}
    assert G._tail_of(cfg, CHUNK, [False]) == {"tail": False}
    assert G._tail_of(cfg, CHUNK, [False, True]) == {}
    assert G._tail_of(cfg, 1, [False]) == {}
