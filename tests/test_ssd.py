"""The state-space recurrence (`ops/ssd.py`), the layer that runs it BESIDE
attention off one norm (``"ssm+full"``, `models/transformer.py`) and the
seventh kind of cache state (`models/generate.py`): the op's three forms
against a NumPy statement of the recurrence (one token repeated, a chunk from
a carried state across two and three boundaries with a ragged last chunk, a
sequence); groups of heads, each reading ITS key and query; the gate before a
norm by group; the convolution's bias and carried inputs; rows that stand,
which keep a state bit for bit; the step kernel through the interpreter; the
fourteen multipliers, each of which moves the logits; every cached program
(whole-prompt prefill, chunks, lanes with a lane that stands, slots at depths
of their own) and the engine against the FAMILY's plain reference; the slot
insert and gather, which carry state, convolution inputs and rows together;
the prefix reuse, a preempted request's replay and what a full cache says,
for a layer that has both a state and rows; four planted faults that each
FAIL; the existing kinds' lowered text, unchanged; and what a configuration
is refused for.

The model is the rehearsal's ``tiny-falcon-h1`` in float32 (3 layers, each 4
state heads of 8 with a state of 16 in 2 groups and a convolution of 4 taps
with a bias beside 4 query heads over 2 key-value heads of 16).  The served
path in bfloat16 against the reference is
tests/benchmark/test_perfbench_family_falcon_h1.py's.
"""

import dataclasses
import functools
import hashlib
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
from ray_tpu.models import (CacheTraffic, cache_gather_slot,
                            cache_insert_slot, decode_step_slots, forward,
                            init_kv_cache, init_params, init_slot_cache,
                            prefill, prefill_chunk_jit, prefill_lanes_jit,
                            prefix_holds)
from ray_tpu.models.generate import (_state_kind, array_dtype, cache_bytes,
                                     cache_capacity, cache_rows,
                                     position_bytes, prefill_chunk_step,
                                     prefill_lanes, prefill_lanes_step)
from ray_tpu.models.transformer import (count_params, decode_flops_per_token,
                                        stack_kinds)
from ray_tpu.ops import ssd
from ray_tpu.ops.short_conv import short_conv
from ray_tpu.serve.decode_session import ContinuousBatchingEngine
from ray_tpu.util import device_profile

T, MAX_LEN, CHUNK = 150, 192, 32
TOL = dict(atol=1e-3, rtol=0)
KIND = "ssm+full"


def _config(name):
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def world():
    c = _config("tiny-falcon-h1")
    model = mf.family_of(c).model
    cfg = dataclasses.replace(model.model_config(c, "serve"),
                              dtype=jnp.float32, param_dtype=jnp.float32,
                              remat=False)
    params = jax.jit(lambda k: model.make(k, c, jnp.float32))(
        jax.random.PRNGKey(7))
    toks = model.tokens(jax.random.PRNGKey(8), (2, T), c)
    # the FAMILY's plain reference: what every program below is held to
    want = jax.jit(lambda p, t: model.logits(p, t, c))(params, toks)
    return types.SimpleNamespace(
        c=c, model=model, cfg=cfg, params=params, toks=toks,
        want=np.asarray(want),
        step=jax.jit(functools.partial(decode_step_slots, cfg=cfg)))


# ------------------------------------------------------------------ the op

def _inputs(seed, b=2, s=75, h=4, p=8, g=2, n=16):
    """(x, B, C, dt, a, D) as a mixer hands them to the recurrence."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    B, C = (jax.random.normal(k, (b, s, g, n)) for k in ks[1:3])
    dt, a = ssd.gates(jax.random.normal(ks[3], (b, s, h)) - 2.0,
                      jnp.zeros((h,)), jnp.log(jnp.linspace(1.0, 16.0, h)))
    return x, B, C, dt, a, jax.random.normal(ks[4], (h,))


def _numpy_rule(x, B, C, dt, a, D, state=None):
    """The recurrence as the module's docstring states it, float64."""
    x, B, C, dt, a, D = (np.asarray(t, np.float64)
                         for t in (x, B, C, dt, a, D))
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    S = np.zeros((b, h, n, p)) if state is None else np.array(state,
                                                              np.float64)
    y = np.zeros((b, s, h, p))
    for t in range(s):
        for j in range(h):
            key, query = B[:, t, j // (h // g)], C[:, t, j // (h // g)]
            S[:, j] = np.exp(a[:, t, j])[:, None, None] * S[:, j] \
                + key[:, :, None] * (dt[:, t, j, None] * x[:, t, j])[:, None]
            y[:, t, j] = np.einsum("bnp,bn->bp", S[:, j], query) \
                + D[j] * x[:, t, j]
    return y, S


def test_step_repeated_and_sequence_are_the_recurrence():
    x, B, C, dt, a, D = _inputs(0, s=21)
    want, last = _numpy_rule(x, B, C, dt, a, D)
    y, state = ssd.sequence(x, B, C, dt, a, D)
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_allclose(state, last, atol=2e-5)
    state = jnp.zeros_like(state)
    for t in range(x.shape[1]):
        y, state = ssd.step(x[:, t], B[:, t], C[:, t], dt[:, t], a[:, t], D,
                            state)
        np.testing.assert_allclose(y, want[:, t], atol=2e-5)
    np.testing.assert_allclose(state, last, atol=2e-5)


@pytest.mark.parametrize("chunk", [25, 32, 64])
def test_chunk_from_a_carried_state_is_the_recurrence(chunk):
    """75 tokens: three whole chunks of 25 (two boundaries crossed with a
    carried state), two of 32 and a RAGGED one of 11, one of 64 and 11;
    whatever stands in a ragged chunk's dead tokens changes nothing."""
    x, B, C, dt, a, D = _inputs(1)
    want, last = _numpy_rule(x, B, C, dt, a, D)
    s = x.shape[1]

    def walk(fill):
        state, out = jnp.zeros((2, 4, 16, 8)), []
        for off in range(0, s, chunk):
            m = min(chunk, s - off)
            cut = [jnp.pad(t[:, off:off + m], [(0, 0), (0, chunk - m)]
                           + [(0, 0)] * (t.ndim - 2), constant_values=fill)
                   for t in (x, B, C, dt, a)]
            y, state = ssd.chunk(*cut, D, state,
                                 jnp.full((2,), m, jnp.int32))
            out.append(y[:, :m])
        return jnp.concatenate(out, axis=1), state

    y, state = walk(0.0)
    np.testing.assert_allclose(y, want, atol=5e-5)
    np.testing.assert_allclose(state, last, atol=5e-5)
    y2, state2 = walk(7.5)                      # garbage in the dead tokens
    np.testing.assert_array_equal(np.asarray(state), np.asarray(state2))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))


def test_a_head_reads_its_own_groups_key_and_query():
    x, B, C, dt, a, D = _inputs(2, s=9)
    y, _ = ssd.sequence(x, B, C, dt, a, D)
    other = jax.random.normal(jax.random.PRNGKey(9), B[:, :, 1].shape)
    y_b, _ = ssd.sequence(x, B.at[:, :, 1].set(other), C, dt, a, D)
    y_c, _ = ssd.sequence(x, B, C.at[:, :, 1].set(other), dt, a, D)
    for got in (y_b, y_c):      # heads 0, 1 read group 0; heads 2, 3 group 1
        np.testing.assert_array_equal(np.asarray(got[:, :, :2]),
                                      np.asarray(y[:, :, :2]))
        assert float(jnp.abs(got[:, :, 2:] - y[:, :, 2:]).max()) > 0.1
    # the chunkwise form and the step agree with the plain one on it
    y_k, _ = ssd.chunk(x, B.at[:, :, 1].set(other), C, dt, a, D,
                       jnp.zeros((2, 4, 16, 8)))
    np.testing.assert_allclose(y_k, y_b, atol=5e-5)


def test_the_gate_comes_first_and_the_norm_goes_by_group():
    y, z = (jax.random.normal(k, (3, 5, 32)) for k in jax.random.split(
        jax.random.PRNGKey(3)))
    w = jnp.linspace(0.5, 1.5, 32)
    got = ssd.gated_norm(y, z, w, 2, 1e-5)
    gated = np.asarray(y * jax.nn.silu(z), np.float64).reshape(3, 5, 2, 16)
    want = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 5, 32) * np.asarray(w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # neither the norm before the gate nor one norm over all channels
    y64 = np.asarray(y, np.float64).reshape(3, 5, 2, 16)
    before = (y64 / np.sqrt((y64 ** 2).mean(-1, keepdims=True) + 1e-5)
              ).reshape(3, 5, 32) * np.asarray(w) * np.asarray(
                  jax.nn.silu(z))
    assert np.abs(np.asarray(got) - before).max() > 0.1
    assert float(jnp.abs(got - ssd.gated_norm(y, z, w, 1, 1e-5)).max()) > .05


def test_the_convolution_has_a_bias_and_carries_its_inputs():
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 19, 6))
    w = jax.random.normal(jax.random.PRNGKey(5), (6, 4))
    bias = jnp.linspace(-1.0, 1.0, 6)
    ext = np.concatenate([np.zeros((2, 3, 6)), np.asarray(u)], axis=1)
    want = sum(np.asarray(w)[:, j] * ext[:, j:j + 19] for j in range(4)) \
        + np.asarray(bias)
    want = want / (1 + np.exp(-want))                       # SiLU after it
    whole, carry = short_conv(u, w, activation=jax.nn.silu, bias=bias)
    np.testing.assert_allclose(whole, want, atol=1e-5)
    assert float(jnp.abs(whole - short_conv(
        u, w, activation=jax.nn.silu)[0]).max()) > 0.3      # the bias counts
    # in two chunks, the second ragged, the inputs carried between them
    first, state = short_conv(u[:, :8], w, None, None, jax.nn.silu, bias)
    pad = jnp.pad(u[:, 8:], [(0, 0), (0, 5), (0, 0)], constant_values=3.0)
    second, state = short_conv(pad, w, state, jnp.full((2,), 11, jnp.int32),
                               jax.nn.silu, bias)
    np.testing.assert_allclose(jnp.concatenate([first, second[:, :11]], 1),
                               want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state), np.asarray(carry))


def test_rows_that_stand_keep_their_state_bit_for_bit():
    x, B, C, dt, a, D = _inputs(5, s=8)
    state = jax.random.normal(jax.random.PRNGKey(6), (2, 4, 16, 8))
    _, new = ssd.chunk(x, B, C, dt, a, D, state, jnp.asarray([0, 5]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert float(jnp.abs(new[1] - state[1]).max()) > 1e-3
    _, new = ssd.step(x[:, 0], B[:, 0], C[:, 0], dt[:, 0], a[:, 0], D, state,
                      jnp.asarray([True, False]))
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(state[1]))
    # a decay that underflows overflows nothing in the chunkwise form
    y, new = ssd.chunk(x, B, C, dt * 1e3, a * 1e3, D, state)
    assert bool(jnp.isfinite(y).all() & jnp.isfinite(new).all())
    # the published gates: softplus with a bias, a decay a head, no clamp
    dt, a = ssd.gates(jnp.asarray([[30.0, -30.0]]), jnp.asarray([0.5, 0.5]),
                      jnp.log(jnp.asarray([2.0, 4.0])))
    np.testing.assert_allclose(dt, [[30.5, np.log1p(np.exp(-29.5))]],
                               rtol=1e-6)
    np.testing.assert_allclose(a, [[-61.0, -4 * float(dt[0, 1])]], rtol=1e-6)


_KERNEL_CASES = {"two of three live": [True, False, True],
                 "all live": [True, True, True],
                 "none live": [False, False, False]}


@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_the_step_kernel_is_the_step(case, monkeypatch):
    """`step_in_place` through the interpreter at whole 128-lane tiles: the
    live slots' states and outputs are `step`'s, a slot that stands and
    every other layer keep their states bit for bit."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    live = jnp.asarray(_KERNEL_CASES[case])
    h, p, g, n = 16, 128, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    s_all = jax.random.normal(ks[0], (2, 3, h, n, p))
    x = jax.random.normal(ks[1], (3, h, p))
    B, C = (jax.random.normal(k, (3, g, n)) for k in ks[2:4])
    dt, a = ssd.gates(jax.random.normal(ks[4], (3, h)), jnp.zeros((h,)),
                      jnp.log(jnp.linspace(1.0, 16.0, h)))
    D = jax.random.normal(ks[5], (h,))
    assert ssd.engages(1, s_all, g)
    y, new = jax.jit(lambda *t: ssd.step_in_place(*t))(
        x, B, C, dt, a, D, s_all, 1, live)
    want_y, want = ssd.step(x, B, C, dt, a, D, s_all[1], live)
    np.testing.assert_allclose(new[1], want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(s_all[0]))
    held = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(new[1])[held],
                                  np.asarray(s_all[1])[held])
    np.testing.assert_allclose(np.asarray(y)[~held],
                               np.asarray(want_y)[~held], atol=2e-5)


def test_what_the_step_kernel_takes_and_refuses(monkeypatch):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    assert ssd.kernel_shape(1, f32(9, 64, 32, 256, 128), 2)     # the cell's
    assert not ssd.kernel_shape(2, f32(9, 64, 32, 256, 128), 2)  # a chunk
    assert not ssd.kernel_shape(1, f32(3, 2, 4, 16, 8), 2)       # no tiles
    assert not ssd.kernel_shape(1, f32(3, 2, 32, 256, 128), 8)   # 4 a group
    assert not ssd.kernel_shape(
        1, jax.ShapeDtypeStruct((3, 2, 32, 256, 128), jnp.bfloat16), 2)
    assert not ssd.engages(1, f32(9, 64, 32, 256, 128), 2)       # a CPU
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert ssd.engages(1, f32(9, 64, 32, 256, 128), 2)


# ------------------------------------------------- the layer and the counts

def test_pattern_weights_and_counts(world):
    cfg, c = world.cfg, world.c
    tree = world.params["layers"]
    assert cfg.kinds == (KIND,) * 3 and cfg.layer_segments == (
        ("layers", 0, 3, KIND),)
    assert stack_kinds(cfg, "ssm_in") == (KIND,)
    assert KIND in stack_kinds(cfg, "wq") and KIND in stack_kinds(cfg, "wk")
    assert tree["ssm_in"].shape == (3, 64, 32 + 96 + 4)   # z | x B C | dt
    assert tree["ssm_conv"].shape == (3, 96, 4)    # 32 + 2 groups x 2 x 16
    assert tree["ssm_conv_b"].shape == (3, 96)
    assert tree["wq"].shape == (3, 64, 4, 16)             # 64 wide = d here
    assert tree["wk"].shape == (3, 64, 2, 16)
    made, _ = init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree_util.tree_map(jnp.shape, made) == \
        jax.tree_util.tree_map(jnp.shape, world.params)
    n = sum(x.size for x in jax.tree_util.tree_leaves(world.params))
    shapes = mf.family_of(c).shapes
    assert n == count_params(cfg) == shapes.count_params(c)
    # the cell's configuration, from its file alone: the issue's count
    real = mf.Manifest().config("falcon-h1-34b")
    assert shapes.layer_params(real) == 430_120_032
    assert shapes.count_params(real) == 4_205_319_008
    assert count_params(world.model.model_config(real, "serve")) == \
        4_205_319_008
    assert shapes.state_bytes(real) == 32 * 256 * 128 * 4 + 3 * 5120 * 2
    # a layer with both attends as a full one AND pays for its state
    plain = dataclasses.replace(cfg, layer_kinds=("full",) * 3)
    per_state = 3 * 4 * 16 * 8
    extra = decode_flops_per_token(cfg, 100) - decode_flops_per_token(
        plain, 100)
    assert extra == 2 * 3 * (64 * 132 + 32 * 64) + 5 * per_state
    assert decode_flops_per_token(cfg, 200) - decode_flops_per_token(
        cfg, 100) == decode_flops_per_token(plain, 200) \
        - decode_flops_per_token(plain, 100) > 0


_MULTIPLIERS = ["embed_scale", "logit_scale", "attn_in_scale",
                "attn_out_scale", "key_scale", "ssm_in_scale",
                "ssm_out_scale", "ffn_gate_scale", "ffn_out_scale"] \
    + [f"ssm_scales.{i}" for i in range(5)]


@pytest.mark.parametrize("name", _MULTIPLIERS)
def test_each_of_the_fourteen_multipliers_moves_the_logits(world, name):
    cfg = world.cfg
    field, _, at = name.partition(".")
    value = getattr(cfg, field)
    assert (value[int(at)] if at else value) != 1.0
    one = 1.0 if not at else tuple(
        1.0 if i == int(at) else m for i, m in enumerate(value))
    got = forward(world.params, world.toks[:1, :24],
                  dataclasses.replace(cfg, **{field: one}))
    spread = world.want[0, :24].std()
    assert np.abs(np.asarray(got)[0] - world.want[0, :24]).max() \
        > 0.02 * spread, name


def test_multipliers_of_one_cost_no_instruction():
    """A model that states none lowers to the text it lowered to: the
    existing kinds' programs (a GPT-2 and a `kimi_linear` tiny preset: the
    plain forward, the slot step, the lanes program) hash as they did on the
    commit before this kind and these fields existed."""
    before = {
        "tiny": ["c72aeb020e17e5b0", "d782eab4d75ee6be", "52d2e657832beaa8"],
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


def test_a_cache_has_a_seventh_kind_beside_rows_in_one_layer(world):
    cfg = world.cfg
    assert cache_rows(cfg) == {"k": (2, 16), "v": (2, 16), "s_ssm": (4, 16),
                               "conv_ssm": (1, 3)}
    cache = init_slot_cache(cfg, 3, MAX_LEN)
    assert {n: a.shape for n, a in cache.items() if n != "pos"} == {
        "k": (3, 3, 2, 16, MAX_LEN), "v": (3, 3, 2, 16, MAX_LEN),
        "s_ssm": (3, 3, 4, 16, 8), "conv_ssm": (3, 3, 1, 3, 96)}
    assert cache["s_ssm"].dtype == jnp.float32 == array_dtype(cfg, "s_ssm")
    assert [_state_kind(n) for n in ("k", "s_ssm", "conv_ssm")] == [
        "full", "ssm", "ssm"]
    state = 4 * 16 * 8 * 4 + 3 * 96 * 4               # a float32 model
    assert position_bytes(cfg) == {"full": 2 * 2 * 16 * 4, "ring": 0,
                                   "state": 0, "ssm": state}
    assert cache_bytes(cache) == {"full": 3 * 3 * 256 * MAX_LEN, "ring": 0,
                                  "state": 0, "ssm": 3 * 3 * state}
    assert cache_capacity(cache, cfg) == MAX_LEN
    step = CacheTraffic(cache, cfg, CHUNK).step((9, 99))
    assert step.column_writes == 2 * 3 * 3      # rows only
    # the SAME three layers once among the rows, once among the states
    assert step[:6] == (
        3 * 110, 3 * 110, 3 * 110 * 256, 3 * 110 * 256, 0, 0)
    # (XLA's form on this backend: three passes over all 3 slots' states)
    assert step[9:12] == (3 * 2, 2 * 3 * 2 * state, 3 * 3 * 3 * state)


def test_the_kernel_counts_the_live_slots_states_alone(monkeypatch):
    wide = dataclasses.replace(
        mf.family_of(_config("tiny-falcon-h1")).model.model_config(
            _config("tiny-falcon-h1"), "serve"),
        ssm_heads=16, ssm_head_dim=128, ssm_state=128)
    cache = jax.eval_shape(functools.partial(init_slot_cache, wide, 3, 64))
    per = position_bytes(wide)["ssm"]
    assert per == 16 * 128 * 128 * 4 + 3 * (16 * 128 + 2 * 2 * 128) * 2
    fetched = lambda live: CacheTraffic(cache, wide, CHUNK).step(
        (5,) * live).state_bytes_fetched
    assert fetched(2) == 3 * 3 * 3 * per
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert [fetched(n) for n in (2, 0)] == [2 * 3 * 2 * per, 0]


def test_the_ssm_scope_stands_around_parts_of_the_model(world):
    """Every instruction the mixer adds has ``ssm`` in its path and one of
    the ten parts behind it: its projections in ``projections``, its
    convolution in ``conv``, the rest in ``attention``; attention's own
    instructions have no ``ssm``."""
    cache = init_slot_cache(world.cfg, 2, 64)
    text = world.step.lower(world.params, jnp.zeros((2,), jnp.int32), cache,
                            jnp.ones((2,), bool)).compile().as_text()
    paths = {p for p in device_profile.op_map(text)["instructions"].values()
             if p}
    mine = {p for p in paths if "ssm" in p.split("/")}
    parts = {device_profile.part_of(p)[0] for p in mine}
    assert {"projections", "conv", "attention"} <= parts
    assert parts <= {"projections", "conv", "attention", "cache_write"}
    assert any("attention" in p.split("/") and "ssm" not in p.split("/")
               for p in paths)


# -------------------------------------------------- the cached programs

def _chunked(w, row, n, cache, chunk=CHUNK):
    host = np.asarray(w.toks[row:row + 1, :n])
    off = 0
    while off < n:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=chunk, capacity=MAX_LEN)
    return logits, cache


def test_plain_and_whole_prompt_forms_agree_with_the_reference(world):
    w = world
    got = forward(w.params, w.toks, w.cfg)
    np.testing.assert_allclose(got, w.want, **TOL)
    logits, cache = prefill(w.params, w.toks[:, :90], w.cfg,
                            init_kv_cache(w.cfg, 2, MAX_LEN))
    np.testing.assert_allclose(logits, w.want[:, 89], **TOL)
    for t in range(90, 96):
        logits, cache = prefill_chunk_jit(w.params, w.toks[:, t:t + 1],
                                          cache, cfg=w.cfg)
        np.testing.assert_allclose(logits, w.want[:, t], **TOL)


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_chunks_and_a_prompt_that_ends_mid_chunk(world, chunk):
    w = world
    logits, cache = _chunked(w, 0, 107, init_kv_cache(w.cfg, 1, MAX_LEN),
                             chunk)
    np.testing.assert_allclose(logits[0], w.want[0, 106], **TOL)
    assert int(cache["pos"]) == 107
    logits, _ = prefill_chunk_jit(w.params, w.toks[:1, 107:108], cache,
                                  cfg=w.cfg)
    np.testing.assert_allclose(logits[0], w.want[0, 107], **TOL)


def test_lanes_with_a_lane_that_stands(world):
    w = world
    cache = init_slot_cache(w.cfg, 3, MAX_LEN)
    prompts = [(np.asarray(w.toks[0:1, :70]), 0), None,
               (np.asarray(w.toks[1:2, :45]), 0)]
    marker = cache["s_ssm"].at[:, 1].set(3.0)
    cache = dict(cache, s_ssm=marker)
    done = {}
    while any(p is not None for p in prompts):
        logits, cache, moved = prefill_lanes_step(
            prefill_lanes_jit, w.params, prompts, cache, w.cfg, chunk=CHUNK,
            capacity=MAX_LEN)
        for lane, m in enumerate(moved):
            if m is None:
                continue
            toks, _ = prompts[lane]
            if m[0] == toks.shape[1]:
                done[lane] = np.asarray(logits[lane])
                prompts[lane] = None
            else:
                prompts[lane] = (toks, m[0])
    np.testing.assert_allclose(done[0], w.want[0, 69], **TOL)
    np.testing.assert_allclose(done[2], w.want[1, 44], **TOL)
    # the lane that stood: bit for bit
    assert float(jnp.abs(cache["s_ssm"][:, 1] - 3.0).max()) == 0.0
    assert float(jnp.abs(cache["k"][:, 1]).max()) == 0.0


def _two_slots(w, depths):
    cache = init_slot_cache(w.cfg, 3, MAX_LEN)
    insert = jax.jit(cache_insert_slot)
    for slot, (row, n) in enumerate(zip((0, 1), depths)):
        _, one = _chunked(w, row, n, init_kv_cache(w.cfg, 1, MAX_LEN))
        cache = insert(cache, one, jnp.int32(slot))
    return cache


def test_slots_at_depths_of_their_own_and_one_that_stands(world):
    w = world
    cache = _two_slots(w, (80, 37))
    held = {n: np.asarray(cache[n][:, 1]) for n in ("s_ssm", "conv_ssm")}
    for i in range(6):
        tok = jnp.asarray([w.toks[0, 80 + i], w.toks[1, 37], 0], jnp.int32)
        logits, cache = w.step(w.params, tok, cache,
                               jnp.asarray([True, False, False]))
        np.testing.assert_allclose(logits[0], w.want[0, 80 + i], **TOL)
    assert cache["pos"].tolist() == [86, 37, 0]
    for n, was in held.items():     # the slot that stood: bit for bit
        np.testing.assert_array_equal(np.asarray(cache[n][:, 1]), was)
    tok = jnp.asarray([w.toks[0, 86], w.toks[1, 37], 0], jnp.int32)
    logits, cache = w.step(w.params, tok, cache,
                           jnp.asarray([True, True, False]))
    np.testing.assert_allclose(logits[0], w.want[0, 86], **TOL)
    np.testing.assert_allclose(logits[1], w.want[1, 37], **TOL)


def test_insert_and_gather_carry_state_inputs_and_rows_together(world):
    w = world
    gathered = jax.jit(cache_gather_slot)(
        _two_slots(w, (40, 30)), jnp.int32(0), jnp.int32(40))
    assert set(gathered) == {"k", "v", "s_ssm", "conv_ssm", "pos"}
    host = np.concatenate([np.asarray(w.toks[0:1, :40]),
                           np.asarray(w.toks[1:2, 40:70])], axis=1)
    want = np.asarray(w.model.logits(w.params, jnp.asarray(host), w.c))
    off, cache = 40, gathered
    while off < 70:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=CHUNK, capacity=MAX_LEN)
    np.testing.assert_allclose(logits[0], want[0, 69], **TOL)


# ------------------------------------------------------- through the engine

def _stream(core, prompt, n, out=None, key=None, op="start", **more):
    r = core.handle(dict({"op": op, "prompt": prompt}, **more))
    assert "error" not in r, r
    toks = list(r["token"])
    while len(toks) < n:
        got = core.handle({"op": "next_chunk", "sid": r["sid"],
                           "max_tokens": n - len(toks)})
        assert "error" not in got, got
        toks += got["tokens"]
        if got.get("done"):
            break
    core.handle({"op": "end", "sid": r["sid"]})
    if out is not None:
        out[key] = toks[:n]
    return toks[:n]


def _forced(w, prompt, stream):
    """The reference's own choice at every generated position of ``prompt +
    stream``."""
    seq = jnp.asarray([prompt + stream[:-1]], jnp.int32)
    logits = np.asarray(w.model.logits(w.params, seq, w.c))[0]
    return logits[len(prompt) - 1:].argmax(-1).tolist()


def _core(w, max_len=MAX_LEN, **engine):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    return DecodeSessionCore(
        w.cfg, max_len=max_len, params=w.params,
        engine=DecodeEngineConfig(prefill_chunk_tokens=CHUNK, **engine))


def test_engine_serves_the_references_tokens(world, monkeypatch):
    """Four sessions at once through chunk programs, the lanes program and
    the fused slot step: every token is the reference's choice at its
    position; the engine counts the layers' rows among the rows and their
    states among the states, neither twice."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    w = world
    core = _core(w, max_slots=3)
    try:
        prompts = [np.asarray(w.toks[i % 2, a:a + n]).tolist()
                   for i, (a, n) in enumerate(
                       ((0, 90), (3, 43), (11, 67), (20, 120)))]
        got = {}
        threads = [threading.Thread(target=_stream,
                                    args=(core, p, 10, got, i))
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
        per = 4 * 16 * 8 * 4 + 3 * 96 * 4
        assert cache["bytes_ssm"] == 3 * 3 * per
        assert cache["bytes_full"] == 3 * 3 * 256 * MAX_LEN
        assert cache["bytes_per_position"] == 3 * 256
        assert cache["state_rows"] == 3 * st["tokens"]
        assert cache["state_bytes_moved"] == 2 * per * cache["state_rows"]
        assert cache["rows_read"] == cache["rows_if_full"]
        assert cache["bytes_read"] == cache["rows_read"] * 256
        span = [e for e in tracing.span_events()
                if e["name"] == "cache:rows"][-1]["args"]
        assert span["bytes_ssm"] == cache["bytes_ssm"]
        assert span["state_bytes_moved"] == 2 * per * span["state_rows"]
    finally:
        core.engine.shutdown()


def test_prefix_replay_and_a_full_cache_with_a_layer_that_has_both(world):
    """A layer that holds rows AND a state is a state layer to the prefix
    reuse (a donor serves only while it stands at the prefix, although any
    donor's rows would do); a preempted request's replay through the chunk
    programs goes on with the tokens it would have had; a prompt the cache
    cannot hold, and one that ends within a chunk of its end, are refused
    with what is the matter."""
    exact = functools.partial(prefix_holds, world.cfg, chunk=CHUNK,
                              capacity=MAX_LEN)
    assert exact(40, 40, 100)
    assert not exact(41, 40, 100)       # it has decoded one token past
    assert not exact(None, 40, 100)     # no such donor
    assert not exact(40, 40, MAX_LEN - 1)   # a chunk window set back
    w = world
    core = _core(w, max_slots=1, prefix_cache_min_tokens=4,
                 token_queue_depth=2)
    try:
        long_ = np.asarray(w.toks[0, :95]).tolist()
        whole = _stream(core, long_, 12)
        assert whole == _forced(w, long_, whole)
        # the ONE slot again, from zeros: a shorter prompt of the other row
        short = np.asarray(w.toks[1, :44]).tolist()
        got = _stream(core, short, 8)
        assert got == _forced(w, short, got)
        # the donor has decoded eight tokens past the shared 40: refused
        hits = core.engine.stats()["prefix"]["applied_hits"]
        fork = short[:40] + np.asarray(w.toks[0, 30:50]).tolist()
        got = _stream(core, fork, 8)
        assert core.engine.stats()["prefix"]["applied_hits"] == hits
        assert got == _forced(w, fork, got)
        # preempted after 5 tokens: the replay of prompt + 5 goes on as the
        # uninterrupted stream did
        rest = _stream(core, long_, 7, op="resume", generated=whole[:5])
        assert whole[:5] + rest == whole
        assert core.engine.stats()["cache_copies"] == 0
        with pytest.raises(ValueError, match="exceeds cache capacity"):
            core.handle({"op": "start", "prompt": [1] * (MAX_LEN + 1)})
        got = _stream(core, short, 4)       # and it serves on
        assert got == _forced(w, short, got)
    finally:
        core.engine.shutdown()
    # a cache of 200 positions under chunks of 32: a prompt of 195 would
    # have its last window set back to 168 over tokens the states have taken
    core = _core(w, max_len=200, max_slots=1)
    try:
        with pytest.raises(RuntimeError, match="cannot be taken back"):
            core.handle({"op": "start", "prompt": [1] * 195})
        got = _stream(core, short, 4)
        assert got == _forced(w, short, got)
    finally:
        core.engine.shutdown()


# --------------------------------------------- planted faults, and refusals

def _served_logits(w, cfg=None, insert=cache_insert_slot, params=None):
    """Row 0 through chunks of 32 into a slot, then 8 slot steps: the
    logits at positions 69 .. 77."""
    cfg, params = cfg or w.cfg, params or w.params
    host = np.asarray(w.toks[0:1, :70])
    off, one = 0, init_kv_cache(cfg, 1, MAX_LEN)
    while off < 70:
        logits, one, off, _ = prefill_chunk_step(
            prefill_chunk_jit, params, host, off, one, cfg, chunk=CHUNK,
            capacity=MAX_LEN)
    cache = jax.jit(insert)(init_slot_cache(cfg, 2, MAX_LEN), one,
                            jnp.int32(0))
    step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
    out = [np.asarray(logits[0])]
    for t in range(70, 78):
        logits, cache = step(params, jnp.asarray([w.toks[0, t], 0]), cache,
                             jnp.asarray([True, False]))
        out.append(np.asarray(logits[0]))
    return np.stack(out)


def _state_not_carried(w, monkeypatch):
    def insert(slot_cache, cache, slot):
        out = cache_insert_slot(slot_cache, cache, slot)
        return dict(out, s_ssm=slot_cache["s_ssm"])
    return _served_logits(w, insert=insert)


def _group_zero_for_all(w, monkeypatch):
    # every head reads group 0's key and query (the module-level jits are
    # cached by the configuration: one that differs in a number no program
    # looks at is traced anew, with the fault in it)
    real_step, real_chunk = ssd.step, ssd.chunk
    first = lambda t, axis: jnp.repeat(jnp.take(t, jnp.asarray([0]), axis),
                                       t.shape[axis], axis)
    monkeypatch.setattr(ssd, "step", lambda x, B, C, *r, **k: real_step(
        x, first(B, 1), first(C, 1), *r, **k))
    monkeypatch.setattr(ssd, "chunk", lambda x, B, C, *r, **k: real_chunk(
        x, first(B, 2), first(C, 2), *r, **k))
    return _served_logits(w, cfg=dataclasses.replace(w.cfg, max_seq_len=385))


def _attention_dropped(w, monkeypatch):
    return _served_logits(w, params=dict(w.params, layers=dict(
        w.params["layers"], wo=jnp.zeros_like(w.params["layers"]["wo"]))))


def _a_multiplier_left_at_one(w, monkeypatch):
    return _served_logits(w, cfg=dataclasses.replace(w.cfg, key_scale=1.0))


@pytest.mark.parametrize("fault", [_state_not_carried, _group_zero_for_all,
                                   _attention_dropped,
                                   _a_multiplier_left_at_one])
def test_four_planted_faults_each_fail(world, monkeypatch, fault):
    w = world
    want = w.want[0, 69:78]
    np.testing.assert_allclose(_served_logits(w), want, **TOL)
    got = fault(w, monkeypatch)
    assert np.abs(got - want).max() > 0.02 * want.std(), fault.__name__


def test_what_a_configuration_is_refused_for(world):
    cfg, toks = world.cfg, world.toks[:1, :8]
    for bad in (dict(ssm_heads=0), dict(ssm_state=0), dict(ssm_groups=3),
                dict(ssm_conv_kernel=1), dict(ssm_scales=(1.0,) * 4),
                dict(attention="mla")):
        broken = dataclasses.replace(cfg, **bad)
        with pytest.raises(ValueError, match="'ssm\\+full' layer needs"):
            init_params(jax.random.PRNGKey(0), broken)
        with pytest.raises(ValueError, match="'ssm\\+full' layer needs"):
            forward(world.params, toks, broken)
    mixed = dataclasses.replace(cfg, layer_kinds=(KIND, "full", KIND))
    with pytest.raises(NotImplementedError, match="two layer counters"):
        prefill_chunk_jit(world.params, toks,
                          init_kv_cache(cfg, 1, MAX_LEN), cfg=mixed)
    # a chunk window set back at the cache's end would run tokens twice,
    # whatever rows the layers hold beside their states
    host = np.asarray(world.toks[0:1, :T])
    with pytest.raises(ValueError, match="cannot be taken back"):
        prefill_chunk_step(prefill_chunk_jit, world.params, host, T - 20,
                           init_kv_cache(cfg, 1, T + 3), cfg, chunk=CHUNK,
                           capacity=T + 3)
