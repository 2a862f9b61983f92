"""The gated delta rule (`ops/delta_rule.py`) and the sixth kind of cache
state (`models/generate.py`): the op's three forms against a NumPy statement
of the recurrence (one token, a chunk at several block sizes, a sequence);
padded tokens and rows that stand, which leave a state bit for bit; strong
decays, which overflow nothing in the chunkwise form; every cached program
(whole-prompt prefill, chunks, lanes with a lane that stands, slots at depths
of their own) and the engine against the full `forward`; a slot reused after
another session; the prefix reuse that hands a state on only from a donor
that stands at the prefix; latent attention with no query latent and no
rotation against its plain form; three planted faults that each FAIL; and
what a configuration is refused for.

The model is the rehearsal's ``tiny-kimi-linear`` in float32 (4 KDA heads of
16 with a convolution of 4 taps on layers 1, 2, 4, 5; latent attention
without positions on layers 3 and 6; layer 1 dense; 4 of 8 experts held).
The plain REFERENCE's agreement is
tests/benchmark/test_perfbench_family_kimi_linear.py's.
"""

import dataclasses
import functools
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
                            init_params, init_slot_cache, lm_loss, prefill,
                            prefill_chunk_jit, prefill_lanes_jit,
                            prefix_holds)
from ray_tpu.models.generate import (_state_kind, array_dtype, cache_bytes,
                                     cache_capacity, cache_rows,
                                     position_bytes, prefill_chunk_step,
                                     prefill_lanes_step)
from ray_tpu.models.transformer import (count_params, decode_flops_per_token,
                                        stack_kinds)
from ray_tpu.ops import delta_rule
from ray_tpu.ops.short_conv import short_conv
from ray_tpu.serve.decode_session import ContinuousBatchingEngine

T, MAX_LEN, CHUNK = 300, 384, 32
TOL = dict(atol=3e-4, rtol=0)


@pytest.fixture(scope="module")
def world():
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           "tiny-kimi-linear.json")) as f:
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

def _inputs(seed, b=2, s=75, h=3, dk=16, dv=16, a_scale=(1e-3, 2.0)):
    """(q, k, v, a, beta, S0) as a KDA layer hands them to the rule."""
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(b, s, h, dk)).astype(np.float32)
            for _ in range(2))
    v = rng.normal(size=(b, s, h, dv)).astype(np.float32)
    q = np.asarray(delta_rule.l2norm(q)) * dk ** -0.5
    k = np.asarray(delta_rule.l2norm(k))
    a = -np.exp(rng.uniform(*np.log(a_scale), size=(b, s, h, dk))
                ).astype(np.float32)
    beta = rng.uniform(0, 1, size=(b, s, h)).astype(np.float32)
    return q, k, v, a, beta, rng.normal(size=(b, h, dk, dv)).astype(
        np.float32)


def _numpy_rule(q, k, v, a, beta, state):
    """Section 1's recurrence, a token, a row and a head at a time, in
    float64."""
    state = state.astype(np.float64).copy()
    out = np.zeros(v.shape)
    for b in range(q.shape[0]):
        for t in range(q.shape[1]):
            for h in range(q.shape[2]):
                s = np.exp(a[b, t, h].astype(np.float64))[:, None] \
                    * state[b, h]
                u = beta[b, t, h] * (v[b, t, h] - k[b, t, h] @ s)
                state[b, h] = s + np.outer(k[b, t, h], u)
                out[b, t, h] = state[b, h].T @ q[b, t, h]
    return out, state


@pytest.mark.parametrize("block", [1, 4, 16, 32, 64, 128])
def test_chunk_is_the_recurrence_at_every_block_size(block):
    """The chunkwise form against the recurrence, to float32 rounding: 75
    tokens are whole blocks and a remainder at every size but 1, and one
    block at 128."""
    q, k, v, a, beta, s0 = _inputs(block)
    want, state = _numpy_rule(q, k, v, a, beta, s0)
    got, new = jax.jit(functools.partial(delta_rule.chunk, block=block))(
        q, k, v, a, beta, s0)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(new, state, atol=5e-6, rtol=0)


def test_step_and_sequence_are_the_recurrence():
    q, k, v, a, beta, s0 = _inputs(3, s=40)
    want, state = _numpy_rule(q, k, v, a, beta, np.zeros_like(s0))
    got, new = delta_rule.sequence(q, k, v, a, beta)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(new, state, atol=2e-6, rtol=0)
    want, state = _numpy_rule(*(t[:, :1] for t in (q, k, v, a, beta)), s0)
    got, new = delta_rule.step(q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                               beta[:, 0], s0)
    np.testing.assert_allclose(got, want[:, 0], atol=2e-6, rtol=0)
    np.testing.assert_allclose(new, state, atol=2e-6, rtol=0)


def test_gates_are_the_published_decay_and_step_size():
    rng = np.random.default_rng(0)
    f = rng.normal(size=(2, 5, 3, 8)).astype(np.float32)
    b = rng.normal(size=(2, 5, 3)).astype(np.float32)
    a_log = np.log(rng.uniform(1, 16, size=3)).astype(np.float32)
    dt_bias = rng.normal(size=(3, 8)).astype(np.float32)
    a, beta = delta_rule.gates(f, b, a_log, dt_bias)
    np.testing.assert_allclose(
        a, -np.exp(a_log)[:, None] * np.log1p(np.exp(f + dt_bias)),
        rtol=1e-5)
    np.testing.assert_allclose(beta, 1 / (1 + np.exp(-b)), rtol=1e-5)
    assert a.dtype == beta.dtype == jnp.float32 and float(a.max()) < 0


def test_padded_tokens_and_rows_that_stand_leave_the_state_bit_for_bit():
    q, k, v, a, beta, s0 = _inputs(5)
    n_valid = jnp.asarray([40, 0])
    got, new = delta_rule.chunk(q, k, v, a, beta, s0, n_valid)
    want, state = _numpy_rule(*(t[:1, :40] for t in (q, k, v, a, beta)),
                              s0[:1])
    np.testing.assert_allclose(got[0, :40], want[0], atol=2e-6, rtol=0)
    np.testing.assert_allclose(new[0], state[0], atol=5e-6, rtol=0)
    np.testing.assert_array_equal(new[1], s0[1])        # bit for bit
    # whatever the padding holds, the state is the valid tokens' alone
    junk = [np.concatenate([t[:, :40], 7.0 * t[:, 40:][:, ::-1]], axis=1)
            for t in (q, k, v)]
    _, again = delta_rule.chunk(*junk, a, beta, s0, n_valid)
    np.testing.assert_array_equal(again, new)
    # one token a row: the row that is not live keeps its state
    _, new = delta_rule.step(q[:, 0], k[:, 0], v[:, 0], a[:, 0], beta[:, 0],
                             s0, jnp.asarray([True, False]))
    np.testing.assert_array_equal(new[1], s0[1])
    assert np.abs(np.asarray(new[0]) - s0[0]).max() > 1e-3
    # ... and the convolution's carried inputs with it
    u = np.random.default_rng(1).normal(size=(2, 9, 6)).astype(np.float32)
    w = np.ones((6, 4), np.float32)
    held = np.random.default_rng(2).normal(size=(2, 3, 6)).astype(np.float32)
    _, carry = short_conv(u, w, held, jnp.asarray([5, 0]),
                          activation=jax.nn.silu)
    np.testing.assert_array_equal(carry[1], held[1])
    np.testing.assert_array_equal(carry[0], u[0, 2:5])


def test_strong_decays_overflow_nothing_in_the_chunkwise_form():
    """``A`` 16 at ``dt`` 0.1 and a gate input that adds to it, over 300
    tokens: a block's decay is exp(-300) and less, its inverse would be inf;
    the differences ``G_i - G_j <= 0`` keep every exponent at or under 0."""
    q, k, v, a, beta, s0 = _inputs(9, b=1, s=300, h=2,
                                   a_scale=(1.6, 30.0))
    assert float(a.sum(1).min()) < -2000
    want, state = _numpy_rule(q, k, v, a, beta, s0)
    for block in (16, 64):
        got, new = delta_rule.chunk(q, k, v, a, beta, s0, block=block)
        assert np.isfinite(got).all() and np.isfinite(new).all()
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
        np.testing.assert_allclose(new, state, atol=1e-5, rtol=0)


# ------------------------------------- the step where the stacked states lie

#: cases of `test_the_step_kernel_is_the_step`: slots, heads, who is live
#: (None: all), a VMEM budget (None: the module's), every log decay (None:
#: drawn), steps in a row
_KERNEL_CASES = {
    "one head a grid step": dict(b=2, h=1),
    "several heads a grid step": dict(b=2, h=16),
    "two blocks of heads a slot": dict(
        b=3, h=16, live=[True, False, True], budget=4 * 8 * 128 * 128 * 4),
    "one slot stands between two that run": dict(
        b=4, h=8, live=[False, True, False, True]),
    "none live": dict(b=2, h=8, live=[False, False]),
    "a decay of 1e-7": dict(b=2, h=2, decay=float(np.log(1e-7))),
    "300 steps in a row": dict(b=1, h=2, steps=300),
}


@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_the_step_kernel_is_the_step(case, monkeypatch):
    """`step_in_place`'s kernel through the interpreter, at states of 128 x
    128, against `step` on the layer cut out and against the NumPy
    recurrence: the layer beside it and a slot that stands keep their states
    bit for bit (a standing slot's ``o`` is zeros), and 300 steps in a row
    drift from `sequence` by no more than rounding."""
    spec = dict(dict(live=None, budget=None, decay=None, steps=1),
                **_KERNEL_CASES[case])
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    if spec["budget"]:
        monkeypatch.setattr(delta_rule, "_VMEM_BLOCK_BUDGET", spec["budget"])
        assert delta_rule._head_block(spec["h"], 128, 128) == 8
    b, h, steps = spec["b"], spec["h"], spec["steps"]
    q, k, v, a, beta, s0 = _inputs(len(case), b=b, s=steps, h=h, dk=128,
                                   dv=128)
    if spec["decay"] is not None:
        a = np.full_like(a, spec["decay"])
        assert np.exp(a).max() < 1.1e-7
    live = None if spec["live"] is None else jnp.asarray(spec["live"])
    assert delta_rule.engages(1, jnp.zeros((2, b, h, 128, 128)))

    if steps > 1:       # from zeros, as `sequence`
        def many(s_all, xs):
            def one(s_all, x):
                o, s_all = delta_rule.step_in_place(*x, s_all, 1)
                return s_all, o
            return jax.lax.scan(one, s_all, xs)
        new, got = jax.jit(many)(
            jnp.zeros((2,) + s0.shape), tuple(
                jnp.swapaxes(t, 0, 1) for t in (q, k, v, a, beta)))
        want, state = delta_rule.sequence(q, k, v, a, beta)
        np.testing.assert_allclose(jnp.swapaxes(got, 0, 1), want, atol=2e-6,
                                   rtol=0)
        np.testing.assert_allclose(new[1], state, atol=2e-6, rtol=0)
        want, state = _numpy_rule(q, k, v, a, beta, np.zeros_like(s0))
        np.testing.assert_allclose(jnp.swapaxes(got, 0, 1), want, atol=2e-6,
                                   rtol=0)
        np.testing.assert_allclose(new[1], state, atol=2e-6, rtol=0)
        return
    s_all = np.stack([s0[::-1], s0])
    one = [t[:, 0] for t in (q, k, v, a, beta)]
    got, new = jax.jit(lambda *x: delta_rule.step_in_place(*x, 1, live))(
        *one, s_all)
    np.testing.assert_array_equal(new[0], s_all[0])     # the layer beside it
    runs = np.ones(b, bool) if live is None else np.asarray(live)
    np.testing.assert_array_equal(new[1][~runs], s0[~runs])     # bit for bit
    np.testing.assert_array_equal(got[~runs], 0.0)
    want, state = delta_rule.step(*one, s0, live)
    np.testing.assert_allclose(got[runs], want[runs], atol=2e-6, rtol=0)
    np.testing.assert_allclose(new[1], state, atol=2e-6, rtol=0)
    want, state = _numpy_rule(*(t[:, :1] for t in (q, k, v, a, beta)), s0)
    np.testing.assert_allclose(got[runs], want[runs, 0], atol=3e-6, rtol=0)
    np.testing.assert_allclose(new[1][runs], state[runs], atol=3e-6, rtol=0)


def test_what_the_step_kernel_takes_and_refuses(monkeypatch):
    """One token a row against float32 states of whole 128 x 128 tiles, on a
    TPU or under the interpreter; anything else is `step` between a cut and
    a placement, whose result is `step`'s bit for bit."""
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    assert delta_rule.kernel_shape(1, f32((20, 32, 32, 128, 128)))
    assert delta_rule.kernel_shape(1, f32((2, 1, 3, 256, 128)))
    for tokens, states in (
            (1, f32((2, 4, 4, 16, 16))),            # the rehearsal's heads
            (1, f32((2, 4, 4, 64, 128))),           # dk no whole tile
            (1, f32((2, 4, 4, 128, 192))),          # nor dv
            (1, jax.ShapeDtypeStruct((2, 4, 4, 128, 128), jnp.bfloat16)),
            (128, f32((20, 4, 32, 128, 128)))):     # a chunk
        assert not delta_rule.kernel_shape(tokens, states), (tokens, states)
        assert not delta_rule.engages(tokens, states)
    taken = f32((2, 2, 4, 128, 128))
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    assert not delta_rule.engages(1, taken)            # this backend: a CPU
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert delta_rule.engages(1, taken)
    # a refused shape under the interpreter all the same: the slices
    q, k, v, a, beta, s0 = _inputs(2, s=1)
    one = [t[:, 0] for t in (q, k, v, a, beta)]
    live = jnp.asarray([False, True])
    got, new = delta_rule.step_in_place(*one, np.stack([s0, s0]), 0, live)
    want, state = delta_rule.step(*one, s0, live)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(new, np.stack([state, s0]))


# ---------------------------------------------- the model and what it holds

def test_pattern_weights_and_counts(world):
    w = world
    cfg = w.cfg
    assert cfg.kinds == ("kda", "kda", "full", "kda", "kda", "full")
    assert cfg.layer_segments == (
        ("dense_layers", 0, 1, "kda"), ("layers", 0, 1, "kda"),
        ("layers", 1, 1, "full"), ("layers", 2, 2, "kda"),
        ("layers", 4, 1, "full"))
    assert (cfg.pos_emb, cfg.q_lora_rank, cfg.attention) == ("none", 0,
                                                             "mla")
    lay = w.params["layers"]
    # each operator's weights over ITS layers of the run alone
    assert lay["kda_in"].shape == (3, 64, 3 * 64)
    assert lay["kda_conv"].shape == (3, 3 * 64, 4)
    assert lay["kda_lo"].shape == (3, 64, 2 * 8 + 4)
    assert lay["wq"].shape == (2, 64, 4, 20) and "wq_a" not in lay \
        and "q_norm" not in lay
    assert w.params["dense_layers"]["kda_in"].shape[0] == 1
    assert "wq" not in w.params["dense_layers"]
    assert stack_kinds(cfg, "kda_fb") == ("kda",)
    assert "kda" not in stack_kinds(cfg, "wq")
    tree, _ = init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree_util.tree_map(lambda x: x.shape, tree) == \
        jax.tree_util.tree_map(lambda x: x.shape, w.params)
    assert count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(tree))
    # the published draw of what decides how long a state remembers
    assert 0.0 <= float(tree["layers"]["kda_a_log"].min()) \
        and float(tree["layers"]["kda_a_log"].max()) <= np.log(16.0)
    dt = jax.nn.softplus(tree["layers"]["kda_dt_bias"])
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11
    # a KDA layer's decode cost does not grow with the context
    grow = decode_flops_per_token(cfg, 200) - decode_flops_per_token(cfg, 100)
    assert grow == 2 * 2 * 4 * (2 * 16 + 8) * 100      # the 2 full layers'


def test_a_cache_has_a_sixth_kind_of_state_of_a_type_of_its_own(world):
    cfg = world.cfg
    assert cache_rows(cfg) == {"kv": (1, 24), "s_delta": (4, 16),
                               "conv_delta": (1, 3)}
    cache = init_slot_cache(cfg, 3, MAX_LEN)
    assert cache["s_delta"].shape == (4, 3, 4, 16, 16)
    assert cache["conv_delta"].shape == (4, 3, 1, 3, 3 * 64)
    assert cache["kv"].shape == (2, 3, 1, 24, MAX_LEN)
    assert cache["s_delta"].dtype == jnp.float32
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    held = init_slot_cache(bf16, 3, MAX_LEN)
    assert (held["s_delta"].dtype, held["conv_delta"].dtype,
            held["kv"].dtype) == (jnp.float32, jnp.bfloat16, jnp.bfloat16)
    assert array_dtype(bf16, "s_delta") == jnp.float32
    assert _state_kind("s_delta") == _state_kind("conv_delta") == "delta"
    # a KDA layer's bytes a sequence: the state at 4, its inputs at 2
    assert position_bytes(bf16) == {
        "full": 24 * 2, "ring": 0, "state": 0,
        "delta": 4 * 16 * 16 * 4 + 3 * 3 * 64 * 2}
    assert cache_bytes(held) == {
        "full": 2 * 3 * 24 * MAX_LEN * 2, "ring": 0, "state": 0,
        "delta": 4 * 3 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 2)}
    assert cache_capacity(held, bf16) == MAX_LEN
    # a step writes a column a slot on the full layers alone
    assert CacheTraffic(held, bf16, CHUNK).step(()).column_writes == 2 * 3


def test_rows_a_step_attends_and_the_state_it_moves(world, monkeypatch):
    step = CacheTraffic(init_slot_cache(world.cfg, 3, MAX_LEN), world.cfg,
                        CHUNK).step((9, 99))
    assert step[:6] == (2 * 110, 6 * 110, 2 * 110 * 96, 6 * 110 * 96, 0, 0)
    per = 4 * 16 * 16 * 4 + 3 * 3 * 64 * 4          # float32 model
    # states of 16 x 16: XLA's form, three passes over all 3 slots' states
    assert step[9:12] == (4 * 2, 2 * 4 * 2 * per, 3 * 4 * 3 * per)
    assert CacheTraffic.STEP_SUMS[9:12] == (
        "state_rows", "state_bytes_moved", "state_bytes_fetched")
    # states of 128 x 128 where the kernel runs: the live slots' alone,
    # twice; on this backend without the interpreter XLA's form again
    wide = dataclasses.replace(world.cfg, kda_head_dim=128)
    cache = init_slot_cache(wide, 3, MAX_LEN)
    per = position_bytes(wide)["delta"]
    assert per == 4 * 128 * 128 * 4 + 3 * 3 * 4 * 128 * 4
    fetched = lambda cache, cfg, live: CacheTraffic(cache, cfg, CHUNK).step(
        (5,) * live).state_bytes_fetched
    assert fetched(cache, wide, 2) == 3 * 4 * 3 * per
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert [fetched(cache, wide, n) for n in (2, 0)] == [2 * 4 * 2 * per, 0]
    # no KDA layer, no state
    plain = dataclasses.replace(world.cfg, layer_kinds=("full",) * 6)
    assert fetched(init_slot_cache(plain, 2, 64), plain, 2) == 0


def test_the_kda_scope_stands_inside_attention_and_conv(world):
    """What the operator adds is named ``kda`` in every instruction's path,
    inside one of the ten parts: its convolutions in ``conv``, the rest in
    ``attention``; the big projections stay ``projections``."""
    from ray_tpu.util import device_profile
    w = world
    slots = init_slot_cache(w.cfg, 2, MAX_LEN)
    text = w.step.lower(w.params, w.toks[:, 0], slots,
                        jnp.ones((2,), bool)).compile().as_text()
    paths = [p for p in device_profile.op_map(text)["instructions"].values()
             if p]
    inside = [p for p in paths if "kda" in p.split("/")]
    assert inside
    assert {device_profile.part_of(p)[0] for p in inside} <= {
        "attention", "conv"}
    assert any(device_profile.part_of(p)[0] == "conv" for p in inside)


# ------------------------------------------------ every cached program

def _chunked(w, row, n, cache, chunk=CHUNK):
    host, off = np.asarray(w.toks[row:row + 1, :n]), 0
    while off < n:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=chunk, capacity=MAX_LEN)
    return logits, cache


def test_plain_and_cached_forms_agree_on_a_whole_prompt(world):
    w = world
    logits, cache = prefill(w.params, w.toks[:, :50], w.cfg,
                            init_kv_cache(w.cfg, 2, MAX_LEN))
    np.testing.assert_allclose(logits, w.want[:, 49], **TOL)
    slots = dict(cache, pos=jnp.full((2,), 50, jnp.int32))
    for t in range(50, 56):
        logits, slots = w.step(w.params, w.toks[:, t], slots,
                               jnp.ones((2,), bool))
        np.testing.assert_allclose(logits, w.want[:, t], **TOL)
    assert np.isfinite(float(lm_loss(w.params, {"tokens": w.toks[:, :40]},
                                     w.cfg)))


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_chunks_and_a_prompt_that_ends_mid_chunk(world, chunk):
    """Chunks of 8 are half a block of the chunkwise form, of 64 four."""
    w = world
    for row, n in ((0, 203), (1, 40)):
        logits, cache = _chunked(w, row, n, init_kv_cache(w.cfg, 1, MAX_LEN),
                                 chunk)
        np.testing.assert_allclose(logits[0], w.want[row, n - 1], **TOL)
        assert int(cache["pos"]) == n


def test_lanes_with_a_lane_that_stands(world):
    w = world
    cache = init_slot_cache(w.cfg, 3, MAX_LEN)
    # what the standing lane holds must stay bit for bit
    mark = jax.random.normal(jax.random.PRNGKey(5), cache["s_delta"].shape)
    cache = dict(cache, s_delta=mark,
                 conv_delta=cache["conv_delta"] + 0.5)
    zero = jax.tree_util.tree_map(
        jnp.zeros_like, {n: cache[n][:, :1] for n in ("s_delta",
                                                       "conv_delta", "kv")})
    insert = jax.jit(cache_insert_slot)
    for lane in (0, 2):
        cache = insert(cache, dict(zero, pos=jnp.int32(0)), jnp.int32(lane))
    prompts = [(np.asarray(w.toks[0:1, :145]), 0), None,
               (np.asarray(w.toks[1:2, :70]), 0)]
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
    np.testing.assert_allclose(logits[0], w.want[0, 144], **TOL)
    np.testing.assert_allclose(logits[2], w.want[1, 69], **TOL)
    np.testing.assert_array_equal(cache["s_delta"][:, 1], mark[:, 1])
    assert float(jnp.abs(cache["conv_delta"][:, 1] - 0.5).max()) == 0.0
    assert not np.asarray(cache["kv"][:, 1]).any()


def _two_slots(w, depths):
    slots = init_slot_cache(w.cfg, 2, MAX_LEN)
    insert = jax.jit(cache_insert_slot)
    for row, n in enumerate(depths):
        _, one = _chunked(w, row, n, init_kv_cache(w.cfg, 1, MAX_LEN))
        slots = insert(slots, one, jnp.int32(row))
    return slots


def test_slots_at_depths_of_their_own_and_one_that_stands(world):
    w = world
    slots = _two_slots(w, (170, 41))
    active = jnp.asarray([True, True])
    for j in range(6):
        logits, slots = w.step(
            w.params, jnp.stack([w.toks[0, 170 + j], w.toks[1, 41 + j]]),
            slots, active)
        np.testing.assert_allclose(logits[0], w.want[0, 170 + j], **TOL)
        np.testing.assert_allclose(logits[1], w.want[1, 41 + j], **TOL)
    # slot 1 stands: both its arrays bit for bit, whatever token it is fed
    before = {n: np.asarray(slots[n][:, 1])
              for n in ("s_delta", "conv_delta")}
    logits, slots = w.step(
        w.params, jnp.stack([w.toks[0, 176], jnp.int32(5)]), slots,
        jnp.asarray([True, False]))
    np.testing.assert_allclose(logits[0], w.want[0, 176], **TOL)
    assert slots["pos"].tolist() == [177, 47]
    for n, held in before.items():
        np.testing.assert_array_equal(slots[n][:, 1], held)
    logits, slots = w.step(w.params,
                           jnp.stack([w.toks[0, 177], w.toks[1, 47]]),
                           slots, active)
    np.testing.assert_allclose(logits[1], w.want[1, 47], **TOL)


def test_latent_attention_with_no_query_latent_and_no_rotation():
    """A model of full layers alone: the absorbed form over the cache (no
    ``wq_a``, no ``q_norm``, nothing turned) against the plain form."""
    cfg = TransformerConfig.tiny(
        vocab_size=97, d_model=32, n_layers=2, n_heads=2, n_kv_heads=None,
        attention="mla", q_lora_rank=0, kv_lora_rank=12, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, pos_emb="none", d_ff=48,
        dtype=jnp.float32, max_seq_len=64)
    params, _ = init_params(jax.random.PRNGKey(1), cfg)
    assert set(params["layers"]) >= {"wq", "wkv_a", "wkv_b", "wo",
                                     "kv_norm"}
    assert not {"wq_a", "wq_b", "q_norm"} & set(params["layers"])
    assert count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, 97)
    want = forward(params, toks, cfg)
    from ray_tpu.models.transformer import rope_tables
    assert rope_tables(cfg, lambda base: base) == {}    # nothing is turned
    logits, cache = prefill(params, toks[:, :30], cfg,
                            init_kv_cache(cfg, 1, 64))
    np.testing.assert_allclose(logits, want[:, 29], **TOL)
    slots = dict(cache, pos=jnp.full((1,), 30, jnp.int32))
    for t in range(30, 36):
        logits, slots = decode_step_slots(params, toks[:, t], slots,
                                          jnp.ones((1,), bool), cfg)
        np.testing.assert_allclose(logits, want[:, t], **TOL)


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
    """Four sessions at once through chunk programs, the lanes program and
    the fused slot step: every token is the full forward's choice at its
    position; the engine counts the latents a full layer attends and the
    state a KDA layer moves."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    w = world
    core = _core(w, max_slots=3)
    try:
        prompts = [np.asarray(w.toks[i % 2, a:a + n]).tolist()
                   for i, (a, n) in enumerate(
                       ((0, 180), (3, 43), (11, 97), (20, 264)))]
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
        per = 4 * 16 * 16 * 4 + 3 * 3 * 64 * 4
        assert cache["bytes_delta"] == 4 * 3 * per
        assert cache["bytes_full"] == 2 * 3 * 24 * MAX_LEN * 4
        assert cache["bytes_per_position"] == 2 * 24 * 4
        assert cache["state_rows"] == 4 * st["tokens"]
        assert cache["state_bytes_moved"] == 2 * per * cache["state_rows"]
        assert cache["rows_read"] * 3 == cache["rows_if_full"]
        assert cache["bytes_read"] == cache["rows_read"] * 96
        span = [e for e in tracing.span_events()
                if e["name"] == "cache:rows"][-1]["args"]
        assert span["bytes_delta"] == cache["bytes_delta"]
        assert span["state_bytes_moved"] == 2 * per * span["state_rows"]
    finally:
        core.engine.shutdown()


def test_a_slot_reused_after_another_session_starts_from_zeros(world):
    """... and the prefix reuse hands a state on only from a donor that
    STANDS at the prefix: one that has decoded past it holds a later state,
    which nothing can take back."""
    w = world
    core = _core(w, max_slots=1, prefix_cache_min_tokens=4,
                 token_queue_depth=2)
    try:
        long_ = np.asarray(w.toks[0, :190]).tolist()
        got = _stream(core, long_, 10)
        assert got == _forced(w, long_, got)
        # the ONE slot again: were the long session's state still there,
        # the short one's first token would already be another
        short = np.asarray(w.toks[1, :44]).tolist()
        got = _stream(core, short, 10)
        assert got == _forced(w, short, got)
        # the donor has decoded ten tokens past the shared 40: refused, the
        # prompt prefills from its start, and the tokens are right
        hits = core.engine.stats()["prefix"]["applied_hits"]
        fork = short[:40] + np.asarray(w.toks[0, 30:50]).tolist()
        got = _stream(core, fork, 10)
        assert core.engine.stats()["prefix"]["applied_hits"] == hits
        assert got == _forced(w, fork, got)
        assert core.engine.stats()["cache_copies"] == 0
    finally:
        core.engine.shutdown()


def test_prefix_exact_serves_only_a_donor_that_stands_at_the_prefix(world):
    exact = functools.partial(prefix_holds, world.cfg, chunk=CHUNK,
                              capacity=MAX_LEN)
    assert exact(40, 40, 100)
    assert not exact(41, 40, 100)       # it has decoded one token past
    assert not exact(None, 40, 100)     # no such donor
    # a chunk window that would be set back at the cache's end
    assert not exact(40, 40, MAX_LEN - 1)
    gathered = jax.jit(cache_gather_slot)(
        _two_slots(world, (40, 30)), jnp.int32(0), jnp.int32(40))
    assert set(gathered) == {"kv", "s_delta", "conv_delta", "pos"}
    host = np.concatenate([np.asarray(world.toks[0:1, :40]),
                           np.asarray(world.toks[1:2, 40:70])], axis=1)
    want = np.asarray(forward(world.params, jnp.asarray(host), world.cfg))
    off, cache = 40, gathered
    while off < 70:
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, world.params, host, off, cache, world.cfg,
            chunk=CHUNK, capacity=MAX_LEN)
    np.testing.assert_allclose(logits[0], want[0, 69], **TOL)


# --------------------------------------------- planted faults, and refusals

def _fault_state_not_carried(w):
    """The delta state NOT carried from a prompt's last chunk into its slot:
    zeros at the first decode step."""
    slots = _two_slots(w, (170, 141))
    slots = dict(slots, s_delta=jnp.zeros_like(slots["s_delta"]))
    out = []
    for j in range(8):
        logits, slots = w.step(
            w.params, jnp.stack([w.toks[0, 170 + j], w.toks[1, 141 + j]]),
            slots, jnp.ones((2,), bool))
        out.append(np.asarray(logits[0]))
    return np.stack(out), w.want[0, 170:178]


def _fault_correction_dropped(w, monkeypatch):
    """``S = S' + beta k v^T``: plain gated linear attention."""
    def step(q, k, v, a, beta, state, live=None):
        state = jnp.exp(a)[..., None] * state \
            + k[..., None] * (beta[..., None] * v)[..., None, :]
        return jnp.einsum("bhk,bhkv->bhv", q, state), state

    monkeypatch.setattr(delta_rule, "step", step)
    return np.asarray(forward(w.params, w.toks[:, :100], w.cfg)), \
        w.want[:, :100]


def _fault_conv_inputs_zeroed(w):
    """The convolutions' carried inputs zeroed at every chunk boundary."""
    host, off = np.asarray(w.toks[0:1, :100]), 0
    cache = init_kv_cache(w.cfg, 1, MAX_LEN)
    while off < 100:
        cache = dict(cache, conv_delta=jnp.zeros_like(cache["conv_delta"]))
        logits, cache, off, _ = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=CHUNK, capacity=MAX_LEN)
    return np.asarray(logits), w.want[0:1, 99]


@pytest.mark.parametrize("fault", ["state not carried",
                                   "correction dropped",
                                   "conv inputs zeroed"])
def test_three_planted_faults_each_fail(world, monkeypatch, fault):
    w = world
    got, want = {
        "state not carried": lambda: _fault_state_not_carried(w),
        "correction dropped": lambda: _fault_correction_dropped(
            w, monkeypatch),
        "conv inputs zeroed": lambda: _fault_conv_inputs_zeroed(w),
    }[fault]()
    # what the comparisons above hold the programs to
    assert not np.allclose(got, want, **TOL), fault
    # ... by far: a tenth of the logits' spread at the worst position
    assert np.abs(got - want).max() > 0.1 * w.want.std(), fault


def test_what_a_configuration_is_refused_for(world):
    cfg, toks = world.cfg, world.toks[:1, :8]
    alone = dataclasses.replace(cfg, layer_kinds=("kda",) * 6)
    with pytest.raises(NotImplementedError, match="KDA layers"):
        prefill_chunk_jit(world.params, toks,
                          init_kv_cache(cfg, 1, MAX_LEN), cfg=alone)
    for bad in (dict(kda_heads=0), dict(kda_gate_rank=0),
                dict(kda_conv_kernel=1)):
        broken = dataclasses.replace(cfg, **bad)
        with pytest.raises(ValueError, match="'kda' layer needs"):
            init_params(jax.random.PRNGKey(0), broken)
        with pytest.raises(ValueError, match="'kda' layer needs"):
            forward(world.params, toks, broken)
    with pytest.raises(NotImplementedError, match="learned"):
        prefill_chunk_jit(world.params, toks, init_kv_cache(cfg, 1, MAX_LEN),
                          cfg=dataclasses.replace(cfg, pos_emb="learned"))
    # a chunk window set back at the cache's end would run tokens twice
    host = np.asarray(world.toks[0:1, :MAX_LEN - 3])
    with pytest.raises(ValueError, match="cannot be taken back"):
        prefill_chunk_step(prefill_chunk_jit, world.params, host,
                           MAX_LEN - 20, init_kv_cache(cfg, 1, MAX_LEN), cfg,
                           chunk=CHUNK, capacity=MAX_LEN)
