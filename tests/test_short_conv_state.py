"""Conv layers beside attention layers: a gated short convolution whose
cache is a fixed state a sequence (the last ``conv_kernel - 1`` inputs of
the convolution) and no positions, two operators' weights in one run's
tree, on the CPU at a tiny size in float32.

The oracle is the whole-sequence form (`ops/short_conv.py` with no state,
`forward` for the model) and, for streams through the engine,
`models.generate` (tests/greedy_reference.py).  The family's independent
float32 reference is compared in
tests/benchmark/test_perfbench_family_lfm2_moe.py.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greedy_reference import greedy_stream
from ray_tpu.models import (TransformerConfig, cache_gather_slot,
                            cache_insert_slot, decode_step_slots, forward,
                            init_kv_cache, init_params, init_slot_cache,
                            prefill, prefill_chunk_jit, prefill_chunked)
from ray_tpu.models.generate import (cache_arrays, cache_bytes,
                                     cache_capacity, cache_rows,
                                     prefill_chunk_step)
from ray_tpu.models.transformer import (count_params, decode_flops_per_token,
                                        flops_per_token, operator_layers)
from ray_tpu.ops.short_conv import conv_block, short_conv

TOL = 2e-5
KINDS = ("conv", "full", "conv", "conv", "conv")


def tiny(**kw) -> TransformerConfig:
    base = dict(
        vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_size=16, d_ff=160, max_seq_len=128, pos_emb="rope",
        rope_base=1e6, activation="swiglu", norm="rmsnorm", norm_eps=1e-5,
        tie_embeddings=True, remat=False, qk_norm=True, layer_kinds=KINDS,
        conv_kernel=3, n_experts=8, expert_top_k=2, router="sigmoid",
        moe_d_ff=32, first_dense_layers=1, dtype=jnp.float32,
        param_dtype=jnp.float32, attention_impl="reference")
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    # a bias that changes choices, norms that are no identity
    params["layers"]["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(7), params["layers"]["router_bias"].shape)
    for i, name in enumerate(("q_norm", "k_norm")):
        params["layers"][name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(20 + i), params["layers"][name].shape)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 72), 0, 256)
    return cfg, params, axes, toks, forward(params, toks, cfg)


# ------------------------------------------------------ ops/short_conv.py

def _conv_case(s=13, d=8, taps=3, b=2):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    return (jax.random.normal(ks[0], (b, s, d), jnp.float32),
            jax.random.normal(ks[1], (d, taps), jnp.float32))


def test_whole_sequence_form_is_the_equation():
    u, w = _conv_case()
    v, state = short_conv(u, w)
    un, wn = np.asarray(u), np.asarray(w)
    for t in range(u.shape[1]):
        want = sum(wn[:, j] * un[:, t - 2 + j] for j in range(3)
                   if t - 2 + j >= 0)
        np.testing.assert_allclose(np.asarray(v[:, t]), want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(state), un[:, -2:])


@pytest.mark.parametrize("split", [
    (13,), (1,) * 13, (5, 8), (8, 5), (1, 12), (12, 1), (4, 4, 4, 1),
    (2, 1, 3, 7), (6, 1, 6)])
@pytest.mark.parametrize("taps", [2, 3, 4])
def test_chunks_with_carry_and_single_steps_are_the_whole_sequence(
        split, taps):
    u, w = _conv_case(taps=taps)
    want, want_state = short_conv(u, w)
    state, got, at = None, [], 0
    for c in split:
        v, state = short_conv(u[:, at:at + c], w, state)
        got.append(v)
        at += c
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(want), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(state), np.asarray(want_state))


@pytest.mark.parametrize("n_valid", [0, 1, 2, 3, 5, 8])
@pytest.mark.parametrize("off", [0, 1, 5])
def test_a_padded_remainders_carry_out_is_its_last_valid_tokens(
        off, n_valid):
    """A chunk of 8 rows of which ``n_valid`` are real, after ``off``
    tokens: the state that leaves is the one after token ``off + n_valid``,
    whatever the padding rows hold, and the real rows' outputs are the
    whole sequence's."""
    u, w = _conv_case()
    want, _ = short_conv(u, w)
    _, before = short_conv(u[:, :off], w) if off else (None, None)
    junk = 7.0 * jnp.ones_like(u[:, :8])
    chunk = jnp.concatenate([u[:, off:off + n_valid], junk], 1)[:, :8]
    v, state = short_conv(chunk, w, before,
                          jnp.full((u.shape[0],), n_valid, jnp.int32))
    np.testing.assert_allclose(np.asarray(v[:, :n_valid]),
                               np.asarray(want[:, off:off + n_valid]),
                               atol=1e-6)
    _, after = short_conv(u[:, :off + n_valid], w)
    np.testing.assert_array_equal(np.asarray(state), np.asarray(after))


def test_rows_advance_each_by_their_own_count():
    u, w = _conv_case(b=3)
    _, before = short_conv(u[:, :4], w)
    _, state = short_conv(u[:, 4:5], w, before, jnp.array([1, 0, 1]))
    _, moved = short_conv(u[:, :5], w)
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(moved[0]))
    np.testing.assert_array_equal(np.asarray(state[1]),
                                  np.asarray(before[1]))


def test_block_is_gate_conv_gate_between_two_projections():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    y = jax.random.normal(ks[0], (2, 9, 8))
    w_in, w_out = (jax.random.normal(ks[1], (8, 24)),
                   jax.random.normal(ks[2], (8, 8)))
    w = jax.random.normal(ks[3], (8, 3))
    out, _ = conv_block(y, w_in, w, w_out)
    b, c, x = jnp.split(y @ w_in, 3, axis=-1)       # in that order
    v, _ = short_conv(b * x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray((c * v) @ w_out),
                               atol=1e-5)


# ----------------------------------------------- two operators in one run

def test_pattern_and_stacks_by_operator(model):
    cfg, params = model[0], model[1]
    assert cfg.layer_segments == (("dense_layers", 0, 1, "conv"),
                                  ("layers", 0, 1, "full"),
                                  ("layers", 1, 3, "conv"))
    assert operator_layers(cfg, "dense_layers") == (0, 1)
    assert operator_layers(cfg, "layers") == (1, 3)
    assert operator_layers(cfg, "layers", 2) == (1, 1)
    lead, main = params["dense_layers"], params["layers"]
    assert "wq" not in lead and lead["conv_in"].shape == (1, 64, 192)
    assert main["wq"].shape[0] == 1 and main["conv_w"].shape == (3, 64, 3)
    assert main["attn_norm"].shape[0] == main["w_in"].shape[0] == 4
    period = tiny(n_layers=9, first_dense_layers=0, layer_kinds=(
        "full", "conv", "conv") * 3)
    assert [s[1:] for s in period.layer_segments] == [
        (0, 1, "full"), (1, 2, "conv"), (3, 1, "full"), (4, 2, "conv"),
        (6, 1, "full"), (7, 2, "conv")]


def test_axes_match_the_tree_operator_by_operator(model):
    cfg, params, axes = model[:3]
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_a = jax.tree_util.tree_leaves_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert [p for p, _ in flat_p] == [p for p, _ in flat_a]
    for (_, w), (_, a) in zip(flat_p, flat_a):
        assert len(a) == w.ndim


def test_counts_by_hand(model):
    cfg, params = model[0], model[1]
    d = 64
    conv = d * 3 * d + d * d + d * 3
    attn = 2 * d * 4 * 16 + 2 * d * 2 * 16 + 2 * 16     # q, o; k, v; norms
    expert_ffn = 8 * 3 * d * 32 + d * 8 + 8
    want = (conv + 3 * d * 160 + 2 * d) + (attn + expert_ffn + 2 * d) \
        + 3 * (conv + expert_ffn + 2 * d) + 256 * d + d
    assert count_params(cfg) == want == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    # a conv layer attends nothing: one attention layer's rows at depth 50
    active = 4 * (2 * 3 * d * 32 + d * 8) + 3 * d * 160 \
        + 4 * 4 * d * d + (attn - 2 * 16) + 256 * d
    assert decode_flops_per_token(cfg, 50) == 2 * active \
        + 2 * (2 * 4 * 16) * 50
    assert flops_per_token(cfg, 40) == 6 * active + 6 * (4 * 16) * 40


def test_a_cache_has_a_state_that_is_no_positions(model):
    cfg = model[0]
    assert cache_rows(cfg) == {"k": (2, 16), "v": (2, 16),
                               "conv_state": (1, 2)}
    for max_len in (32, 96):
        cache = init_slot_cache(cfg, 3, max_len)
        arrs = cache_arrays(cache)
        assert arrs["k"].shape == (1, 3, 2, 16, max_len)
        assert arrs["conv_state"].shape == (4, 3, 1, 2, 64)   # whatever
        assert cache_capacity(cache) == max_len
        assert cache_bytes(cache) == {
            "full": 2 * 3 * 2 * 16 * max_len * 4, "ring": 0,
            "state": 4 * 3 * 2 * 64 * 4}


@pytest.mark.parametrize("chunk", [1, 3, 8, 32])
@pytest.mark.parametrize("n", [5, 19, 32])
def test_chunked_prefill_and_slot_decode_are_the_whole_sequence(
        model, chunk, n):
    """The prompt as padded chunk programs of one width, then the stream a
    token a step in a slot beside two that stand: the logits are
    `forward`'s at every position."""
    cfg, params, _, toks, want = model
    lg, cache = prefill_chunked(params, toks[:1, :n], cfg,
                                init_kv_cache(cfg, 1, 64), chunk=chunk)
    assert float(jnp.abs(lg[0] - want[0, n - 1]).max()) < TOL
    slots = cache_insert_slot(init_slot_cache(cfg, 3, 64), cache,
                              jnp.int32(1))
    active = jnp.array([False, True, False])
    step = jax.jit(decode_step_slots, static_argnames=("cfg",))
    for p in range(n, n + 12):
        tok = jnp.array([3, int(toks[0, p]), 200], jnp.int32)
        lg, slots = step(params, tok, slots, active, cfg=cfg)
        assert float(jnp.abs(lg[1] - want[0, p]).max()) < TOL, p
    assert slots["pos"].tolist() == [0, n + 12, 0]


@pytest.mark.parametrize("widths", [(8, 8, 3), (5, 1, 1, 12), (19,)])
def test_unpadded_chunks_and_single_tokens_walk_the_state(model, widths):
    """The walk the benchmark's comparison makes: `prefill_chunk_jit`
    without ``n_valid``, whole chunks and then a token at a time."""
    cfg, params, _, toks, want = model
    cache, at = init_kv_cache(cfg, 1, 64), 0
    for c in widths:
        lg, cache = prefill_chunk_jit(params, toks[:1, at:at + c], cache,
                                      cfg=cfg)
        at += c
        assert float(jnp.abs(lg[0] - want[0, at - 1]).max()) < TOL
    assert int(cache["pos"]) == sum(widths)


@pytest.mark.parametrize("s", [1, 2, 5, 30])
def test_whole_prompt_prefill_leaves_the_last_tokens_state(model, s):
    cfg, params, _, toks, want = model
    lg, cache = prefill(params, toks[:1, :s], cfg, init_kv_cache(cfg, 1, 64))
    assert float(jnp.abs(lg[0] - want[0, s - 1]).max()) < TOL
    _, chunked = prefill_chunked(params, toks[:1, :s], cfg,
                                 init_kv_cache(cfg, 1, 64), chunk=4)
    np.testing.assert_allclose(np.asarray(cache["conv_state"]),
                               np.asarray(chunked["conv_state"]), atol=TOL)
    lg, _ = prefill_chunk_jit(params, toks[:1, s:s + 1], cache, cfg=cfg)
    assert float(jnp.abs(lg[0] - want[0, s]).max()) < TOL


def test_an_inactive_slots_state_is_bit_identical_after_a_step(model):
    cfg, params, _, toks, _ = model
    _, a = prefill_chunked(params, toks[:1, :9], cfg,
                           init_kv_cache(cfg, 1, 64), chunk=4)
    _, b = prefill_chunked(params, toks[1:, :14], cfg,
                           init_kv_cache(cfg, 1, 64), chunk=4)
    slots = init_slot_cache(cfg, 3, 64)
    slots = cache_insert_slot(slots, a, jnp.int32(0))
    slots = cache_insert_slot(slots, b, jnp.int32(2))
    before = np.asarray(slots["conv_state"])
    active = jnp.array([True, False, False])
    _, after = decode_step_slots(params, jnp.array([5, 6, 7], jnp.int32),
                                 slots, active, cfg)
    got = np.asarray(after["conv_state"])
    np.testing.assert_array_equal(got[:, 1:], before[:, 1:])
    assert not np.array_equal(got[:, 0], before[:, 0])
    assert after["pos"].tolist() == [10, 0, 14]


def test_slot_insert_and_gather_carry_the_state(model):
    cfg, params, _, toks, want = model
    _, a = prefill_chunked(params, toks[:1, :21], cfg,
                           init_kv_cache(cfg, 1, 64), chunk=8)
    slots = jax.jit(cache_insert_slot)(init_slot_cache(cfg, 2, 64), a,
                                       jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(slots["conv_state"][:, 1]),
                                  np.asarray(a["conv_state"][:, 0]))
    assert not np.asarray(slots["conv_state"][:, 0]).any()
    # a donor that STANDS at the prefix: the seeded cache goes on as an
    # unseeded one does
    seeded = jax.jit(cache_gather_slot)(slots, jnp.int32(1), jnp.int32(21))
    assert int(seeded["pos"]) == 21
    np.testing.assert_array_equal(np.asarray(seeded["conv_state"]),
                                  np.asarray(a["conv_state"]))
    for p in range(21, 30):
        lg, seeded = prefill_chunk_jit(params, toks[:1, p:p + 1], seeded,
                                       cfg=cfg)
        assert float(jnp.abs(lg[0] - want[0, p]).max()) < TOL, p


# ------------------------------------------- refused, not answered wrongly

def test_what_a_state_cannot_serve_is_refused(model):
    cfg, params, _, toks, _ = model
    # a prompt that ends within a chunk of the cache's end: the window
    # would be set back over tokens the state has already taken
    cache = init_kv_cache(cfg, 1, 20)
    host = np.asarray(toks[:1, :19])
    _, cache, off, _ = prefill_chunk_step(
        prefill_chunk_jit, params, host, 0, cache, cfg, chunk=8, capacity=20)
    _, cache, off, _ = prefill_chunk_step(
        prefill_chunk_jit, params, host, off, cache, cfg, chunk=8,
        capacity=20)
    with pytest.raises(ValueError, match="set back"):
        prefill_chunk_step(prefill_chunk_jit, params, host, off, cache, cfg,
                           chunk=8, capacity=20)
    only = dataclasses.replace(cfg, layer_kinds=("conv",) * 5)
    with pytest.raises(NotImplementedError, match="full-attention layer"):
        init_and_step(only)
    with pytest.raises(ValueError, match="conv_kernel"):
        init_and_step(dataclasses.replace(cfg, conv_kernel=1))
    with pytest.raises(ValueError, match="layer_kinds"):
        init_and_step(dataclasses.replace(cfg, layer_kinds=(
            "conv", "full", "conv", "conv", "state")))


def init_and_step(cfg):
    params, _ = init_params(jax.random.PRNGKey(0), dataclasses.replace(
        cfg, layer_kinds=KINDS, conv_kernel=3))
    return prefill(params, jnp.zeros((1, 4), jnp.int32), cfg,
                   {"k": jnp.zeros((1, 1, 2, 16, 8)), "pos": jnp.int32(0)})


# ------------------------------------------------------------- the engine

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


@pytest.fixture(scope="module")
def core(model):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg, params = model[0], model[1]
    core = DecodeSessionCore(cfg, max_len=96, params=params,
                             engine=DecodeEngineConfig(
                                 max_slots=2, prefill_chunk_tokens=8,
                                 prefix_cache_min_tokens=2))
    yield core
    core.engine.shutdown()


def test_engine_serves_and_says_what_its_cache_holds(model, core):
    cfg, params = model[0], model[1]
    prompts = [list(range(3, 40)), list(range(50, 59)),
               list(range(100, 130))]
    want = [greedy_stream(cfg, p, 20, max_len=96, params=params)
            for p in prompts]
    assert [_stream(core, p, 20) for p in prompts] == want
    st = core.engine.stats()
    assert st["cache_copies"] == 0 and st["prefill_tails"] >= 3
    cache = st["cache"]
    row = 2 * 2 * 16 * 4                            # K and V, 2 heads of 16
    assert cache["bytes_full"] == 2 * 96 * row and cache["bytes_ring"] == 0
    assert cache["bytes_state"] == 4 * 2 * 2 * 64 * 4
    assert cache["bytes"] == cache["bytes_full"] + cache["bytes_state"]
    assert cache["bytes_per_position"] == row       # a state grows by nothing
    # one attention layer reads a slot's depth, four conv layers two rows
    assert cache["rows_if_full"] % 5 == 0
    assert cache["rows_read"] == cache["rows_if_full"] // 5 \
        + 4 * 2 * st["tokens"]
    assert st["moe"]["experts"] == 8 and st["moe"]["layers"] == 4


def test_engine_writes_the_states_bytes_into_its_cache_rows_span(
        core, monkeypatch):
    from ray_tpu.serve.decode_session import ContinuousBatchingEngine
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    _stream(core, list(range(30)), 8)
    args = [e for e in tracing.span_events()
            if e["name"] == "cache:rows"][-1]["args"]
    stats = core.engine.stats()["cache"]
    assert args["bytes_state"] == stats["bytes_state"] > 0
    assert args["bytes_full"] == stats["bytes_full"]
    assert args["steps"] == 1
    assert args["rows_read"] == args["rows_if_full"] // 5 + 4 * 2


def test_the_engines_cache_sums_are_the_traffics_by_name(core):
    """`CacheTraffic.STEP_SUMS`: the sixteen names the readers of the
    ``cache:rows`` span know, in their order; a real engine's
    ``stats()["cache"]`` holds them behind ``steps``, after its ``bytes*``
    keys."""
    from ray_tpu.models import CacheTraffic
    assert CacheTraffic.STEP_SUMS == (
        "rows_read", "rows_if_full", "bytes_read", "bytes_if_uniform",
        "summary_rows_read", "summary_bytes_read", "index_rows_read",
        "index_bytes_read", "ring_latent_bytes_read", "state_rows",
        "state_bytes_moved", "state_bytes_fetched", "rows_fetched",
        "column_writes", "column_write_calls", "shared_bytes_read")
    keys = tuple(core.engine.stats()["cache"])
    sums = ("steps",) + CacheTraffic.STEP_SUMS
    assert keys[-len(sums):] == sums
    assert keys[:-len(sums)] and all(
        k.startswith("bytes") for k in keys[:-len(sums)])


def test_two_sessions_side_by_side_and_a_slot_reused(model, core):
    """Slots at different depths step together, and a slot taken again
    after a longer session starts from its own prompt's state."""
    import threading
    cfg, params = model[0], model[1]
    prompts = [list(range(60, 95)), [7, 8, 9], list(range(5, 16))]
    got = [None] * 3

    def run(i):
        got[i] = _stream(core, prompts[i], 16)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == [greedy_stream(cfg, p, 16, max_len=96, params=params)
                   for p in prompts]
    assert core.engine.stats()["cache_copies"] == 0


@pytest.mark.parametrize("donor_pos,depth,n,exact", [
    (20, 20, 30, True),     # the donor stands at the prefix
    (20, 20, 92, True),     # ... and the walk 20, 28, .. ends at 92 <= 96
    (21, 20, 30, False),    # one token past it: its state has moved on
    (45, 20, 30, False),
    (12, 20, 30, False),    # (cannot be: a donor holds its whole prompt)
    (20, 20, 93, False),    # the seeded walk's last window would be set
    (90, 90, 95, False),    # back at the cache's end (capacity 96)
])
def test_a_prefix_donor_serves_only_while_it_stands_at_the_prefix(
        core, donor_pos, depth, n, exact):
    eng = core.engine
    kept = dict(eng._donors)
    try:
        eng._donors[0] = types.SimpleNamespace(pos=donor_pos)
        assert eng._prefix_exact(0, depth, n) is exact
        eng._donors.pop(0)
        assert eng._prefix_exact(0, depth, n) is False
    finally:
        eng._donors.clear()
        eng._donors.update(kept)


def test_a_donor_that_moved_on_is_refused_and_the_stream_is_exact(
        model, core):
    cfg, params = model[0], model[1]
    hits = core.engine.stats()["prefix"]["applied_hits"]
    system = list(range(140, 160))
    a = _stream(core, system + [1], 6)
    assert a == greedy_stream(cfg, system + [1], 6, max_len=96,
                              params=params)
    b = _stream(core, system + [2, 3], 6)
    assert core.engine.stats()["prefix"]["applied_hits"] == hits
    assert b == greedy_stream(cfg, system + [2, 3], 6, max_len=96,
                              params=params)
