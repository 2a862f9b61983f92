"""Latent attention with a cache of latents, a no-drop routed-expert layer
and a layer pattern with a leading dense layer, at a tiny size on the CPU:
the program against itself (absorbed against plain, cached against
uncached) and against dense arithmetic written out here.  The comparison
with the plain reference of the benchmark's family is in
tests/benchmark/test_perfbench_family_glm4_moe_lite.py.

Tolerances: everything here computes in float32 (a router in bfloat16
activations flips experts on near-ties, which is measured in the family's
test, not tolerated here), so two orders of the same sums agree to float32
rounding: 2e-5 on logits of order 1.
"""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (TransformerConfig, cache_gather_slot,
                            cache_insert_slot, count_params,
                            decode_flops_per_token, decode_step_slots,
                            engine_flops_table, flops_per_token, forward,
                            init_kv_cache, init_params, init_slot_cache,
                            prefill, prefill_chunk_jit)
from ray_tpu.models.generate import (_decode_step_slots, _prefill_chunk,
                                     cache_arrays, cache_capacity, cache_rows,
                                     chunk_window, padded_chunk)
from ray_tpu.ops import latent_attention as mla
from ray_tpu.ops.moe import moe_ffn, routed_ffn, sigmoid_route
from ray_tpu.ops.rotary import apply_rotary, rotary_angles

TOL = 2e-5


def tiny(**kw) -> TransformerConfig:
    base = dict(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=160,
        max_seq_len=128, pos_emb="rope", rope_base=1e4, activation="swiglu",
        norm="rmsnorm", norm_eps=1e-5, tie_embeddings=False, remat=False,
        attention="mla", q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
        n_experts=8, expert_top_k=2, router="sigmoid", moe_d_ff=32,
        n_shared_experts=1, routed_scaling_factor=1.8, first_dense_layers=1,
        dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference")
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    # a bias that changes choices, as a trained model's does
    params["layers"]["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(7), params["layers"]["router_bias"].shape)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 72), 0, 256)
    return cfg, params, axes, toks, forward(params, toks, cfg)


def test_pattern_is_runs_of_identical_layers_each_one_stacked_tree(model):
    cfg, params, axes, _, _ = model
    assert cfg.layer_runs == (("dense_layers", 1), ("layers", 2))
    assert TransformerConfig.tiny().layer_runs == (("layers", 2),)
    # the leading run has a dense feed-forward of its own width, no router
    assert params["dense_layers"]["w_in"].shape == (1, 64, 160)
    assert "router" not in params["dense_layers"]
    assert params["layers"]["w_in"].shape == (2, 8, 64, 32)
    assert params["layers"]["ws_in"].shape == (2, 64, 32)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda a: 0, axes,
                                   is_leaf=lambda a: isinstance(a, tuple)))


def test_counts_by_hand(model):
    cfg, params, _, _, _ = model
    attn = 64 * 24 + 24 * 4 * 20 + 64 * 24 + 16 * 4 * 28 + 4 * 16 * 64
    assert attn == 10_880
    expert, dense_ffn = 3 * 64 * 32, 3 * 64 * 160
    norms = 2 * 64 + 24 + 16
    held = (attn + dense_ffn + norms) + 2 * (
        attn + 9 * expert + 64 * 8 + 8 + norms) + 2 * 256 * 64 + 64
    assert count_params(cfg) == held == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    # a token meets 2 routed experts and the shared one, every router
    active = (attn + dense_ffn) + 2 * (attn + 3 * expert + 64 * 8) + 256 * 64
    assert flops_per_token(cfg, 40) == 6 * active + 6 * 3 * (4 * 36 // 2) * 40
    # absorbed decode: a head's query meets 16 + 8 cached values, its
    # probabilities the 16 latent ones
    assert decode_flops_per_token(cfg, 50) == \
        2 * active + 2 * 3 * 4 * (2 * 16 + 8) * 50
    assert engine_flops_table(cfg, 100)["decode_step"] == \
        decode_flops_per_token(cfg, 50)


def test_cache_is_one_array_of_latents_positions_last(model):
    cfg = model[0]
    assert cache_rows(cfg) == {"kv": (1, 24)}
    assert cache_rows(TransformerConfig.tiny()) == {"k": (2, 16),
                                                    "v": (2, 16)}
    cache = init_slot_cache(cfg, 3, 64)
    assert set(cache) == {"kv", "pos"} and cache["kv"].shape == (3, 3, 1, 24,
                                                                 64)
    assert cache_capacity(cache) == 64 and list(cache_arrays(cache)) == ["kv"]
    assert init_kv_cache(cfg, 1, 64)["pos"].shape == ()


def test_chunked_prefill_tails_and_slot_decode_match_the_full_forward(model):
    """Chunks of 32, the tail as one more of them, padded (the engine's
    walk, `chunk_window`), slot insert with the padded columns above
    ``pos``, then decode steps over slots at DIFFERENT depths: every logit
    against the uncached forward."""
    cfg, params, _, toks, full = model
    lengths = (67, 35)              # 2 chunks + a tail of 3; 1 chunk + 3
    slots = init_slot_cache(cfg, 2, 96)
    insert = jax.jit(cache_insert_slot)
    host = np.asarray(toks)
    for b, n in enumerate(lengths):
        pc, off = init_kv_cache(cfg, 1, 96), 0
        while off < n:
            start, n_valid = chunk_window(off, n, 32, 96)
            lg, pc = prefill_chunk_jit(
                params, padded_chunk(host[b:b + 1], start, n_valid, 32), pc,
                cfg=cfg, n_valid=np.int32(n_valid))
            off = start + n_valid
            assert float(jnp.abs(lg[0] - full[b, off - 1]).max()) < TOL
        assert int(pc["pos"]) == n
        slots = insert(slots, pc, jnp.int32(b))
    step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
    for j in range(4):
        tok = jnp.stack([toks[b, n + j] for b, n in enumerate(lengths)])
        lg, slots = step(params, tok, slots, jnp.ones((2,), bool))
        for b, n in enumerate(lengths):
            assert float(jnp.abs(lg[b] - full[b, n + j]).max()) < TOL
    assert [int(p) for p in slots["pos"]] == [71, 39]
    # the whole-prompt prefill fills the same cache
    lg, whole = prefill(params, toks[:, :35], cfg, init_kv_cache(cfg, 2, 96))
    assert float(jnp.abs(lg - full[:, 34]).max()) < TOL
    assert float(jnp.abs(whole["kv"][:, 1, :, :, :35]
                         - slots["kv"][:, 1, :, :, :35]).max()) < TOL


def test_prefix_donor_copy_of_a_latent_cache(model):
    """`cache_gather_slot` copies whatever arrays the cache has: a prompt
    that shares 40 tokens with a live slot prefills only its suffix."""
    cfg, params, _, toks, full = model
    pc = init_kv_cache(cfg, 1, 96)
    for off in (0, 32):
        _, pc = prefill_chunk_jit(params, toks[:1, off:off + 32], pc, cfg=cfg)
    slots = jax.jit(cache_insert_slot)(init_slot_cache(cfg, 2, 96), pc,
                                       jnp.int32(1))
    got = jax.jit(cache_gather_slot)(slots, jnp.int32(1), jnp.int32(40))
    assert set(got) == {"kv", "pos"} and got["kv"].shape == (3, 1, 1, 24, 96)
    assert int(got["pos"]) == 40
    for off in range(40, 44):
        lg, got = prefill_chunk_jit(params, toks[:1, off:off + 1], got,
                                    cfg=cfg)
        assert float(jnp.abs(lg[0] - full[0, off]).max()) < TOL


def test_absorbed_attention_is_the_plain_one():
    """The same function of (queries, latents): keys and values built a
    head, against the up-projections folded into query and output."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    b, s, h, nope, rope, v, kl, d = 2, 24, 4, 12, 8, 16, 16, 64
    q_nope = jax.random.normal(ks[0], (b, s, h, nope))
    q_rope = jax.random.normal(ks[1], (b, s, h, rope))
    latent = jax.random.normal(ks[2], (b, s, kl + rope))
    wkv_b = jax.random.normal(ks[3], (kl, h, nope + v)) / 4
    wo = jax.random.normal(ks[4], (h, v, d)) / 8
    plain = mla.attend_plain(q_nope, q_rope, latent, wkv_b, wo,
                             impl="reference")
    cached = jnp.pad(jnp.swapaxes(latent, 1, 2), ((0, 0), (0, 0), (0, 8)))
    mask = jnp.arange(s + 8)[None, None, :] <= jnp.arange(s)[None, :, None]
    absorbed = mla.attend_absorbed(q_nope, q_rope, cached, wkv_b, wo, mask)
    assert float(jnp.abs(plain - absorbed).max()) < 1e-4 * float(
        jnp.abs(plain).max())
    # what a cache holds: the normed latent beside the rotated shared key
    y = jax.random.normal(ks[5], (b, s, d))
    wkv_a = jax.random.normal(ks[0], (d, kl + rope)) / 8
    cos, sin = rotary_angles(s, rope, 1e4)
    rot = functools.partial(apply_rotary, cos=cos, sin=sin)
    lat = mla.latents(y, wkv_a, jnp.ones((kl,)), kv_lora=kl, eps=1e-5,
                      rotate=rot)
    raw = y @ wkv_a
    want_c = raw[..., :kl] / jnp.sqrt(
        jnp.square(raw[..., :kl]).mean(-1, keepdims=True) + 1e-5)
    assert float(jnp.abs(lat[..., :kl] - want_c).max()) < 1e-5
    assert float(jnp.abs(lat[..., kl:]
                         - rot(raw[..., None, kl:])[..., 0, :]).max()) < 1e-6


def _dense_experts(y, idx, w, w_in, w_gate, w_out):
    """Every token through every expert, weighted: the arithmetic the
    grouped layer has to equal."""
    up = jnp.einsum("nd,edf->nef", y, w_in)
    gate = jnp.einsum("nd,edf->nef", y, w_gate)
    out = jnp.einsum("nef,efd->ned", jax.nn.silu(gate) * up, w_out)
    weight = jnp.einsum("nk,nke->ne", w,
                        jax.nn.one_hot(idx, w_in.shape[0], dtype=y.dtype))
    return jnp.einsum("ned,ne->nd", out, weight)


@pytest.fixture(scope="module")
def experts():
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    E, d, f, n = 8, 32, 16, 37
    return {"y": jax.random.normal(ks[0], (n, d)),
            "router": jax.random.normal(ks[1], (d, E)) / 5,
            "w_in": jax.random.normal(ks[2], (E, d, f)) / 5,
            "w_gate": jax.random.normal(ks[3], (E, d, f)) / 5,
            "w_out": jax.random.normal(ks[4], (E, f, d)) / 4}


def test_bias_moves_the_choice_and_never_the_weight(experts):
    e = experts
    no_bias = jnp.zeros((8,))
    bias = jnp.zeros((8,)).at[5].set(10.0)         # expert 5 always chosen
    idx0, w0 = sigmoid_route(e["y"], e["router"], no_bias, 2, 1.8)
    idx1, w1 = sigmoid_route(e["y"], e["router"], bias, 2, 1.8)
    assert bool((idx1 == 5).any(-1).all()) and not bool(
        (idx0 == 5).any(-1).all())
    # the weights are the SCORES of the chosen, normalised, times 1.8:
    # nothing of the bias's 10 is in them
    scores = jax.nn.sigmoid(e["y"] @ e["router"])
    for idx, w in ((idx0, w0), (idx1, w1)):
        s = jnp.take_along_axis(scores, idx, axis=-1)
        assert float(jnp.abs(w - 1.8 * s / s.sum(-1, keepdims=True)).max()) \
            < 1e-6
        assert float(jnp.abs(w.sum(-1) - 1.8).max()) < 1e-5
    assert w0.dtype == jnp.float32


def test_no_token_is_dropped_at_any_load(experts):
    """Every token to the same two experts, the worst imbalance there is:
    the grouped layer equals the dense arithmetic, where the capacity
    einsum (capacity 2 x 37 x 2 / 8 = 24 pairs an expert) loses tokens."""
    e = experts
    bias = jnp.zeros((8,)).at[jnp.array([2, 6])].set(10.0)
    idx, w = sigmoid_route(e["y"], e["router"], bias, 2)
    assert set(np.asarray(idx).ravel()) == {2, 6}
    got, load = routed_ffn(e["y"], idx, w, e["w_in"], e["w_out"],
                           e["w_gate"])
    want = _dense_experts(e["y"], idx, w, e["w_in"], e["w_gate"], e["w_out"])
    assert float(jnp.abs(got - want).max()) < TOL
    assert (int(load.experts_touched), int(load.load_max)) == (2, 37)
    # and at an even load
    idx, w = sigmoid_route(e["y"], e["router"], jnp.zeros((8,)), 2)
    got, load = routed_ffn(e["y"], idx, w, e["w_in"], e["w_out"],
                           e["w_gate"])
    want = _dense_experts(e["y"], idx, w, e["w_in"], e["w_gate"], e["w_out"])
    assert float(jnp.abs(got - want).max()) < TOL
    counts = np.bincount(np.asarray(idx).ravel(), minlength=8)
    assert int(load.load_max) == counts.max()
    assert int(load.experts_touched) == (counts > 0).sum()
    # the softmax presets' capacity einsum DOES drop at that load
    y3 = e["y"][None]
    skew = e["router"].at[:, 2].add(100 * jnp.sign(e["y"].mean(0)))
    dropped, _ = moe_ffn(jnp.abs(y3), skew, e["w_in"], e["w_out"],
                         e["w_gate"], top_k=1, capacity_factor=1.0)
    assert int((jnp.abs(dropped[0]).sum(-1) == 0).sum()) > 0


def test_rows_that_do_not_count_touch_no_expert(experts):
    """A decode batch's slots that are not live are routed nowhere; one
    token alone (4 pairs are fewer than a row block) still comes out."""
    e = experts
    idx, w = sigmoid_route(e["y"], e["router"], jnp.zeros((8,)), 2)
    valid = jnp.arange(37) < 3
    got, load = routed_ffn(e["y"], idx, w, e["w_in"], e["w_out"],
                           e["w_gate"], valid)
    want = _dense_experts(e["y"], idx, w, e["w_in"], e["w_gate"], e["w_out"])
    assert float(jnp.abs(got[:3] - want[:3]).max()) < TOL
    assert float(jnp.abs(got[3:]).max()) == 0.0
    assert int(load.experts_touched) == len(set(np.asarray(idx[:3]).ravel()))
    one, load = routed_ffn(e["y"][:1], idx[:1], w[:1], e["w_in"], e["w_out"],
                           e["w_gate"])
    assert one.shape == (1, 32) and int(load.experts_touched) == 2
    assert float(jnp.abs(one - want[:1]).max()) < TOL


def test_programs_return_what_their_expert_layers_routed(model):
    cfg, params, _, toks, _ = model
    _, pc, load = jax.jit(functools.partial(_prefill_chunk, cfg=cfg))(
        params, toks[:1, :32], init_kv_cache(cfg, 1, 64))
    touched, load_max, pairs = (int(x) for x in load)
    assert 2 * 2 <= touched <= 2 * 8 and 2 * 8 <= load_max <= 2 * 32
    # every expert is held: each of the 32 tokens' 2 pairs, in 2 layers
    assert pairs == 2 * 32 * 2
    slots = jax.jit(cache_insert_slot)(init_slot_cache(cfg, 4, 64), pc,
                                       jnp.int32(2))
    active = jnp.arange(4) == 2
    _, _, load = jax.jit(functools.partial(_decode_step_slots, cfg=cfg))(
        params, jnp.full((4,), toks[0, 32]), slots, active)
    # one live slot: its token's 2 experts in each of the 2 expert layers
    assert [int(x) for x in load] == [4, 2, 4]
    # a model without such a layer reports zeros
    dense = TransformerConfig.tiny(dtype=jnp.float32)
    p2, _ = init_params(jax.random.PRNGKey(0), dense)
    _, _, load = _prefill_chunk(p2, toks[:1, :8], init_kv_cache(dense, 1, 32),
                                dense)
    assert [int(x) for x in load] == [0, 0, 0]


def test_softmax_presets_keep_the_capacity_einsum():
    """Which expert layer a model takes follows from its router kind."""
    cfg = TransformerConfig.tiny(n_experts=4, dtype=jnp.float32)
    assert cfg.router == "softmax"
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    assert "router_bias" not in params["layers"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    text = str(jax.make_jaxpr(functools.partial(forward, cfg=cfg))(
        params, toks))
    assert "ragged_dot" not in text
    sig = dataclasses.replace(cfg, router="sigmoid")
    p2, _ = init_params(jax.random.PRNGKey(0), sig)
    assert "ragged_dot" in str(jax.make_jaxpr(
        functools.partial(forward, cfg=sig))(p2, toks))


def test_moe_load_spans_outlive_a_busy_serve_ring(monkeypatch):
    """The engine's `moe:load` spans are what the benchmark's `.agent`
    readers sum after the run, from the ring as the process left it: they
    sit in a category of their own, so the `serve` spans of a busy window
    (two a `next_chunk` call) cannot push them out of the bounded ring."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import (ContinuousBatchingEngine,
                                              DecodeSessionCore)
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    cfg = tiny()
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    core = DecodeSessionCore(cfg, max_len=96, params=params,
                             engine=DecodeEngineConfig(max_slots=2))
    try:
        out = core.handle({"op": "start", "prompt": list(range(5, 14))})
        got = len(out["token"])
        while got < 4:
            got += len(core.handle({"op": "next_chunk", "sid": out["sid"],
                                    "max_tokens": 4})["tokens"])
        core.handle({"op": "end", "sid": out["sid"]})
    finally:
        core.engine.shutdown()
    now = time.time()
    for i in range(2 * tracing._buffer().per_category):
        tracing.record_span(f"serve_exec::flood{i}", "serve", now, now)
    loads = [e for e in tracing.span_events() if e["name"] == "moe:load"]
    assert loads and all(e["cat"] == "moe" for e in loads)
    assert sum(e["args"]["steps"] for e in loads) > 0


def test_engine_serves_the_latent_model_in_place():
    """Through `DecodeSessionCore` with engine defaults: tokens equal the
    uncached greedy continuation, no dispatch copied a cache, and the step's
    routing counts arrived with its tokens."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = tiny()
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    core = DecodeSessionCore(cfg, max_len=96, params=params,
                             engine=DecodeEngineConfig(max_slots=3))
    try:
        prompts = [list(range(5, 5 + n)) for n in (35, 9)]
        sids, got = [], []
        for p in prompts:
            out = core.handle({"op": "start", "prompt": p})
            sids.append(out["sid"])
            got.append(list(out["token"]))
        for sid, toks in zip(sids, got):
            while len(toks) < 6:
                more = core.handle({"op": "next_chunk", "sid": sid,
                                    "max_tokens": 6 - len(toks)})
                assert "error" not in more, more
                toks += more["tokens"]
        for p, toks in zip(prompts, got):
            seq = list(p)
            for t in toks[:6]:
                nxt = int(forward(params, jnp.asarray([seq]), cfg)[0, -1]
                          .argmax())
                assert nxt == t
                seq.append(t)
        stats = core.engine.stats()
        assert stats["cache_copies"] == 0
        moe = stats["moe"]
        assert moe["steps"] == stats["steps"] > 0
        assert (moe["layers"], moe["experts"]) == (2, 8)
        # a live row gives top_k pairs an expert layer, and touches at
        # least top_k experts a layer a step
        assert moe["pairs"] == stats["tokens"] * 2 * 2
        assert 2 * 2 * moe["steps"] <= moe["experts_touched"] <= moe["pairs"]
        assert moe["steps"] * 2 <= moe["load_max"] <= moe["pairs"]
        cache = stats["cache"]
        assert cache["bytes"] == 3 * 3 * 24 * 96 * 4
        assert cache["bytes_per_position"] == 3 * 24 * 4
        for sid in sids:
            core.handle({"op": "end", "sid": sid})
    finally:
        core.engine.shutdown()
    # a stopped engine holds no weights and no cache (whoever loads the
    # next model into this process needs the room)
    engine = core.engine
    engine._thread.join(timeout=10)
    assert engine.params is None and engine._cache is None
