"""A SHORTCUT-CONNECTED layer with IDENTITY experts
(`models/transformer.py` ``shortcut_moe``, ``zero_experts``, router
``"softmax_bias"``; `ops/moe.py` `softmax_route`, `routed_ffn`'s
``identity_from``): a published layer is two latent-attention sublayers with
a dense feed-forward each and ONE routed branch that leaves the stream behind
the first attention and rejoins it behind the second feed-forward; the router
is a softmax over the experts AND a number of outputs that compute nothing.

The router and the identity pairs against a loop over tokens (a row whose
choices are ALL identity, a row that does not count, gradients, what the
grouped matmul is handed); the pattern's weights, counts and cache (TWO latent
rows a published layer); every cached program (whole-prompt prefill, chunks
with a padded last one, lanes with a lane that stands, slots at depths of
their own) against the FAMILY's plain reference, logits and not tokens; the
shares of an expert-parallel layer, the identity part counted once; the
engine's counters; and the other models' lowered text, unchanged.

The model is the rehearsal's ``tiny-longcat`` in float32 (3 published layers
= 6 sublayers at width 64: 4 heads of 16 + 8 | 16 over latents of 32 | 16,
dense 128, 8 experts of 32 of which 4 are held, 4 identity, 3 a token).
`forward` and `lm_loss` against the reference, the served path in bfloat16
and the rehearsed cell are tests/benchmark/test_perfbench_family_longcat_flash.py's.
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
from perfbench import reference
from perfbench.tools import rehearse
from ray_tpu.models import (cache_insert_slot, decode_step_slots, forward,
                            init_kv_cache, init_params, init_slot_cache,
                            prefill, prefill_chunk_jit, prefill_lanes_jit)
from ray_tpu.models.generate import (cache_bytes, cache_rows, position_bytes,
                                     prefill_chunk, prefill_chunk_step,
                                     prefill_lanes, prefill_lanes_step)
from ray_tpu.models.transformer import (check_kinds, count_params,
                                        routed_branch)
from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops import moe
from ray_tpu.serve.decode_session import ContinuousBatchingEngine

T, MAX_LEN, CHUNK = 96, 128, 32
TOL = dict(atol=3e-4, rtol=0)


def _config(name):
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def world():
    c = _config("tiny-longcat")
    model = mf.family_of(c).model
    cfg = dataclasses.replace(model.model_config(c, "serve"),
                              dtype=jnp.float32, param_dtype=jnp.float32,
                              remat=False)
    params = jax.jit(lambda k: model._make(k, c=c, dtype=jnp.float32))(
        jax.random.PRNGKey(7))
    toks = model.tokens(jax.random.PRNGKey(8), (2, T), c)
    # the FAMILY's plain reference: what every program below is held to
    ref = jax.jit(lambda p, t: model._logits(p, t, c, "float32"))
    return types.SimpleNamespace(
        c=c, model=model, cfg=cfg, params=params, toks=toks, ref=ref,
        want=np.asarray(ref(params, toks)),
        step=jax.jit(functools.partial(decode_step_slots, cfg=cfg)))


# ------------------------------------------ the router, the identity pairs

def _loop(y, idx, w, w_in, w_gate, w_out, valid, offset, identity_from):
    """`routed_ffn` a token and a pair at a time, in numpy."""
    y, idx, w = (np.asarray(a, np.float64) for a in (y, idx, w))
    out = np.zeros_like(y)
    for i in range(y.shape[0]):
        if valid is not None and not valid[i]:
            continue
        for e, we in zip(idx[i].astype(int), w[i]):
            if e >= identity_from:
                out[i] += we * y[i]
            elif offset <= e < offset + w_in.shape[0]:
                a, b, o = (np.asarray(m[e - offset], np.float64)
                           for m in (w_in, w_gate, w_out))
                g = y[i] @ b
                out[i] += we * ((g / (1 + np.exp(-g)) * (y[i] @ a)) @ o)
    return out


def test_softmax_route_chooses_by_bias_and_weighs_by_score():
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.standard_normal((9, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 12)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((12,)) * 0.2, jnp.float32)
    idx, w = moe.softmax_route(y, router, bias, 3, 6.0)
    p = np.asarray(jax.nn.softmax(y @ router, axis=-1))
    want = np.argsort(-(p + np.asarray(bias)), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(idx), want)
    # the score alone times the factor: no bias in it, not renormalised
    np.testing.assert_allclose(
        np.asarray(w), 6.0 * np.take_along_axis(p, want, -1), rtol=1e-5)
    assert not np.allclose(np.asarray(w).sum(-1), 6.0)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32


@pytest.mark.parametrize("offset,held", [(0, 8), (2, 4)])
def test_identity_pairs_join_no_group_and_add_their_rows(monkeypatch, offset,
                                                         held):
    """8 experts and 4 identity outputs, 3 a token, all held or experts 2-5:
    against the loop; a row whose 3 choices are ALL identity touches no
    expert and gets ``sum(w) y``; a row that does not count gets zeros and
    counts nowhere; the grouped matmul is handed the pairs on HELD experts
    alone (its work list ends where they end)."""
    rng = np.random.default_rng(1)
    n, d, f, E = 10, 16, 8, 8
    y = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    idx = rng.integers(0, E + 4, (n, 3))
    idx[0] = [8, 10, 11]                    # all identity
    idx[1] = [9, 0, 3]
    idx = jnp.asarray(idx, jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (n, 3)), jnp.float32)
    w_in, w_gate = (jnp.asarray(rng.standard_normal((held, d, f)) / 4,
                                jnp.float32) for _ in range(2))
    w_out = jnp.asarray(rng.standard_normal((held, f, d)) / 3, jnp.float32)
    valid = jnp.asarray([True] * n).at[4].set(False)
    seen, real = [], moe.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul", lambda lhs, rhs, sizes: (
        seen.append(sizes), real(lhs, rhs, sizes))[1])
    out, load = moe.routed_ffn(y, idx, w, w_in, w_out, w_gate, valid,
                               expert_offset=offset, identity_from=E)
    want = _loop(y, idx, w, w_in, w_gate, w_out, np.asarray(valid), offset, E)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[0]),
                               float(w[0].sum()) * np.asarray(y[0]),
                               atol=1e-6)
    assert not np.asarray(out[4]).any()
    live = np.asarray(idx)[np.asarray(valid)]
    here = ((live >= offset) & (live < offset + held)).sum()
    assert int(load.pairs) == here and int(load.zero_pairs) == \
        (live >= E).sum() and (live >= E).sum() >= 4
    assert int(load.experts_touched) == len(
        set(live[(live >= offset) & (live < offset + held)].tolist()))
    # all three matmuls: the held pairs and no row more
    assert len(seen) == 3 and all(int(s.sum()) == here for s in seen)
    *_, ends, _ = gm.work_list(seen[0], 1, 128)
    assert int(ends[-1]) == here < live.size
    # a router without identity outputs: no scope, no count, no operand
    text = str(jax.make_jaxpr(lambda *a: moe.routed_ffn(
        *a, expert_offset=offset))(y, jnp.minimum(idx, E - 1), w, w_in, w_out,
                                   w_gate, valid))
    with_z = str(jax.make_jaxpr(lambda *a: moe.routed_ffn(
        *a, expert_offset=offset, identity_from=E))(y, idx, w, w_in, w_out,
                                                    w_gate, valid))
    plain, _ = moe.routed_ffn(y, jnp.minimum(idx, E - 1), w, w_in, w_out,
                              w_gate, valid, expert_offset=offset)
    assert moe.Load(1, 2, 3).zero_pairs == 0 and len(text) < len(with_z)
    assert plain.shape == out.shape
    # gradients pass the fallback's grouped matmul and the identity sum
    g = jax.grad(lambda y, w_in: moe.routed_ffn(
        y, idx, w, w_in, w_out, w_gate, valid, expert_offset=offset,
        identity_from=E)[0].sum(), argnums=(0, 1))(y, w_in)
    assert float(jnp.abs(g[0][0] - w[0].sum()).max()) < 1e-5   # d(sum w y)/dy
    assert float(jnp.abs(g[1]).sum()) > 0 and not np.asarray(g[0][4]).any()


def test_four_shares_add_up_to_the_uncut_branch(world):
    """A routed branch of 8 experts and 4 identity outputs, 3 a token, shared
    by FOUR chips of two experts each: the parts the PROGRAM computes for the
    four shares (each told which experts it holds, each routing over all 12
    outputs), with the identity part that every chip computes alike counted
    ONCE, add up to the uncut REFERENCE's ``M(u)``: every expert held."""
    w = world
    model = w.model
    whole = dict(w.c, n_routed_experts=8, deployment=dict(
        w.c["deployment"], experts_routed=8, expert_offset=0))
    params = jax.jit(lambda k: model._make(k, c=whole, dtype=jnp.float32))(
        jax.random.PRNGKey(13))
    lay = params["layers"]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), jnp.float32)
    r = reference._round_inputs("float32")
    routing = {k: lay[k][1] for k in ("router", "router_bias")}
    stacks = ("we_in", "we_gate", "we_out")
    with jax.default_matmul_precision("highest"):
        lp = dict(routing, **{k: lay[k] for k in stacks})
        uncut = jnp.stack([model.routed_part(r, row, lp, whole, 0, 8, 1)
                           for row in u])
        identity = uncut - jnp.stack([model.routed_part(
            r, row, lp, whole, 0, 8, 1, identity=False) for row in u])
        total, zero_pairs = identity, set()
        for chip in range(4):
            one = dict(whole, n_routed_experts=2, deployment=dict(
                whole["deployment"], expert_offset=2 * chip))
            cfg = dataclasses.replace(
                model.model_config(one, "serve"), dtype=jnp.float32,
                param_dtype=jnp.float32)
            mine = dict(routing, **{k: lay[k][1, 2 * chip:2 * chip + 2]
                                    for k in stacks})
            m, load = routed_branch(cfg, u, mine)
            total = total + (m - identity)
            assert int(load[2]) <= 2 * 24 * 3 and len(load) == 4
            zero_pairs.add(int(load[3]))
    assert float(jnp.abs(total - uncut).max()) < 1e-4
    assert float(jnp.abs(identity).max()) > 0.01
    assert float(jnp.abs(uncut - identity).max()) > 0.01
    assert len(zero_pairs) == 1 and zero_pairs.pop() > 0    # every chip's


# ------------------------------------------------- pattern, counts, cache

def test_pattern_weights_counts_and_cache(world):
    cfg, params = world.cfg, world.params
    assert (cfg.n_layers, cfg.expert_layers, cfg.load_counts,
            cfg.reports_load, cfg.hands_down) == (6, 3, 4, True, True)
    assert cfg.kinds == ("full",) * 6 and cfg.layer_runs == (("layers", 6),)
    assert cfg.expert_stacks == ("we_in", "we_gate", "we_out")
    assert (cfg.n_experts, cfg.n_experts_held, cfg.zero_experts,
            cfg.expert_top_k, cfg.router) == (8, 4, 4, 3, "softmax_bias")
    assert cfg.latent_scales("full") == (np.sqrt(2.0), 2.0)
    lay = params["layers"]
    # attention, norms and the dense feed-forward over the 6 sublayers; the
    # router over 8 + 4 outputs, its bias and the 4 held experts over the 3
    # that route
    assert {k: v.shape for k, v in lay.items()} == {
        "attn_norm": (6, 64), "mlp_norm": (6, 64), "q_norm": (6, 32),
        "kv_norm": (6, 16), "wq_a": (6, 64, 32), "wq_b": (6, 32, 4, 24),
        "wkv_a": (6, 64, 24), "wkv_b": (6, 16, 4, 32), "wo": (6, 4, 16, 64),
        "w_in": (6, 64, 128), "w_gate": (6, 64, 128), "w_out": (6, 128, 64),
        "router": (3, 64, 12), "router_bias": (3, 12),
        "we_in": (3, 4, 64, 32), "we_gate": (3, 4, 64, 32),
        "we_out": (3, 4, 32, 64)}
    made = jax.eval_shape(lambda k: init_params(k, cfg)[0],
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, made) == \
        jax.tree_util.tree_map(jnp.shape, params)
    assert count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params)) \
        == mf.family_of(world.c).shapes.count_params(world.c)
    # a published layer holds TWO latents a position: 6 rows of 16 + 8
    assert cache_rows(cfg) == {"kv": (1, 24)}
    cache = init_slot_cache(cfg, 3, MAX_LEN)
    assert cache["kv"].shape == (6, 3, 1, 24, MAX_LEN)
    assert position_bytes(cfg)["full"] == 24 * 4
    assert cache_bytes(cache) == {"full": 6 * 3 * 24 * MAX_LEN * 4,
                                  "ring": 0, "state": 0}
    # what is refused says so
    for bad in (dict(n_layers=5), dict(router="sigmoid", zero_experts=4),
                dict(n_shared_experts=1), dict(router="softmax"),
                dict(layer_kinds=("full", "window") * 3, sliding_window=8)):
        with pytest.raises(ValueError, match="shortcut_moe|zero_experts"):
            check_kinds(dataclasses.replace(cfg, **bad))
    with pytest.raises(ValueError, match="expected 'softmax', 'sigmoid'"):
        check_kinds(dataclasses.replace(cfg, router="top", shortcut_moe=False,
                                        zero_experts=0))


# ------------------------------------------------- the cached programs

def _chunked(w, row, n, cache):
    off, host = 0, np.asarray(w.toks[row:row + 1, :n])
    while off < n:
        logits, cache, off, n_valid = prefill_chunk_step(
            prefill_chunk_jit, w.params, host, off, cache, w.cfg,
            chunk=CHUNK, capacity=MAX_LEN)
        np.testing.assert_allclose(logits[0], w.want[row, off - 1], **TOL)
    return logits, cache, n_valid


@pytest.mark.parametrize("program", ["whole", "chunks", "lanes", "slots"])
def test_cached_programs_give_the_references_logits(world, program):
    w = world
    if program == "whole":      # the plain form, a whole prompt, then steps
        got = jax.jit(functools.partial(forward, cfg=w.cfg))(w.params,
                                                             w.toks)
        np.testing.assert_allclose(got, w.want, **TOL)
        logits, cache = jax.jit(functools.partial(prefill, cfg=w.cfg))(
            w.params, w.toks[:, :70], cache=init_kv_cache(w.cfg, 2, MAX_LEN))
        np.testing.assert_allclose(logits, w.want[:, 69], **TOL)
        slots = dict(cache, pos=jnp.full((2,), 70, jnp.int32))
        for t in range(70, 76):
            logits, slots = w.step(w.params, w.toks[:, t], slots,
                                   jnp.ones((2,), bool))
            np.testing.assert_allclose(logits, w.want[:, t], **TOL)
    elif program == "chunks":   # 32 + 32 + a padded remainder of 11
        _, cache, n_valid = _chunked(w, 0, 75,
                                     init_kv_cache(w.cfg, 1, MAX_LEN))
        assert int(cache["pos"]) == 75 and n_valid == 11
    elif program == "lanes":    # two prompts at once, a lane that stands
        cache = init_slot_cache(w.cfg, 3, MAX_LEN)
        prompts = [(np.asarray(w.toks[0:1, :90]), 0), None,
                   (np.asarray(w.toks[1:2, :41]), 0)]
        logits = {}
        while any(p is not None for p in prompts):
            lg, cache, moved = prefill_lanes_step(
                prefill_lanes_jit, w.params, prompts, cache, w.cfg,
                chunk=CHUNK, capacity=MAX_LEN)
            for p, m in enumerate(moved):
                if m is not None:
                    logits[p] = np.asarray(lg[p])
                    prompts[p] = (prompts[p][0], m[0]) \
                        if m[0] < prompts[p][0].shape[1] else None
        np.testing.assert_allclose(logits[0], w.want[0, 89], **TOL)
        np.testing.assert_allclose(logits[2], w.want[1, 40], **TOL)
        assert not np.asarray(cache["kv"][:, 1]).any()
    else:       # three slots at 60, 40 and 5, the second standing
        slots = init_slot_cache(w.cfg, 3, MAX_LEN)
        for row, (src, n) in enumerate(((0, 60), (1, 40), (1, 5))):
            _, one, _ = _chunked(w, src, n, init_kv_cache(w.cfg, 1, MAX_LEN))
            slots = cache_insert_slot(slots, one, jnp.int32(row))
        before = np.asarray(slots["kv"][:, 1])
        for j in range(8):
            tok = jnp.stack([w.toks[0, 60 + j], jnp.int32(3),
                             w.toks[1, 5 + j]])
            logits, slots = w.step(w.params, tok, slots,
                                   jnp.asarray([True, False, True]))
            np.testing.assert_allclose(logits[0], w.want[0, 60 + j], **TOL)
            np.testing.assert_allclose(logits[2], w.want[1, 5 + j], **TOL)
        assert np.asarray(slots["pos"]).tolist() == [68, 40, 13]
        # (its one token's column lands AHEAD of its pos, where no query
        # of its own looks)
        np.testing.assert_array_equal(
            np.delete(np.asarray(slots["kv"][:, 1]), 40, axis=-1),
            np.delete(before, 40, axis=-1))


_FAULTS = {
    # (a) the identity pairs add nothing
    "identity_adds_nothing": lambda mp: mp.setattr(
        moe, "routed_ffn", lambda *a, identity_from=None, **kw:
        _REAL["routed_ffn"](*a, **kw)),
    # (d) the chosen weights renormalised to sum 1 before the factor
    "weights_renormalised": lambda mp: mp.setattr(
        moe, "softmax_route", lambda y, r, b, k, s: (lambda idx, w: (
            idx, w / w.sum(-1, keepdims=True) * s))(
                *_REAL["softmax_route"](y, r, b, k, 1.0))),
    # (f) the softmax taken over the real outputs only
    "softmax_over_the_experts_alone": lambda mp: mp.setattr(
        moe, "softmax_route", lambda y, r, b, k, s: _REAL["softmax_route"](
            y, r.at[:, 8:].add(-1e9), b, k, s)),
}
_REAL = {"routed_ffn": moe.routed_ffn, "softmax_route": moe.softmax_route}


@pytest.mark.parametrize("fault", list(_FAULTS))
def test_planted_faults_in_the_routed_branch_each_fail(world, monkeypatch,
                                                       fault):
    """A fault in what this router ADDS, planted where the cached programs
    are traced: chunk programs to position 64 read far from the reference, by
    a twentieth of its logits' spread and more, where the sound programs read
    within `TOL`."""
    w = world
    _FAULTS[fault](monkeypatch)
    fn = jax.jit(lambda p, t, cache: prefill_chunk(p, t, cache, w.cfg))
    cache, worst = init_kv_cache(w.cfg, 1, MAX_LEN), 0.0
    for off in range(0, 64, CHUNK):
        logits, cache = fn(w.params, w.toks[0:1, off:off + CHUNK], cache)
        worst = max(worst, float(np.abs(
            np.asarray(logits[0]) - w.want[0, off + CHUNK - 1]).max()))
    assert worst > 0.05 * w.want.std(), (fault, worst)


# ------------------------------------------------------------ the engine

def _stream(core, prompt, n, out, key):
    r = core.handle({"op": "start", "prompt": prompt, "max_new_tokens": n})
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
    out[key] = toks[:n]


def _core(cfg, params, **engine):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    return DecodeSessionCore(
        cfg, max_len=MAX_LEN, params=params,
        engine=DecodeEngineConfig(prefill_chunk_tokens=CHUNK, **engine))


def test_engine_serves_the_references_tokens_and_counts_what_it_routed(
        world, monkeypatch):
    """Three sessions at once through chunk programs, the lanes program and
    the fused slot step: every token is the reference's choice at its
    position; ``moe:load`` carries the identity pairs and the pairs chosen
    beside what it carried, ``layers`` the sublayers that ROUTE; a sigmoid
    model's configuration answers what it answered."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    w = world
    core = _core(w.cfg, w.params, max_slots=3)
    try:
        prompts = [np.asarray(w.toks[i % 2, a:a + n]).tolist()
                   for i, (a, n) in enumerate(((0, 70), (3, 33), (11, 50)))]
        got = {}
        threads = [threading.Thread(target=_stream,
                                    args=(core, p, 10, got, i))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        for i, p in enumerate(prompts):
            seq = p + got[i][:-1]
            padded = jnp.zeros_like(w.toks).at[0, :len(seq)].set(
                jnp.asarray(seq, jnp.int32))
            want = np.asarray(w.ref(w.params, padded))[0, :len(seq)]
            assert got[i] == want[len(p) - 1:].argmax(-1).tolist(), i
        st = core.engine.stats()
        assert st["cache_copies"] == 0
        m = st["moe"]
        assert (m["layers"], m["experts"]) == (3, 4) and m["steps"] > 0
        # every live row chose 3 outputs on each of 3 routing sublayers: on a
        # held expert, on another chip's, or on an identity output
        assert m["chosen"] > 0 and m["chosen"] % 9 == 0
        assert 0 < m["zero_pairs"] < m["chosen"]
        assert 0 < m["pairs"] <= m["chosen"] - m["zero_pairs"]
        assert m["experts_touched"] <= m["steps"] * 3 * 4
        span = [e for e in tracing.span_events()
                if e["name"] == "moe:load"][-1]["args"]
        assert span["layers"] == 3 and span["experts"] == 4
        assert set(span) >= {"zero_pairs", "chosen"} or not span["steps"]
    finally:
        core.engine.shutdown()
    # a sigmoid model answers as it did (every layer behind the dense one
    # routes, three sums, no new key: tests/test_latent_moe.py has its span),
    # a dense model routes nothing
    c = _config("tiny-glm")
    cfg = mf.family_of(c).model.model_config(c, "serve")
    assert (cfg.expert_layers, cfg.load_counts, cfg.reports_load) == \
        (2, 3, True)
    dense = mf.family_of(_config("tiny")).model.model_config(
        _config("tiny"), "serve")
    assert (dense.expert_layers, dense.reports_load) == (0, False)


# ------------------------------------------- the other models' programs

def test_models_without_the_new_fields_lower_to_the_text_they_lowered_to():
    """A GPT-2, a `glm4_moe_lite`, a `glm_moe_dsa` and a `phi4flash` tiny
    preset (the slot step, the lanes program, the chunk program) hash as
    they did on the commit before the fields existed: no new operand, no
    ``zero_experts`` scope, no fourth count."""
    before = {
        "tiny": ["d782eab4d75ee6be", "8d1f143ae8b5cef0",
                 "33faba62922cbe2d"],
        "tiny-glm": ["3bbfe50e47be0bd0", "458a023c68850f2d",
                     "23a0fc97423ef558"],
        "tiny-glm-moe-dsa": ["7fbc3b0adc68eb4a", "64a2350a00f44098",
                             "0f6ce95e267838ae"],
        "tiny-phi4flash": ["6308c6748f852d35", "61cbb41961fe040e",
                           "1f33054967d72d0b"]}
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
        one = dict(jax.eval_shape(functools.partial(init_slot_cache, cfg, 1,
                                                    64)),
                   pos=jax.ShapeDtypeStruct((), jnp.int32))
        texts = [
            jax.jit(functools.partial(decode_step_slots, cfg=cfg)).lower(
                params, i32(3), cache,
                jax.ShapeDtypeStruct((3,), jnp.bool_)).as_text(),
            jax.jit(lambda p, t, ch, n: prefill_lanes(p, t, ch, cfg, n)
                    ).lower(params, i32(3, 8), cache, i32(3)).as_text(),
            jax.jit(lambda p, t, ch: prefill_chunk(p, t, ch, cfg)
                    ).lower(params, i32(1, 8), one).as_text()]
        assert "zero_experts" not in "".join(texts)
        assert [hashlib.sha256(t.encode()).hexdigest()[:16]
                for t in texts] == want, name
