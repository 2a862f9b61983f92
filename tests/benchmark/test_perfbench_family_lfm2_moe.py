"""Family ``lfm2_moe``: its counts against counts made by hand (at the
published widths and at the rehearsal's tiny size) and against the program's
own arithmetic; its configuration and traffic files against what they state;
its plain reference against the program through chunked prefill, single-
token tails and slot decode over TWO OPERATORS (attention layers with rows a
position, conv layers with a state a sequence), and its gradient against the
program's.  The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-lfm2.json``, beside the rehearsal's (a
PR that changes the program adds files to the benchmark and edits none), so
the shared parametrised cases of test_perfbench_reference.py and
test_perfbench_rehearsal.py do not find it: they are called from here, on
this family.

Tolerances.  Float32 against float32 (two implementations of the same
equations, both at ``highest``): 1e-4 on logits of order 1, 2e-4 relative on
the whole gradient.  Bfloat16 against float32 is held to the rehearsal's
limits files, whose readings say what a flipped expert costs.
"""

import ast
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
from perfbench import reference, verdict, weights
from perfbench.tools import rehearse

import test_perfbench_reference as shared_reference
import test_perfbench_rehearsal as shared_rehearsal

TINY_MANIFEST = os.path.join(mf.ROOT, rehearse.REHEARSAL,
                             "BENCHMARK.tiny-lfm2.json")
CELL = "tiny-lfm2.serve-closed"
REAL_CELL = "lfm2-8b-a1b.serve-reason-closed"
NEW_METRICS = {"cache.state_bytes_share.reason", "moe.rows_per_expert.reason"}

# by hand, from the published config.json: d 2048, 32 query and 8 key-value
# heads of 64, conv of 3 taps, 32 experts of 1792, a dense layer of 7168
CONV = 2048 * 6144 + 2048 * 2048 + 2048 * 3
ATTN = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
EXPERT = 3 * 2048 * 1792
EXPERT_FFN = 32 * EXPERT + 2048 * 32 + 32
NORMS = 2 * 2048
DENSE_CONV_LAYER = 3 * 2048 * 7168 + CONV + NORMS
EMBED = 65536 * 2048
AS_RUN = 3 * ((EXPERT_FFN + ATTN + NORMS) + 3 * (EXPERT_FFN + CONV + NORMS)) \
    + DENSE_CONV_LAYER + EMBED + 2048


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("lfm2-8b-a1b")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-lfm2")
    return c, mf.family_of(c)


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    s = fam.shapes
    assert (CONV, ATTN, EXPERT_FFN) == (16_783_360, 10_485_888, 352_387_104)
    assert s.conv_params(c) == CONV and s.attention_params(c) == ATTN
    assert s.expert_params(c) == EXPERT and s.head_dim(c) == 64
    assert (EXPERT_FFN + CONV + NORMS, EXPERT_FFN + ATTN + NORMS) == (
        369_174_560, 362_877_088)
    assert DENSE_CONV_LAYER == 60_827_648
    assert AS_RUN == 4_606_249_728 and s.count_params(c) == AS_RUN
    # whole, as published: 2 dense layers and 22 expert layers, 18 conv and
    # 6 attention: 8.34 B, 16.7 GB in bfloat16, more than a chip holds
    whole = s.count_params(c["published"])
    assert whole == 8_339_930_560 and 2 * whole > 16e9
    assert s.vocab(c) == 65_536 and s.positions(c) == 128_000
    assert s.layers(c) == (1, 12) and s.operators(c) == (3, 10)
    active = 3 * (ATTN - 128) + 10 * 4 * 2048 * 2048 + 3 * 2048 * 7168 \
        + 12 * (4 * EXPERT + 2048 * 32) + EMBED
    assert s.train_flops_per_token(c, 1024) == \
        6.0 * active + 6.0 * 3 * 2048 * 1024
    # a cached position costs an ATTENTION layer's keys and values only
    assert s.cache_row_values(c) == 1024 and s.state_values(c) == 4096
    assert s.decode_step_bytes(c, 101.0) - s.decode_step_bytes(c, 1.0) == \
        3 * 100 * 2048
    # weights: everything outside the routed experts once (the embedding as
    # the head), then the experts the step touched; four where no run
    # counted them; the states of ONE slot while any slot is live
    fixed = AS_RUN - 12 * 32 * EXPERT
    assert s.decode_step_bytes(c, 0.0, experts_touched=0) == 2.0 * fixed
    assert s.decode_step_bytes(c, 0.0) == 2.0 * (fixed + 12 * 4 * EXPERT)
    assert s.decode_step_bytes(c, 1.0, experts_touched=32) == \
        2.0 * (AS_RUN + 3 * 1024 + 10 * 4096)
    # the step at the cell's load: 32 slots at a mean depth of 1150
    step = s.decode_step_bytes(c, 32 * 1150.0, experts_touched=31.4)
    assert 9.2e9 < step < 9.3e9
    k = s.kernels(c, 32, 1)["grouped_matmul"]
    assert (k["fwd_flops"], k["calls"]) == (2.0 * 128 * 2048 * 1792, 36)
    assert k["fwd_bytes"] == 2.0 * (32 * 2048 * 1792 + 128 * (2048 + 1792))
    assert s.kernels(c, 1, 1)["grouped_matmul"]["fwd_bytes"] == \
        2.0 * (4 * 2048 * 1792 + 4 * 3840)
    one = 2.0 * 2 * 32 * 512 * 512 * 64 / 2
    f = s.kernels(c, 2, 512)["flash_attention"]
    assert (f["fwd_flops"], f["bwd_flops"], f["calls"]) == (2 * one,
                                                            5 * one, 3)


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "lfm2-8b-a1b")
    assert sorted(c["reduced"]) == sorted(entry["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types"])
    assert entry["source"] == c["source"] and entry["file"].endswith(
        "configs/lfm2-8b-a1b.json")
    # every published key is there, and only the reduced ones differ
    differs = {k for k, v in c["published"].items() if c[k] != v}
    assert differs == set(c["reduced"]) == set(c["changed"])
    pub = c["published"]
    assert (pub["num_hidden_layers"], c["num_hidden_layers"]) == (24, 13)
    assert (pub["num_dense_layers"], c["num_dense_layers"]) == (2, 1)
    # the dense conv layer, then THREE WHOLE periods: published layers 1-13
    assert c["layer_types"] == pub["layer_types"][1:14] == \
        ["conv"] + ["full_attention", "conv", "conv", "conv"] * 3
    # no width is cut, every expert and the whole vocabulary are held
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "conv_L_cache", "vocab_size",
                "rope_theta", "norm_eps", "routed_scaling_factor"):
        assert c[key] == pub[key], key
    a = c["assumed"]
    assert a["tie_word_embeddings"] is True and a["hidden_act"] == "silu"
    assert a["expert_bias_balance_tokens"] == 2048 and \
        a["expert_bias_std"] > 0
    for key in ("in_proj_order", "rotary_pairing", "expert_bias", "weights",
                "qk_norm", "conv_taps"):
        assert len(a[key]) > 40, key
    assert len(c["departures"]) >= 3
    assert c["deployment"]["pipeline_stages"] == 2
    assert c["precision"]["serve"]["params"] == "bfloat16" and \
        c["precision"]["serve"]["router"] == "float32"


def test_traffic_file_has_the_cells_parameters():
    m = mf.Manifest()
    cell = m.cell(REAL_CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "serve-reason-closed")
    assert len(cell["why"]) <= 200 and "attention" in cell["why"]
    t = m.traffic(cell["traffic"])
    assert t["kind"] == "serve-closed" and t["clients"] == 32
    assert t["prompt_tokens"] == {"dist": "uniform", "low": 256,
                                  "high": 1024}
    assert t["distinct_prompt_lengths"] == 32
    assert t["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert t["requests_per_client"] == 16
    assert t["engine"] == {"max_slots": 32, "max_len": 4096}
    assert (t["settle_s"], t["trace_seconds"]) == (2.0, 12.0)
    assert t["check"]["sample_requests"] == 2
    from perfbench.kinds import serve_closed, serve_common
    lengths = serve_common.prompt_lengths(t)
    assert len(set(lengths)) == 32 and 256 <= min(lengths) \
        and max(lengths) <= 1024
    # ONE length a caller, so every seed's window holds the same requests
    plans = [serve_closed.plan_for(t, m.config(cell["config"]), seed)
             for seed in (5, 3200000001)]
    for plan in plans:
        assert len(plan) == 32
        for i, mine in enumerate(plan):
            assert {len(r.prompt) for r in mine} == {lengths[i]}
            assert len(mine) == 16 and {r.n_out for r in mine} == {1024}
    # the longest request fits the cache with chunks to spare: no chunk
    # window is ever set back at its end (a conv state cannot be)
    assert max(lengths) + 1024 + 128 <= t["engine"]["max_len"]
    # the bias is balanced over contexts as long as the cell's longest
    c = m.config(cell["config"])
    assert c["assumed"]["expert_bias_balance_tokens"] >= max(lengths) + 1024
    assert set(m.limits(cell["name"])) == {"logit_err", "token_gap"}


def test_counts_are_the_programs_and_the_cache_has_a_state(real, tiny):
    from ray_tpu.models import init_params, init_slot_cache
    from ray_tpu.models.transformer import count_params, flops_per_token
    for (c, fam), max_len in ((real, 4096), (tiny, 128)):
        cfg = fam.model.model_config(c, "serve")
        assert fam.shapes.count_params(c) == count_params(cfg)
        for s in (64, 100_000):
            assert fam.shapes.train_flops_per_token(c, s) == \
                flops_per_token(cfg, s)
        made = jax.eval_shape(
            lambda k: fam.model.make(k, c, cfg.param_dtype),
            jax.random.PRNGKey(0))
        leaves = jax.tree_util.tree_leaves(made)
        assert sum(x.size for x in leaves) == fam.shapes.count_params(c)
        assert {x.dtype for x in leaves} == {jnp.dtype(jnp.bfloat16)}
        theirs = jax.eval_shape(lambda k: init_params(k, cfg)[0],
                                jax.random.PRNGKey(0))
        assert jax.tree_util.tree_map(lambda x: x.shape, made) == \
            jax.tree_util.tree_map(lambda x: x.shape, theirs)
        assert "lm_head" not in made                         # tied
        n_attn, n_conv = fam.shapes.operators(c)
        assert made["layers"]["wq"].shape[0] == n_attn
        assert made["layers"]["conv_in"].shape[0] == n_conv - 1
        assert made["dense_layers"]["conv_in"].shape[0] == 1
        cache = jax.eval_shape(lambda: init_slot_cache(cfg, 3, max_len))
        assert set(cache) == {"k", "v", "conv_state", "pos"}
        hk, hd = c["num_key_value_heads"], fam.shapes.head_dim(c)
        assert cache["k"].shape == (n_attn, 3, hk, hd, max_len)
        assert cache["conv_state"].shape == (
            n_conv, 3, 1, c["conv_L_cache"] - 1, c["hidden_size"])
        assert cache["conv_state"].size // 3 == \
            n_conv * fam.shapes.state_values(c)
    # the cell's slot cache: 0.81 GB of rows on 3 layers and 2.6 MB of
    # states on 10, where 13 attention layers would hold 3.5 GB
    c, fam = real
    cache = jax.eval_shape(lambda: init_slot_cache(
        fam.model.model_config(c, "serve"), 32, 4096))
    rows = 2 * cache["k"].size * 2
    assert (rows, cache["conv_state"].size * 2) == (805_306_368, 2_621_440)
    assert rows // 3 * 13 == 3_489_660_928
    # tiny, by hand: d 64, 4 query and 2 key-value heads of 16
    conv = 64 * 192 + 64 * 64 + 64 * 3
    attn = 2 * 64 * 64 + 2 * 64 * 32 + 2 * 16
    ffn = 8 * 3 * 64 * 32 + 64 * 8 + 8
    assert tiny[1].shapes.count_params(tiny[0]) == (
        conv + 3 * 64 * 160 + 128 + (attn + ffn + 128)
        + 3 * (conv + ffn + 128) + 256 * 64 + 64)


def test_the_reference_imports_nothing_of_the_programs_model_code():
    """The family's model.py names the program in `model_config` alone."""
    path = mf.family("lfm2_moe").path("model")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        mods = [node.module] if isinstance(node, ast.ImportFrom) else \
            [a.name for a in node.names] if isinstance(node, ast.Import) \
            else []
        assert not any(m and m.startswith("ray_tpu") for m in mods), mods
    inside = {fn.name for fn in tree.body if isinstance(fn, ast.FunctionDef)
              for n in ast.walk(fn) if isinstance(n, ast.ImportFrom)
              and (n.module or "").startswith("ray_tpu")}
    assert inside == {"model_config"}


def _f32(model, c, **kw):
    return dataclasses.replace(
        model.model_config(c, "serve", attention_impl="reference", **kw),
        dtype=jnp.float32, param_dtype=jnp.float32)


def _through_the_cache(params, toks, cfg, plan, max_len=128):
    """The program's served path, teacher forced.  ``plan`` gives each
    row's prefill as a list of chunk widths fed WITHOUT ``n_valid`` (the
    walk the benchmark's own comparison makes: whole chunks, then a token
    at a time); the rest of the row is decode steps over slots at
    different depths, a slot that has run out standing inactive beside the
    others.  -> (logits [b, s, V], which positions were computed)."""
    from ray_tpu.models import (cache_insert_slot, decode_step_slots,
                                init_kv_cache, init_slot_cache,
                                prefill_chunk_jit)
    b, s = toks.shape
    got = np.zeros((b, s, cfg.vocab_size), np.float32)
    have = np.zeros((b, s), bool)
    slots = init_slot_cache(cfg, b, max_len)
    insert = jax.jit(cache_insert_slot)
    depth = []
    for i, widths in enumerate(plan):
        pc, off = init_kv_cache(cfg, 1, max_len), 0
        for take in widths:
            lg, pc = prefill_chunk_jit(params, toks[i:i + 1, off:off + take],
                                       pc, cfg=cfg)
            off += take
            got[i, off - 1], have[i, off - 1] = np.asarray(lg[0]), True
        depth.append(off)
        slots = insert(slots, pc, jnp.int32(i))
    step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
    for j in range(s - min(depth)):
        tok, active = np.zeros((b,), np.int32), np.zeros((b,), bool)
        for i, n in enumerate(depth):
            if n + j < s:
                tok[i], active[i] = toks[i, n + j], True
        lg, slots = step(params, jnp.asarray(tok), slots,
                         jnp.asarray(active))
        for i, n in enumerate(depth):
            if n + j < s:
                got[i, n + j], have[i, n + j] = np.asarray(lg[i]), True
    return got, have


PLAN = ([8] * 8 + [1] * 3, [1] * 3 + [8] * 5, [5, 8, 8, 8, 1])


def test_chunks_tails_and_slot_decode_over_rows_and_states(tiny):
    """Float32 both: three sessions of 120 positions, prefilled in chunks
    and single tokens from different offsets and decoded side by side at
    different depths, against the reference's full forward, which has no
    cache, no state and no sort."""
    c, fam = tiny
    model = fam.model
    key = weights.key_of(2**31 + 29)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (3, 120), c)
    cfg = _f32(model, c)
    want = model.logits(params, toks, c)
    with jax.default_matmul_precision("highest"):
        got, have = _through_the_cache(params, toks, cfg, PLAN)
    assert have.sum() == (11 + 53) + (8 + 77) + (5 + 90)
    err = jnp.abs(jnp.asarray(got) - want).max(-1)
    assert float(jnp.where(have, err, 0).max()) < 1e-4


def test_padded_chunk_programs_are_the_references_too(tiny):
    """The engine's own walk: `prefill_chunked` cuts a prompt into padded
    programs of ONE width, and the state that leaves the last is its last
    real token's."""
    from ray_tpu.models import (decode_step_slots, init_kv_cache,
                                init_slot_cache, prefill_chunked)
    from ray_tpu.models.generate import cache_insert_slot
    c, fam = tiny
    model = fam.model
    key = weights.key_of(19)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (1, 60), c)
    cfg = _f32(model, c)
    want = model.logits(params, toks, c)
    with jax.default_matmul_precision("highest"):
        for n, chunk in ((37, 32), (41, 8), (5, 32)):
            lg, pc = prefill_chunked(params, toks[:, :n], cfg,
                                     init_kv_cache(cfg, 1, 128), chunk=chunk)
            assert float(jnp.abs(lg[0] - want[0, n - 1]).max()) < 1e-4
            slots = cache_insert_slot(init_slot_cache(cfg, 2, 128), pc,
                                      jnp.int32(1))
            for p in range(n, n + 6):
                lg, slots = decode_step_slots(
                    params, jnp.array([0, int(toks[0, p])], jnp.int32),
                    slots, jnp.array([False, True]), cfg)
                assert float(jnp.abs(lg[1] - want[0, p]).max()) < 1e-4


def test_gradient_of_lm_loss_is_the_references(tiny):
    from ray_tpu.models import lm_loss
    c, fam = tiny
    model = fam.model
    key = weights.key_of(11)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 48), c)
    cfg = dataclasses.replace(
        model.model_config(c, "train", attention_impl="reference",
                           remat=False), dtype=jnp.float32)
    l_ref, g_ref = model.loss_and_grad(params, toks, c)
    with jax.default_matmul_precision("highest"):
        l_got, g_got = jax.value_and_grad(
            functools.partial(lm_loss, cfg=cfg))(params, {"tokens": toks})
    assert abs(float(l_got - l_ref)) < 1e-5
    assert float(reference.tree_rel_error(g_got, g_ref)) < 2e-4
    # the bias is a constant of the loss: it moves choices, not weights
    assert float(jnp.abs(g_got["layers"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g_ref["layers"]["router_bias"]).max()) == 0.0
    # every operator of every layer took part, each in its own stack
    for run, name in (("dense_layers", "conv_w"), ("layers", "conv_w"),
                      ("layers", "conv_in"), ("layers", "wq")):
        g = jnp.abs(g_got[run][name])
        assert float(g.reshape(g.shape[0], -1).max(-1).min()) > 0.0, name


def test_served_path_in_bfloat16_passes_and_the_fp8_control_fails(tiny):
    """The comparison of ``correct`` on the path the cell times (chunks,
    tails, decode over rows and states), not on `forward`."""
    c, fam = tiny
    model = fam.model
    key = weights.key_of(3)
    params = model.make(key, c, model.param_dtype(c, "serve"))
    toks = model.tokens(jax.random.fold_in(key, 2), (3, 80), c)
    cfg = model.model_config(c, "serve", attention_impl="reference")
    got, have = _through_the_cache(
        params, toks, cfg, ([8] * 4, [1] * 3 + [8] * 3, [5, 8, 8]))
    v = got.shape[-1]
    keep = jnp.asarray(have.reshape(-1))
    want = model.logits(params, toks, c).reshape(-1, v)[keep]
    got = jnp.asarray(got.reshape(-1, v))[keep]
    ctl = model.logits(params, toks, c, "fp8").reshape(-1, v)[keep]
    limits = _tiny_manifest().limits(CELL)
    sane = {"requests_completed": True}
    program = {k: float(x) for k, x in reference.logit_numbers(
        got, want, got.argmax(-1)).items()}
    control = {k: float(x) for k, x in reference.logit_numbers(
        ctl, want, ctl.argmax(-1)).items()}
    assert verdict.verdict(program, limits, sane)["correct"], program
    assert not verdict.verdict(control, limits, sane)["correct"], control


def test_the_drawn_bias_is_balanced_as_a_trained_one_is(tiny):
    """`make` with calibration tokens: the experts of every expert layer
    meet about their even share of the pairs, on the calibration tokens
    and on fresh ones, where the bias as drawn sends most pairs to a few;
    and the balanced bias is the seed's alone."""
    c, fam = tiny
    model = fam.model
    drawn = dict(c, assumed=dict(c["assumed"], expert_bias_std=0.1))
    even = dict(drawn, assumed=dict(drawn["assumed"],
                                    expert_bias_balance_tokens=512))
    key = weights.key_of(2**31 + 3)
    toks = model.tokens(jax.random.fold_in(key, 99), (2, 256), c)
    worst = {}
    for name, conf in (("drawn", drawn), ("even", even)):
        params = model.make(key, conf, jnp.float32)
        again = model.make(key, conf, jnp.float32)
        assert bool((params["layers"]["router_bias"]
                     == again["layers"]["router_bias"]).all())
        shares = []

        def count(scores, bias):
            _, chosen = jax.lax.top_k(scores + bias, 2)
            load = jnp.zeros((8,)).at[chosen.reshape(-1)].add(1.0)
            shares.append(load / load.sum() * 8)
            return bias

        model._walk(params, toks, conf, "float32", count)
        assert len(shares) == 4                      # the expert layers
        worst[name] = float(jnp.stack(shares).max())
    # the fullest expert of any layer: about its even share, not thrice it
    assert worst["even"] < 1.6 < worst["drawn"], worst
    # everything but the bias is the same weights
    a, b = (model.make(key, conf, jnp.float32) for conf in (drawn, even))
    assert bool((a["layers"]["router"] == b["layers"]["router"]).all())
    assert bool((a["layers"]["w_in"] == b["layers"]["w_in"]).all())
    assert not bool((a["layers"]["router_bias"]
                     == b["layers"]["router_bias"]).all())


def test_tiny_manifest_and_the_roots_have_no_problem():
    m = _tiny_manifest()
    assert mf.problems(m) == []
    assert [w["name"] for w in m.data["workloads"]] == [CELL]
    # the readers this PR adds are rehearsed under the names the cell has
    root = mf.Manifest()
    assert mf.problems(root) == []
    own = {x["name"] for x in root.data["per_layer"]
           if x.get("workloads") == [REAL_CELL]}
    assert own == NEW_METRICS <= {x["name"] for x in m.data["per_layer"]}
    assert [x["name"] for x in root.data["per_layer"][-2:]] == [
        "cache.state_bytes_share.reason", "moe.rows_per_expert.reason"]
    assert root.data["workloads"][-1]["name"] == REAL_CELL
    assert root.data["configs"][-1]["name"] == "lfm2-8b-a1b"
    assert len(root.data["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in root.data["workloads"]) == 1
    listed = [x["name"] for x in root.data["per_layer"]
              if REAL_CELL in x.get("workloads", ())]
    for name in ("moe.experts_touched.agent", "decode_step_roofline.agent",
                 "prefill_chunk.device_ms.agent",
                 "engine.prefill_share.agent",
                 "expert_matmul.device_share.agent",
                 "cache.rows_read_share.mixed", "hbm_peak_gb.batch",
                 "decode_step.device_ms.batch", "compiles_in_window",
                 "setup.weights_s", "engine.idle_pct.readback.batch"):
        assert name in listed, name
    # one metric stays the cell's that a test of its family pins it to
    assert "moe.load_max_over_mean.agent" not in listed
    assert REAL_CELL in next(x for x in root.data["end_to_end"]
                             if x["name"] == "serve_tok_s")["workloads"]
    # nothing the benchmark had lists a cell it did not list, but this one
    for x in root.data["per_layer"]:
        assert x["layer"] and x["moves"] in {"serve_tok_s", "setup_s",
                                             "train_tok_s", "ttft_p95_ms"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest:
    the whole path through `serve.run` and the engine, prompts of 8-40
    tokens as padded chunks of 32 over four conv states and one layer of
    rows.  The traced run also finds the engine's ``cache:rows`` and
    ``moe:load`` spans, and the readers of the device trace find no device
    plane on the CPU and leave theirs out."""
    lines = []

    def rehearsed(*a, **kw):
        lines.extend(rehearse_cell(*a, manifest_path=TINY_MANIFEST, **kw))
        return lines

    rehearse_cell = rehearse.rehearse
    monkeypatch.setattr(rehearse, "manifest", _tiny_manifest)
    monkeypatch.setattr(rehearse, "rehearse", rehearsed)
    shared_rehearsal.test_cell_kind_rehearsed_on_the_cpu(CELL, trace)
    if trace:
        got = lines[-1]["metrics"]
        assert 2 <= got["moe.experts_touched.agent"]["value"] <= 8
        # every live row brings 2 pairs: a touched expert gets 1 or more
        assert 1 <= got["moe.rows_per_expert.reason"]["value"] <= 4
        # 4 states of 2 x 64 beside one layer of 128 rows of 2 x 2 x 16
        assert got["cache.state_bytes_share.reason"]["value"] == \
            pytest.approx(100 * 4 * 128 / (4 * 128 + 128 * 64))
        # contexts of 9-48 rows on one layer of five, two rows on four
        assert 20 < got["cache.rows_read_share.mixed"]["value"] < 40
        for name in ("decode_step_roofline.agent",
                     "prefill_chunk.device_ms.agent",
                     "engine.prefill_share.agent",
                     "expert_matmul.device_share.agent"):
            assert name not in got, name


def test_readers_leave_their_metrics_out_where_no_span_is():
    """A program without ``bytes_state`` in its ``cache:rows`` span (the
    parent of the PR that added it, a model without conv layers) gives the
    new readers nothing, and they raise nothing."""
    def run(events):
        return types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                     _ring_spans=events)

    state = mf.metric_reader("cache.state_bytes_share.reason")
    rows = mf.metric_reader("moe.rows_per_expert.reason")
    assert state(run([])) is None and rows(run([])) is None
    parent = run([{"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
        "steps": 10, "rows_read": 300, "rows_if_full": 400,
        "bytes_full": 100, "bytes_ring": 300}}])
    assert state(parent) is None and rows(parent) is None
    ours = run([
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_full": 900, "bytes_state": 100}},
        {"name": "cache:rows", "ts": 3e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_full": 700, "bytes_ring": 200,
            "bytes_state": 100}},
        {"name": "cache:rows", "ts": 44e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_full": 1, "bytes_state": 1}},   # ends late
        {"name": "moe:load", "ts": 1e6, "dur": 2e6, "args": {
            "steps": 5, "experts_touched": 100, "pairs": 410,
            "load_max": 30, "layers": 4, "experts": 8}}])
    assert state(ours) == 10.0 and rows(ours) == 4.1


def test_reference_is_the_programs_function_in_float32(tiny):
    c, fam = tiny
    shared_reference.test_reference_is_the_programs_function_in_float32(
        (c, fam.model))


@pytest.mark.parametrize("seed", shared_reference.SEEDS)
def test_training_program_passes_and_fp8_control_fails(tiny, seed):
    c, fam = tiny
    shared_reference.test_training_program_passes_and_fp8_control_fails(
        (c, fam.model), seed)


@pytest.mark.parametrize("seed", shared_reference.SEEDS)
def test_serving_program_passes_and_fp8_control_fails(tiny, seed):
    c, fam = tiny
    shared_reference.test_serving_program_passes_and_fp8_control_fails(
        (c, fam.model), seed)


def test_weights_come_from_the_seed_alone(tiny):
    c, fam = tiny
    shared_reference.test_weights_come_from_the_seed_alone((c, fam.model))


def test_query_and_key_scales_and_the_embedding_are_the_files(real, tiny):
    assert real[0]["assumed"]["qk_norm_scale"] == 1.0
    c, fam = tiny
    c = dict(c, assumed=dict(c["assumed"], qk_norm_scale=1.5))
    params = fam.model.make(weights.key_of(4), c, jnp.float32)
    for name in ("q_norm", "k_norm"):
        a = np.asarray(params["layers"][name])
        assert a.shape == (1, 16) and (a == 1.5).all()
    assert "q_norm" not in params["dense_layers"]      # a conv layer
    # the embedding is the head: logits of order 1
    tok = np.asarray(params["embed"]["tok"])
    assert tok.std() == pytest.approx(1 / np.sqrt(64), rel=0.05)
    taps = np.asarray(params["layers"]["conv_w"])
    assert taps.std() == pytest.approx(1 / np.sqrt(3), rel=0.1)


def test_limits_files_say_where_their_readings_come_from():
    here = os.path.join(mf.BENCH_DIR, "limits", REAL_CELL + ".json")
    with open(here) as f:
        body = json.load(f)
    for name, limit in body["limits"].items():
        r = body["readings"][name]
        assert r["program_seeds"] >= 8 and r["control_seeds"] >= 3
        assert r["program_largest"] < limit < r["control_smallest"], name
    assert "why" in body and "how" in body
    for cell in (CELL, "tiny-lfm2.train"):
        with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "limits",
                               cell + ".json")) as f:
            body = json.load(f)
        for name, limit in body["limits"].items():
            r = body["readings"][name]
            assert r["program_max"] < limit < r["control_min"], name
