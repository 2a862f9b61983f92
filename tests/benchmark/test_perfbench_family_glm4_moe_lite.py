"""Family ``glm4_moe_lite``: its counts against counts made by hand (at the
published widths and at the rehearsal's tiny size) and against the program's
own arithmetic; its configuration and traffic files against what they
state; its plain reference against the program through chunked prefill,
single-token tails and slot decode over the latent cache, and its gradient
against the program's.  The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-glm.json``, beside the rehearsal's (a PR
that changes the program adds files to the benchmark and edits none), so the
shared parametrised cases of test_perfbench_reference.py and
test_perfbench_rehearsal.py do not find it: they are called from here, on
this family.  Its limits files lie in the rehearsal's ``limits/``, where
every rehearsed cell's are looked up.

Tolerances.  Float32 against float32 (two implementations of the same
equations, both at ``highest``): 1e-4 on logits of order 1, 2e-4 relative
on the whole gradient; a router's near-tie cannot flip there beyond what
float32 resolves.  Bfloat16 against float32 is held to the rehearsal's
limits file, whose readings say what a flipped expert costs.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import manifest as mf
from perfbench import reference, verdict, weights
from perfbench.tools import rehearse

import test_perfbench_reference as shared_reference
import test_perfbench_rehearsal as shared_rehearsal

TINY_MANIFEST = os.path.join(mf.ROOT, rehearse.REHEARSAL,
                             "BENCHMARK.tiny-glm.json")
CELL = "tiny-glm.serve-closed"

# by hand, from the published config.json: d 2048, 20 heads, q_lora 768,
# kv_lora 512, nope 192, rope 64, v 256
ATTN = (2048 * 768 + 768 * 20 * (192 + 64) + 2048 * (512 + 64)
        + 512 * 20 * (192 + 256) + 20 * 256 * 2048)
EXPERT = 3 * 2048 * 1536
NORMS = 2 * 2048 + 768 + 512
DENSE_LAYER = ATTN + 3 * 2048 * 10240 + NORMS
OUTSIDE = ATTN + EXPERT + 2048 * 64 + 64 + NORMS     # shared, router, bias
AS_RUN = DENSE_LAYER + 5 * (OUTSIDE + 64 * EXPERT) + 2 * 154880 * 2048 + 2048


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("glm-4.7-flash")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-glm")
    return c, mf.family_of(c)


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    s = fam.shapes
    assert (ATTN, EXPERT) == (21_757_952, 9_437_184)
    assert s.attention_params(c) == ATTN and s.expert_params(c) == EXPERT
    assert AS_RUN == 3_895_625_536 and s.count_params(c) == AS_RUN
    assert s.vocab(c) == 154_880 and s.positions(c) == 202_752
    assert s.layers(c) == (1, 5)
    # a token meets 4 routed experts and the shared one, the router, the head
    active = (ATTN + 3 * 2048 * 10240) + 5 * (ATTN + 5 * EXPERT + 2048 * 64) \
        + 154880 * 2048
    assert s.train_flops_per_token(c, 1024) == \
        6.0 * active + 6.0 * 6 * (20 * (256 + 256) // 2) * 1024
    # a cached position a layer: 512 latent + 64 rotary key values, 1152
    # bytes, where keys and values a head would be 20 x (256 + 256) x 2
    assert s.cache_row_values(c) == 576
    rows = s.decode_step_bytes(c, 1.0) - s.decode_step_bytes(c, 0.0)
    assert rows == 6 * 1152
    # weights: everything but the embedding table and the routed experts
    # once, then the experts the step touched; one token's 4 where no run
    # counted them
    fixed = DENSE_LAYER + 5 * OUTSIDE + 154880 * 2048 + 2048
    assert s.decode_step_bytes(c, 0.0) == 2.0 * (fixed + 5 * 4 * EXPERT)
    assert s.decode_step_bytes(c, 0.0, experts_touched=41.5) == \
        2.0 * (fixed + 5 * 41.5 * EXPERT)
    assert s.decode_step_bytes(c, 0.0, experts_touched=64) == \
        2.0 * (AS_RUN - 154880 * 2048)
    k = s.kernels(c, 2, 512)["flash_attention"]
    one = 2.0 * 2 * 20 * 512 * 512 * 256 / 2
    assert (k["fwd_flops"], k["bwd_flops"], k["calls"]) == (2 * one,
                                                            5 * one, 6)


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "glm-4.7-flash")
    assert sorted(c["reduced"]) == sorted(entry["reduced"]) == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert entry["source"] == c["source"]
    # every published key is there, and only the two reduced ones differ
    differs = {k for k, v in c["published"].items() if c[k] != v}
    assert differs == set(c["reduced"])
    assert (c["published"]["num_hidden_layers"], c["num_hidden_layers"]) == (
        47, 6)
    assert (c["published"]["num_nextn_predict_layers"],
            c["num_nextn_predict_layers"]) == (1, 0)
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 5
    assert c["assumed"]["e_score_correction_bias_std"] > 0
    assert len(c["departures"]) >= 3 and c["deployment"]["stands_for"]
    assert c["precision"]["serve"] == {
        "params": "bfloat16", "compute": "bfloat16", "router": "float32",
        "softmax": "float32", "logits": "float32"}


def test_traffic_file_has_the_cells_parameters():
    m = mf.Manifest()
    cell = m.cell("glm-4.7-flash.serve-agent-closed")
    assert (cell["chips"], cell["traffic"]) == (1, "serve-agent-closed")
    t = m.traffic(cell["traffic"])
    assert t["kind"] == "serve-closed" and t["clients"] == 16
    assert t["prompt_tokens"] == {"dist": "uniform", "low": 512,
                                  "high": 2048}
    assert t["distinct_prompt_lengths"] == 32
    assert t["output_tokens"] == {"dist": "fixed", "value": 256}
    assert t["requests_per_client"] == 16
    assert t["engine"] == {"max_slots": 16, "max_len": 4096}
    assert t["check"]["sample_requests"] == 2
    from perfbench.kinds import serve_common
    lengths = serve_common.prompt_lengths(t)
    assert len(set(lengths)) == 32 and 512 <= min(lengths) \
        and max(lengths) <= 2048
    # the longest request fits a cache row
    assert max(lengths) + 256 <= t["engine"]["max_len"]
    limits = m.limits(cell["name"])
    assert set(limits) == {"logit_err", "token_gap"}


def test_counts_are_the_programs_and_the_cache_is_as_wide(real, tiny):
    from ray_tpu.models import init_params, init_slot_cache
    from ray_tpu.models.transformer import count_params, flops_per_token
    for c, fam in (real, tiny):
        cfg = fam.model.model_config(c, "serve")
        assert fam.shapes.count_params(c) == count_params(cfg)
        assert fam.shapes.train_flops_per_token(c, 64) == \
            flops_per_token(cfg, 64)
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
        cache = jax.eval_shape(lambda: init_slot_cache(cfg, 3, 128))
        assert set(cache) == {"kv", "pos"}          # one array, no second
        per_row = cache["kv"].size * 2 / (3 * 128)
        assert per_row == fam.shapes.decode_step_bytes(c, 1.0) \
            - fam.shapes.decode_step_bytes(c, 0.0)
    assert real[1].shapes.count_params(real[0]) == AS_RUN
    # tiny, by hand: d 64, 4 heads, q_lora 24, kv_lora 16, 12 | 8 | 16
    attn = 64 * 24 + 24 * 4 * 20 + 64 * 24 + 16 * 4 * 28 + 4 * 16 * 64
    norms = 2 * 64 + 24 + 16
    assert tiny[1].shapes.count_params(tiny[0]) == (
        attn + 3 * 64 * 160 + norms
        + 2 * (attn + 9 * 3 * 64 * 32 + 64 * 8 + 8 + norms)
        + 2 * 256 * 64 + 64)


def _f32(model, c, **kw):
    return dataclasses.replace(
        model.model_config(c, "serve", attention_impl="reference", **kw),
        dtype=jnp.float32, param_dtype=jnp.float32)


def _through_the_cache(params, toks, cfg, chunk=32, prefill_to=(67, 40)):
    """The program's served path, teacher forced: chunks of ``chunk``,
    single-token tails, slot insert, decode steps over slots at different
    depths.  -> (logits [b, s, V], which positions were computed)."""
    import numpy as np

    from ray_tpu.models import (cache_insert_slot, decode_step_slots,
                                init_kv_cache, init_slot_cache,
                                prefill_chunk_jit)
    b, s = toks.shape
    got = np.zeros((b, s, cfg.vocab_size), np.float32)
    have = np.zeros((b, s), bool)
    slots = init_slot_cache(cfg, b, 128)
    insert = jax.jit(cache_insert_slot)
    for i, n in enumerate(prefill_to):
        pc, off = init_kv_cache(cfg, 1, 128), 0
        while off < n:
            take = chunk if n - off >= chunk else 1
            lg, pc = prefill_chunk_jit(params, toks[i:i + 1, off:off + take],
                                       pc, cfg=cfg)
            off += take
            got[i, off - 1], have[i, off - 1] = np.asarray(lg[0]), True
        slots = insert(slots, pc, jnp.int32(i))
    step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
    for j in range(s - max(prefill_to)):
        tok = jnp.stack([toks[i, n + j] for i, n in enumerate(prefill_to)])
        lg, slots = step(params, tok, slots, jnp.ones((b,), bool))
        for i, n in enumerate(prefill_to):
            got[i, n + j], have[i, n + j] = np.asarray(lg[i]), True
    return got, have


def test_chunks_tails_and_slot_decode_over_latents_are_the_reference(tiny):
    """Float32 both: the absorbed attention over cached latents, the
    grouped experts and the layer pattern against the reference's full
    forward, which has no cache, no absorption and no sort."""
    c, fam = tiny
    model = fam.model
    key = weights.key_of(2**31 + 29)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 80), c)
    cfg = _f32(model, c)
    want = model.logits(params, toks, c)
    with jax.default_matmul_precision("highest"):
        got, have = _through_the_cache(params, toks, cfg)
    assert have.sum() == (2 + 3 + 13) + (1 + 8 + 13)
    err = jnp.abs(jnp.asarray(got) - want).max(-1)
    assert float(jnp.where(have, err, 0).max()) < 1e-4


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
    # the bias is a constant of the loss: it moves choices, not weights,
    # and there is no auxiliary loss for this router
    assert float(jnp.abs(g_got["layers"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g_ref["layers"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g_got["layers"]["router"]).max()) > 0.0


def test_served_path_in_bfloat16_passes_and_the_fp8_control_fails(tiny):
    """The comparison of ``correct`` on the path the cell times (chunks,
    tails, decode over the cache), not on `forward`."""
    c, fam = tiny
    model = fam.model
    key = weights.key_of(3)
    params = model.make(key, c, model.param_dtype(c, "serve"))
    toks = model.tokens(jax.random.fold_in(key, 2), (2, 80), c)
    cfg = model.model_config(c, "serve", attention_impl="reference")
    got, have = _through_the_cache(params, toks, cfg)
    v = got.shape[-1]
    keep = jnp.asarray(have.reshape(-1))
    want = model.logits(params, toks, c).reshape(-1, v)[keep]
    got = jnp.asarray(got.reshape(-1, v))[keep]
    ctl = model.logits(params, toks, c, "fp8").reshape(-1, v)[keep]
    limits = _tiny_manifest().limits("tiny-glm.serve-closed")
    sane = {"requests_completed": True}
    program = {k: float(x) for k, x in reference.logit_numbers(
        got, want, got.argmax(-1)).items()}
    control = {k: float(x) for k, x in reference.logit_numbers(
        ctl, want, ctl.argmax(-1)).items()}
    assert verdict.verdict(program, limits, sane)["correct"], program
    assert not verdict.verdict(control, limits, sane)["correct"], control


@pytest.mark.parametrize("std,most", [(0.1, 0.01), (1.0, 0.002)])
def test_how_often_rounding_the_routers_input_flips_an_expert(tiny, std,
                                                             most):
    """A near-tie between the last chosen score and the next flips an expert
    when the router's input is rounded to bfloat16 (the program's
    activations) and not (the reference's).  Measured here at width 64 over
    4096 random normed inputs: the rounding of the router's OWN input flips
    0.15 % of tokens at the cell's bias spread of 0.1 and none at the
    rehearsal's 1.0.  What a whole bfloat16 model flips is ten times that
    and more, because the error of every layer before arrives in the
    router's input too (one token-layer in twenty at width 64: the
    readings in the rehearsal's limits files; at the published width, the
    chip's readings in the cell's limits file)."""
    c, fam = tiny
    c = dict(c, assumed={"e_score_correction_bias_std": std})
    params = fam.model.make(weights.key_of(5), c, jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    y = jax.random.normal(jax.random.PRNGKey(2), (1, 4096, 64))
    y = y / jnp.sqrt(jnp.square(y).mean(-1, keepdims=True))
    exact = fam.model.expert_weights(y, lp, c) > 0
    rounded = fam.model.expert_weights(
        y.astype(jnp.bfloat16).astype(jnp.float32), lp, c) > 0
    assert int(exact.sum()) == 4096 * c["num_experts_per_tok"]
    flipped = float((exact != rounded).any(-1).mean())
    assert (std == 1.0 or 0.0 < flipped) and flipped < most, flipped


def test_tiny_manifest_has_no_problem():
    m = _tiny_manifest()
    assert mf.problems(m) == []
    assert [w["name"] for w in m.data["workloads"]] == [CELL]
    # the readers this PR adds are rehearsed under the names the cell has
    real = {x["name"] for x in mf.Manifest().data["per_layer"]
            if x.get("workloads") == ["glm-4.7-flash.serve-agent-closed"]}
    assert real and real <= {x["name"] for x in m.data["per_layer"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest;
    the traced run also finds the engine's ``moe:load`` spans (2 pairs a
    token over 8 experts at the tiny size), and the readers of the device
    trace find no device plane on the CPU and leave their metrics out."""
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
        experts = _tiny_manifest().config("tiny-glm")["n_routed_experts"]
        assert 1 <= got["moe.experts_touched.agent"]["value"] <= experts
        assert 1 <= got["moe.load_max_over_mean.agent"]["value"] <= experts
        for name in ("decode_step_roofline.agent",
                     "prefill_chunk.device_ms.agent",
                     "engine.prefill_share.agent"):
            assert name not in got, name


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


def test_limits_files_say_where_their_readings_come_from():
    here = os.path.join(mf.BENCH_DIR, "limits",
                        "glm-4.7-flash.serve-agent-closed.json")
    with open(here) as f:
        body = json.load(f)
    for name, limit in body["limits"].items():
        r = body["readings"][name]
        assert r["program_seeds"] >= 8 and r["control_seeds"] >= 2
        assert r["program_largest"] < limit < r["control_smallest"], name
    assert "why" in body and "how" in body
