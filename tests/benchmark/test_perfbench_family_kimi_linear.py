"""Family ``kimi_linear``: its counts against counts made by hand (at the
published widths) and against the program's own arithmetic; its
configuration, traffic and limits files against what they state and against
the catalog's entry; its plain reference (the delta rule as the RECURRENCE)
against the program (`forward` and `lm_loss` in float32; prefill in chunks,
whose delta rule is the CHUNKWISE form, then decode THROUGH THE CACHE against
the reference's one full forward, logits and not tokens; `forward` in
bfloat16 under the rehearsal's limits with the fp8 control failing them); the
SIXTEEN shares of an expert layer, the shared expert counted once, against
the uncut reference's layer; the three new readers on hand-made runs; and the
tiny cell rehearsed end to end.  The mathematics of the op and of the cached
programs over the sixth state kind is tests/test_delta_rule.py's.

The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-kimi-linear.json``, beside the
rehearsal's (a PR that changes the program adds files to the benchmark and
edits none), so the shared parametrised cases of test_perfbench_reference.py
and test_perfbench_rehearsal.py do not find it: they are called from here, on
this family.  The root manifest is looked at by MEMBERSHIP, never by a last
entry, a count or a whole list, so that the next cell does not fail this
file.
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
                             "BENCHMARK.tiny-kimi-linear.json")
CELL = "tiny-kimi-linear.serve-closed"
REAL_CELL = "kimi-linear-48b-a3b.serve-think-closed"
NEW_METRICS = ("kda.state_bytes_share.think", "device.kda_share.batch",
               "decode_step_roofline.think")

# by hand, from the published config.json: d 2304; a KDA layer of 32 heads of
# 128, conv 4, gate rank 128; latent attention of 32 heads of 128 + 64 | 128,
# kv_lora 512, no query latent; a dense SwiGLU of 9216; experts of 1024, 256
# routed
KDA = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
       + 3 * 4096 * 4 + 32 + 4096 + 128)
MLA = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304 + 512
EXPERT = 3 * 2304 * 1024
OPERATORS = 20 * KDA + 7 * MLA + 27 * 2 * 2304
OUTSIDE = OPERATORS + 3 * 2304 * 9216 + 26 * (EXPERT + 2304 * 256 + 256)
STATE = 32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2   # a KDA layer's, a sequence
ROW = 576 * 2                                   # a latent row: bf16


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("kimi-linear-48b-a3b")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-kimi-linear")
    return c, mf.family_of(c)


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    s = fam.shapes
    assert KDA == 39_514_272 == s.kda_params(c)
    assert MLA == 29_114_880 == s.attention_params(c)
    assert EXPERT == 7_077_888 == s.expert_params(c)
    held = OUTSIDE + 26 * 16 * EXPERT + 2 * 20480 * 2304 + 2304
    assert s.count_params(c) == held == 4_296_057_728      # 8.59 GB bf16
    assert (s.vocab(c), s.positions(c), s.layers(c), s.kda_layers(c),
            s.experts_routed(c), s.gate_rank(c)) == (
                20480, 1048576, (1, 26), 20, 256, 128)
    assert s.kinds(c) == ("kda", "kda", "kda", "full") * 6 + (
        "kda", "kda", "full")
    # what a slot carries: 43.4 MB whatever the context, 8064 B a position
    assert s.state_bytes(c) == STATE == 2_170_880
    assert 20 * STATE == 43_417_600 and 7 * ROW == 8064
    assert 32 * 20 * STATE == 1_389_363_200                # the states
    assert 32 * 5632 * 7 * ROW == 1_453_326_336            # the latents
    # a decode step: every weight outside the routed experts but the
    # embedding table once, the touched experts, the latents at each slot's
    # depth, and each slot's state once read and once written
    outside = OUTSIDE + 20480 * 2304 + 2304
    assert s.decode_step_bytes(c, 32 * 2_700, experts_touched=10.0,
                               depths=[2_700]) == \
        2.0 * (outside + 26 * 10.0 * EXPERT) + 32 * 2_700 * 7 * ROW \
        + 2 * 32 * 20 * STATE
    # ... the state does not grow with the depth, the latents do
    deep = s.decode_step_bytes(c, 32 * 5_000, experts_touched=10.0,
                               depths=[4_000, 6_000])
    assert deep - s.decode_step_bytes(
        c, 32 * 2_700, experts_touched=10.0, depths=[2_700]) == \
        32 * 2_300 * 7 * ROW
    # without depths or counted experts: ONE slot at all the rows, the share
    # held of a token's eight experts
    assert s.decode_step_bytes(c, 86_400) == \
        2.0 * (outside + 26 * 0.5 * EXPERT) + 86_400 * 7 * ROW \
        + 2 * 20 * STATE
    assert 2 * 32 * 20 * STATE == 2_778_726_400            # the issue's 2.7 GB
    assert s.kernels(c, 1, 4096) == {}
    matmul = (20 * (KDA - 3 * 4096 * 4 - 32 - 4096 - 128) + 7 * (MLA - 512)
              + 3 * 2304 * 9216 + 26 * (2304 * 256 + 1.5 * EXPERT)
              + 20480 * 2304)
    assert s.train_flops_per_token(c, 4096) == 6.0 * matmul \
        + 6.0 * 7 * 32 * 320 * 2048 + 21.0 * 20 * 32 * 128 * 128


def test_counts_are_the_programs(real, tiny):
    """`count_params` of the program's own configuration and the leaves its
    initialiser would make (shapes alone at the real size), and the tree
    the family makes."""
    from ray_tpu.models import count_params, init_params
    from ray_tpu.models.generate import cache_rows, position_bytes
    for c, fam in (real, tiny):
        cfg = fam.model.model_config(c, "serve")
        tree = jax.eval_shape(lambda k: init_params(k, cfg)[0],
                              jax.random.PRNGKey(0))
        leaves = sum(x.size for x in jax.tree_util.tree_leaves(tree))
        assert fam.shapes.count_params(c) == count_params(cfg) == leaves
        made = jax.eval_shape(
            lambda k: fam.model.make(k, c, jnp.bfloat16),
            jax.random.PRNGKey(0))
        assert jax.tree_util.tree_map(lambda x: x.shape, made) == \
            jax.tree_util.tree_map(lambda x: x.shape, tree)
        per = position_bytes(cfg)
        assert per["delta"] == fam.shapes.state_bytes(c)
        assert per["full"] == 2 * fam.shapes.cache_row_values(c)
    c, fam = real
    cfg = fam.model.model_config(c, "serve")
    assert cfg.kinds == fam.shapes.kinds(c) and cfg.kinds.count("kda") == 20
    assert cfg.layer_runs == (("dense_layers", 1), ("layers", 26))
    assert len(cfg.layer_segments) == 15
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_kernel,
            cfg.kda_gate_rank) == (32, 128, 4, 128)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_offset,
            cfg.expert_top_k) == (256, 16, 0, 8)
    assert (cfg.pos_emb, cfg.q_lora_rank) == ("none", 0)
    assert cache_rows(cfg) == {"kv": (1, 576), "s_delta": (32, 128),
                               "conv_delta": (1, 3)}


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "kimi-linear-48b-a3b")
    cut = ["num_experts", "vocab_size"]
    assert c["reduced"] == entry["reduced"] == cut
    assert entry["source"] == c["source"] and entry["file"].endswith(
        "configs/kimi-linear-48b-a3b.json")
    # every key of the catalog's entry is there, every width as published,
    # the depth and the layer pattern too
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        pub = next(d for d in map(json.loads, f)
                   if d["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert pub["source_url"] == c["source"]
    assert c["published"] == pub["config"]
    differ = [k for k, v in pub["config"].items() if c[k] != v]
    assert sorted(differ) == sorted(cut)
    assert set(cut) <= set(c["changed"])
    assert c["num_hidden_layers"] == 27 and c["first_k_dense_replace"] == 1
    assert c["linear_attn_config"] == pub["config"]["linear_attn_config"]
    d = c["deployment"]
    assert (d["chips_sharing_a_layer"], d["experts_routed"],
            d["expert_offset"], d["published_layers"]) == (
                16, 256, 0, "1-27, all")
    assert d["experts_routed"] == pub["config"]["num_experts"] \
        == 16 * c["num_experts"]
    assert 8 * c["vocab_size"] == pub["config"]["vocab_size"]
    for key in ("kda_gate_rank", "conv", "qk_norm", "decay", "beta",
                "output_gate", "kda_a_range", "kda_dt_range",
                "why_decay_draw", "e_score_correction_bias_std",
                "softmax_scale", "attention_out_gain", "weights"):
        assert key in c["assumed"], key
    assert c["assumed"]["kda_a_range"] == [1.0, 16.0]
    assert c["assumed"]["kda_dt_range"] == [0.001, 0.1]
    assert c["departures"][0] == "none in the mathematics"
    serve = c["precision"]["serve"]
    assert serve["state"] == serve["decay"] == serve["router"] == "float32"
    assert serve["params"] == serve["compute"] == "bfloat16"


def test_traffic_and_limits_files_have_the_cells_parameters():
    m = mf.Manifest()
    t = m.traffic("serve-think-closed")
    assert (t["kind"], t["clients"], t["requests_per_client"]) == \
        ("serve-closed", 32, 16)
    assert t["prompt_tokens"] == {"dist": "loguniform", "low": 1024,
                                  "high": 4096}
    assert t["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert t["distinct_prompt_lengths"] == 32
    assert t["engine"] == {"max_slots": 32, "max_len": 5632}
    assert (t["settle_s"], t["trace_seconds"], t["check"]) == \
        (2.0, 12.0, {"sample_requests": 2})
    from perfbench.kinds import serve_common
    lengths = serve_common.prompt_lengths(t)
    # one length a caller; the longest with its output fits the cache and
    # leaves a chunk of room (no chunk window is ever set back)
    assert len(lengths) == 32 and lengths[:2] == [1046, 1093]
    assert min(lengths) >= 1024 and max(lengths) + 1024 + 128 <= 5632
    cell = m.cell(REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("kimi-linear-48b-a3b", "serve-think-closed", 1)
    limits = m.limits(REAL_CELL)
    assert set(limits) == {"logit_err", "token_gap"}
    with open(os.path.join(mf.BENCH_DIR, "limits", REAL_CELL + ".json")) as f:
        body = json.load(f)
    assert {"limits", "readings", "how", "why"} <= set(body)
    assert set(body["readings"]["planted_faults"]) >= {
        "state_not_carried", "correction_dropped", "conv_inputs_zeroed"}


def test_the_reference_imports_nothing_of_the_programs_model_code():
    fam = mf.family("kimi_linear")
    for part in ("shapes", "model"):
        with open(fam.path(part)) as f:
            tree = ast.parse(f.read())
        names = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                names.add(node.module or "")
            elif isinstance(node, ast.Import):
                names.update(a.name for a in node.names)
        assert not any(n.startswith("ray_tpu.ops") for n in names), names
        if part == "shapes":
            assert not any(n.split(".")[0] in ("jax", "numpy", "ray_tpu")
                           for n in names), names
    # the program's configuration is made inside `model_config` alone, and
    # the rule is the reference's own recurrence, token by token
    with open(fam.path("model")) as f:
        src = f.read()
    assert src.count("from ray_tpu") == 1 == src.count(
        "from ray_tpu.models import TransformerConfig")
    assert "jax.lax.scan(one, jnp.zeros((h, dk, v.shape[-1]), F32)" in src
    assert "triangular_solve" not in src and "cumsum" not in src


def test_reference_is_the_programs_function_in_float32(tiny):
    c, fam = tiny
    shared_reference.test_reference_is_the_programs_function_in_float32(
        (c, fam.model))


def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        tiny):
    """The served path's mathematics against the reference's ONE full
    forward, logits at every generated position: chunk programs (absorbed
    attention over cached latents; the delta rule CHUNKWISE, two blocks of
    16 a chunk, against the state the last chunk left), then slot decode
    steps (the rule a token), in float32 at ``highest``.  1e-4 absolute on
    logits of spread about 1: float32 rounding in two orders of summation
    (the reference runs the recurrence; the program solves a block's
    triangular system and sums over blocks); a state lost, a correction
    dropped or a convolution's inputs zeroed reads 1e-1 and more
    (tests/test_delta_rule.py's planted faults)."""
    from ray_tpu.models import (decode_step_slots, init_kv_cache,
                                prefill_chunk_jit)
    from ray_tpu.models.generate import prefill_chunk_step
    c, fam = tiny
    model = fam.model
    key = weights.key_of(21)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (1, 200), c)
    cfg = dataclasses.replace(model.model_config(c, "serve"),
                              dtype=jnp.float32, param_dtype=jnp.float32)
    want = np.asarray(model.logits(params, toks, c))[0]
    with jax.default_matmul_precision("highest"):
        cache, off, host = init_kv_cache(cfg, 1, 256), 0, np.asarray(toks)
        while off < 170:        # five chunks of 32, one of 10
            logits, cache, off, _ = prefill_chunk_step(
                prefill_chunk_jit, params, host[:, :170], off, cache, cfg,
                chunk=32, capacity=256)
        assert float(np.abs(logits[0] - want[169]).max()) < 1e-4
        assert cache["s_delta"].dtype == jnp.float32
        slots = dict(cache, pos=jnp.full((1,), 170, jnp.int32))
        step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
        for t in range(170, 200):
            logits, slots = step(params, toks[:, t], slots,
                                 jnp.ones((1,), bool))
            assert float(np.abs(logits[0] - want[t]).max()) < 1e-4, t


def test_the_references_recurrence_without_its_correction_is_another(tiny):
    """`delta_recurrence` is what the program is held to: leave the
    correction out of it (plain gated linear attention) and it moves."""
    c, fam = tiny
    rng = np.random.default_rng(0)
    q, k = (rng.normal(size=(50, 2, 8)).astype(np.float32) for _ in "qk")
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(50, 2, 8)).astype(np.float32)
    a = -rng.uniform(0.01, 0.5, size=(50, 2, 8)).astype(np.float32)
    beta = rng.uniform(0.2, 1.0, size=(50, 2)).astype(np.float32)
    full = fam.model.delta_recurrence(q, k, v, a, beta)
    plain = fam.model.delta_recurrence(q, k, v, a, beta, correct=False)
    np.testing.assert_allclose(full[0], plain[0], atol=1e-6)    # S_0 = 0
    assert float(jnp.abs(full - plain).max()) > 0.1


def test_loss_is_the_references(tiny):
    from ray_tpu.models import lm_loss
    c, fam = tiny
    model = fam.model
    key = weights.key_of(11)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 48), c)
    cfg = dataclasses.replace(model.model_config(c, "train", remat=False),
                              dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = lm_loss(params, {"tokens": toks}, cfg)
    assert abs(float(got - model.loss(params, toks, c))) < 1e-5
    assert 4.5 < float(got) < 8.0      # ln 256 = 5.5 over random tokens
    # ... and its gradient is finite through the recurrence
    _, grad = model.loss_and_grad(params, toks, c)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grad))


@pytest.mark.parametrize("seed", shared_reference.SEEDS[:2])
def test_serving_program_passes_and_fp8_control_fails(tiny, seed):
    """test_perfbench_reference.py's case under this family's limits, but
    for its last line: at width 64 a bfloat16 router score that flips one of
    a token's two experts moves a logit more than all rounding does, so the
    program swings ten-fold with the seed (0.016-0.170 over 8 seeds, the
    limits file) and the control (0.21-0.31) stands 1.2 times above the
    worst of them, not the 3 times the shared case asks for.  The limits sit
    between the two over 8 seeds; at the published widths the chip reads
    the program at a small fraction of the control
    (perfbench/limits/kimi-linear-48b-a3b.serve-think-closed.json)."""
    from ray_tpu.models import forward
    c, fam = tiny
    model = fam.model
    key = weights.key_of(seed)
    params = model.make(key, c, model.param_dtype(c, "serve"))
    assert {x.dtype for x in jax.tree_util.tree_leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    toks = model.tokens(jax.random.fold_in(key, 2), (3, 40), c)
    cfg = model.model_config(c, "serve", attention_impl="reference")
    want = model.logits(params, toks, c)
    v = want.shape[-1]
    want = want.reshape(-1, v)
    got = forward(params, toks, cfg).reshape(-1, v)
    ctl = model.logits(params, toks, c, "fp8").reshape(-1, v)
    limits = _tiny_manifest().limits(CELL)
    program = {k: float(x) for k, x in reference.logit_numbers(
        got, want, got.argmax(-1)).items()}
    control = {k: float(x) for k, x in reference.logit_numbers(
        ctl, want, ctl.argmax(-1)).items()}
    sane = {"requests_completed": True}
    assert verdict.verdict(program, limits, sane)["correct"], program
    assert not verdict.verdict(control, limits, sane)["correct"], control
    assert all(control[k] > limits[k] for k in limits), control
    assert control["logit_err"] > 2.5 * program["logit_err"]


def test_weights_come_from_the_seed_alone(tiny):
    c, fam = tiny
    shared_reference.test_weights_come_from_the_seed_alone((c, fam.model))
    # what decides how long a state remembers is the stated draw
    p = fam.model.make(weights.key_of(3), c, jnp.float32)["layers"]
    assert 0.0 <= float(p["kda_a_log"].min()) \
        and float(p["kda_a_log"].max()) <= np.log(16.0)
    dt = jax.nn.softplus(p["kda_dt_bias"])
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11


def test_sixteen_shares_add_up_to_the_uncut_layer(tiny):
    """A layer of 16 routed experts, 2 a token, shared by SIXTEEN chips of
    one expert each: the routed parts the PROGRAM computes for the sixteen
    shares (each told which expert it holds, each routing over all 16),
    with the shared expert that every chip computes alike counted once, add
    up to the uncut REFERENCE's layer: every expert held."""
    from ray_tpu.models.transformer import _ffn
    c, fam = tiny
    model = fam.model
    whole = dict(c, num_experts=16, deployment=dict(
        c["deployment"], experts_routed=16, expert_offset=0))
    params = model.make(weights.key_of(13), whole, jnp.float32)
    lay = params["layers"]
    own = ("kda_", "wq", "wkv", "wo", "kv_norm")    # the operators' weights
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), jnp.float32)
    r = reference._round_inputs("float32")
    with jax.default_matmul_precision("highest"):
        lp = {k: (v if k in ("w_in", "w_gate", "w_out") else v[1])
              for k, v in lay.items() if not k.startswith(own)}
        uncut = jnp.stack([
            model.routed_part(r, row, lp, whole, 0, 16, 1)
            + model._swiglu(r, row, lp["ws_in"], lp["ws_gate"], lp["ws_out"])
            for row in y])
        shared = jnp.stack([model._swiglu(
            r, row, lp["ws_in"], lp["ws_gate"], lp["ws_out"]) for row in y])
        total = shared
        for chip in range(16):
            one = dict(whole, num_experts=1, deployment=dict(
                whole["deployment"], expert_offset=chip))
            cfg = dataclasses.replace(
                model.model_config(one, "serve"), dtype=jnp.float32,
                param_dtype=jnp.float32)
            mine = {k: (v[1, chip:chip + 1] if k in ("w_in", "w_gate",
                                                     "w_out") else v[1])
                    for k, v in lay.items() if not k.startswith(own)}
            z, _, load = _ffn(cfg, y, mine)
            total = total + (z - shared)
            assert int(load[2]) <= 2 * 24 * 2      # its own pairs alone
    assert float(jnp.abs(total - uncut).max()) < 1e-4
    assert float(jnp.abs(uncut - shared).max()) > 0.1


def test_tiny_manifest_and_the_roots_have_no_problem():
    assert mf.problems(_tiny_manifest()) == []
    root = mf.Manifest()
    assert mf.problems(root) == []
    # by membership, never by count, position or a whole list
    assert "kimi-linear-48b-a3b" in [c["name"] for c in root.data["configs"]]
    assert REAL_CELL in [w["name"] for w in root.data["workloads"]]
    assert root.cell(REAL_CELL)["chips"] == 1
    assert REAL_CELL in next(x for x in root.data["end_to_end"]
                             if x["name"] == "serve_tok_s")["workloads"]
    per_layer = {x["name"]: x for x in root.data["per_layer"]}
    for name in NEW_METRICS:
        assert REAL_CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "serve_tok_s"
    reported = {x["name"] for x in root.metrics_for(REAL_CELL, True)}
    assert set(NEW_METRICS) <= reported
    assert {"moe.experts_touched.agent", "decode_step_roofline.agent",
            "device.share.attention.batch", "device.share.conv.batch",
            "device.idle_share.batch", "hbm_peak_gb.batch",
            "setup.warmup_s"} <= reported
    assert {x["name"] for x in root.metrics_for(REAL_CELL, False)} == {
        "serve_tok_s", "setup_s"}


def _spans_run(events):
    return types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                 _ring_spans=events)


def test_the_state_share_reader_on_hand_made_spans():
    read = mf.metric_reader("kda.state_bytes_share.think")
    assert read(_spans_run([])) is None
    other = _spans_run([{"name": "cache:rows", "ts": 1e6, "dur": 2e6,
                         "args": {"steps": 10, "rows_read": 50,
                                  "rows_if_full": 100, "bytes_read": 100}}])
    # no state moved: a model without KDA layers, the parent: nothing
    assert read(other) is None
    ours = _spans_run([
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "bytes_read": 100, "state_rows": 4, "state_bytes_moved": 300}},
        {"name": "cache:rows", "ts": 3e6, "dur": 2e6, "args": {
            "bytes_read": 100, "state_rows": 4, "state_bytes_moved": 300}},
        {"name": "cache:rows", "ts": 44e6, "dur": 2e6, "args": {   # ends late
            "bytes_read": 1, "state_rows": 1, "state_bytes_moved": 1}}])
    assert read(ours) == 75.0
    # the cell's own arithmetic: a slot at depth 2,700 reads 2,700 latents on
    # 7 layers and moves its state twice on 20
    at = _spans_run([{"name": "cache:rows", "ts": 1e6, "dur": 1e6, "args": {
        "bytes_read": 2_700 * 7 * ROW, "state_rows": 20,
        "state_bytes_moved": 2 * 20 * STATE}}])
    assert read(at) == pytest.approx(79.95, abs=0.01)


def test_the_roofline_reader_on_a_hand_made_run(real, monkeypatch):
    """The family's floor at the run's mean batch, its counted experts and
    the depths its slots stood at, over the HBM peak, over the step's device
    time; nothing where there is no trace, no ``moe:load`` span, or a
    family whose floor counts no state."""
    from perfbench import moe_load, readers
    c, fam = real
    read = mf.metric_reader("decode_step_roofline.think")
    req = types.SimpleNamespace(prompt=[0] * 2_700, tokens=[0] * 2,
                                arrivals=[(1.0, 2)])

    def run(family, trace={"programs": {}}):
        return types.SimpleNamespace(
            trace=trace, family=family, config=c,
            raw={"requests": [req], "counters": {
                "before": {"steps": 0, "tokens": 0},
                "after": {"steps": 10, "tokens": 320}}},
            peaks=lambda: {"hbm_bytes_per_s": 819e9})

    monkeypatch.setattr(readers, "program_ms",
                        lambda run, pattern: None if run.trace is None
                        else 20.0)
    touched = [10.0]
    monkeypatch.setattr(moe_load, "experts_touched_per_layer_step",
                        lambda run: touched[0])
    got = read(run(fam))
    floor = fam.shapes.decode_step_bytes(
        c, 32 * 2_700.5, experts_touched=10.0, depths=[2_700, 2_701])
    assert got == pytest.approx(100 * floor / 819e9 / 0.020)
    assert 40 < got < 100
    assert read(run(fam, trace=None)) is None
    assert read(run(mf.family("gpt2"))) is None
    assert read(run(mf.family("glm_moe_dsa"))) is None      # no state
    touched[0] = None
    assert read(run(fam)) is None


def test_the_kda_share_reader_gives_nothing_without_its_scope(
        tmp_path, monkeypatch):
    """An untraced run, a session that left no op map, and maps in which no
    operation stands in a ``kda`` scope (a program without KDA layers: the
    parent) all give None; with the scope, its operations' share, whatever
    part they fall in."""
    from perfbench import parts, spans, xplane
    read = mf.metric_reader("device.kda_share.batch")
    assert read(types.SimpleNamespace(trace=None)) is None
    run = types.SimpleNamespace(trace={}, raw={"trace": {"dir": "x"}})
    monkeypatch.setattr(spans, "session_dir", lambda run: str(tmp_path))
    assert read(run) is None
    os.makedirs(tmp_path / "programs")
    path = "jit(fused_step)/while/body/attention/%smul"

    def leave(scope):
        with open(tmp_path / "programs" / "worker-1.decode_step.json",
                  "w") as f:
            json.dump({"program": "decode_step", "maps": [{
                "module": "jit_fused_step", "instructions": {
                    "fusion.1": path % scope,
                    "fusion.2": "jit(fused_step)/while/body/attention/"
                                "kda/conv/mul",
                    "fusion.3": "jit(fused_step)/while/body/ffn/dot"}}]}, f)

    monkeypatch.setattr(xplane, "find", lambda d: d)
    monkeypatch.setattr(xplane, "read", lambda p: {"devices": {"d0": {
        "modules": [(0.0, 10.0, "jit_fused_step(1)")],
        "ops": [(0.0, 2.0, "fusion.1"), (2.0, 3.0, "fusion.2"),
                (3.0, 10.0, "fusion.3")]}}})
    leave("")
    assert read(run) == pytest.approx(10.0)     # the convolutions alone
    leave("kda/")
    assert read(run) == pytest.approx(30.0)
    # the parts still add up: the scope stands inside attention, and its
    # convolutions fall in conv
    assert parts.place(path % "kda/") == ("attention", "forward")
    assert parts.place("jit(f)/attention/kda/conv/mul") == (
        "conv", "forward")


@pytest.mark.parametrize("trace", [1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest:
    the whole path through `serve.run` and the engine, prompts of 8-40
    tokens as padded chunks over a latent cache on 2 layers and delta states
    on 4.  The traced run finds the engine's ``cache:rows`` spans with the
    state the steps moved; the readers of the device trace find no device
    plane on the CPU and leave theirs out."""
    lines = []

    def rehearsed(*a, **kw):
        lines.extend(rehearse_cell(*a, manifest_path=TINY_MANIFEST, **kw))
        return lines

    rehearse_cell = rehearse.rehearse
    monkeypatch.setattr(rehearse, "manifest", _tiny_manifest)
    monkeypatch.setattr(rehearse, "rehearse", rehearsed)
    shared_rehearsal.test_cell_kind_rehearsed_on_the_cpu(CELL, trace)
    got = lines[-1]["metrics"]
    # (the engine writes a ``cache:rows`` span every two seconds: on a
    # loaded machine none may END inside a window of three, and the span
    # readers then leave their metrics out)
    if "cache.rows_read_share.mixed" in got:
        # 2 of 6 layers attend rows
        assert got["cache.rows_read_share.mixed"]["value"] == \
            pytest.approx(100 / 3)
        # a slot's 4 states of 2176 B, read and written, beside 9-48 rows
        # of 48 B on 2 layers
        assert 75 < got["kda.state_bytes_share.think"]["value"] < 97
        assert got["moe.experts_touched.agent"]["value"] > 0
    for name in ("decode_step_roofline.think", "device.kda_share.batch",
                 "decode_step.device_ms.batch"):
        assert name not in got, name
