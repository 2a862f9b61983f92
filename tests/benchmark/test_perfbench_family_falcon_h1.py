"""Family ``falcon_h1``: its counts against counts made by hand (at the
published widths) and against the program's own arithmetic; its
configuration, traffic and limits files against what they state and against
the catalog's entry; its plain reference (the scan as the RECURRENCE, the
convolution as shifted products, attention dense) against the program
(`forward` and `lm_loss` in float32; prefill in chunks, whose scan is the
CHUNKWISE form, then decode THROUGH THE CACHE against the reference's one
full forward, logits and not tokens; `forward` in bfloat16 under the
rehearsal's limits with the fp8 control failing them); the three branches of
a layer, which the drawn weights keep at one order; the four new readers on
hand-made runs; and the tiny cell rehearsed end to end.  The mathematics of
the op and of the cached programs over the seventh state kind is
tests/test_ssd.py's.

The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-falcon-h1.json``, beside the rehearsal's
(a PR that changes the program adds files to the benchmark and edits none),
so the shared parametrised cases of test_perfbench_reference.py and
test_perfbench_rehearsal.py do not find it: they are called from here, on
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
                             "BENCHMARK.tiny-falcon-h1.json")
CELL = "tiny-falcon-h1.serve-closed"
REAL_CELL = "falcon-h1-34b.serve-chat-closed"
NEW_METRICS = ("ssm.state_bytes_share.chat", "device.ssm_share.batch",
               "decode_step_roofline.chat", "ssm_step_roofline.chat")

# by hand, from the published config.json: d 5120; a mixer of 32 heads of
# 128 (4096 wide), state 256 in 2 groups, conv 4 with a bias; 20 query heads
# over 4 key-value heads of 128; a SwiGLU of 21504
MIXER = (5120 * (4096 + (4096 + 2 * 2 * 256) + 32) + 4096 * 5120
         + 5120 * 4 + 5120 + 3 * 32 + 4096)
ATTENTION = 2 * 5120 * 20 * 128 + 2 * 5120 * 4 * 128
FFN = 3 * 5120 * 21504
LAYER = MIXER + ATTENTION + FFN + 2 * 5120
STATE = 32 * 256 * 128 * 4 + 3 * 5120 * 2       # a layer's, a sequence
ROW = 2 * 4 * 128 * 2                           # a layer's, a position


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("falcon-h1-34b")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-falcon-h1")
    return c, mf.family_of(c)


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    s = fam.shapes
    assert MIXER == 68_351_072 == s.ssm_params(c)
    assert ATTENTION == 31_457_280 == s.attention_params(c)
    assert FFN == 330_301_440 == s.ffn_params(c)
    assert LAYER == 430_120_032 == s.layer_params(c)
    held = 9 * LAYER + 2 * 32640 * 5120 + 5120
    assert s.count_params(c) == held == 4_205_319_008      # 8.41 GB bf16
    assert (s.vocab(c), s.positions(c)) == (32640, 262144)
    assert s.ssm_widths(c) == (4096, 5120, 9248)
    # what a slot carries: 38.0 MB whatever the context, 18 KB a position
    assert s.state_bytes(c) == STATE == 4_225_024
    assert 9 * STATE == 38_025_216 and 9 * ROW == 18_432
    assert 64 * 9 * STATE == 2_433_613_824                 # the states
    assert 64 * 1536 * 9 * ROW == 1_811_939_328            # the rows
    # a decode step: every weight but the embedding table once, the rows at
    # each slot's depth, and each slot's state once read and once written
    weights_ = held - 32640 * 5120
    assert s.decode_step_bytes(c, 60 * 620, depths=[620]) == \
        2.0 * weights_ + 60 * 620 * 9 * ROW + 2 * 60 * 9 * STATE
    # ... the state does not grow with the depth, the rows do
    deep = s.decode_step_bytes(c, 60 * 1200, depths=[1000, 1400])
    assert deep - s.decode_step_bytes(c, 60 * 620, depths=[620]) == \
        60 * 580 * 9 * ROW
    # without depths: ONE slot at all the rows, the fewest states possible
    assert s.decode_step_bytes(c, 60 * 620) == \
        2.0 * weights_ + 60 * 620 * 9 * ROW + 2 * 9 * STATE
    # the kernel: a live slot's float32 state once in, once out, a layer
    k = s.kernels(c, 60, 1)["ssd_step"]
    assert k == {"step_flops": 5.0 * 60 * 32 * 256 * 128,
                 "step_bytes": 2.0 * 60 * 32 * 256 * 128 * 4, "calls": 9}


def test_counts_are_the_programs(real, tiny):
    from ray_tpu.models.generate import position_bytes
    from ray_tpu.models.transformer import (count_params,
                                            decode_flops_per_token,
                                            flops_per_token)
    for c, fam in (real, tiny):
        cfg = fam.model.model_config(c, "serve")
        assert count_params(cfg) == fam.shapes.count_params(c)
        assert flops_per_token(cfg, 512) == pytest.approx(
            fam.shapes.train_flops_per_token(c, 512))
        per = position_bytes(cfg)
        assert per["ssm"] == fam.shapes.state_bytes(c)
        assert per["full"] == fam.shapes.cache_row_values(c) * 2
        # a token's cost grows with its depth by the rows it attends alone
        L, h, hd = (c["num_hidden_layers"], c["num_attention_heads"],
                    c["head_dim"])
        assert decode_flops_per_token(cfg, 300) - decode_flops_per_token(
            cfg, 100) == 2 * 2 * h * hd * 200 * L
    c, fam = real
    cfg = fam.model.model_config(c, "serve")
    assert cfg.kinds == ("ssm+full",) * 9
    assert (cfg.embed_scale, cfg.logit_scale, cfg.key_scale) == (
        5.656854249492381, 0.0078125, 0.011048543456039804)
    assert cfg.ssm_scales == (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "falcon-h1-34b")
    cut = ["num_hidden_layers", "vocab_size"]
    assert c["reduced"] == entry["reduced"] == cut
    assert entry["source"] == c["source"] and entry["file"].endswith(
        "configs/falcon-h1-34b.json")
    # every key of the catalog's entry is there, every width as published
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        pub = next(d for d in map(json.loads, f)
                   if d["name"] == "Falcon-H1-34B-Instruct")
    assert pub["source_url"] == c["source"]
    assert c["published"] == pub["config"]
    differ = [k for k, v in pub["config"].items() if c[k] != v]
    assert sorted(differ) == sorted(cut)
    assert set(cut) <= set(c["changed"])
    assert 8 * c["num_hidden_layers"] == pub["config"]["num_hidden_layers"]
    assert 8 * c["vocab_size"] == pub["config"]["vocab_size"]
    d = c["deployment"]
    assert (d["stages"], d["chips_sharing_a_layer"]) == (8, 1)
    a = c["assumed"]
    assert a["state_dtype"] == "float32" and "dt_clamp" in a
    w = a["weights"]
    assert w["a_range"] == [1.0, 16.0] and w["dt_range"] == [0.001, 0.1]
    for key in ("rule", "why_decay_draw", "conv_bias_std",
                "attention_out_gain", "branch_ratios"):
        assert key in w, key
    assert set(w["branch_ratios"]) >= {"mixer", "attention", "feed_forward"}
    assert c["departures"][0].startswith("none in the mathematics")
    serve = c["precision"]["serve"]
    assert serve["state"] == serve["step"] == serve["decay"] == "float32"
    assert serve["params"] == serve["compute"] == "bfloat16"


def test_traffic_and_limits_files_have_the_cells_parameters():
    m = mf.Manifest()
    t = m.traffic("serve-chat-closed")
    assert (t["kind"], t["clients"], t["requests_per_client"]) == \
        ("serve-closed", 64, 16)
    assert t["prompt_tokens"] == {"dist": "loguniform", "low": 128,
                                  "high": 1024}
    assert t["output_tokens"] == {"dist": "fixed", "value": 384}
    assert t["distinct_prompt_lengths"] == 64
    assert t["engine"] == {"max_slots": 64, "max_len": 1536}
    assert (t["settle_s"], t["trace_seconds"], t["check"]) == \
        (2.0, 12.0, {"sample_requests": 2})
    from perfbench.kinds import serve_common
    lengths = serve_common.prompt_lengths(t)
    # one length a caller; the longest with its output fits the cache and
    # leaves a chunk of room (no chunk window is ever set back: a state
    # could not run tokens twice)
    assert len(lengths) == 64 and len(set(lengths)) == 64
    assert min(lengths) >= 128 and max(lengths) + 384 + 128 <= 1536
    assert 380 < sum(lengths) / 64 < 480
    cell = m.cell(REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("falcon-h1-34b", "serve-chat-closed", 1)
    limits = m.limits(REAL_CELL)
    assert set(limits) == {"logit_err", "token_gap"}
    with open(os.path.join(mf.BENCH_DIR, "limits", REAL_CELL + ".json")) as f:
        body = json.load(f)
    assert {"limits", "readings", "how", "why"} <= set(body)
    assert set(body["readings"]["planted_faults"]) >= {
        "state_not_carried", "group_zero_for_all", "attention_dropped",
        "multiplier_left_at_one"}


def test_the_reference_imports_nothing_of_the_programs_model_code():
    fam = mf.family("falcon_h1")
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
    # the scan is the reference's own recurrence, token by token
    with open(fam.path("model")) as f:
        src = f.read()
    assert src.count("from ray_tpu") == 1 == src.count(
        "from ray_tpu.models import TransformerConfig")
    assert "jax.lax.scan(one, jnp.zeros((H, N, P), F32)" in src
    assert "cumsum" not in src and "pallas" not in src


def test_reference_is_the_programs_function_in_float32(tiny):
    c, fam = tiny
    shared_reference.test_reference_is_the_programs_function_in_float32(
        (c, fam.model))


def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        tiny):
    """The served path's mathematics against the reference's ONE full
    forward, logits at every generated position: chunk programs (attention
    over cached rows; the scan CHUNKWISE, a chunk one block, against the
    state the last chunk left), then slot decode steps (the scan a token),
    in float32 at ``highest``.  2e-4 absolute on logits of spread about 1:
    float32 rounding in two orders of summation; a state lost, a group's key
    misread or a branch dropped reads 2e-2 and more (tests/test_ssd.py's
    planted faults)."""
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
    assert 0.5 < want.std() < 2.0
    with jax.default_matmul_precision("highest"):
        cache, off, host = init_kv_cache(cfg, 1, 256), 0, np.asarray(toks)
        while off < 170:        # five chunks of 32, one of 10
            logits, cache, off, _ = prefill_chunk_step(
                prefill_chunk_jit, params, host[:, :170], off, cache, cfg,
                chunk=32, capacity=256)
        assert float(np.abs(logits[0] - want[169]).max()) < 2e-4
        assert cache["s_ssm"].dtype == jnp.float32
        slots = dict(cache, pos=jnp.full((1,), 170, jnp.int32))
        step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
        for t in range(170, 200):
            logits, slots = step(params, toks[:, t], slots,
                                 jnp.ones((1,), bool))
            assert float(np.abs(logits[0] - want[t]).max()) < 2e-4, t


def test_the_three_branches_add_at_one_order(tiny):
    """With the weights drawn as the file states (a weight before a
    multiplier at its inverse), the mixer's, attention's and the
    feed-forward's branch are each 0.15-1.5 of the stream they are added to;
    with W_k and W_o at the plain 1 / sqrt(fan_in) under the published
    multipliers the attention branch is a twentieth of that and below."""
    c, fam = tiny
    model = fam.model
    key = weights.key_of(3)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 64), c)
    ratios = np.asarray(model.branch_ratios(
        model.make(key, c, jnp.float32), toks, c))
    assert ratios.shape == (3, 3)
    assert 0.15 < ratios.min() and ratios.max() < 1.5, ratios
    plain = dict(c, **{k: 1.0 for k in (
        "attention_out_multiplier", "key_multiplier")})
    # the same draw read as if no gain had been given: W_k and W_o at their
    # plain scale, the multipliers applied
    p = model.make(key, plain, jnp.float32)
    under = np.asarray(model.branch_ratios(p, toks, c))
    assert under[:, 1].max() < 0.05 * ratios[:, 1].min(), under


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
    """test_perfbench_reference.py's case under this family's limits: no
    rounded score chooses anything here, so the program reads a tenth of the
    control at width 64 already (the limits file)."""
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
    assert control["logit_err"] > 3 * program["logit_err"]


def test_weights_come_from_the_seed_alone(tiny):
    c, fam = tiny
    shared_reference.test_weights_come_from_the_seed_alone((c, fam.model))
    # what decides how long a state remembers is the stated draw
    p = fam.model.make(weights.key_of(3), c, jnp.float32)["layers"]
    assert 0.0 <= float(p["ssm_a_log"].min()) \
        and float(p["ssm_a_log"].max()) <= np.log(16.0)
    dt = jax.nn.softplus(p["ssm_dt_bias"])
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11
    assert 0.05 < float(p["ssm_conv_b"].std()) < 0.2
    # a weight before a multiplier stands at its inverse
    assert float(p["wk"].std()) * c["key_multiplier"] \
        * c["attention_in_multiplier"] * 8.0 == pytest.approx(1.0, rel=0.05)


def test_tiny_manifest_and_the_roots_have_no_problem():
    assert mf.problems(_tiny_manifest()) == []
    root = mf.Manifest()
    assert mf.problems(root) == []
    # by membership, never by count, position or a whole list
    assert "falcon-h1-34b" in [c["name"] for c in root.data["configs"]]
    assert REAL_CELL in [w["name"] for w in root.data["workloads"]]
    assert root.cell(REAL_CELL)["chips"] == 1
    assert REAL_CELL in next(x for x in root.data["end_to_end"]
                             if x["name"] == "serve_tok_s")["workloads"]
    per_layer = {x["name"]: x for x in root.data["per_layer"]}
    for name in NEW_METRICS:
        assert REAL_CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "serve_tok_s"
        assert callable(mf.metric_reader(name))
    reported = {x["name"] for x in root.metrics_for(REAL_CELL, True)}
    assert set(NEW_METRICS) <= reported
    assert {"device.share.attention.batch", "device.share.conv.batch",
            "device.idle_share.batch", "hbm_peak_gb.batch",
            "cache.rows_read_share.mixed", "setup.warmup_s"} <= reported
    assert not {"moe.experts_touched.agent", "device.kda_share.batch",
                "decode_step_roofline.think"} & reported
    assert {x["name"] for x in root.metrics_for(REAL_CELL, False)} == {
        "serve_tok_s", "setup_s"}


def _spans_run(events):
    return types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                 _ring_spans=events)


def test_the_state_share_reader_on_hand_made_spans():
    read = mf.metric_reader("ssm.state_bytes_share.chat")
    assert read(_spans_run([])) is None
    # a KDA model's spans move state too, but hold no ``bytes_ssm``: nothing
    other = _spans_run([{"name": "cache:rows", "ts": 1e6, "dur": 2e6,
                         "args": {"bytes_read": 100, "bytes_delta": 7,
                                  "state_rows": 4,
                                  "state_bytes_moved": 300}}])
    assert read(other) is None
    ours = _spans_run([
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "bytes_read": 100, "bytes_ssm": 9, "state_rows": 4,
            "state_bytes_moved": 300}},
        {"name": "cache:rows", "ts": 3e6, "dur": 2e6, "args": {
            "bytes_read": 100, "bytes_ssm": 9, "state_rows": 4,
            "state_bytes_moved": 300}},
        {"name": "cache:rows", "ts": 44e6, "dur": 2e6, "args": {   # ends late
            "bytes_read": 1, "bytes_ssm": 9, "state_rows": 1,
            "state_bytes_moved": 1}}])
    assert read(ours) == 75.0
    # the cell's own arithmetic: a slot at depth 620 reads 620 rows on 9
    # layers and moves its state twice on the same 9
    at = _spans_run([{"name": "cache:rows", "ts": 1e6, "dur": 1e6, "args": {
        "bytes_read": 620 * 9 * ROW, "bytes_ssm": 1, "state_rows": 9,
        "state_bytes_moved": 2 * 9 * STATE}}])
    assert read(at) == pytest.approx(86.94, abs=0.01)


def _step_run(c, family, trace={"programs": {}}, steps=10, tokens=600):
    req = types.SimpleNamespace(prompt=[0] * 620, tokens=[0] * 2,
                                arrivals=[(1.0, 2)])
    return types.SimpleNamespace(
        trace=trace, family=family, config=c,
        raw={"requests": [req], "counters": {
            "before": {"steps": 0, "tokens": 0},
            "after": {"steps": steps, "tokens": tokens}}},
        peaks=lambda: {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})


def test_the_roofline_readers_on_hand_made_runs(real, monkeypatch):
    """The whole step: the family's floor at the run's mean batch over the
    depths its slots stood at, over the HBM peak, over the step's device
    time.  The kernel: its calls' floor at the mean batch over its device
    time a step.  Nothing where there is no trace, where the family counts
    experts or no state, and where no operation of the trace is the
    kernel."""
    from perfbench import readers, xplane
    c, fam = real
    whole = mf.metric_reader("decode_step_roofline.chat")
    monkeypatch.setattr(readers, "program_ms",
                        lambda run, pattern: None if run.trace is None
                        else 22.0)
    got = whole(_step_run(c, fam))
    floor = fam.shapes.decode_step_bytes(c, 60 * 620.5, depths=[620, 621])
    assert got == pytest.approx(100 * floor / 819e9 / 0.022)
    assert 50 < got < 100
    assert whole(_step_run(c, fam, trace=None)) is None
    assert whole(_step_run(c, mf.family("gpt2"))) is None
    assert whole(_step_run(c, mf.family("kimi_linear"))) is None   # experts
    kernel = mf.metric_reader("ssm_step_roofline.chat")
    trace = {"programs": {"jit_fused_step": {
        "count": 5, "device_s": 0.11, "mean_gap_s": None}},
        "ops": {"tpu_custom_call:ssd_step.3": 0.030, "fusion.7": 0.05,
                "tpu_custom_call:delta_rule_step.1": 1.0}}
    got = kernel(_step_run(c, fam, trace=trace))
    least = 9 * 2.0 * 60 * 32 * 256 * 128 * 4 / 819e9
    assert got == pytest.approx(100 * least / (0.030 / 5))
    assert 50 < got < 100
    assert kernel(_step_run(c, fam, trace=None)) is None
    xla = dict(trace, ops={"fusion.7": 0.05})       # XLA's form of the step
    assert kernel(_step_run(c, fam, trace=xla)) is None
    assert xplane.op_seconds(trace, r"^tpu_custom_call:ssd_step") == 0.030


def test_the_ssm_share_reader_gives_nothing_without_its_scope(
        tmp_path, monkeypatch):
    """An untraced run, a session that left no op map, and maps in which no
    operation stands in an ``ssm`` scope (a program without such layers: the
    parent) all give None; with the scope, its operations' share, whatever
    part they fall in."""
    from perfbench import parts, spans, xplane
    read = mf.metric_reader("device.ssm_share.batch")
    assert read(types.SimpleNamespace(trace=None)) is None
    run = types.SimpleNamespace(trace={}, raw={"trace": {"dir": "x"}})
    monkeypatch.setattr(spans, "session_dir", lambda run: str(tmp_path))
    assert read(run) is None
    os.makedirs(tmp_path / "programs")

    def leave(scope):
        with open(tmp_path / "programs" / "worker-1.decode_step.json",
                  "w") as f:
            json.dump({"program": "decode_step", "maps": [{
                "module": "jit_fused_step", "instructions": {
                    "fusion.1": f"jit(f)/while/body/{scope}projections/dot",
                    "fusion.2": f"jit(f)/while/body/{scope}conv/mul",
                    "fusion.3": f"jit(f)/while/body/{scope}attention/ssm/"
                                f"mul",
                    "fusion.4": "jit(f)/while/body/ffn/dot"}}]}, f)

    monkeypatch.setattr(xplane, "find", lambda d: d)
    monkeypatch.setattr(xplane, "read", lambda p: {"devices": {"d0": {
        "modules": [(0.0, 10.0, "jit_fused_step(1)")],
        "ops": [(0.0, 2.0, "fusion.1"), (2.0, 3.0, "fusion.2"),
                (3.0, 4.0, "fusion.3"), (4.0, 10.0, "fusion.4")]}}})
    leave("")
    assert read(run) == pytest.approx(10.0)     # the recurrence's own scope
    leave("ssm/")
    assert read(run) == pytest.approx(40.0)
    # the parts still add up: the scope stands around them
    assert parts.place("jit(f)/ssm/projections/dot") == (
        "projections", "forward")
    assert parts.place("jit(f)/ssm/conv/mul") == ("conv", "forward")
    assert parts.place("jit(f)/ssm/attention/ssm/mul") == (
        "attention", "forward")


@pytest.mark.parametrize("trace", [1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest:
    the whole path through `serve.run` and the engine, prompts of 8-40
    tokens as padded chunks over rows AND states on all 3 layers.  The
    traced run finds the engine's ``cache:rows`` spans with the state the
    steps moved; the readers of the device trace find no device plane on the
    CPU and leave theirs out."""
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
        # every layer attends rows, and every layer moves a state
        assert got["cache.rows_read_share.mixed"]["value"] == \
            pytest.approx(100.0)
        # a slot's 3 states of 2624 B, read and written, beside 9-48 rows
        # of 128 B on the same 3 layers
        assert 40 < got["ssm.state_bytes_share.chat"]["value"] < 85
    for name in ("decode_step_roofline.chat", "device.ssm_share.batch",
                 "ssm_step_roofline.chat", "decode_step.device_ms.batch"):
        assert name not in got, name
