"""Family ``phi4flash``: its counts against counts made by hand (at the
published widths) and against the program's own arithmetic; its
configuration, traffic and limits files against what they state and against
the catalog's entry; its plain reference (the scan as the RECURRENCE,
attention dense a key-value pair at a time, every layer on every row) against
the program (`forward` in float32; `forward` in bfloat16 under the rehearsal's
limits with the fp8 control failing them); the six new readers on hand-made
runs; and the tiny cell rehearsed end to end.  The cached programs over the
shared rows, the eighth state kind and the stateless tail are
tests/test_shared_cache.py's; the ops' tests/test_selective_scan.py's and
tests/test_diff_attention.py's.

The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-phi4flash.json``; the root manifest is
looked at by MEMBERSHIP, never by a last entry, a count or a whole list, so
that the next cell does not fail this file.
"""

import ast
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
                             "BENCHMARK.tiny-phi4flash.json")
CELL = "tiny-phi4flash.serve-closed"
REAL_CELL = "phi-4-mini-flash.serve-deepreason-closed"
NEW_METRICS = ("cache.shared_bytes_share.deepreason",
               "prefill.tail_rows_share.deepreason",
               "device.cross_share.batch", "device.gmu_share.batch",
               "selective_scan_roofline.deepreason",
               "decode_step_roofline.deepreason")

# by hand, from the published config.json and the class's defaults: d 2560;
# a mixer of 5120 channels, state 16, conv 4 with a bias, step rank 160; 40
# query heads of 64 over 20 key heads (10 rows of 128) with biases; a SwiGLU
# of 10240; two LayerNorms with a bias a layer
D, E, FF, V = 2560, 5120, 10240, 200064
MAMBA = (D * 2 * E + E * 4 + E + E * (160 + 32) + 160 * E + E + 16 * E + E
         + E * D)
ROWS = D * 1280 * 2 + 1280 * 2                  # keys, values, their biases
CROSS = 2 * (D * D + D) + 4 * 64 + 128          # q, o, lambdas, the norm
GMU = 2 * D * E
FFN = 3 * D * FF
NORMS = 4 * D
STATE = 16 * E * 4 + 3 * E * 2                  # a mamba layer's, a sequence
ROW = 2 * 20 * 64 * 2                           # layer 17's, a position


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("phi-4-mini-flash")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-phi4flash")
    return c, mf.family_of(c)


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    sh = fam.shapes
    kinds = sh.layer_kinds(c)
    assert kinds[:4] == ["mamba", "window", "mamba", "window"]
    assert kinds[14:20] == ["mamba", "window", "mamba", "full", "gmu",
                            "cross"]
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert sh.sizes(c) == {"inner": E, "state": 16, "conv": 4,
                           "dt_rank": 160}
    assert sh.mamba_params(c) == MAMBA
    assert sh.attention_params(c, "cross") == CROSS
    assert sh.attention_params(c, "full") == CROSS + ROWS
    total = (9 * MAMBA + 9 * (CROSS + ROWS) + 7 * CROSS + 7 * GMU
             + 32 * (FFN + NORMS) + V * D + 2 * D)
    assert sh.count_params(c) == total == 3_852_562_944
    assert sh.state_bytes(c) == STATE == 358_400
    assert sh.cache_row_values(c) * 2 == ROW == 5120
    assert sh.shared_row_readers(c) == 8
    # a step at 40 slots of depth 3900: weights once (the table is the
    # head), the one array eight times, 8 rings of 512, 9 states twice
    floor = sh.decode_step_bytes(c, 40 * 3900, depths=[3900])
    assert floor == 2 * total + 8 * 40 * 3900 * ROW + 8 * 40 * 512 * ROW \
        + 2 * 40 * 9 * STATE
    assert floor == pytest.approx(15.19e9, rel=1e-3)
    # shallower than the window a ring reads the depth
    assert sh.decode_step_bytes(c, 100, depths=[100]) == 2 * total \
        + 16 * 100 * ROW + 2 * 9 * STATE
    k = sh.kernels(c, 4, 128)["selective_scan_chunk"]
    assert k["calls"] == 9
    assert k["chunk_flops"] == 6.0 * 4 * 128 * 16 * E
    # a, dt and m [128, 5120]; keys and queries a lane tile a token; the
    # state in and out a row; A and D once: all float32
    assert k["chunk_bytes"] == 4.0 * (4 * (3 * 128 * E + 2 * 128 * 16 * 128
                                           + 2 * 16 * E) + 16 * E + E)
    assert 40e6 < k["chunk_bytes"] < 45e6


def test_counts_are_the_programs(real, tiny):
    from ray_tpu.models.generate import cache_rows, position_bytes
    from ray_tpu.models.transformer import count_params
    for c, fam in (real, tiny):
        cfg = fam.model.model_config(c, "serve")
        assert fam.shapes.count_params(c) == count_params(cfg)
        assert list(cfg.kinds) == fam.shapes.layer_kinds(c)
        per = position_bytes(cfg)
        assert per["full"] == per["ring"] == \
            fam.shapes.cache_row_values(c) * 2
        assert per["mamba"] == fam.shapes.state_bytes(c)
        assert cfg.stateless_tail == sum(
            k in ("gmu", "cross") for k in cfg.kinds)
    cfg = real[1].model.model_config(real[0], "serve")
    assert cache_rows(cfg)["k"] == cache_rows(cfg)["v"] == (10, 128)
    assert cfg.stateless_tail == 14 and cfg.sliding_window == 512


def test_configuration_file_states_that_nothing_was_cut(real):
    c, _ = real
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Phi-4-mini-flash-reasoning")
    assert c["source"] == entry["source_url"]
    assert c["published"] == entry["config"]
    assert c["reduced"] == []
    for key, value in entry["config"].items():      # every key as published
        assert c[key] == value, key
    assert c["deployment"]["stages"] == 1 \
        and c["deployment"]["chips_sharing_a_layer"] == 1
    assert set(c["assumed"]["sizes"]) == {
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank"}
    assert not set(c["assumed"]["sizes"]) & set(entry["config"])
    root = mf.Manifest().data
    mine = next(x for x in root["configs"] if x["name"] == c["name"])
    assert mine["reduced"] == [] and mine["source"] == c["source"]
    assert 1 <= len(mine["why"]) <= 200
    assert 1 <= len(mf.Manifest().cell(REAL_CELL)["why"]) <= 200


def test_traffic_and_limits_files_have_the_cells_parameters():
    m = mf.Manifest()
    t = m.traffic("serve-deepreason-closed")
    assert t["kind"] == "serve-closed" and t["clients"] == 40
    assert t["prompt_tokens"] == {"dist": "loguniform", "low": 1024,
                                  "high": 8192}
    assert t["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert t["distinct_prompt_lengths"] == 40
    assert t["requests_per_client"] == 16
    assert t["engine"] == {"max_slots": 40, "max_len": 9728}
    assert (t["settle_s"], t["trace_seconds"],
            t["check"]["sample_requests"]) == (2.0, 12.0, 1)
    limits = m.limits(REAL_CELL)
    assert set(limits) == {"logit_err", "token_gap"}
    assert all(0 < x < 1 for x in limits.values())


def test_the_reference_imports_nothing_of_the_programs_model_code():
    fam = mf.family("phi4flash")
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
    with open(fam.path("model")) as f:
        src = f.read()
    assert src.count("from ray_tpu") == 1 == src.count(
        "from ray_tpu.models import TransformerConfig")
    assert "jax.lax.scan(one, jnp.zeros(A.shape, F32)" in src
    assert "pallas" not in src and "cumsum" not in src


def test_reference_is_the_programs_function_in_float32(tiny):
    c, fam = tiny
    shared_reference.test_reference_is_the_programs_function_in_float32(
        (c, fam.model))


@pytest.mark.parametrize("seed", shared_reference.SEEDS[:2])
def test_serving_program_passes_and_fp8_control_fails(tiny, seed):
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
    p = fam.model.make(weights.key_of(3), c, jnp.float32)["layers"]
    # what decides how long a state remembers is the stated draw
    np.testing.assert_allclose(np.exp(p["mamba_a_log"][0, :, 0]),
                               np.arange(1.0, 9.0), rtol=1e-5)
    dt = jax.nn.softplus(p["mamba_dt_b"])
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11
    for name in ("bq", "bk", "bv", "bo", "mamba_conv_b"):
        assert 0.05 < float(p[name].std()) < 0.2, name
    # each operator's weights over ITS layers alone
    assert p["mamba_in"].shape[0] == 2 == p["gmu_in"].shape[0]
    assert p["wq"].shape[0] == 4 and p["wk"].shape[0] == 2


def test_tiny_manifest_and_the_roots_have_no_problem():
    assert mf.problems(_tiny_manifest()) == []
    root = mf.Manifest()
    assert mf.problems(root) == []
    # by membership, never by count, position or a whole list
    assert "phi-4-mini-flash" in [c["name"] for c in root.data["configs"]]
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
            "device.ssm_share.batch", "decode_step.device_ms.batch",
            "setup.warmup_s"} <= reported
    assert not {"moe.experts_touched.agent", "ssm_step_roofline.chat",
                "decode_step_roofline.chat"} & reported
    assert {x["name"] for x in root.metrics_for(REAL_CELL, False)} == {
        "serve_tok_s", "setup_s"}


def _spans_run(events):
    return types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                 _ring_spans=events)


def test_the_span_readers_on_hand_made_spans():
    shared = mf.metric_reader("cache.shared_bytes_share.deepreason")
    tail = mf.metric_reader("prefill.tail_rows_share.deepreason")
    assert shared(_spans_run([])) is None and tail(_spans_run([])) is None
    # a model whose every row set is read by its holders: no such byte
    other = _spans_run([
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "bytes_read": 100, "state_bytes_moved": 300}},
        {"name": "engine:lanes", "ts": 1e6, "dur": 2e6, "args": {
            "programs": 3, "chunks": 5}}])
    assert shared(other) is None and tail(other) is None
    # the cell's own arithmetic: a slot at depth 3900 reads the one array
    # on 8 layers, 512 ring rows on 8, and moves 9 states twice
    at = _spans_run([
        {"name": "cache:rows", "ts": 1e6, "dur": 1e6, "args": {
            "bytes_read": 8 * 3900 * ROW + 8 * 512 * ROW,
            "shared_bytes_read": 8 * 3900 * ROW,
            "state_bytes_moved": 2 * 9 * STATE}},
        {"name": "cache:rows", "ts": 44e6, "dur": 2e6, "args": {  # ends late
            "bytes_read": 1, "shared_bytes_read": 1}},
        {"name": "engine:lanes", "ts": 1e6, "dur": 2e6, "args": {
            "programs": 3, "chunks": 9, "rows_fed": 2 * 512 + 128,
            "tail_rows": 2 * 4 + 1}}])
    assert shared(at) == pytest.approx(85.35, abs=0.01)
    assert tail(at) == pytest.approx(100 / 128)


def _run(c, family, trace={"programs": {}}, **counters):
    req = types.SimpleNamespace(prompt=[0] * 3900, tokens=[0] * 2,
                                arrivals=[(1.0, 2)])
    after = dict({"steps": 10, "tokens": 400, "prefill_chunks": 30,
                  "program_shapes": ["decode_step:40",
                                     "prefill_chunk:1x128",
                                     "prefill_chunk:4x128"]}, **counters)
    return types.SimpleNamespace(
        trace=trace, family=family, config=c,
        stamps={"open": 0.0, "close": 45.0},
        _ring_spans=[{"name": "engine:lanes", "ts": 1e6, "dur": 2e6,
                      "args": {"programs": 10, "chunks": 30}}],
        raw={"requests": [req], "counters": {
            "before": dict.fromkeys(after, 0), "after": after}},
        peaks=lambda: {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})


def test_the_roofline_readers_on_hand_made_runs(real, monkeypatch):
    from perfbench import readers
    c, fam = real
    whole = mf.metric_reader("decode_step_roofline.deepreason")
    monkeypatch.setattr(readers, "program_ms",
                        lambda run, pattern: None if run.trace is None
                        else 22.0)
    got = whole(_run(c, fam))
    floor = fam.shapes.decode_step_bytes(c, 40 * 3900.5,
                                         depths=[3900, 3901])
    assert got == pytest.approx(100 * floor / 819e9 / 0.022)
    assert 50 < got < 100
    assert whole(_run(c, fam, trace=None)) is None
    assert whole(_run(c, mf.family("falcon_h1"))) is None
    assert whole(_run(c, mf.family("gpt2"))) is None
    kernel = mf.metric_reader("selective_scan_roofline.deepreason")
    trace = {"programs": {"jit_prefill_chunk": {
        "count": 5, "device_s": 0.1, "mean_gap_s": None}},
        "ops": {"tpu_custom_call:selective_scan_chunk.3": 0.010,
                "fusion.7": 0.05, "tpu_custom_call:ssd_step.1": 1.0}}
    got = kernel(_run(c, fam, trace=trace))
    # 3 live lanes a program of 128 rows, 9 calls, memory bound
    least = 9 * fam.shapes.kernels(c, 3.0, 128)[
        "selective_scan_chunk"]["chunk_bytes"] / 819e9
    assert got == pytest.approx(100 * least / (0.010 / 5))
    assert 0 < got < 100
    assert kernel(_run(c, fam, trace=None)) is None
    xla = dict(trace, ops={"fusion.7": 0.05})       # XLA's form of the scan
    assert kernel(_run(c, fam, trace=xla)) is None
    other = types.SimpleNamespace(shapes=types.SimpleNamespace(
        kernels=lambda *a: {"ssd_step": {}}))   # a family without the kernel
    assert kernel(_run(c, other, trace=trace)) is None


@pytest.mark.parametrize("scope", ["cross", "gmu"])
def test_the_scope_readers_give_nothing_without_their_scope(
        tmp_path, monkeypatch, scope):
    from perfbench import parts, spans, xplane
    read = mf.metric_reader(f"device.{scope}_share.batch")
    assert read(types.SimpleNamespace(trace=None)) is None
    run = types.SimpleNamespace(trace={}, raw={"trace": {"dir": "x"}})
    monkeypatch.setattr(spans, "session_dir", lambda run: str(tmp_path))
    assert read(run) is None
    os.makedirs(tmp_path / "programs")

    def leave(around):
        with open(tmp_path / "programs" / "worker-1.decode_step.json",
                  "w") as f:
            json.dump({"program": "decode_step", "maps": [{
                "module": "jit_fused_step", "instructions": {
                    "fusion.1": f"jit(f)/while/body/{around}projections/dot",
                    "fusion.2": f"jit(f)/while/body/{around}attention/mul",
                    "fusion.3": "jit(f)/while/body/attention/diff/mul",
                    "fusion.4": "jit(f)/while/body/ffn/dot"}}]}, f)

    monkeypatch.setattr(xplane, "find", lambda d: d)
    monkeypatch.setattr(xplane, "read", lambda p: {"devices": {"d0": {
        "modules": [(0.0, 10.0, "jit_fused_step(1)")],
        "ops": [(0.0, 2.0, "fusion.1"), (2.0, 3.0, "fusion.2"),
                (3.0, 4.0, "fusion.3"), (4.0, 10.0, "fusion.4")]}}})
    leave("")
    assert read(run) is None        # a program without such layers
    leave(scope + "/")
    assert read(run) == pytest.approx(30.0)
    # the parts still add up: the scope stands around them
    assert parts.place(f"jit(f)/{scope}/projections/dot") == (
        "projections", "forward")
    assert parts.place(f"jit(f)/{scope}/attention/diff/mul") == (
        "attention", "forward")


@pytest.mark.parametrize("trace", [1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest: the
    whole path through `serve.run` and the engine, prompts of 8-40 tokens as
    padded chunks of 32 over ONE layer of rows, a ring and two states, the
    tail on one row.  The traced run finds the engine's spans; the readers
    of the device trace find no device plane on the CPU and leave theirs
    out."""
    lines = []

    def rehearsed(*a, **kw):
        lines.extend(rehearse_cell(*a, manifest_path=TINY_MANIFEST, **kw))
        return lines

    rehearse_cell = rehearse.rehearse
    monkeypatch.setattr(rehearse, "manifest", _tiny_manifest)
    monkeypatch.setattr(rehearse, "rehearse", rehearsed)
    shared_rehearsal.test_cell_kind_rehearsed_on_the_cpu(CELL, trace)
    got = lines[-1]["metrics"]
    # (the engine writes a span every two seconds: on a loaded machine none
    # may END inside a window of three, and its readers then leave theirs
    # out)
    if "cache.shared_bytes_share.deepreason" in got:
        # 3 layers read the one array at depths of 9-48; 1 ring of 8; 2
        # states of 4864 B twice
        assert 15 < got["cache.shared_bytes_share.deepreason"]["value"] < 60
    if "prefill.tail_rows_share.deepreason" in got:
        assert got["prefill.tail_rows_share.deepreason"]["value"] == \
            pytest.approx(100 / 32)
    for name in ("decode_step_roofline.deepreason",
                 "device.cross_share.batch", "device.gmu_share.batch",
                 "selective_scan_roofline.deepreason",
                 "decode_step.device_ms.batch"):
        assert name not in got, name
