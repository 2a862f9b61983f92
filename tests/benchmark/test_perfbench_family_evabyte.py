"""Family ``evabyte``: its counts against counts made by hand (at the
published widths) and against the program's own arithmetic; its
configuration, traffic and limits files against what they state; its plain
reference against the program (`forward` and `lm_loss` in float32, `forward`
in bfloat16 under the rehearsal's limits with the fp8 control failing them)
and against plain attention in the equations' two limits; the three new
readers on hand-made runs; and the tiny cell rehearsed end to end.  The
mathematics of the cached programs over the fourth state kind is
tests/test_eva_attention.py's.

The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-evabyte.json``, beside the rehearsal's (a
PR that changes the program adds files to the benchmark and edits none), so
the shared parametrised cases of test_perfbench_reference.py and
test_perfbench_rehearsal.py do not find it: they are called from here, on
this family.  The root manifest is looked at by MEMBERSHIP, never by a last
entry or a count, so that the next cell does not fail this file.
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
from perfbench import weights
from perfbench.tools import rehearse

import test_perfbench_reference as shared_reference
import test_perfbench_rehearsal as shared_rehearsal

TINY_MANIFEST = os.path.join(mf.ROOT, rehearse.REHEARSAL,
                             "BENCHMARK.tiny-evabyte.json")
CELL = "tiny-evabyte.serve-closed"
REAL_CELL = "evabyte.serve-bytedoc-closed"
NEW_METRICS = ("decode_step_roofline.bytedoc",
               "cache.summary_bytes_share.bytedoc",
               "device.summary_share.batch")

# by hand, from the published config.json: d 4096, 32 heads of 128 (MHA),
# a SwiGLU of 11008, 320 ids under 8 prediction heads
LAYER = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
OUTSIDE = 320 * 4096 + 4096 * 8 * 320 + 4096
ROW = 32 * 128 * 2 * 2            # a key and a value of every head, bf16


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("evabyte")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-evabyte")
    return c, mf.family_of(c)


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    s = fam.shapes
    assert LAYER == 202_391_552 and s.layer_params(c) == LAYER
    assert s.count_params(c) == 8 * LAYER + OUTSIDE == 1_630_932_992
    assert s.count_params_published(c) == 32 * LAYER + OUTSIDE \
        == 6_488_330_240 == c["published"]["parameters"]
    assert (s.vocab(c), s.positions(c), s.head_dim(c)) == (320, 32768, 128)
    assert 2 * s.cache_row_values(c) == ROW == 16_384
    # a query at depth t: its window's rows up to itself, a summary a chunk
    # of the windows before
    assert s.attended_rows(c, 0) == (1, 0)
    assert s.attended_rows(c, 2047) == (2048, 0)
    assert s.attended_rows(c, 2048) == (1, 128)
    assert s.attended_rows(c, 17_000) == (617, 1024)
    # a decode step: every weight but the embedding table once; of the cache
    # what the slots attend.  Twelve slots that stand at 17,000
    weights_ = 8 * LAYER + 4096 * 8 * 320 + 4096
    assert s.decode_step_bytes(c, 12 * 17_000, depths=[17_000]) == \
        2.0 * weights_ + 8 * 12 * (617 + 1024) * ROW
    # ... at two depths: the mean of what each attends, slots by the mean
    assert s.decode_step_bytes(c, 12 * 9_524, depths=[2_048, 17_000]) == \
        2.0 * weights_ + 8 * 12 * ((1 + 128) + (617 + 1024)) / 2 * ROW
    # without depths: the least any slots with those positions attend
    assert s.decode_step_bytes(c, 160_000) == \
        2.0 * weights_ + 8 * 10_000 * ROW
    per_tok = 8 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 2560
    assert s.train_flops_per_token(c, 4096) == 6.0 * per_tok \
        + 6.0 * 8 * 32 * 128 * 2 * (1024 + 1024 / 16)
    assert s.kernels(c, 1, 4096) == {}


def test_counts_are_the_programs(real, tiny):
    """`count_params` of the program's own configuration and the leaves its
    initialiser would make (shapes alone at the real size), and the tree
    the family makes."""
    from ray_tpu.models import count_params, init_params
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
    c, fam = real
    cfg = fam.model.model_config(c, "serve")
    assert count_params(cfg) == 1_630_932_992
    assert count_params(dataclasses.replace(
        cfg, n_layers=32, layer_kinds=("eva",) * 32)) == 6_488_330_240
    assert (cfg.head_dim, cfg.sliding_window, cfg.summary_chunk,
            cfg.window_chunk, cfg.logit_size) == (128, 2048, 16, 128, 2560)
    assert cfg.norm_unit_offset and cfg.fp32_residual and cfg.fp32_logits
    assert cfg.stream_dtype == jnp.float32 and cfg.dtype == jnp.bfloat16


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "evabyte")
    assert c["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == c["source"] and entry["file"].endswith(
        "configs/evabyte.json")
    # every key of the catalog's entry is there, and only depth differs
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        pub = next(d for d in map(json.loads, f)
                   if d["name"] == "EvaByte")
    assert pub["source_url"] == c["source"]
    differ = [k for k, v in pub["config"].items() if c[k] != v]
    assert differ == ["num_hidden_layers"] and set(c["changed"]) == {
        "num_hidden_layers"}
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"]) \
        == (8, 32)
    d = c["deployment"]
    assert (d["pipeline_stages"], d["stage"], d["layers_published"]) == \
        (4, 0, 32)
    assert d["pipeline_stages"] * c["num_hidden_layers"] == 32
    # every assumed value has its reason beside it
    a = c["assumed"]
    for key in ("phi_std", "mu_std", "query_gain", "attention_out_gain",
                "norm_gain_std", "keys_rotated_before_pooling",
                "pooling_logits_unscaled", "rotary_pairs", "head_columns"):
        assert key in a, key
    assert sum(k.startswith("why") for k in a) >= 7
    assert c["precision"]["serve"]["residual_adds"] == "float32"


def test_traffic_and_limits_files_have_the_cells_parameters():
    m = mf.Manifest()
    t = m.traffic("serve-bytedoc-closed")
    assert (t["kind"], t["clients"], t["requests_per_client"]) == \
        ("serve-closed", 12, 16)
    assert t["prompt_tokens"] == {"dist": "loguniform", "low": 4096,
                                  "high": 24576}
    assert t["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert t["distinct_prompt_lengths"] == 12
    assert t["engine"] == {"max_slots": 12, "max_len": 26624}
    assert (t["settle_s"], t["trace_seconds"], t["check"]) == \
        (2.0, 12.0, {"sample_requests": 2})
    from perfbench.kinds import serve_common
    lengths = serve_common.prompt_lengths(t)
    # one length a caller; every prompt passes two windows and fits
    assert len(set(lengths)) == 12 and min(lengths) >= 2 * 2048
    assert max(lengths) + 1024 <= 26624 and 26624 % 2048 == 0
    cell = m.cell(REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("evabyte", "serve-bytedoc-closed", 1)
    limits = m.limits(REAL_CELL)
    assert set(limits) == {"logit_err", "token_gap"}
    with open(os.path.join(mf.BENCH_DIR, "limits", REAL_CELL + ".json")) as f:
        body = json.load(f)
    assert {"limits", "readings", "how", "why"} <= set(body)


def test_the_reference_imports_nothing_of_the_programs_model_code():
    fam = mf.family("evabyte")
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
    # the program's configuration is made inside `model_config` alone
    with open(fam.path("model")) as f:
        src = f.read()
    assert src.count("ray_tpu") == src.count("from ray_tpu.models import "
                                            "TransformerConfig") + \
        src.count("`ray_tpu.models.init_params`")


def test_loss_is_the_references(tiny):
    """Every prediction head's cross entropy, head p against the byte 1 + p
    positions on."""
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
    assert 3.0 < float(got) < 6.0          # about ln 40 over random bytes


@pytest.mark.parametrize("limit", ["window_holds_all", "chunk_of_one"])
def test_the_references_two_limits_are_plain_attention(tiny, limit):
    """The reference's own attention against softmax(QK^T)V written out
    here: with a window that holds the sequence no summary is visible, and
    with chunks of one and no offset a summary is its token."""
    c, fam = tiny
    model = fam.model
    if limit == "window_holds_all":
        c = dict(c, window_size=64)
    else:
        c = dict(c, chunk_size=1, assumed=dict(c["assumed"], mu_std=0.0))
    key = weights.key_of(9)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 64), c)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(model.hidden, c=c))(params, toks)

        def plain_layer(x, lp):
            y = model._rms(x, lp["attn_norm"], c["rms_norm_eps"])
            pos = jnp.arange(64)
            q = model._rotate(jnp.einsum("bsd,dhe->bshe", y, lp["wq"]), pos,
                              float(c["rope_theta"]))
            k = model._rotate(jnp.einsum("bsd,dhe->bshe", y, lp["wk"]), pos,
                              float(c["rope_theta"]))
            v = jnp.einsum("bsd,dhe->bshe", y, lp["wv"])
            z = jnp.einsum("bqhe,bkhe->bhqk", q, k) / 4.0
            z = jnp.where(pos[:, None] >= pos[None, :], z, -jnp.inf)
            o = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(z, -1), v)
            h = x + jnp.einsum("bshe,hed->bsd", o, lp["wo"])
            y = model._rms(h, lp["mlp_norm"], c["rms_norm_eps"])
            gate = y @ lp["w_gate"]
            return h + (jax.nn.silu(gate) * (y @ lp["w_in"])) @ lp["w_out"]

        @jax.jit
        def plain(params, toks):
            x = params["embed"]["tok"][toks]
            for i in range(c["num_hidden_layers"]):
                x = plain_layer(x, jax.tree_util.tree_map(
                    lambda a: a[i], params["layers"]))
            return model._rms(x, params["final_norm"], c["rms_norm_eps"])

        want = plain(params, toks)
    assert float(jnp.abs(got - want).max()) < 2e-4


def test_the_draws_are_the_files(real, tiny):
    """phi at a scale at which a chunk's pooling weights are visibly
    uneven, mu and the norms' g not zero, the gains where the file says."""
    assert real[0]["assumed"]["phi_std"] == 0.133
    c, fam = tiny
    p = fam.model.make(weights.key_of(4), c, jnp.float32)
    lay = p["layers"]
    assert lay["adaptive_phi"].shape == lay["adaptive_mu_k"].shape \
        == (2, 4, 16)
    assert float(lay["adaptive_phi"].std()) == pytest.approx(0.375, rel=0.2)
    assert float(lay["adaptive_mu_k"].std()) == pytest.approx(0.5, rel=0.2)
    assert float(lay["attn_norm"].std()) == pytest.approx(0.1, rel=0.2)
    assert float(lay["wq"].std()) == pytest.approx(2 / 8, rel=0.05)
    assert float(lay["wk"].std()) == pytest.approx(1 / 8, rel=0.05)
    assert float(lay["wo"].std()) == pytest.approx(2 / 8, rel=0.05)
    assert float(p["embed"]["tok"].std()) == pytest.approx(1.0, rel=0.05)
    assert p["lm_head"].shape == (64, 3 * 40)
    # a chunk's pooling weights at that phi: the largest several times the
    # even share
    k = jax.random.normal(jax.random.PRNGKey(0), (512, 4, 16))
    a = jax.nn.softmax(jnp.einsum("nce,e->nc", k,
                                  lay["adaptive_phi"][0, 0]), -1)
    assert float(a.max(-1).mean()) > 2 / 4


def test_tiny_manifest_and_the_roots_have_no_problem():
    assert mf.problems(_tiny_manifest()) == []
    root = mf.Manifest()
    assert mf.problems(root) == []
    # by membership, never by count or position
    assert "evabyte" in [c["name"] for c in root.data["configs"]]
    assert REAL_CELL in [w["name"] for w in root.data["workloads"]]
    assert REAL_CELL in next(x for x in root.data["end_to_end"]
                             if x["name"] == "serve_tok_s")["workloads"]
    per_layer = {x["name"]: x for x in root.data["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [REAL_CELL]
        assert per_layer[name]["moves"] == "serve_tok_s"
    reported = {x["name"] for x in root.metrics_for(REAL_CELL, True)}
    assert set(NEW_METRICS) <= reported and len(reported) >= 30
    assert not {n for n in reported if n.startswith("moe.")}
    assert sum(w["chips"] == 4 for w in root.data["workloads"]) == 1


def _spans_run(events):
    return types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                 _ring_spans=events)


def test_the_summary_bytes_reader_on_hand_made_spans():
    read = mf.metric_reader("cache.summary_bytes_share.bytedoc")
    assert read(_spans_run([])) is None
    parent = _spans_run([{"name": "cache:rows", "ts": 1e6, "dur": 2e6,
                          "args": {"steps": 10, "bytes_read": 100,
                                   "bytes_if_uniform": 700}}])
    assert read(parent) is None         # no summary key: nothing, no raise
    ours = _spans_run([
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_read": 100, "summary_bytes_read": 30}},
        {"name": "cache:rows", "ts": 3e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_read": 60, "summary_bytes_read": 18}},
        {"name": "cache:rows", "ts": 44e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_read": 1, "summary_bytes_read": 1}}])
    assert read(ours) == 30.0
    # the cell's own arithmetic: at depth 17,000 a slot reads 617 ring rows
    # and 1,024 summary rows
    got = read(_spans_run([{"name": "cache:rows", "ts": 1e6, "dur": 1e6,
                            "args": {"bytes_read": (617 + 1024) * ROW,
                                     "summary_bytes_read": 1024 * ROW}}]))
    assert got == pytest.approx(62.4, abs=0.05)


def test_the_roofline_reader_on_a_hand_made_run(real):
    """The family's floor at the run's mean batch over the depths its slots
    stood at, over the HBM peak, over the step's device time; nothing where
    there is no trace, and nothing from a family without depths."""
    c, fam = real
    read = mf.metric_reader("decode_step_roofline.bytedoc")
    req = types.SimpleNamespace(prompt=[0] * 17_000, tokens=[0] * 2,
                                arrivals=[(1.0, 2)])
    trace = {"programs": {}}

    def run(family, trace=trace):
        return types.SimpleNamespace(
            trace=trace, family=family, config=c,
            raw={"requests": [req], "counters": {
                "before": {"steps": 0, "tokens": 0},
                "after": {"steps": 10, "tokens": 120}}},
            peaks=lambda: {"hbm_bytes_per_s": 819e9})

    from perfbench import readers
    ms = 12.0
    orig = readers.program_ms
    readers.program_ms = lambda run, pattern: None if run.trace is None \
        else ms
    try:
        got = read(run(fam))
        floor = fam.shapes.decode_step_bytes(
            c, 12 * 17_000.5, depths=[17_000, 17_001])
        assert got == pytest.approx(100 * floor / 819e9 / 0.012)
        assert 40 < got < 100
        assert read(run(fam, trace=None)) is None
        assert read(run(mf.family("gpt2"))) is None
    finally:
        readers.program_ms = orig


def test_the_summary_share_reader_gives_nothing_without_its_scope(
        tmp_path, monkeypatch):
    """An untraced run, a session that left no op map, and maps in which no
    operation stands in a ``summary`` scope (a program without summaries:
    the parent) all give None; with the scope, its operations' share."""
    from perfbench import parts, spans, xplane
    read = mf.metric_reader("device.summary_share.batch")
    assert read(types.SimpleNamespace(trace=None)) is None
    run = types.SimpleNamespace(trace={}, raw={"trace": {"dir": "x"}})
    monkeypatch.setattr(spans, "session_dir", lambda run: str(tmp_path))
    assert read(run) is None
    os.makedirs(tmp_path / "programs")
    path = "jit(fused_step)/while/body/cache_write/%sdot_general"

    def leave(scope):
        with open(tmp_path / "programs" / "worker-1.decode_step.json",
                  "w") as f:
            json.dump({"program": "decode_step", "maps": [{
                "module": "jit_fused_step", "instructions": {
                    "fusion.1": path % scope,
                    "fusion.2": "jit(fused_step)/while/body/ffn/dot"}}]}, f)

    leave("")
    assert read(run) is None
    leave("summary/")
    monkeypatch.setattr(xplane, "find", lambda d: d)
    monkeypatch.setattr(xplane, "read", lambda p: {"devices": {"d0": {
        "modules": [(0.0, 10.0, "jit_fused_step(1)")],
        "ops": [(0.0, 2.0, "fusion.1"), (2.0, 10.0, "fusion.2")]}}})
    assert read(run) == pytest.approx(20.0)
    assert parts.place(path % "summary/") == ("cache_write", "forward")


@pytest.mark.parametrize("trace", [1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest:
    the whole path through `serve.run` and the engine, prompts of 8-40
    bytes as padded chunks of 8 over rings of 40 rows and 32 summary rows,
    through the first window's edge.  The traced run finds the engine's
    ``cache:rows`` spans with the summary bytes; the readers of the device
    trace find no device plane on the CPU and leave theirs out."""
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
    # loaded machine none may END inside a window of three, and both
    # readers then leave their metric out)
    if "cache.bytes_read_share.longreason" in got:
        # contexts of 9-48 rows: past 32 a slot reads 8 summary rows beside
        # the 1-16 of its window; before, none
        assert 5 < got["cache.summary_bytes_share.bytedoc"]["value"] < 40
        # ... fewer bytes than full layers would
        assert 40 < got["cache.bytes_read_share.longreason"]["value"] < 100
    for name in ("decode_step_roofline.bytedoc",
                 "device.summary_share.batch",
                 "prefill_chunk.device_ms.agent"):
        assert name not in got, name


def test_reference_is_the_programs_function_in_float32(tiny):
    c, fam = tiny
    shared_reference.test_reference_is_the_programs_function_in_float32(
        (c, fam.model))


@pytest.mark.parametrize("seed", shared_reference.SEEDS[:2])
def test_serving_program_passes_and_fp8_control_fails(tiny, seed,
                                                      monkeypatch):
    c, fam = tiny
    monkeypatch.setattr(shared_reference, "_limits",
                        lambda cell: _tiny_manifest().limits(cell))
    shared_reference.test_serving_program_passes_and_fp8_control_fails(
        (c, fam.model), seed)


def test_weights_come_from_the_seed_alone(tiny):
    c, fam = tiny
    shared_reference.test_weights_come_from_the_seed_alone((c, fam.model))
