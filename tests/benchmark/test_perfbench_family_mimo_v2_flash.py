"""Family ``mimo_v2_flash``: its counts against counts made by hand (at the
published widths and at the rehearsal's tiny size) and against the program's
own arithmetic; its configuration, traffic and limits files against what
they state; its plain reference against the program (`forward` and the
gradient of `lm_loss` in float32, `forward` in bfloat16 under the rehearsal's
limits with the fp8 control failing them); the expert layer's sixteen shares against the uncut layer; the new
reader on hand-made spans; and the tiny cell rehearsed end to end.  The
mathematics of the cached programs over the two cache shapes is
tests/test_mixed_kv_heads.py's.

The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-mimo.json``, beside the rehearsal's (a
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
import pytest

from perfbench import manifest as mf
from perfbench import reference, weights
from perfbench.tools import rehearse

import test_perfbench_reference as shared_reference
import test_perfbench_rehearsal as shared_rehearsal

TINY_MANIFEST = os.path.join(mf.ROOT, rehearse.REHEARSAL,
                             "BENCHMARK.tiny-mimo.json")
CELL = "tiny-mimo.serve-closed"
REAL_CELL = "mimo-v2-flash.serve-longreason-closed"
NEW_METRIC = "cache.bytes_read_share.longreason"

# by hand, from the published config.json: d 4096, 64 query heads of 192,
# values of 128, 4 key-value heads in a full layer and 8 in a window layer
W_Q, W_O = 4096 * 64 * 192, 64 * 128 * 4096
FULL = W_Q + 4096 * 4 * 192 + 4096 * 4 * 128 + W_O
WINDOW = W_Q + 4096 * 8 * 192 + 4096 * 8 * 128 + W_O + 64       # + sinks
EXPERT = 3 * 4096 * 2048
ROUTER = 4096 * 256 + 256
NORMS = 2 * 4096
EMBED = 19072 * 4096
AS_RUN = (FULL + 3 * 4096 * 16384 + NORMS) \
    + 5 * (WINDOW + 16 * EXPERT + ROUTER + NORMS) \
    + (FULL + 16 * EXPERT + ROUTER + NORMS) + 2 * EMBED + 4096


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("mimo-v2-flash")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-mimo")
    return c, mf.family_of(c)


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    s = fam.shapes
    assert (FULL, WINDOW) == (89_128_960, 94_371_904)
    assert (s.attention_params(c, s.FULL), s.attention_params(c, s.WINDOW)) \
        == (FULL, WINDOW)
    assert s.attention_matmuls(c, s.WINDOW) == 94_371_840
    assert s.expert_params(c) == EXPERT == 25_165_824
    assert s.count_params(c) == AS_RUN == 3_429_955_392
    assert s.layer_counts(c) == (5, 2) and s.expert_layers(c) == 6
    assert s.rotated_dims(c) == 64 and s.experts_routed(c) == 256
    assert (s.vocab(c), s.positions(c)) == (19072, 262144)
    # a position: 2560 B on a full layer, 5120 on a window layer
    assert (2 * s.cache_row_values(c, s.FULL),
            2 * s.cache_row_values(c, s.WINDOW)) == (2560, 5120)
    # a decode step: the weights outside the routed experts and the
    # embedding, the head among them, the counted experts a layer, the live
    # rows of 2 full layers and at most 128 rows of 5 window layers
    outside = AS_RUN - 6 * 16 * EXPERT - EMBED
    rows = 32 * 5000
    assert s.decode_step_bytes(c, rows, experts_touched=10.25) == 2.0 * (
        outside + 6 * 10.25 * EXPERT) + 2 * rows * 2560 + 5 * 128 * 5120
    assert s.decode_step_bytes(c, 100) == 2.0 * outside \
        + 2 * 100 * 2560 + 5 * 100 * 5120
    # a token's 8 experts: a sixteenth of them is held here
    per_tok = 2 * FULL + 5 * (WINDOW - 64) + 3 * 4096 * 16384 \
        + 6 * (4096 * 256 + 0.5 * EXPERT) + EMBED
    assert s.train_flops_per_token(c, 4096) == 6.0 * per_tok \
        + 6.0 * 64 * 160 * (2 * 4096 + 5 * 256)
    assert s.kernels(c, 1, 4096) == {}


def test_counts_are_the_programs(real, tiny):
    """`count_params` of the program's own configuration and the leaves
    its initialiser would make (shapes alone at the real size)."""
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
    assert count_params(cfg) == 151_276
    c, fam = real
    cfg = fam.model.model_config(c, "serve")
    assert count_params(cfg) == 3_429_955_392
    assert (cfg.head_dim, cfg.value_dim, cfg.rope_dim) == (192, 128, 64)
    assert cfg.layer_runs == (("dense_layers", 1), ("layers", 6))


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "mimo-v2-flash")
    assert sorted(c["reduced"]) == sorted(entry["reduced"]) == sorted([
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"])
    assert entry["source"] == c["source"] and entry["file"].endswith(
        "configs/mimo-v2-flash.json")
    # every published key is there, and only the reduced ones differ
    pub = c["published"]
    differs = {k for k, v in pub.items() if c[k] != v}
    assert differs == set(c["reduced"]) == set(c["changed"])
    assert (pub["num_hidden_layers"], c["num_hidden_layers"]) == (48, 7)
    # published layer 0, then ONE WHOLE PERIOD: published layers 6-11
    assert c["hybrid_layer_pattern"] == pub["hybrid_layer_pattern"][:1] \
        + pub["hybrid_layer_pattern"][6:12] == [0, 1, 1, 1, 1, 1, 0]
    assert c["moe_layer_freq"] == pub["moe_layer_freq"][:1] \
        + pub["moe_layer_freq"][6:12]
    assert (pub["n_routed_experts"], c["n_routed_experts"]) == (256, 16)
    assert c["vocab_size"] * 8 == pub["vocab_size"]
    d = c["deployment"]
    assert (d["chips_sharing_a_layer"], d["experts_routed"],
            d["expert_offset"], d["window_chunk"]) == (16, 256, 0, 128)
    for key in ("stands_for", "experts_held", "vocabulary_held", "cache",
                "load"):
        assert len(d[key]) >= 15, key
    a = c["assumed"]
    assert a["expert_bias_balance_tokens"] == 8192 and \
        a["expert_bias_std"] > 0 and a["sink_std"] > 0
    for key in ("sink", "value_scale", "rotated_dims", "window_edge",
                "norms", "expert_bias", "expert_placement", "weights"):
        assert len(a[key]) > 40, key
    assert len(c["departures"]) >= 4
    assert c["precision"]["serve"]["params"] == "bfloat16" and \
        c["precision"]["serve"]["router"] == "float32"


def test_traffic_and_limits_files_have_the_cells_parameters():
    m = mf.Manifest()
    cell = m.cell(REAL_CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "mimo-v2-flash", "serve-longreason-closed")
    assert "1/16 load" in cell["why"] and len(cell["why"]) <= 200
    t = m.traffic(cell["traffic"])
    assert t["kind"] == "serve-closed" and "draft" not in t["engine"]
    assert (t["clients"], t["requests_per_client"],
            t["distinct_prompt_lengths"]) == (32, 16, 32)
    assert t["prompt_tokens"] == {"dist": "loguniform", "low": 2048,
                                  "high": 8192}
    assert t["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert t["engine"] == {"max_slots": 32, "max_len": 8192 + 1024 + 512}
    assert (t["settle_s"], t["trace_seconds"],
            t["check"]["sample_requests"]) == (2.0, 12.0, 2)
    from perfbench.kinds import serve_common
    lengths = serve_common.prompt_lengths(t)
    assert len(set(lengths)) == 32 and 2048 <= min(lengths) \
        and max(lengths) <= 8192                   # ONE length a caller
    with open(os.path.join(mf.BENCH_DIR, "limits", REAL_CELL + ".json")) as f:
        body = json.load(f)
    assert body["limits"] == m.limits(REAL_CELL)
    for name, limit in body["limits"].items():
        r = body["readings"][name]
        assert r["program_seeds"] >= 8 and r["control_seeds"] >= 3
        assert r["program_largest"] < limit < r["control_smallest"], name
    assert len(body["why"]) > 40 and len(body["how"]) > 40
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "limits",
                           CELL + ".json")) as f:
        body = json.load(f)
    for name, limit in body["limits"].items():
        r = body["readings"][name]
        assert r["program_max"] < limit < r["control_min"], name


def test_the_reference_imports_nothing_of_the_programs_model_code():
    """The family's model.py names the program in `model_config` alone."""
    with open(mf.family("mimo_v2_flash").path("model")) as f:
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
    with open(mf.family("mimo_v2_flash").path("shapes")) as f:
        assert "jax" not in {a.name.split(".")[0]
                             for n in ast.walk(ast.parse(f.read()))
                             if isinstance(n, ast.Import) for a in n.names}


def _f32(model, c, **kw):
    return dataclasses.replace(
        model.model_config(c, "serve", attention_impl="reference", **kw),
        dtype=jnp.float32, param_dtype=jnp.float32)


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        tiny):
    """The guide's share test at the real deployment's counts: a router
    256 wide, 8 a token, 16 chips with 16 experts each, at a tiny width.
    The parts that the sixteen shares give (each computed by the PROGRAM's
    expert layer told which experts it holds; there is no shared expert to
    count once) add up to the uncut layer, the program's with all 256
    experts held and the reference's; nothing stands in for absent ones."""
    from ray_tpu.models.transformer import _ffn
    c, fam = tiny
    model = fam.model
    whole = dict(c, n_routed_experts=256, num_experts_per_tok=8,
                 moe_intermediate_size=8,
                 assumed=dict(c["assumed"], expert_bias_std=0.1),
                 deployment=dict(c["deployment"], experts_routed=256,
                                 expert_offset=0))
    params = model.make(weights.key_of(9), whole, jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"])
    assert lp["router"].shape == (64, 256) and lp["w_in"].shape == (256, 64,
                                                                    8)
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    r = reference._round_inputs("float32")
    with jax.default_matmul_precision("highest"):
        uncut_ref = model.routed_part(r, y, lp, whole, 0, 256)
        uncut, _, load = _ffn(_f32(model, whole), y, lp)
        assert int(load[2]) == 2 * 24 * 8
        assert float(jnp.abs(uncut - uncut_ref).max()) < 1e-5
        total, landed, touched = 0.0, 0, 0
        for chip in range(16):
            mine = dict(whole, n_routed_experts=16, deployment=dict(
                whole["deployment"], expert_offset=16 * chip))
            lp_mine = dict(lp, **{k: lp[k][16 * chip:16 * chip + 16]
                                  for k in ("w_in", "w_gate", "w_out")})
            part, _, load = _ffn(_f32(model, mine), y, lp_mine)
            want = model.routed_part(r, y, lp_mine, mine, 16 * chip, 16)
            assert float(jnp.abs(part - want).max()) < 1e-5
            total, landed = total + part, landed + int(load[2])
            touched += int(load[0])
        # every pair landed on exactly one chip
        assert landed == 2 * 24 * 8 and touched == int(
            (model.expert_weights(y, lp, whole) > 0).any((0, 1)).sum())
        assert float(jnp.abs(total - uncut_ref).max()) < 1e-5
        # a share alone is NOT the layer: the other chips' part is left out
        assert float(jnp.abs(part - uncut_ref).max()) > 1e-2


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
    # the bias is a constant of the loss; the sinks are learned
    assert float(jnp.abs(g_got["layers"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g_got["layers"]["sink"]).min()) > 0.0
    # every layer's own key projection took part, of either kind
    for name in ("wk", "wk_win", "wv", "wv_win"):
        assert float(jnp.abs(g_got["layers"][name]).max(
            axis=(1, 2, 3)).min()) > 0.0, name


def test_the_drawn_bias_is_balanced_and_the_experts_placed(tiny):
    """`make` with calibration tokens: the experts of every expert layer
    meet about their even share of the pairs where the bias as drawn sends
    most pairs to a few, the chip's own experts theirs, and the result is
    the seed's alone."""
    c, fam = tiny
    model = fam.model
    drawn = dict(c, assumed=dict(c["assumed"], expert_bias_std=0.1))
    even = dict(drawn, assumed=dict(drawn["assumed"],
                                    expert_bias_balance_tokens=512))
    key = weights.key_of(2**31 + 3)
    toks = model.tokens(jax.random.fold_in(key, 99), (2, 256), c)
    worst, mine, made = {}, {}, {}
    for name, conf in (("drawn", drawn), ("even", even)):
        params = made[name] = model.make(key, conf, jnp.float32)
        shares = []

        def count(scores, lp):
            _, chosen = jax.lax.top_k(scores + lp["router_bias"], 2)
            load = jnp.zeros((8,)).at[chosen.reshape(-1)].add(1.0)
            shares.append(load / load.sum() * 8)
            return {}

        model._walk(params, toks, conf, "float32", count)
        assert len(shares) == 4                      # the expert layers
        worst[name] = float(jnp.stack(shares).max())
        # experts 2-3 are this chip's: a quarter of the pairs is their share
        mine[name] = [float(s[2:4].sum() / 2) for s in shares]
    assert worst["even"] < 1.6 < worst["drawn"], worst
    assert all(0.6 < x < 1.5 for x in mine["even"]), mine
    # everything but the routers is the same weights
    a, b = made["drawn"]["layers"], made["even"]["layers"]
    assert bool((a["w_in"] == b["w_in"]).all())
    assert not bool((a["router_bias"] == b["router_bias"]).all())


def test_sinks_and_the_embedding_are_the_files(real, tiny):
    assert real[0]["assumed"]["sink_mean"] == 4.0
    c, fam = tiny
    c = dict(c, assumed=dict(c["assumed"], sink_mean=3.0, sink_std=0.5))
    params = fam.model.make(weights.key_of(4), c, jnp.float32)
    sink = params["layers"]["sink"]
    assert sink.shape == (3, 4) and "sink" not in params["dense_layers"]
    assert 2.0 < float(sink.mean()) < 4.0 and float(sink.std()) > 0.1
    assert float(params["embed"]["tok"].std()) == pytest.approx(1.0, rel=0.05)
    assert float(params["lm_head"].std()) == pytest.approx(1 / 8, rel=0.05)


def test_tiny_manifest_and_the_roots_have_no_problem():
    m = _tiny_manifest()
    assert mf.problems(m) == []
    assert CELL in [w["name"] for w in m.data["workloads"]]
    assert NEW_METRIC in {x["name"] for x in m.data["per_layer"]}
    root = mf.Manifest()
    assert mf.problems(root) == []
    cells = {w["name"]: w for w in root.data["workloads"]}
    assert cells[REAL_CELL]["chips"] == 1
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    assert "mimo-v2-flash" in {x["name"] for x in root.data["configs"]}
    listed = {x["name"] for x in root.data["per_layer"]
              if REAL_CELL in x.get("workloads", ())}
    for name in (NEW_METRIC, "moe.experts_touched.agent",
                 "decode_step_roofline.agent",
                 "prefill_chunk.device_ms.agent",
                 "engine.prefill_share.agent",
                 "expert_matmul.device_share.agent",
                 "device.share.experts.batch", "moe.rows_per_expert.reason",
                 "moe.load_max_over_mean.mixed",
                 "cache.rows_read_share.mixed",
                 "cache.ring_bytes_share.mixed", "hbm_peak_gb.batch",
                 "decode_step.device_ms.batch", "device.share.unnamed.batch",
                 "engine.host_ms_per_step.batch", "compiles_in_window",
                 "setup.weights_s", "engine.idle_pct.readback.batch"):
        assert name in listed, name
    # what does not apply, and the two whose lists a test of their own PR
    # pins (test_perfbench_family_glm4_moe_lite.py, _engine_ahead.py)
    for name in ("moe.load_max_over_mean.agent", "engine.ahead_share.batch",
                 "decode_step_roofline.batch",
                 "cache.state_bytes_share.reason", "device.share.conv.batch"):
        assert name not in listed, name
    new = next(x for x in root.data["per_layer"] if x["name"] == NEW_METRIC)
    assert new == {"name": NEW_METRIC, "unit": "%", "better": "lower",
                   "source": "program_span", "layer": "model programs",
                   "moves": "serve_tok_s", "workloads": [REAL_CELL]}
    assert REAL_CELL in next(x for x in root.data["end_to_end"]
                             if x["name"] == "serve_tok_s")["workloads"]


def test_the_new_reader_on_hand_made_spans():
    """``bytes_read`` over ``bytes_if_uniform`` of the window's spans,
    summed; a program whose spans lack the keys (the parent) gives the
    reader nothing, and it raises nothing."""
    def run(events):
        return types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                     _ring_spans=events)

    read = mf.metric_reader(NEW_METRIC)
    assert read(run([])) is None
    parent = run([{"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
        "steps": 10, "rows_read": 300, "rows_if_full": 400,
        "bytes_full": 100, "bytes_ring": 300}}])
    assert read(parent) is None
    ours = run([
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_read": 100, "bytes_if_uniform": 700}},
        {"name": "cache:rows", "ts": 3e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_read": 60, "bytes_if_uniform": 300}},
        {"name": "cache:rows", "ts": 44e6, "dur": 2e6, "args": {
            "steps": 10, "bytes_read": 1, "bytes_if_uniform": 1}},  # late
        {"name": "moe:load", "ts": 1e6, "dur": 2e6, "args": {
            "steps": 5, "bytes_read": 5, "bytes_if_uniform": 5}}])
    assert read(ours) == 16.0
    # the cell's own arithmetic: at depth c a step reads 2 x 2560 c + 5 x
    # 5120 x 128 of the 7 x 5120 c that uniform layers would
    for depth, share in ((3000, 17.3), (9000, 15.3)):
        got = read(run([{"name": "cache:rows", "ts": 1e6, "dur": 1e6,
                         "args": {"steps": 1, "bytes_read":
                                  2 * 2560 * depth + 5 * 5120 * 128,
                                  "bytes_if_uniform": 7 * 5120 * depth}}]))
        assert got == pytest.approx(share, abs=0.05)


@pytest.mark.parametrize("trace", [1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest:
    the whole path through `serve.run` and the engine, prompts of 8-40
    tokens as padded chunks of 8 (the ring's room) over two cache shapes.
    The traced run also finds the engine's ``cache:rows`` spans with the
    bytes by kind, and the readers of the device trace find no device
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
        # 3 rings of 12 rows x 80 B beside 2 full layers of 128 x 40 B
        assert got["cache.ring_bytes_share.mixed"]["value"] == \
            pytest.approx(100 * 3 * 12 * 80 / (3 * 12 * 80 + 2 * 128 * 40))
        # contexts of 9-48 rows: 2 layers' rows and 3 windows of 4
        rows = got["cache.rows_read_share.mixed"]["value"]
        assert 40 < rows < 65
        # ... the full layers' rows at half a ring's: 20 points less
        assert got[NEW_METRIC]["value"] == pytest.approx(rows - 20.0)
        for name in ("decode_step_roofline.agent",
                     "prefill_chunk.device_ms.agent",
                     "engine.prefill_share.agent",
                     "expert_matmul.device_share.agent"):
            assert name not in got, name


def test_reference_is_the_programs_function_in_float32(tiny):
    c, fam = tiny
    shared_reference.test_reference_is_the_programs_function_in_float32(
        (c, fam.model))


@pytest.mark.parametrize("seed", shared_reference.SEEDS[:2])
def test_serving_program_passes_and_fp8_control_fails(tiny, seed):
    c, fam = tiny
    shared_reference.test_serving_program_passes_and_fp8_control_fails(
        (c, fam.model), seed)


def test_weights_come_from_the_seed_alone(tiny):
    c, fam = tiny
    shared_reference.test_weights_come_from_the_seed_alone((c, fam.model))
