"""Family ``longcat_flash``: its counts against counts made by hand (at the
published widths) and against the program's own arithmetic; its
configuration, traffic and limits files against what they state and against
the catalog's entry; its plain reference against the program (`forward` and
`lm_loss` in float32; `forward` in bfloat16 under the rehearsal's limits with
the fp8 control failing them); `make`'s balanced and placed routers; the new
readers on hand-made runs; and the tiny cell rehearsed end to end.  The cached
programs, the router and the identity pairs against a loop, the shares of an
expert-parallel layer and the engine's counters against this family's
reference are tests/test_shortcut_moe.py's.

The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-longcat.json``, beside the rehearsal's (a
PR that changes the program adds files to the benchmark and edits none), so
the shared parametrised cases of test_perfbench_reference.py and
test_perfbench_rehearsal.py do not find it: they are called from here, on this
family.  The root manifest is looked at by MEMBERSHIP, never by a last entry,
a count or a whole list, so that the next cell does not fail this file.
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
from perfbench import reference, verdict, weights
from perfbench.tools import rehearse

import test_perfbench_reference as shared_reference
import test_perfbench_rehearsal as shared_rehearsal

TINY_MANIFEST = os.path.join(mf.ROOT, rehearse.REHEARSAL,
                             "BENCHMARK.tiny-longcat.json")
CELL = "tiny-longcat.serve-closed"
REAL_CELL = "longcat-flash-chat.serve-assist-closed"
NEW_METRICS = ("moe.zero_pairs_share.assist",
               "device.zero_experts_share.batch",
               "decode_step_roofline.assist")
FAULTS = ("identity_pairs_add_nothing", "routed_added_a_sublayer_early",
          "routed_from_the_second_norm", "weights_renormalised",
          "bias_in_the_weights", "softmax_over_real_experts_only",
          "kv_latent_not_rescaled", "second_sublayer_reads_the_firsts_rows")

# by hand, from the published config.json: d 6144; a sublayer 64 heads of 128
# + 64 | 128 over latents of 1536 | 512 and a dense SwiGLU of 12288; a layer
# two of them and a router of 512 + 256 outputs over experts of 2048
ATTN = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
        + 64 * 128 * 6144)
DENSE, EXPERT = 3 * 6144 * 12288, 3 * 6144 * 2048
ROUTER, NORMS = 6144 * 768, 2 * 6144 + 1536 + 512
OUTSIDE = 2 * (ATTN + DENSE + NORMS) + ROUTER + 768
ROW = 576 * 2                               # bf16 bytes a position a sublayer


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("longcat-flash-chat")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-longcat")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def make32(tiny):
    """The tiny configuration's float32 `make`, compiled ONCE for the
    module's cases that draw weights by a key."""
    c, fam = tiny
    return jax.jit(lambda k: fam.model._make(k, c=c, dtype=jnp.float32))


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    s = fam.shapes
    assert set(mf.FAMILY_INTERFACE["shapes"]) <= set(dir(s))
    assert set(mf.FAMILY_INTERFACE["model"]) <= set(dir(fam.model))
    assert ATTN == 90_570_752 == s.attention_params(c)
    assert DENSE == 226_492_416 == s.dense_params(c)
    assert EXPERT == 37_748_736 == s.expert_params(c)
    assert ROUTER == 4_718_592 == s.router_params(c)
    assert OUTSIDE == 638_874_368 == s.outside_experts(c)
    held = 4 * (OUTSIDE + 16 * EXPERT) + 2 * 16384 * 6144 + 6144
    assert s.count_params(c) == held == 5_172_749_312      # 10.35 GB bf16
    # the whole model, every expert and the whole vocabulary: the published
    # 560 B; its active range 18.6-31.3 B is 0 to 12 real experts a token
    whole = dict(c, num_layers=28, n_routed_experts=512, vocab_size=131072)
    assert round(s.count_params(whole) / 1e9, 1) == 560.7
    active = 28 * OUTSIDE + 2 * 131072 * 6144
    assert (round(active / 1e9, 1), round((active + 28 * 12 * EXPERT) / 1e9,
                                          1)) == (19.5, 32.2)
    assert (s.vocab(c), s.positions(c), s.sublayers(c), s.experts_routed(c),
            s.zero_experts(c), s.router_outputs(c)) == (
        16384, 131072, 8, 512, 256, 768)
    # a position of the cache: a latent row a SUBLAYER, two a published layer
    assert 2 * s.cache_row_values(c) == ROW
    assert 64 * 3584 * 8 * ROW == 2_113_929_216
    # of a token's 12 choices, the outputs chosen alike, a quarter of one
    # lands on the 16 held of 768
    assert s.real_experts_per_token(c) == 0.25
    weights_ = 4 * OUTSIDE + 16384 * 6144 + 6144
    assert s.decode_step_bytes(c, 64 * 1700, experts_touched=10.0) == \
        2.0 * (weights_ + 4 * 10.0 * EXPERT + 8 * 64 * 1700 * 576)
    assert s.decode_step_bytes(c, 1000) == \
        2.0 * (weights_ + 4 * 0.25 * EXPERT + 8 * 1000 * 576)
    assert s.train_flops_per_token(c, 4096) == 6.0 * (
        4 * (2 * (ATTN + DENSE) + ROUTER + 0.25 * EXPERT) + 16384 * 6144) \
        + 6.0 * 8 * 64 * 160 * 4096
    assert s.kernels(c, 2, 1024)["flash_attention"]["calls"] == 8


def test_counts_are_the_programs(real, tiny):
    """`count_params` of the program's own configuration and the leaves its
    initialiser would make (shapes alone at the real size), the tree the
    family makes, and the program's FLOP counts."""
    from ray_tpu.models import count_params, init_params
    from ray_tpu.models.generate import cache_rows, position_bytes
    from ray_tpu.models.transformer import flops_per_token
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
        assert position_bytes(cfg)["full"] == \
            2 * fam.shapes.cache_row_values(c)
        assert flops_per_token(cfg, 512) == \
            fam.shapes.train_flops_per_token(c, 512)
    c, fam = real
    cfg = fam.model.model_config(c, "serve")
    assert cfg.kinds == ("full",) * 8 and cfg.layer_runs == (("layers", 8),)
    assert (cfg.shortcut_moe, cfg.expert_layers, cfg.load_counts) == \
        (True, 4, 4)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_offset,
            cfg.zero_experts, cfg.expert_top_k, cfg.router,
            cfg.routed_scaling_factor) == (512, 16, 0, 256, 12,
                                           "softmax_bias", 6.0)
    assert cfg.latent_scales("full") == (2.0, 12 ** 0.5)
    assert (cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rope_base,
            cfg.ff_dim, cfg.expert_ff_dim) == (64, 1536, 512, 1e7, 12288,
                                               2048)
    assert cache_rows(cfg) == {"kv": (1, 576)}


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "longcat-flash-chat")
    cut = ["num_layers", "n_routed_experts", "vocab_size"]
    assert c["reduced"] == entry["reduced"] == cut
    assert entry["source"] == c["source"] and entry["file"].endswith(
        "configs/longcat-flash-chat.json")
    # every key of the catalog's entry is there, every width as published
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        pub = next(d for d in map(json.loads, f)
                   if d["name"] == "LongCat-Flash-Chat")
    assert pub["source_url"] == c["source"]
    assert c["published"] == pub["config"]
    differ = [k for k, v in pub["config"].items() if c[k] != v]
    assert sorted(differ) == sorted(cut) and set(c["changed"]) == set(cut)
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"],
            c["zero_expert_num"], c["moe_topk"]) == (4, 16, 16384, 256, 12)
    d = c["deployment"]
    assert (d["chips_sharing_a_layer"], d["pipeline_stage"],
            d["experts_routed"], d["expert_offset"],
            d["published_layers"]) == (32, "1 of 7", 512, 0, "0-3")
    assert d["experts_routed"] == pub["config"]["n_routed_experts"] \
        == 32 * c["n_routed_experts"]
    assert 8 * c["vocab_size"] == pub["config"]["vocab_size"]
    assert 7 * c["num_layers"] == pub["config"]["num_layers"]
    for key in ("router_form", "block_wiring", "zero_expert_type",
                "tie_word_embeddings", "mla_scale", "real_experts_per_token",
                "e_score_correction_bias", "expert_bias_balance_tokens",
                "expert_placement", "router_gain", "router_gain_why",
                "rotary_pairing", "weights"):
        assert key in c["assumed"], key
    assert len(c["departures"]) >= 4
    assert c["precision"]["serve"]["router"] == "float32"
    assert c["precision"]["serve"]["params"] == "bfloat16"


def test_traffic_and_limits_files_have_the_cells_parameters():
    m = mf.Manifest()
    t = m.traffic("serve-assist-closed")
    assert (t["kind"], t["clients"], t["requests_per_client"]) == \
        ("serve-closed", 64, 16)
    assert t["prompt_tokens"] == {"dist": "loguniform", "low": 512,
                                  "high": 3072}
    assert t["output_tokens"] == {"dist": "fixed", "value": 512}
    assert t["distinct_prompt_lengths"] == 64
    assert t["engine"] == {"max_slots": 64, "max_len": 3584}
    assert (t["settle_s"], t["trace_seconds"], t["check"]) == \
        (2.0, 12.0, {"sample_requests": 2})
    from perfbench.kinds import serve_common
    lengths = serve_common.prompt_lengths(t)
    # one length a caller, and every one fits with its output
    assert len(set(lengths)) == 64 and sorted(lengths)[:2] == [519, 534]
    assert min(lengths) >= 512 and max(lengths) + 512 <= 3584
    assert 1400 < sum(lengths) / 64 < 1460
    cell = m.cell(REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("longcat-flash-chat", "serve-assist-closed", 1)
    limits = m.limits(REAL_CELL)
    assert set(limits) == {"logit_err", "token_gap"}
    with open(os.path.join(mf.BENCH_DIR, "limits", REAL_CELL + ".json")) as f:
        body = json.load(f)
    assert {"limits", "readings", "how", "why"} <= set(body)
    faults = body["readings"]["planted_faults"]
    assert set(faults) >= set(FAULTS)
    # the control fails each limit; every planted fault fails a limit or
    # stands in ``why`` as unseen with its reading
    for name in FAULTS:
        seen = any(faults[name][k] > limits[k] for k in limits)
        assert seen or name in body["why"], name
    for k in limits:
        r = body["readings"][k]
        assert r["program_largest"] < limits[k] < r["control_smallest"]
    assert "branch_sizes" in body["readings"]


def test_the_reference_imports_nothing_of_the_programs_model_code():
    fam = mf.family("longcat_flash")
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
    # the program's configuration is made inside `model_config` alone; the
    # router, the identity part and the two-sublayer block are its own
    with open(fam.path("model")) as f:
        src = f.read()
    assert src.count("ray_tpu") == src.count("from ray_tpu.models import "
                                            "TransformerConfig") + \
        src.count("`ray_tpu.models.init_params`")
    assert "jax.nn.softmax(jnp.einsum(\"sd,de->se\"" in src
    assert "top_k(p +" in src and "def published_layer(" in src


def test_reference_is_the_programs_function_in_float32(tiny, make32):
    """`forward` and `lm_loss` against the family's reference, each ONE
    program (eagerly its six sublayers are hundreds of small ones)."""
    from ray_tpu.models import forward, lm_loss
    c, fam = tiny
    model = fam.model
    key = weights.key_of(5)
    params = make32(key)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 48), c)
    cfg = dataclasses.replace(
        model.model_config(c, "train", attention_impl="reference",
                           remat=False),
        dtype=jnp.float32, param_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(forward, cfg=cfg))(params, toks)
        lost = jax.jit(functools.partial(lm_loss, cfg=cfg))(
            params, {"tokens": toks})
    want = model.logits(params, toks, c)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert int(toks.max()) < fam.shapes.vocab(c)
    assert abs(float(lost - model.loss(params, toks, c))) < 1e-5
    assert 4.5 < float(lost) < 8.0      # ln 256 = 5.5 over random tokens


@pytest.mark.parametrize("seed", shared_reference.SEEDS[:1])
def test_serving_program_passes_and_fp8_control_fails(tiny, seed):
    """test_perfbench_reference.py's case under this family's limits, but
    for its last line: at width 64 a bfloat16 score that changes one of a
    token's three outputs moves a logit more than all rounding does, so the
    control stands 1.5-17 times above the program, not always the 3 the
    shared case asks for.  The limits sit between the two over 8 seeds (the
    limits file)."""
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
    got = jax.jit(functools.partial(forward, cfg=cfg))(
        params, toks).reshape(-1, v)
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


def test_weights_come_from_the_seed_alone(make32):
    a, b, other = (make32(weights.key_of(s))
                   for s in (2**31 + 5, 2**31 + 5, 5))
    tree_map, leaves = jax.tree_util.tree_map, jax.tree_util.tree_leaves
    same, differs = jax.jit(lambda a, b, other: (
        tree_map(lambda x, y: (x == y).all(), a, b),
        tree_map(lambda x, y: (x != y).any(), a, other)))(a, b, other)
    assert all(bool(x) for x in leaves(same))
    assert any(bool(x) for x in leaves(differs))


def test_the_bias_is_balanced_and_the_experts_placed_by_load(tiny):
    """`make` with calibration tokens (the real configuration's
    ``assumed.expert_bias_balance_tokens``; the tiny one states none and draws
    its routers as they fall): on FRESH tokens every routing sublayer's
    busiest output draws under twice its target share where the drawn bias
    leaves it several, the mean of REAL experts a token stands near the
    target of 2 of 3, and the chip's 4 held experts of 8 draw about half the
    real pairs on every layer."""
    c, fam = tiny
    model = fam.model
    toks = model.tokens(jax.random.PRNGKey(61), (384,), c)

    def loads(n):
        cc = dict(c, assumed=dict(c["assumed"],
                                  expert_bias_balance_tokens=n))

        def counted(key):       # (one program: weights, then the walk)
            return model._walk(model._make(key, cc, jnp.float32), toks, cc,
                               "float32")[1]["load"]
        out = []
        for x in jax.jit(counted)(weights.key_of(2)):
            real, zero = x[:8], x[8:]
            out.append((float(real[:4].sum() / real.sum()),
                        float(real.max() / (384 * 2 / 8)),
                        float(real.sum() / 384),
                        float(zero.max() / (384 * 1 / 4))))
        return out

    drawn, balanced = loads(0), loads(512)
    assert len(drawn) == len(balanced) == 3
    assert max(max(m, z) for _, m, _, z in balanced) < 2.0 \
        < max(max(m, z) for _, m, _, z in drawn)
    assert all(1.6 < mean < 2.4 for _, _, mean, _ in balanced), balanced
    assert all(0.38 < share < 0.62 for share, _, _, _ in balanced), balanced


def test_tiny_manifest_and_the_roots_have_no_problem():
    tiny = _tiny_manifest()
    assert mf.problems(tiny) == []
    assert set(NEW_METRICS) <= {x["name"] for x in tiny.data["per_layer"]}
    root = mf.Manifest()
    assert mf.problems(root) == []
    assert os.path.getsize(root.path) < 64 * 1024
    # by membership, never by count, position or a whole list
    assert "longcat-flash-chat" in [c["name"] for c in root.data["configs"]]
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
    assert {"moe.experts_touched.agent", "expert_matmul.device_share.agent",
            "device.share.experts.batch", "device.share.ffn.batch",
            "device.idle_share.batch", "decode_step.device_ms.batch",
            "cache.rows_read_share.mixed", "hbm_peak_gb.batch",
            "setup.warmup_s"} <= reported
    assert {x["name"] for x in root.metrics_for(REAL_CELL, False)} == {
        "serve_tok_s", "setup_s"}


def _spans_run(events):
    return types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                 _ring_spans=events)


def test_the_zero_pairs_reader_on_hand_made_spans():
    read = mf.metric_reader("moe.zero_pairs_share.assist")
    assert read(_spans_run([])) is None
    # no such key (a sigmoid router; the parent): nothing, no raise
    assert read(_spans_run([{"name": "moe:load", "ts": 1e6, "dur": 2e6,
                             "args": {"steps": 5, "pairs": 100, "layers": 4,
                                      "experts": 16}}])) is None
    ours = _spans_run([
        {"name": "moe:load", "ts": 1e6, "dur": 2e6, "args": {
            "steps": 10, "pairs": 20, "chosen": 3072, "zero_pairs": 1000}},
        {"name": "moe:load", "ts": 3e6, "dur": 2e6, "args": {
            "steps": 10, "pairs": 20, "chosen": 3072, "zero_pairs": 1048}},
        {"name": "moe:load", "ts": 44e6, "dur": 2e6, "args": {   # ends late
            "steps": 1, "chosen": 1, "zero_pairs": 1}},
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "chosen": 10 ** 9}}])
    assert read(ours) == pytest.approx(100 * 2048 / 6144)


def test_the_roofline_reader_on_a_hand_made_run(real, monkeypatch):
    """The family's floor at the run's mean batch, its counted experts and
    the mean depth its slots stood at, over the HBM peak, over the step's
    device time; nothing where there is no trace, no ``moe:load`` span, or a
    family that counts no identity experts."""
    from perfbench import moe_load, readers
    c, fam = real
    read = mf.metric_reader("decode_step_roofline.assist")
    req = types.SimpleNamespace(prompt=[0] * 1_500, tokens=[0] * 2,
                                arrivals=[(1.0, 2)])

    def run(family, trace={"programs": {}}):
        return types.SimpleNamespace(
            trace=trace, family=family, config=c,
            raw={"requests": [req], "counters": {
                "before": {"steps": 0, "tokens": 0},
                "after": {"steps": 10, "tokens": 640}}},
            peaks=lambda: {"hbm_bytes_per_s": 819e9})

    monkeypatch.setattr(readers, "program_ms",
                        lambda run, pattern: None if run.trace is None
                        else 15.0)
    touched = [10.0]
    monkeypatch.setattr(moe_load, "experts_touched_per_layer_step",
                        lambda run: touched[0])
    got = read(run(fam))
    floor = fam.shapes.decode_step_bytes(c, 64 * 1_500.5,
                                         experts_touched=10.0)
    assert got == pytest.approx(100 * floor / 819e9 / 0.015)
    assert 40 < got < 100
    assert read(run(fam, trace=None)) is None
    assert read(run(mf.family("gpt2"))) is None
    assert read(run(mf.family("glm4_moe_lite"))) is None    # no identity
    touched[0] = None
    assert read(run(fam)) is None


def test_the_scope_reader_gives_nothing_without_its_scope(real, tmp_path,
                                                          monkeypatch):
    """An untraced run, a session that left no op map, and maps in which no
    operation stands in a ``zero_experts`` scope (a router without identity
    outputs: the parent) all give None; with the scope, its operations' share
    of all programs' device time, and the operations still count among
    ``experts``."""
    from perfbench import parts, spans, xplane
    share = mf.metric_reader("device.zero_experts_share.batch")
    assert share(types.SimpleNamespace(trace=None)) is None
    run = types.SimpleNamespace(trace={"programs": {}},
                                raw={"trace": {"dir": "x"}})
    monkeypatch.setattr(spans, "session_dir", lambda run: str(tmp_path))
    assert share(run) is None
    os.makedirs(tmp_path / "programs")
    body = "jit(fused_step)/while/body/closed_call/%s"

    def leave(scope):
        with open(tmp_path / "programs" / "worker-1.decode_step.json",
                  "w") as f:
            json.dump({"program": "decode_step", "maps": [{
                "module": "jit_fused_step", "instructions": {
                    "fusion.1": body % ("experts/" + scope + "mul"),
                    "fusion.2": body % "experts/dot_general",
                    "fusion.3": body % "ffn/dot"}}]}, f)

    monkeypatch.setattr(xplane, "find", lambda d: d)
    monkeypatch.setattr(xplane, "read", lambda p: {"devices": {"d0": {
        "modules": [(0.0, 10.0, "jit_fused_step(1)")],
        "ops": [(0.0, 0.05, "fusion.1"), (1.0, 4.0, "fusion.2"),
                (4.0, 10.0, "fusion.3")]}}})
    leave("")
    assert share(run) is None
    leave("zero_experts/")
    assert share(run) == pytest.approx(100 * 0.05 / 10.0)
    assert parts.place(body % "experts/zero_experts/mul")[0] == "experts"


@pytest.mark.parametrize("trace", [1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest: the
    whole path through `serve.run` and the engine, prompts of 8-40 tokens as
    padded chunks and lanes over six latent rows a position.  The traced run
    finds the engine's ``moe:load`` spans with the identity pairs; the
    readers of the device trace find no device plane on the CPU and leave
    theirs out."""
    lines = []

    def rehearsed(*a, **kw):
        lines.extend(rehearse_cell(*a, manifest_path=TINY_MANIFEST, **kw))
        return lines

    rehearse_cell = rehearse.rehearse
    monkeypatch.setattr(rehearse, "manifest", _tiny_manifest)
    monkeypatch.setattr(rehearse, "rehearse", rehearsed)
    shared_rehearsal.test_cell_kind_rehearsed_on_the_cpu(CELL, trace)
    got = lines[-1]["metrics"]
    # (the engine writes a ``moe:load`` span every two seconds: on a loaded
    # machine none may END inside a window of three, and the span readers
    # then leave their metrics out)
    if "moe.zero_pairs_share.assist" in got:
        # 4 of 12 outputs are identity experts and the drawn bias is wide
        assert 10 < got["moe.zero_pairs_share.assist"]["value"] < 60
        assert got["moe.experts_touched.agent"]["value"] > 0
    for name in ("decode_step_roofline.assist",
                 "device.zero_experts_share.batch",
                 "decode_step.device_ms.batch"):
        assert name not in got, name
