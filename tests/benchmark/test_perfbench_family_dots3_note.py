"""Family ``dots3_note``: its counts against counts made by hand (at the
published widths) and against the program's own arithmetic; its
configuration, traffic and limits files against what they state and against
the catalog's entry; its plain reference against the program (`forward` and
`lm_loss` in float32; `forward` in bfloat16 under the rehearsal's limits with
the fp8 control failing them); the SIXTEEN shares of an expert layer, the
shared expert counted once, against the uncut reference's layer; the new
readers on hand-made runs; and the tiny cell rehearsed end to end.  The
cached programs over the ring of latents (chunks across the seam, lanes,
slots, the engine, the kernel, the planted faults) against this family's
reference are tests/test_window_latent.py's.

The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-dots3-note.json``, beside the
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
import pytest

from perfbench import manifest as mf
from perfbench import reference, verdict, weights
from perfbench.tools import rehearse

import test_perfbench_reference as shared_reference
import test_perfbench_rehearsal as shared_rehearsal

TINY_MANIFEST = os.path.join(mf.ROOT, rehearse.REHEARSAL,
                             "BENCHMARK.tiny-dots3-note.json")
CELL = "tiny-dots3-note.serve-closed"
REAL_CELL = "dots3-note-prev.serve-notes-closed"
NEW_METRICS = ("device.window_latent_share.batch",
               "cache.ring_latent_bytes_share.notes",
               "decode_step_roofline.notes",
               "latent_attention_ring_roofline.notes")
FULL, SLIDING = "full_attention", "sliding_attention"

# by hand, from the published config.json: d 5120; a full layer 128 heads of
# 128 + 64 | 128 over latents of 1024 | 512, an indexer of 64 heads of 128; a
# sliding layer 64 heads of 192 + 64 | 128 over latents of 1024 | 1024; a gate
# a head; a dense SwiGLU of 13824; experts of 1536, 256 routed
ATTN_FULL = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
             + 128 * 128 * 5120 + 5120 * 128)
ATTN_WIN = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
            + 64 * 128 * 5120 + 5120 * 64)
INDEXER = 1024 * 64 * 128 + 5120 * (128 + 64) + 2 * 128
EXPERT = 3 * 5120 * 1536
NORMS_FULL, NORMS_WIN = 2 * 5120 + 1024 + 512, 2 * 5120 + 1024 + 1024
MOE = EXPERT + 5120 * 256 + 256         # of an expert layer, outside the
#   routed experts and the operator: the shared expert, the router, its bias
FULL_OP, WIN_OP = ATTN_FULL + NORMS_FULL + INDEXER, ATTN_WIN + NORMS_WIN
ROW, RING_ROW, KEY = 576 * 2, 1088 * 2, 128 * 2     # bf16 bytes a position


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("dots3-note-prev")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-dots3-note")
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
    assert ATTN_FULL == 134_676_480 == s.attention_params(c, FULL)
    assert ATTN_WIN == 90_832_896 == s.attention_params(c, SLIDING)
    assert INDEXER == 9_371_904 == s.indexer_params(c)
    assert EXPERT == 23_592_960 == s.expert_params(c)
    outside = (FULL_OP + 3 * 5120 * 13824) + 2 * (FULL_OP + MOE) \
        + 6 * (WIN_OP + MOE)
    held = outside + 8 * 16 * EXPERT + 2 * 19008 * 5120 + 5120
    assert s.count_params(c) == held == 4_603_365_632      # 9.21 GB bf16
    assert (s.vocab(c), s.positions(c), s.layers(c), s.experts_routed(c),
            s.kind_layers(c, FULL), s.kind_layers(c, SLIDING)) == (
        19008, 524288, (1, 8), 256, 3, 6)
    # a position of the cache: a latent row and an index key on a full
    # layer, a wider latent row on a sliding layer, which holds 768 of them
    assert 2 * s.cache_row_values(c, FULL) == ROW
    assert 2 * s.cache_row_values(c, SLIDING) == RING_ROW
    assert s.ring_rows(c) == 768
    assert 16 * 17408 * 3 * (ROW + KEY) == 1_176_502_272    # rows and keys
    assert 16 * 6 * 768 * RING_ROW == 160_432_128           # the rings
    # a slot at depth t must read min(t, 2048) latents and t keys on 3
    # layers, min(t, 513) ring rows on 6
    assert {k: 2 * v for k, v in s.attended_values(c, 100).items()} == {
        "full": 3 * 100 * ROW, "index": 3 * 100 * KEY,
        "ring": 6 * 100 * RING_ROW}
    deep = {k: 2 * v for k, v in s.attended_values(c, 10_000).items()}
    assert deep == {"full": 3 * 2048 * ROW, "index": 3 * 10_000 * KEY,
                    "ring": 6 * 513 * RING_ROW}
    # a decode step: every weight outside the routed experts but the
    # embedding table once, the touched experts, what the slots must read
    weights_ = outside + 19008 * 5120 + 5120
    assert s.decode_step_bytes(c, 16 * 10_000, experts_touched=6.0,
                               depths=[10_000]) == \
        2.0 * (weights_ + 8 * 6.0 * EXPERT) + 16 * sum(deep.values())
    # ... at two depths: the mean of what each reads, slots by the mean
    assert s.decode_step_bytes(c, 16 * 5_050, experts_touched=6.0,
                               depths=[100, 10_000]) == \
        2.0 * (weights_ + 8 * 6.0 * EXPERT) + 16 * (
            100 * (3 * (ROW + KEY) + 6 * RING_ROW) + sum(deep.values())) / 2
    # without depths or counted experts: one slot at all the rows, the
    # share held of a token's eight experts
    assert s.decode_step_bytes(c, 160_000) == \
        2.0 * (weights_ + 8 * 0.5 * EXPERT) + 3 * 2048 * ROW \
        + 3 * 160_000 * KEY + 6 * 513 * RING_ROW
    # the widened kernel: one step's call at 16 live slots past the window
    k = s.kernels(c, 16, 1)["latent_attention_ring"]
    assert k == {"step_flops": 2.0 * 16 * 64 * 513 * (1088 + 1024),
                 "step_bytes": 2.0 * 16 * (513 * 1088 + 64 * (1088 + 1024)),
                 "calls": 6}
    active_moe = 5120 * 256 + (0.5 + 1) * EXPERT
    assert s.train_flops_per_token(c, 8192) == 6.0 * (
        3 * ATTN_FULL + 6 * ATTN_WIN + 3 * INDEXER + 3 * 5120 * 13824
        + 8 * active_moe + 19008 * 5120) + 6.0 * (
        3 * 128 * 320 * 2048.0 + 6 * 64 * 384 * 513.0) \
        + 6.0 * 3 * 64 * 128 * 4096


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
        assert (per["full"], per["ring"]) == tuple(
            2 * fam.shapes.cache_row_values(c, k) for k in (FULL, SLIDING))
        assert per["index"] == 2 * c["index_head_dim"]
    c, fam = real
    cfg = fam.model.model_config(c, "serve")
    assert cfg.kinds == ("index", "index") + ("window",) * 3 + ("index",) \
        + ("window",) * 3
    assert cfg.layer_runs == (("dense_layers", 1), ("layers", 8))
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.sliding_window) == (64, 128, 2048, 513)
    win = cfg.latent_of("window")
    assert (cfg.n_heads, cfg.kv_lora_rank, cfg.rope_base) == (128, 512, 8e7)
    assert (win.n_heads, win.kv_lora_rank, win.qk_nope_head_dim,
            cfg.rope_base_of("window")) == (64, 1024, 192, 5e4)
    assert cfg.latent_scales("index") == (5 ** 0.5, 10 ** 0.5)
    assert cfg.latent_scales("window") == (5 ** 0.5, 5 ** 0.5)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_offset,
            cfg.expert_top_k) == (256, 16, 0, 8)
    assert cache_rows(cfg) == {"kv": (1, 576), "kv_win": (1, 1088),
                               "k_idx": (1, 128)}


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "dots3-note-prev")
    cut = ["num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size"]
    assert c["reduced"] == entry["reduced"] == cut
    assert entry["source"] == c["source"] and entry["file"].endswith(
        "configs/dots3-note-prev.json")
    # every key of the catalog's entry is there, every width as published
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        pub = next(d for d in map(json.loads, f)
                   if d["name"] == "dots3-note-prev")
    assert pub["source_url"] == c["source"]
    assert c["published"] == pub["config"]
    differ = [k for k, v in pub["config"].items() if c[k] != v]
    assert sorted(differ) == sorted(cut) and set(c["changed"]) == set(cut)
    # the nine layers kept are published layers 0 to 8: two whole periods
    # (sliding x 3, full) behind the two leading full layers
    assert c["layer_types"] == pub["config"]["layer_types"][:9] == [
        FULL, FULL] + [SLIDING] * 3 + [FULL] + [SLIDING] * 3
    assert (pub["config"]["layer_types"].count(FULL),
            pub["config"]["layer_types"].count(SLIDING)) == (13, 33)
    d = c["deployment"]
    assert (d["chips_sharing_a_layer"], d["pipeline_stage"],
            d["experts_routed"], d["expert_offset"],
            d["published_layers"]) == (16, "1 of 5", 256, 0, "0-8")
    assert d["experts_routed"] == pub["config"]["n_routed_experts"] \
        == 16 * c["n_routed_experts"]
    assert 8 * c["vocab_size"] == pub["config"]["vocab_size"]
    for key in ("apply_mla_qkv_lora_rescale", "attention_gate_type",
                "index_key_norm", "index_key_norm_eps",
                "index_head_weight_scale", "index_rotated_dims",
                "every_full_layer_indexes", "sliding_window_size", "ties",
                "e_score_correction_bias_std", "rotary_pairing", "weights"):
        assert key in c["assumed"], key
    assert len(c["departures"]) >= 5
    assert any("vision" in x and "audio" in x for x in c["departures"])
    assert c["precision"]["serve"]["indexer"] == "float32" \
        == c["precision"]["serve"]["router"]


def test_traffic_and_limits_files_have_the_cells_parameters():
    m = mf.Manifest()
    t = m.traffic("serve-notes-closed")
    assert (t["kind"], t["clients"], t["requests_per_client"]) == \
        ("serve-closed", 16, 16)
    assert t["prompt_tokens"] == {"dist": "loguniform", "low": 4096,
                                  "high": 16384}
    assert t["output_tokens"] == {"dist": "fixed", "value": 1024}
    assert t["distinct_prompt_lengths"] == 16
    assert t["engine"] == {"max_slots": 16, "max_len": 17408}
    assert (t["settle_s"], t["trace_seconds"], t["check"]) == \
        (2.0, 12.0, {"sample_requests": 2})
    from perfbench.kinds import serve_common
    lengths = serve_common.prompt_lengths(t)
    # one length a caller, each at least twice index_topk and eight times
    # the window, and every one fits with its output
    assert len(set(lengths)) == 16 and lengths[:2] == [4277, 4664]
    assert min(lengths) >= 2 * 2048 and max(lengths) + 1024 <= 17408
    cell = m.cell(REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("dots3-note-prev", "serve-notes-closed", 1)
    limits = m.limits(REAL_CELL)
    assert set(limits) == {"logit_err", "token_gap"}
    with open(os.path.join(mf.BENCH_DIR, "limits", REAL_CELL + ".json")) as f:
        body = json.load(f)
    assert {"limits", "readings", "how", "why"} <= set(body)
    faults = body["readings"]["planted_faults"]
    assert set(faults) >= {"window_edge_dropped", "head_gate_left_out",
                           "sliding_latent_not_rescaled"}
    # the control and every planted fault fail a limit
    for name in ("window_edge_dropped", "head_gate_left_out",
                 "sliding_latent_not_rescaled"):
        assert any(faults[name][k] > limits[k] for k in limits), name
    for k in limits:
        r = body["readings"][k]
        assert r["program_largest"] < limits[k] < r["control_smallest"]


def test_the_reference_imports_nothing_of_the_programs_model_code():
    fam = mf.family("dots3_note")
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
    # selection is the reference's own full sort, the window its own mask
    with open(fam.path("model")) as f:
        src = f.read()
    assert src.count("ray_tpu") == src.count("from ray_tpu.models import "
                                            "TransformerConfig") + \
        src.count("`ray_tpu.models.init_params`") + src.count(
            "`ray_tpu/ops/rotary.py`")
    assert "argsort" in src and "top_k(s +" in src and "def window(" in src


def test_reference_is_the_programs_function_in_float32(tiny, make32):
    """test_perfbench_reference.py's case, with the module's compiled `make`
    and the program's forward as ONE program (eagerly its six layers are
    hundreds of small ones)."""
    from ray_tpu.models import forward
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
    want = model.logits(params, toks, c)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert int(toks.max()) < fam.shapes.vocab(c)


def test_loss_is_the_references(tiny, make32):
    from ray_tpu.models import lm_loss
    c, fam = tiny
    model = fam.model
    key = weights.key_of(11)
    params = make32(key)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 48), c)
    cfg = dataclasses.replace(model.model_config(c, "train", remat=False),
                              dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(lm_loss, cfg=cfg))(
            params, {"tokens": toks})
    assert abs(float(got - model.loss(params, toks, c))) < 1e-5
    assert 4.5 < float(got) < 8.0      # ln 256 = 5.5 over random tokens


@pytest.mark.parametrize("seed", shared_reference.SEEDS[:1])
def test_serving_program_passes_and_fp8_control_fails(tiny, seed):
    """test_perfbench_reference.py's case under this family's limits, but
    for its last line: at width 64 a bfloat16 score that changes ONE of a
    query's 24 chosen rows, or one of a token's experts, moves a logit more
    than all rounding does, so the control stands 1.8-8 times above the
    program, not always the 3 the shared case asks for.  The limits sit
    between the two over 8 seeds (the limits file)."""
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
    assert control["logit_err"] > 1.8 * program["logit_err"]


def test_weights_come_from_the_seed_alone(make32):
    """test_perfbench_reference.py's case with ONE compiled `make` for its
    three keys (the layout against the program's initialiser is
    `test_counts_are_the_programs`')."""
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
    ``assumed.expert_bias_balance_tokens``; the tiny one states none and
    draws its routers as they fall): on FRESH tokens every expert layer's
    busiest expert draws under twice an even share where the drawn bias
    leaves it three to four, and the chip's 4 held experts of 8 draw about
    half the pairs on every layer where they drew a thirtieth to all."""
    c, fam = tiny
    model = fam.model
    toks = model.tokens(jax.random.PRNGKey(61), (384,), c)

    def loads(n):
        cc = dict(c, assumed=dict(c["assumed"],
                                  expert_bias_balance_tokens=n))

        def counted(key):       # (one program: weights, then the walk)
            seen = []

            def spy(scores, lp):
                _, chosen = jax.lax.top_k(
                    scores + lp["router_bias"].astype(jnp.float32), 2)
                seen.append(jnp.zeros((8,)).at[chosen.reshape(-1)].add(1.0))
                return {}
            model._walk(model._make(key, cc, jnp.float32), toks, cc,
                        "float32", spy)
            return jnp.stack(seen)
        return [(float(x[:4].sum() / x.sum()), float(x.max() / x.mean()))
                for x in jax.jit(counted)(weights.key_of(2))]

    drawn, balanced = loads(0), loads(512)
    assert len(drawn) == len(balanced) == 5
    assert max(m for _, m in balanced) < 2.0 < max(m for _, m in drawn)
    assert all(0.38 < share < 0.62 for share, _ in balanced), balanced
    assert not all(0.38 < share < 0.62 for share, _ in drawn), drawn


def test_sixteen_shares_add_up_to_the_uncut_layer(tiny):
    """A layer of 16 routed experts, 2 a token, shared by SIXTEEN chips of
    one expert each: the routed parts the PROGRAM computes for the sixteen
    shares (each told which expert it holds, each routing over all 16),
    with the shared expert that every chip computes alike counted once, add
    up to the uncut REFERENCE's layer: every expert held."""
    from ray_tpu.models.transformer import _ffn
    c, fam = tiny
    model = fam.model
    whole = dict(c, n_routed_experts=16, deployment=dict(
        c["deployment"], experts_routed=16, expert_offset=0))
    params = model.make(weights.key_of(13), whole, jnp.float32)
    lay = params["layers"]
    ffn = ("mlp_norm", "router", "router_bias", "ws_in", "ws_gate", "ws_out")
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), jnp.float32)
    r = reference._round_inputs("float32")
    with jax.default_matmul_precision("highest"):
        lp = dict({k: lay[k][1] for k in ffn},
                  **{k: lay[k] for k in ("w_in", "w_gate", "w_out")})
        shared = jnp.stack([model._swiglu(
            r, row, lp["ws_in"], lp["ws_gate"], lp["ws_out"]) for row in y])
        uncut = shared + jnp.stack([
            model.routed_part(r, row, lp, whole, 0, 16, 1) for row in y])
        total = shared
        for chip in range(16):
            one = dict(whole, n_routed_experts=1, deployment=dict(
                whole["deployment"], expert_offset=chip))
            cfg = dataclasses.replace(
                model.model_config(one, "serve"), dtype=jnp.float32,
                param_dtype=jnp.float32)
            mine = dict({k: lay[k][1] for k in ffn},
                        **{k: lay[k][1, chip:chip + 1]
                           for k in ("w_in", "w_gate", "w_out")})
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
    assert "dots3-note-prev" in [c["name"] for c in root.data["configs"]]
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
            "device.share.attention.batch", "device.idle_share.batch",
            "device.indexer_share.batch", "cache.index_bytes_share.longdoc",
            "hbm_peak_gb.batch", "setup.warmup_s"} <= reported
    assert {x["name"] for x in root.metrics_for(REAL_CELL, False)} == {
        "serve_tok_s", "setup_s"}


def _spans_run(events):
    return types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                 _ring_spans=events)


def test_the_ring_share_reader_on_hand_made_spans():
    read = mf.metric_reader("cache.ring_latent_bytes_share.notes")
    assert read(_spans_run([])) is None
    # no such key (the parent), or none counted (a model without a ring of
    # latents): nothing, no raise
    for args in ({"bytes_read": 100},
                 {"bytes_read": 100, "ring_latent_bytes_read": 0}):
        assert read(_spans_run([{"name": "cache:rows", "ts": 1e6,
                                 "dur": 2e6, "args": args}])) is None
    ours = _spans_run([
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "bytes_read": 100, "ring_latent_bytes_read": 30}},
        {"name": "cache:rows", "ts": 3e6, "dur": 2e6, "args": {
            "bytes_read": 60, "ring_latent_bytes_read": 18}},
        {"name": "cache:rows", "ts": 44e6, "dur": 2e6, "args": {  # ends late
            "bytes_read": 1, "ring_latent_bytes_read": 1}}])
    assert read(ours) == 30.0
    # the cell's own arithmetic: a slot at depth 10,000 reads 513 ring rows
    # on 6 layers, 2048 latents and 10,000 index keys on 3
    at = _spans_run([{"name": "cache:rows", "ts": 1e6, "dur": 1e6, "args": {
        "bytes_read": 6 * 513 * RING_ROW + 3 * 2048 * ROW + 3 * 10_000 * KEY,
        "ring_latent_bytes_read": 6 * 513 * RING_ROW}}])
    assert read(at) == pytest.approx(31.2, abs=0.1)


def test_the_roofline_reader_on_a_hand_made_run(real, monkeypatch):
    """The family's floor at the run's mean batch, its counted experts and
    the depths its slots stood at, over the HBM peak, over the step's device
    time; nothing where there is no trace, no ``moe:load`` span, or a family
    that counts no ring."""
    from perfbench import moe_load, readers
    c, fam = real
    read = mf.metric_reader("decode_step_roofline.notes")
    req = types.SimpleNamespace(prompt=[0] * 9_000, tokens=[0] * 2,
                                arrivals=[(1.0, 2)])

    def run(family, trace={"programs": {}}):
        return types.SimpleNamespace(
            trace=trace, family=family, config=c,
            raw={"requests": [req], "counters": {
                "before": {"steps": 0, "tokens": 0},
                "after": {"steps": 10, "tokens": 160}}},
            peaks=lambda: {"hbm_bytes_per_s": 819e9})

    monkeypatch.setattr(readers, "program_ms",
                        lambda run, pattern: None if run.trace is None
                        else 12.0)
    touched = [6.0]
    monkeypatch.setattr(moe_load, "experts_touched_per_layer_step",
                        lambda run: touched[0])
    got = read(run(fam))
    floor = fam.shapes.decode_step_bytes(
        c, 16 * 9_000.5, experts_touched=6.0, depths=[9_000, 9_001])
    assert got == pytest.approx(100 * floor / 819e9 / 0.012)
    assert 40 < got < 100
    assert read(run(fam, trace=None)) is None
    assert read(run(mf.family("gpt2"))) is None
    assert read(run(mf.family("glm_moe_dsa"))) is None     # counts no ring
    touched[0] = None
    assert read(run(fam)) is None


def test_the_scope_readers_give_nothing_without_their_scope(
        real, tmp_path, monkeypatch):
    """An untraced run, a session that left no op map, and maps in which no
    operation stands in a ``window_latent`` scope (a program without such
    layers: the parent) all give None; with the scope, its operations'
    share whatever part they fall in, and the kernel's roofline from the
    decode step's ``latent_attention_cache`` calls INSIDE the scope alone."""
    from perfbench import parts, readers, spans, xplane
    c, fam = real
    share = mf.metric_reader("device.window_latent_share.batch")
    roof = mf.metric_reader("latent_attention_ring_roofline.notes")
    assert share(types.SimpleNamespace(trace=None)) is None
    assert roof(types.SimpleNamespace(trace=None)) is None
    run = types.SimpleNamespace(
        trace={"programs": {}}, raw={"trace": {"dir": "x"}, "counters": {
            "before": {"steps": 0, "tokens": 0},
            "after": {"steps": 10, "tokens": 160}}},
        family=fam, config=c, peaks=lambda: {"hbm_bytes_per_s": 819e9,
                                             "bf16_flops": 197e12})
    monkeypatch.setattr(spans, "session_dir", lambda run: str(tmp_path))
    assert share(run) is None and roof(run) is None
    os.makedirs(tmp_path / "programs")
    body = "jit(fused_step)/while/body/closed_call/%s"
    call = "cond/branch_0_fun/attention/jit(attend_cache)/" \
           "latent_attention_cache/pallas_call"

    def leave(scope):
        with open(tmp_path / "programs" / "worker-1.decode_step.json",
                  "w") as f:
            json.dump({"program": "decode_step", "maps": [{
                "module": "jit_fused_step", "instructions": {
                    "latent_attention_cache.19": body % (scope + call),
                    "latent_attention_cache.16": body % call,
                    "fusion.2": body % (scope + "projections/dot_general"),
                    "fusion.3": body % "ffn/dot"}}]}, f)

    monkeypatch.setattr(xplane, "find", lambda d: d)
    monkeypatch.setattr(xplane, "read", lambda p: {"devices": {"d0": {
        "modules": [(0.0, 10.0, "jit_fused_step(1)")],
        "ops": [(0.0, 1.0, "tpu_custom_call:latent_attention_cache.19"),
                (1.0, 3.0, "tpu_custom_call:latent_attention_cache.16"),
                (3.0, 6.0, "fusion.2"), (6.0, 10.0, "fusion.3")]}}})
    monkeypatch.setattr(xplane, "program",
                        lambda trace, pattern: {"count": 1000})
    leave("")
    assert share(run) is None and roof(run) is None
    leave("window_latent/")
    assert share(run) == pytest.approx(40.0)
    # one second of the kernel over 1000 steps: 1 ms a step for six calls
    cost = fam.shapes.kernels(c, 16.0, 1)["latent_attention_ring"]
    least = 6 * max(cost["step_bytes"] / 819e9, cost["step_flops"] / 197e12)
    assert roof(run) == pytest.approx(100 * least / 1e-3)
    assert 5 < roof(run) < 100
    # the parts still add up: the scope stands AROUND parts
    assert parts.place(body % ("window_latent/" + call)) == (
        "attention", "forward")
    assert readers.DECODE_STEP == r"^jit_fused_step$"


@pytest.mark.parametrize("trace", [1])
def test_cell_rehearsed_on_the_cpu(monkeypatch, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest:
    the whole path through `serve.run` and the engine, prompts of 8-40
    tokens as padded chunks over two latent arrays and the full layers'
    index keys, contexts up to three times the window and index_topk.  The
    traced run finds the engine's ``cache:rows`` spans with the ring's
    bytes; the readers of the device trace find no device plane on the CPU
    and leave theirs out."""
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
        # contexts of 9-48 rows: at most 24 chosen on 3 layers, 13 in the
        # window on 3
        assert 20 < got["dsa.rows_attended_share.longdoc"]["value"] < 95
        # a ring row costs 40 values, a latent 56, an index key 16
        assert 10 < got["cache.ring_latent_bytes_share.notes"]["value"] < 60
        assert 5 < got["cache.index_bytes_share.longdoc"]["value"] < 40
        assert got["moe.experts_touched.agent"]["value"] > 0
    for name in ("decode_step_roofline.notes",
                 "latent_attention_ring_roofline.notes",
                 "device.window_latent_share.batch",
                 "device.indexer_share.batch",
                 "decode_step.device_ms.batch"):
        assert name not in got, name
