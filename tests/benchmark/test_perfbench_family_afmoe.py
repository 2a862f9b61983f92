"""Family ``afmoe``: its counts against counts made by hand (at the published
widths and at the rehearsal's tiny size) and against the program's own
arithmetic; its configuration and traffic files against what they state; its
plain reference against the program through chunked prefill, single-token
tails and slot decode over TWO KINDS of cache (the full layer's rows and the
window layers' rings, which the tiny sessions wrap several times); the share
test (the parts of an expert layer that all the chips' shares give, the
shared expert counted once, add up to the uncut layer); and its gradient
against the program's.  The tiny configuration has a manifest of its own,
``testdata/rehearsal/BENCHMARK.tiny-afmoe.json``, beside the rehearsal's (a
PR that changes the program adds files to the benchmark and edits none), so
the shared parametrised cases of test_perfbench_reference.py and
test_perfbench_rehearsal.py do not find it: they are called from here, on
this family.

Tolerances.  Float32 against float32 (two implementations of the same
equations, both at ``highest``): 1e-4 on logits of order 1, 2e-4 relative on
the whole gradient.  Bfloat16 against float32 is held to the rehearsal's
limits files, whose readings say what a flipped expert costs.
"""

import dataclasses
import functools
import json
import os

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
                             "BENCHMARK.tiny-afmoe.json")
CELL = "tiny-afmoe.serve-closed"
DEALT = "tiny-afmoe.serve-closed-dealt"    # the real cell's traffic kind
REAL_CELL = "trinity-large-preview.serve-mixed-closed"

# by hand, from the published config.json: d 3072, 48 query and 8 key-value
# heads of 128, experts of 3072, a dense layer of 12288, a router of 256
ATTN = 3 * 3072 * 48 * 128 + 2 * 3072 * 8 * 128        # q, gate, o; k, v
EXPERT = 3 * 3072 * 3072
NORMS = 4 * 3072 + 2 * 128
DENSE_LAYER = ATTN + 3 * 3072 * 12288 + NORMS
OUTSIDE = ATTN + EXPERT + 3072 * 256 + 256 + NORMS     # shared, router, bias
AS_RUN = DENSE_LAYER + 4 * (OUTSIDE + 32 * EXPERT) + 2 * 25024 * 3072 + 3072


def _tiny_manifest() -> mf.Manifest:
    return mf.Manifest(TINY_MANIFEST, os.path.join(
        mf.ROOT, rehearse.REHEARSAL, "traffic"))


@pytest.fixture(scope="module")
def real():
    c = mf.Manifest().config("trinity-large-preview")
    return c, mf.family_of(c)


@pytest.fixture(scope="module")
def tiny():
    c = _tiny_manifest().config("tiny-afmoe")
    return c, mf.family_of(c)


def test_counts_by_hand_at_the_published_widths(real):
    c, fam = real
    s = fam.shapes
    assert (ATTN, EXPERT) == (62_914_560, 28_311_552)
    assert s.attention_params(c) == ATTN and s.expert_params(c) == EXPERT
    assert DENSE_LAYER == 176_173_312
    assert OUTSIDE + 32 * EXPERT == 997_995_008
    assert AS_RUN == 4_321_903_872 and s.count_params(c) == AS_RUN
    assert s.vocab(c) == 25_024 and s.positions(c) == 262_144
    assert s.layers(c) == (1, 4) and s.window_layers(c) == 4
    assert s.experts_routed(c) == 256
    # of a token's 4 experts an eighth is held here: half an expert a layer
    active = (ATTN + 3 * 3072 * 12288) \
        + 4 * (ATTN + 1.5 * EXPERT + 3072 * 256) + 25024 * 3072
    assert s.train_flops_per_token(c, 1024) == \
        6.0 * active + 6.0 * 48 * 128 * 5 * 1024
    # past twice the window a window layer's mean row sees the window
    assert s.train_flops_per_token(c, 16384) == \
        6.0 * active + 6.0 * 48 * 128 * (16384 + 4 * 8192)
    # a cached position a layer: keys and values of 8 heads of 128, 4096 B
    assert s.cache_row_values(c) == 2048
    # the full layer's rows grow with the live rows, a window layer's stop
    # at the window: the least any number of slots with those rows reads
    assert s.decode_step_bytes(c, 100.0) - s.decode_step_bytes(c, 0.0) == \
        5 * 100 * 4096
    assert s.decode_step_bytes(c, 90_000.0) - s.decode_step_bytes(c, 0.0) \
        == (90_000 + 4 * 4096) * 4096
    # weights: everything but the embedding table and the routed experts
    # once, then the HELD experts the step touched; none where no run
    # counted them (a token's four may all live on other chips)
    fixed = DENSE_LAYER + 4 * OUTSIDE + 25024 * 3072 + 3072
    assert s.decode_step_bytes(c, 0.0) == 2.0 * fixed
    assert s.decode_step_bytes(c, 0.0, experts_touched=6.5) == \
        2.0 * (fixed + 4 * 6.5 * EXPERT)
    assert s.decode_step_bytes(c, 0.0, experts_touched=32) == \
        2.0 * (AS_RUN - 25024 * 3072)
    k = s.kernels(c, 2, 512)["flash_attention"]
    one = 2.0 * 2 * 48 * 512 * 512 * 128 / 2
    assert (k["fwd_flops"], k["bwd_flops"], k["calls"]) == (2 * one,
                                                            5 * one, 5)
    # past the window only the full layer's attention is the flash kernel's
    assert s.kernels(c, 1, 8192)["flash_attention"]["calls"] == 1


def test_configuration_file_states_its_cut(real):
    c, _ = real
    entry = next(x for x in mf.Manifest().data["configs"]
                 if x["name"] == "trinity-large-preview")
    assert sorted(c["reduced"]) == sorted(entry["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"])
    assert entry["source"] == c["source"]
    # every published key is there, and only the reduced ones differ
    differs = {k for k, v in c["published"].items() if c[k] != v}
    assert differs == set(c["reduced"]) == set(c["changed"])
    pub = c["published"]
    assert (pub["num_hidden_layers"], c["num_hidden_layers"]) == (60, 5)
    assert (pub["num_dense_layers"], c["num_dense_layers"]) == (6, 1)
    assert (pub["num_experts"], c["num_experts"]) == (256, 32)
    assert (pub["vocab_size"], c["vocab_size"]) == (200192, 25024)
    assert pub["vocab_size"] == 8 * c["vocab_size"]
    # one dense layer, then one WHOLE period of the published pattern
    assert pub["layer_types"][8:12] == c["layer_types"][1:] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert c["layer_types"][0] == pub["layer_types"][0]
    # no width is cut
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts_per_tok",
                "sliding_window", "num_shared_experts"):
        assert c[key] == pub[key], key
    d = c["deployment"]
    assert "8 chips share each layer" in d["stands_for"]
    assert (d["chips_sharing_a_layer"], d["experts_routed"],
            d["expert_offset"], d["window_chunk"]) == (8, 256, 0, 128)
    assert d["experts_routed"] == d["chips_sharing_a_layer"] \
        * c["num_experts"]
    assert c["assumed"]["expert_bias_std"] > 0
    assert c["assumed"]["expert_bias_balance_tokens"] == 16384
    assert len(c["assumed"]) >= 7 and len(c["departures"]) >= 3
    assert c["precision"]["serve"] == {
        "params": "bfloat16", "compute": "bfloat16", "router": "float32",
        "softmax": "float32", "norm_statistics": "float32",
        "logits": "float32"}


def test_traffic_file_has_the_cells_parameters():
    m = mf.Manifest()
    cell = m.cell(REAL_CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "serve-mixed-closed")
    assert len(cell["why"]) <= 200
    t = m.traffic(cell["traffic"])
    assert t["kind"] == "serve-closed-dealt" and t["clients"] == 16
    assert t["prompt_tokens"] == {"dist": "loguniform", "low": 1024,
                                  "high": 16384}
    assert t["distinct_prompt_lengths"] == 32
    assert t["output_tokens"] == {"dist": "fixed", "value": 256}
    assert t["requests_per_client"] == 16
    assert t["engine"] == {"max_slots": 16, "max_len": 16896}
    assert t["check"]["sample_requests"] == 2
    from perfbench.kinds import serve_common
    lengths = serve_common.prompt_lengths(t)
    assert len(set(lengths)) == 32 and 1024 <= min(lengths) \
        and max(lengths) <= 16384
    # half of the prompts are past the window; the longest request fits
    assert sum(n > 4096 for n in lengths) == 16
    assert 5000 < sum(lengths) / 32 < 6000
    assert max(lengths) + 256 <= t["engine"]["max_len"]
    # the two callers whose first requests the comparison samples own one
    # prompt under the window and one that wraps the ring of 4224 rows
    for caller in (0, 1):
        short, long = lengths[caller::16]
        assert short + 256 < 4096 and long + 256 > 4096 + 128
    # ... and the mix's schedule_seed deals caller 0 the short one first and
    # caller 1 the long one, on every seed (ISSUE 32: one under 4096, one
    # of 4608-8192)
    from perfbench.kinds import serve_closed_dealt
    c = m.config(cell["config"])
    plan = serve_closed_dealt.plan_for(t, c, 3200000001)
    first = [len(mine[0].prompt) for mine in plan]
    assert first[0] < 4096 and 4608 <= first[1] <= 8192
    # the calibration of the expert bias covers every position they reach
    c = m.config(cell["config"])
    assert c["assumed"]["expert_bias_balance_tokens"] >= max(lengths)
    limits = m.limits(cell["name"])
    assert set(limits) == {"logit_err", "token_gap"}


@pytest.mark.parametrize("seeds", [(5, 2**31 + 5), (3200000001, 77)])
def test_dealt_kind_times_the_same_sizes_on_every_seed(tiny, seeds):
    """kinds/serve_closed_dealt.py: the sizes and their order on every
    caller are the mix's (``schedule_seed``), the tokens the seed's; each
    caller owns every ``clients``-th length, as in `serve-closed`."""
    from perfbench.kinds import serve_closed, serve_closed_dealt, serve_common
    c, _ = tiny
    t = dict(_tiny_manifest().traffic("serve-closed"), schedule_seed=11)
    a, b = (serve_closed_dealt.plan_for(t, c, s) for s in seeds)
    sizes = [[(len(r.prompt), r.n_out) for r in mine] for mine in a]
    assert sizes == [[(len(r.prompt), r.n_out) for r in mine] for mine in b]
    assert [r.prompt for r in a[0]] != [r.prompt for r in b[0]]
    lengths = serve_common.prompt_lengths(t)
    n, per = t["clients"], t["requests_per_client"]
    assert len(a) == n and all(len(mine) == per for mine in a)
    for i, mine in enumerate(a):
        assert sorted({len(r.prompt) for r in mine}) == sorted(lengths[i::n])
        own = len(lengths[i::n])
        assert all(len(mine[j].prompt) == len(mine[j % own].prompt)
                   for j in range(per))
    # another schedule_seed, another order; `serve-closed` follows the seed
    other = serve_closed_dealt.plan_for(dict(t, schedule_seed=12), c, seeds[0])
    assert [[len(r.prompt) for r in mine] for mine in other] != \
        [[n_ for n_, _ in mine] for mine in sizes]
    x, y = (serve_closed.plan_for(t, c, s) for s in (5, 6))
    assert [[len(r.prompt) for r in m_] for m_ in x] != \
        [[len(r.prompt) for r in m_] for m_ in y]
    vocab = mf.family_of(c).shapes.vocab(c)
    assert all(0 <= tok < vocab for r in a[0] for tok in r.prompt)


def test_counts_are_the_programs_and_the_cache_has_two_kinds(real, tiny):
    from ray_tpu.models import init_params, init_slot_cache
    from ray_tpu.models.transformer import count_params, flops_per_token
    for (c, fam), max_len in ((real, 16896), (tiny, 128)):
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
        cache = jax.eval_shape(lambda: init_slot_cache(cfg, 3, max_len))
        assert set(cache) == {"k", "v", "k_win", "v_win", "pos"}
        hk, hd = c["num_key_value_heads"], c["head_dim"]
        ring = c["sliding_window"] + c["deployment"]["window_chunk"]
        assert cache["k"].shape == (1, 3, hk, hd, max_len)
        assert cache["k_win"].shape == (4, 3, hk, hd, ring) \
            == cache["v_win"].shape
        # one further position of a slot costs the full layer's row only
        assert fam.shapes.decode_step_bytes(c, 1.0) \
            - fam.shapes.decode_step_bytes(c, 0.0) == 5 * 2 * hk * hd * 2
    # the cell's slot cache: 1.11 GB of full rows and 1.11 GB of rings,
    # where five full layers would be 5.54 GB
    c, fam = real
    cache = jax.eval_shape(lambda: init_slot_cache(
        fam.model.model_config(c, "serve"), 16, 16896))
    full = 2 * cache["k"].size * 2
    rings = 2 * cache["k_win"].size * 2
    assert (full, rings) == (1_107_296_256, 1_107_296_256)
    assert 5 * full == 5_536_481_280
    # tiny, by hand: d 64, 4 query and 2 key-value heads of 24
    attn = 3 * 64 * 4 * 24 + 2 * 64 * 2 * 24
    norms = 4 * 64 + 2 * 24
    assert tiny[1].shapes.count_params(tiny[0]) == (
        attn + 3 * 64 * 160 + norms
        + 4 * (attn + 3 * 3 * 64 * 32 + 64 * 8 + 8 + norms)
        + 2 * 256 * 64 + 64)


def _f32(model, c, **kw):
    return dataclasses.replace(
        model.model_config(c, "serve", attention_impl="reference", **kw),
        dtype=jnp.float32, param_dtype=jnp.float32)


def _through_the_cache(params, toks, cfg, plan, max_len=128, slots=None,
                       slot_of=None):
    """The program's served path, teacher forced.  ``plan`` gives each
    row's prefill as a list of chunk widths (the rest of the row is decode
    steps over slots at different depths): chunks of any width up to the
    ring's room, at any offset, so that some straddle the ring's seam.
    -> (logits [b, s, V], which positions were computed, slot cache)."""
    from ray_tpu.models import (cache_insert_slot, decode_step_slots,
                                init_kv_cache, init_slot_cache,
                                prefill_chunk_jit)
    b, s = toks.shape
    got = np.zeros((b, s, cfg.vocab_size), np.float32)
    have = np.zeros((b, s), bool)
    if slots is None:
        slots = init_slot_cache(cfg, b, max_len)
    slot_of = slot_of or list(range(b))
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
        slots = insert(slots, pc, jnp.int32(slot_of[i]))
    step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
    n_slots = slots["pos"].shape[0]
    for j in range(s - min(depth)):
        tok = np.zeros((n_slots,), np.int32)
        active = np.zeros((n_slots,), bool)
        for i, n in enumerate(depth):
            if n + j < s:
                tok[slot_of[i]], active[slot_of[i]] = toks[i, n + j], True
        lg, slots = step(params, jnp.asarray(tok), slots,
                         jnp.asarray(active))
        for i, n in enumerate(depth):
            if n + j < s:
                got[i, n + j] = np.asarray(lg[slot_of[i]])
                have[i, n + j] = True
    return got, have, slots


# 8-wide chunks from offsets 0, 3 and 5: with a ring of 16 rows a chunk
# that starts at 11 or 13 (mod 16) straddles the seam
PLAN = ([8] * 8 + [1] * 3, [1] * 3 + [8] * 5, [5, 8, 8, 8, 1])


def test_chunks_tails_and_slot_decode_over_full_rows_and_rings(tiny):
    """Float32 both: sessions of 120 positions cross the window of 8 and
    wrap the ring of 16 rows seven times, with chunks that straddle its
    seam, against the reference's full forward, which has no cache, no
    ring and no sort."""
    c, fam = tiny
    model = fam.model
    key = weights.key_of(2**31 + 29)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (3, 120), c)
    cfg = _f32(model, c)
    want = model.logits(params, toks, c)
    with jax.default_matmul_precision("highest"):
        got, have, slots = _through_the_cache(params, toks, cfg, PLAN)
    assert slots["k_win"].shape[-1] == 16 and slots["k"].shape[-1] == 128
    assert have.sum() == (11 + 53) + (8 + 77) + (5 + 90)
    err = jnp.abs(jnp.asarray(got) - want).max(-1)
    assert float(jnp.where(have, err, 0).max()) < 1e-4
    # a wider chunk than the ring has room for is refused, not answered
    from ray_tpu.models import init_kv_cache, prefill_chunk_jit
    with pytest.raises(ValueError, match="window_chunk"):
        prefill_chunk_jit(params, toks[:1, :9], init_kv_cache(cfg, 1, 128),
                          cfg=cfg)


def test_a_slot_reused_after_a_longer_session_reads_nothing_of_it(tiny):
    """Slot 1 first holds a session of 120 positions (its rings wrapped
    seven times, its full rows written to 120), then a session of 40: what
    the longer one left in the rings and past the shorter one's rows is
    masked by the position each column holds."""
    c, fam = tiny
    model = fam.model
    key = weights.key_of(41)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 120), c)
    cfg = _f32(model, c)
    from ray_tpu.models import init_slot_cache
    with jax.default_matmul_precision("highest"):
        _, _, slots = _through_the_cache(
            params, toks[:1], cfg, ([8] * 4,), slot_of=[1],
            slots=init_slot_cache(cfg, 2, 128))
        assert int(slots["pos"][1]) == 120
        short = toks[1:, :40]
        got, have, slots = _through_the_cache(
            params, short, cfg, ([8, 8, 1, 1],), slots=slots, slot_of=[1])
    assert int(slots["pos"][1]) == 40
    want = model.logits(params, short, c)
    err = jnp.abs(jnp.asarray(got) - want).max(-1)
    assert have.sum() == 4 + 22
    assert float(jnp.where(have, err, 0).max()) < 1e-4


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(tiny):
    """The guide's share test.  4 chips share each layer, 2 of 8 experts
    each: the routed parts that the four shares give (each computed by the
    PROGRAM's expert layer told which experts it holds), plus the shared
    expert counted once, equal the uncut layer, the program's with all 8
    experts held and the reference's."""
    from ray_tpu.models.transformer import _ffn
    c, fam = tiny
    model = fam.model
    # a bias as narrow as the cell's: the scores choose, all 8 are met
    c = dict(c, assumed=dict(c["assumed"], expert_bias_std=0.1,
                             expert_bias_balance_tokens=0))
    whole = dict(c, num_experts=8, deployment=dict(c["deployment"],
                                                   expert_offset=0))
    params = model.make(weights.key_of(9), whole, jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[2], params["layers"])
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    r = reference._round_inputs("float32")
    with jax.default_matmul_precision("highest"):
        uncut_ref = model.routed_part(r, y, lp, whole, 0, 8) \
            + model.shared_part(r, y, lp)
        uncut, _, load = _ffn(_f32(model, whole), y, lp)
        assert [int(x) for x in load[::2]] == [8, 2 * 24 * 2]
        touched = 0
        assert float(jnp.abs(uncut - uncut_ref).max()) < 1e-5
        shared = model.shared_part(r, y, lp)
        total, landed = shared, 0
        for chip in range(4):
            mine = dict(c, deployment=dict(c["deployment"],
                                           expert_offset=2 * chip))
            lp_mine = dict(lp, **{k: lp[k][2 * chip:2 * chip + 2]
                                  for k in ("w_in", "w_gate", "w_out")})
            part, _, load = _ffn(_f32(model, mine), y, lp_mine)
            # the program's part is its routed share and the shared expert
            routed = part - shared
            want = model.routed_part(r, y, lp_mine, mine, 2 * chip, 2)
            assert float(jnp.abs(routed - want).max()) < 1e-5
            total, landed = total + routed, landed + int(load[2])
            touched += int(load[0])
        # every pair landed on exactly one chip; nothing stood in for any
        assert landed == 2 * 24 * 2 and touched == 8
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
    # the bias is a constant of the loss: it moves choices, not weights
    assert float(jnp.abs(g_got["layers"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g_ref["layers"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g_got["layers"]["router"]).max()) > 0.0
    # the gate of every layer took part, the full layer's (the last) too
    assert float(jnp.abs(g_got["layers"]["wg"]).max(axis=(1, 2, 3)).min()) \
        > 0.0


def test_served_path_in_bfloat16_passes_and_the_fp8_control_fails(tiny):
    """The comparison of ``correct`` on the path the cell times (chunks,
    tails, decode over rows and rings), not on `forward`."""
    c, fam = tiny
    model = fam.model
    key = weights.key_of(3)
    params = model.make(key, c, model.param_dtype(c, "serve"))
    toks = model.tokens(jax.random.fold_in(key, 2), (3, 80), c)
    cfg = model.model_config(c, "serve", attention_impl="reference")
    got, have, _ = _through_the_cache(
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
    """`make` gives the bias the result of the balancing update on the
    seed's own weights where the file names calibration tokens: on FRESH
    tokens every expert then meets about its even share of the pairs, where
    the bias as drawn leaves some experts a tenth of it and others twice
    it.  Every chip's share of the pairs follows, which is what keeps a
    cell that holds 32 of 256 experts from timing the seed's draw."""
    c, fam = tiny
    model = fam.model
    base = dict(c, num_experts=8,
                deployment=dict(c["deployment"], expert_offset=0))
    fresh = model.tokens(jax.random.PRNGKey(99), (1, 512), base)

    def loads(n_tokens):
        cc = dict(base, assumed=dict(c["assumed"], expert_bias_std=0.1,
                                     expert_bias_balance_tokens=n_tokens))
        params = jax.jit(lambda k: model.make(k, cc, jnp.float32))(
            weights.key_of(2))
        seen = []

        def count(scores, lp):
            _, chosen = jax.lax.top_k(
                scores + lp["router_bias"].astype(jnp.float32), 2)
            seen.append(np.bincount(np.asarray(chosen).ravel(),
                                    minlength=8))
            return {}
        model._walk(params, fresh, cc, "float32", count)
        return np.stack(seen), params

    drawn, p0 = loads(0)
    balanced, p1 = loads(1024)
    assert drawn.shape == balanced.shape == (4, 8)
    assert (drawn.sum(1) == 512 * 2).all()
    even = 512 * 2 / 8
    assert drawn.min() < 0.2 * even and drawn.max() > 1.7 * even
    assert balanced.min() > 0.5 * even

    def unevenness(loads):      # a layer's spread over its mean, averaged
        return float((loads.std(1) / loads.mean(1)).mean())
    assert unevenness(balanced) < 0.5 * unevenness(drawn)
    # only the router differs (its bias, and the order of its columns:
    # the experts dealt to the chips by load), and it is the layers' own
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()), p0, p1)
    assert not same["layers"].pop("router_bias")
    assert not same["layers"].pop("router")
    assert all(jax.tree_util.tree_leaves(same))
    for a, b in zip(p0["layers"]["router"], p1["layers"]["router"]):
        assert sorted(map(tuple, np.asarray(a).T.round(5).tolist())) == \
            sorted(map(tuple, np.asarray(b).T.round(5).tolist()))


def test_tiny_manifest_has_no_problem():
    m = _tiny_manifest()
    assert mf.problems(m) == []
    assert [w["name"] for w in m.data["workloads"]] == [CELL, DEALT]
    # the readers this PR adds are rehearsed under the names the cell has
    real = {x["name"] for x in mf.Manifest().data["per_layer"]
            if x.get("workloads") == [REAL_CELL]}
    assert real == {"cache.rows_read_share.mixed",
                    "cache.ring_bytes_share.mixed",
                    "moe.load_max_over_mean.mixed"}
    assert real <= {x["name"] for x in m.data["per_layer"]}
    # and the root manifest, with the new cell appended, has none either
    root = mf.Manifest()
    assert mf.problems(root) == []
    assert root.data["workloads"][-1]["name"] == REAL_CELL
    listed = [x["name"] for x in root.data["per_layer"]
              if REAL_CELL in x.get("workloads", ())]
    for name in ("moe.experts_touched.agent", "decode_step_roofline.agent",
                 "prefill_chunk.device_ms.agent",
                 "engine.prefill_share.agent", "hbm_peak_gb.batch",
                 "decode_step.device_ms.batch", "compiles_in_window"):
        assert name in listed, name
    assert "decode_step_roofline.batch" not in listed
    # one metric stays the all-experts-held cell's alone (the benchmark's
    # own test of that family pins it); its twin reads the same spans here
    assert "moe.load_max_over_mean.agent" not in listed


@pytest.mark.parametrize("cell,trace", [(CELL, 0), (CELL, 1), (DEALT, 0)])
def test_cell_rehearsed_on_the_cpu(monkeypatch, cell, trace):
    """test_perfbench_rehearsal.py's case, under this family's manifest:
    the whole path through `serve.run` and the engine with rings of 16 rows
    that prompts of 8-40 tokens wrap.  The traced run also finds the
    engine's ``cache:rows`` and ``moe:load`` spans, and the readers of the
    device trace find no device plane on the CPU and leave theirs out.
    The third case is the same path under the traffic kind the real cell
    has (kinds/serve_closed_dealt.py)."""
    lines = []

    def rehearsed(*a, **kw):
        lines.extend(rehearse_cell(*a, manifest_path=TINY_MANIFEST, **kw))
        return lines

    rehearse_cell = rehearse.rehearse
    monkeypatch.setattr(rehearse, "manifest", _tiny_manifest)
    monkeypatch.setattr(rehearse, "rehearse", rehearsed)
    shared_rehearsal.test_cell_kind_rehearsed_on_the_cpu(cell, trace)
    if trace:
        got = lines[-1]["metrics"]
        # 2 of 8 experts are held: no step can touch more of them
        assert 0 < got["moe.experts_touched.agent"]["value"] <= 2
        assert 1 <= got["moe.load_max_over_mean.mixed"]["value"] <= 2
        # contexts of 9-48 rows against a window of 8 in 4 layers of 5
        assert 20 < got["cache.rows_read_share.mixed"]["value"] < 100
        # 4 rings of 16 rows beside one layer of 128
        assert got["cache.ring_bytes_share.mixed"]["value"] == \
            pytest.approx(100 * 4 * 16 / (4 * 16 + 128))
        for name in ("decode_step_roofline.agent",
                     "prefill_chunk.device_ms.agent",
                     "engine.prefill_share.agent"):
            assert name not in got, name


def test_readers_leave_their_metrics_out_where_no_span_is():
    """A program without the ``cache:rows`` span (the parent of the PR that
    added it) gives the readers nothing, and they raise nothing."""
    import types

    from perfbench import cache_rows
    run = types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                _ring_spans=[
        {"name": "moe:load", "ts": 1e6, "dur": 2e6, "args": {"steps": 3}}])
    assert cache_rows.window_sums(run) is None
    for name in ("cache.rows_read_share.mixed",
                 "cache.ring_bytes_share.mixed"):
        assert mf.metric_reader(name)(run) is None
    run = types.SimpleNamespace(stamps={"open": 0.0, "close": 45.0},
                                _ring_spans=[
        {"name": "cache:rows", "ts": 1e6, "dur": 2e6, "args": {
            "steps": 10, "rows_read": 300, "rows_if_full": 400,
            "bytes_full": 100, "bytes_ring": 300}},
        {"name": "cache:rows", "ts": 3e6, "dur": 2e6, "args": {
            "steps": 10, "rows_read": 100, "rows_if_full": 400,
            "bytes_full": 100, "bytes_ring": 300}},
        {"name": "cache:rows", "ts": 44e6, "dur": 2e6, "args": {
            "steps": 10, "rows_read": 1, "rows_if_full": 1}}])
    assert mf.metric_reader("cache.rows_read_share.mixed")(run) == 50.0
    assert mf.metric_reader("cache.ring_bytes_share.mixed")(run) == 75.0


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


@pytest.mark.parametrize("scale", [1.0, 2.0])
def test_query_and_key_scales_are_the_files(real, tiny, scale):
    """``assumed.qk_norm_scale`` is every ``q_norm`` and ``k_norm`` entry
    of every layer, dense and expert runs alike, and the reference's scores
    are that much wider: their standard deviation is the scale squared."""
    assert real[0]["assumed"]["qk_norm_scale"] == 2.0
    c, fam = tiny
    c = dict(c, assumed=dict(c["assumed"], qk_norm_scale=scale))
    params = fam.model.make(weights.key_of(4), c, jnp.float32)
    for run in ("dense_layers", "layers"):
        for name in ("q_norm", "k_norm"):
            a = np.asarray(params[run][name])
            assert a.shape[-1] == c["head_dim"] and (a == scale).all()
    lp = jax.tree_util.tree_map(lambda a: a[0], params["dense_layers"])
    y = jax.random.normal(jax.random.PRNGKey(0), (1, 256, c["hidden_size"]))
    q, k = (fam.model._rms(jnp.einsum("bsd,dhk->bhsk", y, lp[w]), lp[n],
                           c["rms_norm_eps"])
            for w, n in (("wq", "q_norm"), ("wk", "k_norm")))
    scores = jnp.einsum("bhsk,bhtk->bhst", q, k[:, :1]) \
        / np.sqrt(c["head_dim"])
    assert float(scores.std()) == pytest.approx(scale ** 2, rel=0.15)


def test_limits_files_say_where_their_readings_come_from():
    here = os.path.join(mf.BENCH_DIR, "limits", REAL_CELL + ".json")
    with open(here) as f:
        body = json.load(f)
    for name, limit in body["limits"].items():
        r = body["readings"][name]
        assert r["program_seeds"] >= 8 and r["control_seeds"] >= 3
        assert r["program_largest"] < limit < r["control_smallest"], name
    assert "why" in body and "how" in body
