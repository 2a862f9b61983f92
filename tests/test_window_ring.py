"""Window layers beside full layers: two kinds of cached state behind the
same functions (`models/generate.py`), a head size of its own, gated GQA with
query/key norms, sandwich norms, an embedding multiplier, and an expert layer
that holds a share of its experts, on the CPU at a tiny size.

The oracle is `forward` (whole sequence, the plain attention with a window
mask written out: no cache, no ring) and, for streams through the engine,
`models.generate` (tests/greedy_reference.py).  The family's independent
float32 reference is compared in tests/benchmark/test_perfbench_family_afmoe.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greedy_reference import greedy_stream
from ray_tpu.models import (TransformerConfig, cache_gather_slot,
                            cache_insert_slot, decode_step_slots, forward,
                            init_kv_cache, init_params, init_slot_cache,
                            prefill, prefill_chunk_jit)
from ray_tpu.models.generate import (_ring_mask, _ring_write_chunk,
                                     cache_arrays, cache_bytes,
                                     cache_capacity, cache_rows, window_ring)
from ray_tpu.models.transformer import count_params, decode_flops_per_token
from ray_tpu.ops.attention import multi_head_attention, reference_attention
from ray_tpu.ops.moe import routed_ffn, sigmoid_route

TOL = 2e-5
WINDOW, ROOM = 8, 4


def tiny(**kw) -> TransformerConfig:
    base = dict(
        vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_size=24, d_ff=160, max_seq_len=128, pos_emb="rope",
        rope_base=1e4, rope_layers="window", activation="swiglu",
        norm="rmsnorm", norm_eps=1e-5, tie_embeddings=False, remat=False,
        qk_norm=True, attn_gate=True, sandwich_norm=True, embed_scale=8.0,
        layer_kinds=("window",) * 4 + ("full",), sliding_window=WINDOW,
        window_chunk=ROOM, n_experts=8, experts_held=2, expert_offset=4,
        expert_top_k=2, router="sigmoid", moe_d_ff=32, n_shared_experts=1,
        routed_scaling_factor=2.448, first_dense_layers=1,
        dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference")
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    # a bias that changes choices, norms that are no identity
    params["layers"]["router_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(7), params["layers"]["router_bias"].shape)
    for run in ("dense_layers", "layers"):
        for i, name in enumerate(("q_norm", "k_norm", "post_attn_norm",
                                  "post_mlp_norm")):
            params[run][name] = 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(20 + i), params[run][name].shape)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 72), 0, 256)
    return cfg, params, axes, toks, forward(params, toks, cfg)


def test_pattern_is_cut_where_the_kind_changes(model):
    cfg, params, axes, _, _ = model
    assert cfg.layer_runs == (("dense_layers", 1), ("layers", 4))
    # the kind repeats INSIDE the run of expert layers: two segments of it
    assert cfg.layer_segments == (("dense_layers", 0, 1, "window"),
                                  ("layers", 0, 3, "window"),
                                  ("layers", 3, 1, "full"))
    period = tiny(n_layers=9, first_dense_layers=0, layer_kinds=(
        "window", "window", "full") * 3)
    assert [s[1:] for s in period.layer_segments] == [
        (0, 2, "window"), (2, 1, "full"), (3, 2, "window"), (5, 1, "full"),
        (6, 2, "window"), (8, 1, "full")]
    # a model of one kind has one segment a run, as before
    assert TransformerConfig.tiny().layer_segments == (
        ("layers", 0, 2, "full"),)
    assert cfg.head_dim == 24 != cfg.d_model // cfg.n_heads
    assert cfg.rotates("window") and not cfg.rotates("full")
    assert TransformerConfig.tiny().rotates("full")
    # queries and the gate are heads x head_size wide, not d_model
    lay = params["layers"]
    assert lay["wq"].shape == lay["wg"].shape == (4, 64, 4, 24)
    assert lay["wk"].shape == (4, 64, 2, 24) and lay["wo"].shape == (
        4, 4, 24, 64)
    # the router scores all 8 experts, the stacks hold this chip's 2
    assert lay["router"].shape == (4, 64, 8) and lay["router_bias"].shape \
        == (4, 8)
    assert lay["w_in"].shape == (4, 2, 64, 32)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


def test_counts_by_hand(model):
    cfg, params, _, _, _ = model
    attn = 3 * 64 * 4 * 24 + 2 * 64 * 2 * 24
    norms = 4 * 64 + 2 * 24
    expert = 3 * 64 * 32
    held = (attn + 3 * 64 * 160 + norms) + 4 * (
        attn + 3 * expert + 64 * 8 + 8 + norms) + 2 * 256 * 64 + 64
    assert count_params(cfg) == held == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    # of a token's 2 experts a quarter is held here: half an expert
    active = (attn + 3 * 64 * 160) + 4 * (attn + 1.5 * expert + 64 * 8) \
        + 256 * 64
    # at depth 50 a window layer reads its window, the full layer all 50
    assert decode_flops_per_token(cfg, 50) == \
        2 * active + 2 * 2 * 4 * 24 * (50 + 4 * WINDOW)
    assert decode_flops_per_token(cfg, 5) == \
        2 * active + 2 * 2 * 4 * 24 * 5 * 5


def test_a_cache_has_two_kinds_of_state(model):
    cfg = model[0]
    assert cache_rows(cfg) == {"k": (2, 24), "v": (2, 24),
                               "k_win": (2, 24), "v_win": (2, 24)}
    assert cache_rows(TransformerConfig.tiny()) == {"k": (2, 16),
                                                    "v": (2, 16)}
    cache = init_slot_cache(cfg, 3, 64)
    shapes = {n: a.shape for n, a in cache_arrays(cache).items()}
    # the window layers' ring: window + the widest chunk, whatever max_len
    assert shapes == {"k": (1, 3, 2, 24, 64), "v": (1, 3, 2, 24, 64),
                      "k_win": (4, 3, 2, 24, 12), "v_win": (4, 3, 2, 24, 12)}
    assert window_ring(cfg, 64) == window_ring(cfg, 10_000) == 12
    assert window_ring(cfg, 10) == 10       # never more than the context
    assert cache_capacity(cache) == 64
    assert cache_bytes(cache) == {"full": 2 * 3 * 2 * 24 * 64 * 4,
                                  "ring": 2 * 4 * 3 * 2 * 24 * 12 * 4,
                                  "state": 0}      # no conv layer here
    assert init_kv_cache(cfg, 1, 64)["k_win"].shape == (4, 1, 2, 24, 12)
    one_kind = init_slot_cache(TransformerConfig.tiny(), 3, 64)
    assert cache_bytes(one_kind)["ring"] == 0


@pytest.mark.parametrize("pos", [0, 3, 7, 8, 11, 12, 13, 23, 24, 40, 100])
@pytest.mark.parametrize("c", [1, 3, 4])
def test_ring_mask_is_the_window_over_the_positions_columns_hold(pos, c):
    """Brute force: write positions 0 .. pos + c - 1 into a ring of 12
    columns in order, then ask which columns each new token may see."""
    ring = WINDOW + ROOM
    held = np.full(ring, -1)
    for p in range(pos + c):
        held[p % ring] = p
    got = np.asarray(_ring_mask(jnp.int32(pos), c, ring, WINDOW))
    for i in range(c):
        q = pos + i
        want = (held >= 0) & (held <= q) & (q - held < WINDOW)
        assert (got[i] == want).all(), (pos, i)
        # every position of the window is there to be seen
        assert want.sum() == min(q + 1, WINDOW)
    per_slot = np.asarray(_ring_mask(jnp.asarray([pos, 0, 5]), c, ring,
                                     WINDOW))
    assert per_slot.shape == (3, c, ring) and (per_slot[0] == got).all()


@pytest.mark.parametrize("pos", [0, 5, 8, 9, 10, 11, 12, 21, 23, 35])
@pytest.mark.parametrize("c", [1, 2, 4])
def test_a_chunk_that_straddles_the_seam_is_written_in_two_pieces(pos, c):
    ring = WINDOW + ROOM
    before = jnp.arange(2 * 1 * 2 * 3 * ring, dtype=jnp.float32).reshape(
        2, 1, 2, 3, ring)
    cols = -1.0 - jnp.arange(1 * 2 * 3 * c, dtype=jnp.float32).reshape(
        1, 2, 3, c)
    got = jax.jit(lambda a, p: _ring_write_chunk(p, c, ring)(a, 1, cols))(
        before, jnp.int32(pos))
    want = np.array(before)
    for i in range(c):
        want[1, ..., (pos + i) % ring] = np.asarray(cols)[..., i]
    assert (np.asarray(got) == want).all()


def test_window_mask_of_the_plain_attention():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 20, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 20, 2, 8))
    got = multi_head_attention(q, k, v, window=5)
    for i in (0, 4, 5, 19):
        lo = max(0, i - 4)
        row = reference_attention(q[:, i:i + 1], k[:, lo:i + 1],
                                  v[:, lo:i + 1], causal=False)
        assert float(jnp.abs(row[:, 0] - got[:, i]).max()) < 1e-5
    # a sequence inside the window is plain causal attention
    assert float(jnp.abs(multi_head_attention(q, k, v, window=20)
                         - multi_head_attention(q, k, v)).max()) == 0.0
    with pytest.raises(NotImplementedError, match="window"):
        multi_head_attention(q, k, v, window=5, impl="flash")
    with pytest.raises(ValueError, match="causal"):
        multi_head_attention(q, k, v, window=5, causal=False)


def test_held_experts_compute_their_own_part_and_count_it():
    d, f, E, k, n = 16, 8, 8, 2, 40
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    y = jax.random.normal(keys[0], (n, d))
    router = jax.random.normal(keys[1], (d, E))
    bias = 0.1 * jax.random.normal(keys[2], (E,))
    w_in, w_gate = (jax.random.normal(kk, (E, d, f)) for kk in keys[3:5])
    w_out = jax.random.normal(keys[5], (E, f, d))
    idx, w = sigmoid_route(y, router, bias, k, 2.0)
    whole, load = routed_ffn(y, idx, w, w_in, w_out, w_gate)
    assert int(load.pairs) == n * k
    parts, landed = 0, 0
    for off in (0, 2, 4, 6):
        part, load = routed_ffn(y, idx, w, w_in[off:off + 2],
                                w_out[off:off + 2], w_gate[off:off + 2],
                                expert_offset=off)
        counts = np.bincount(np.asarray(idx).ravel(), minlength=E)[off:off + 2]
        assert int(load.pairs) == counts.sum()
        assert int(load.load_max) == counts.max()
        assert int(load.experts_touched) == (counts > 0).sum()
        parts, landed = parts + part, landed + int(load.pairs)
    assert landed == n * k
    assert float(jnp.abs(parts - whole).max()) < 1e-4
    # a token none of whose experts is held gets nothing, not garbage
    none = np.asarray((idx < 6).all(-1))
    part, _ = routed_ffn(y, idx, w, w_in[6:], w_out[6:], w_gate[6:],
                         expert_offset=6)
    assert none.any() and float(jnp.abs(part[none]).max()) == 0.0
    # rows that do not count land nowhere either
    valid = jnp.arange(n) < 7
    part, load = routed_ffn(y, idx, w, w_in[:4], w_out[:4], w_gate[:4], valid,
                            expert_offset=0)
    assert int(load.pairs) == int((np.asarray(idx)[:7] < 4).sum())
    assert float(jnp.abs(part[7:]).max()) == 0.0


def _walk(cfg, params, toks, widths, max_len=128):
    """Batch-1 walk of ``toks`` [1, s] in chunks of ``widths``."""
    cache, off, out = init_kv_cache(cfg, 1, max_len), 0, {}
    for take in widths:
        lg, cache = prefill_chunk_jit(params, toks[:, off:off + take], cache,
                                      cfg=cfg)
        off += take
        out[off - 1] = lg[0]
    return out, cache


@pytest.mark.parametrize("widths", [
    [4] * 18, [1] + [4] * 17 + [1] * 3, [3, 4, 4, 2, 1, 4, 4, 4, 3, 4] + [4] * 9,
    [1] * 30])
def test_chunks_over_rings_are_the_whole_sequence(model, widths):
    """Sessions of up to 72 positions cross the window of 8 and wrap the
    ring of 12 rows six times; widths and offsets that are no multiple of
    the ring make chunks straddle its seam."""
    cfg, params, _, toks, want = model
    got, cache = _walk(cfg, params, toks[:1], widths)
    assert int(cache["pos"]) == sum(widths)
    for p, lg in got.items():
        assert float(jnp.abs(lg - want[0, p]).max()) < TOL, p


def test_a_padded_chunk_writes_ahead_of_pos_and_harms_nothing(model):
    """The engine's one program: ``n_valid`` real rows, the rest padding
    that is written ahead of ``pos`` into the ring (over positions no
    later query's window reaches) and routed to no expert."""
    cfg, params, _, toks, want = model
    cache = init_kv_cache(cfg, 1, 128)
    off = 0
    for n_valid in (4, 4, 4, 4, 4, 3):               # 23 tokens
        buf = np.full((1, 4), 99, np.int32)
        buf[0, :n_valid] = np.asarray(toks[0, off:off + n_valid])
        lg, cache = prefill_chunk_jit(params, buf, cache, cfg=cfg,
                                      n_valid=np.int32(n_valid))
        off += n_valid
        assert float(jnp.abs(lg[0] - want[0, off - 1]).max()) < TOL
    for p in range(23, 40):                           # then single tokens
        lg, cache = prefill_chunk_jit(params, toks[:1, p:p + 1], cache,
                                      cfg=cfg)
        assert float(jnp.abs(lg[0] - want[0, p]).max()) < TOL, p


def test_slots_at_different_depths_and_a_slot_reused(model):
    cfg, params, _, toks, want = model
    slots = init_slot_cache(cfg, 3, 128)
    insert = jax.jit(cache_insert_slot)
    _, a = _walk(cfg, params, toks[:1], [4] * 10)           # 40 positions
    _, b = _walk(cfg, params, toks[1:], [3])                # 3 positions
    slots = insert(insert(slots, a, jnp.int32(2)), b, jnp.int32(0))
    step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
    active = jnp.asarray([True, False, True])
    for j in range(32):
        tok = jnp.asarray([toks[1, 3 + j], 7, toks[0, 40 + j]])
        lg, slots = step(params, tok, slots, active)
        assert float(jnp.abs(lg[0] - want[1, 3 + j]).max()) < TOL, j
        assert float(jnp.abs(lg[2] - want[0, 40 + j]).max()) < TOL, j
    assert [int(p) for p in slots["pos"]] == [35, 0, 72]
    # slot 2 held 72 positions (its rings wrapped six times); a session of
    # 5 positions takes it over and sees nothing of them
    _, short = _walk(cfg, params, toks[1:], [4, 1])
    slots = insert(slots, short, jnp.int32(2))
    for j in range(20):
        tok = jnp.asarray([0, 0, toks[1, 5 + j]])
        lg, slots = step(params, tok, slots, jnp.asarray([False, False, True]))
        assert float(jnp.abs(lg[2] - want[1, 5 + j]).max()) < TOL, j


@pytest.mark.parametrize("s", [5, 12, 13, 30, 61])
def test_whole_prompt_prefill_fills_the_ring_at_its_positions(model, s):
    """`prefill` writes a prompt longer than the ring as the ring would
    hold it after that many single writes: the last 12 positions, each at
    its position mod 12; decode goes on from there."""
    cfg, params, _, toks, want = model
    lg, cache = jax.jit(lambda p, t, c: prefill(p, t, cfg, c))(
        params, toks[:1, :s], init_kv_cache(cfg, 1, 128))
    assert float(jnp.abs(lg[0] - want[0, s - 1]).max()) < TOL
    _, walked = _walk(cfg, params, toks[:1], [1] * s)
    ring = cache["k_win"].shape[-1]
    cols = [p % ring for p in range(max(0, s - ring), s)]
    for name in ("k_win", "v_win"):
        assert float(jnp.abs(cache[name][..., cols]
                             - walked[name][..., cols]).max()) < TOL
    for p in range(s, min(s + 14, 72)):
        lg, cache = prefill_chunk_jit(params, toks[:1, p:p + 1], cache,
                                      cfg=cfg)
        assert float(jnp.abs(lg[0] - want[0, p]).max()) < TOL, p


def test_what_a_ring_cannot_serve_is_refused_not_answered(model):
    cfg, params, _, toks, _ = model
    with pytest.raises(ValueError, match="window_chunk"):
        prefill_chunk_jit(params, toks[:1, :5], init_kv_cache(cfg, 1, 128),
                          cfg=cfg)
    only = dataclasses.replace(cfg, layer_kinds=("window",) * 5)
    with pytest.raises(NotImplementedError, match="window layers only"):
        prefill_chunk_jit(params, toks[:1, :4], init_kv_cache(only, 1, 128),
                          cfg=only)
    bad = dataclasses.replace(cfg, layer_kinds=("window", "full"))
    with pytest.raises(ValueError, match="layer_kinds"):
        prefill_chunk_jit(params, toks[:1, :4], init_kv_cache(cfg, 1, 128),
                          cfg=bad)


# ------------------------------------------------------- through the engine

def _stream(core, prompt, n):
    r = core.handle({"op": "start", "prompt": prompt})
    assert "error" not in r, r
    toks = list(r["token"])
    while len(toks) < n:
        out = core.handle({"op": "next_chunk", "sid": r["sid"],
                           "max_tokens": n - len(toks)})
        assert "error" not in out, out
        toks += out["tokens"]
        if out.get("done"):
            break
    core.handle({"op": "end", "sid": r["sid"]})
    return toks[:n]


@pytest.fixture(scope="module")
def core(model):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg, params = model[0], model[1]
    core = DecodeSessionCore(cfg, max_len=96, params=params,
                             engine=DecodeEngineConfig(max_slots=2))
    yield core
    core.engine.shutdown()


def test_engine_serves_over_rings_and_says_what_its_cache_holds(model, core):
    cfg, params = model[0], model[1]
    eng = core.engine
    # the chunk width is cut to the room a ring leaves beside its window
    assert eng.ecfg.prefill_chunk_tokens == ROOM
    prompts = [list(range(3, 40)), list(range(50, 59)),
               list(range(100, 130))]
    want = [greedy_stream(cfg, p, 20, max_len=96, params=params)
            for p in prompts]
    assert [_stream(core, p, 20) for p in prompts] == want
    st = eng.stats()
    assert st["cache_copies"] == 0
    assert {s for s in st["program_shapes"] if "prefill" in str(s)} \
        and st["prefill_chunk_tokens"] == ROOM
    cache = st["cache"]
    # bytes by state kind: one full layer of 96 rows, four rings of 12
    row = 2 * 2 * 24 * 4                           # K and V, 2 heads of 24
    assert cache["bytes_full"] == 2 * 96 * row
    assert cache["bytes_ring"] == 4 * 2 * 12 * row
    assert cache["bytes"] == cache["bytes_full"] + cache["bytes_ring"]
    assert cache["bytes_per_position"] == row      # a ring grows by nothing
    # rows the decode steps attended: each step of a slot at depth p reads
    # p + 1 rows of the full layer and, every prompt here being past the
    # window, 8 of each window layer (the engine decodes ahead of its
    # callers, so the steps are at least theirs)
    depth = sum(len(p) + j + 1 for p in prompts for j in range(19))
    assert cache["steps"] == st["steps"] >= 3 * 19
    assert cache["rows_if_full"] >= 5 * depth
    assert cache["rows_read"] == cache["rows_if_full"] // 5 \
        + 4 * WINDOW * st["tokens"]
    # held experts: 2 of 8, and only the pairs that landed on them
    assert st["moe"]["experts"] == 2 and st["moe"]["layers"] == 4
    assert 0 < st["moe"]["pairs"] < st["tokens"] * 2 * 4
    assert st["moe"]["experts_touched"] <= 2 * 4 * st["moe"]["steps"]


def test_engine_writes_cache_rows_spans(model, core, monkeypatch):
    from ray_tpu.serve.decode_session import ContinuousBatchingEngine
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    before = len([e for e in tracing.span_events()
                  if e["name"] == "cache:rows"])
    _stream(core, list(range(30)), 8)
    rows = [e for e in tracing.span_events() if e["name"] == "cache:rows"]
    assert len(rows) - before >= 6
    last = rows[-1]
    assert last["cat"] == "cache"
    args = last["args"]
    # one step of one slot past its window: 5 layers' rows if all were
    # full, the full layer's and four windows' as it is
    assert args["steps"] == 1 and args["rows_if_full"] % 5 == 0
    depth = args["rows_if_full"] // 5
    assert args["rows_read"] == depth + 4 * WINDOW
    assert args["bytes_ring"] == core.engine.stats()["cache"]["bytes_ring"]
    loads = [e for e in tracing.span_events() if e["name"] == "moe:load"]
    assert loads[-1]["args"]["experts"] == 2


def test_prefix_reuse_only_where_the_donors_ring_is_still_exact(model):
    """A donor whose whole context still fits its window serves a shared
    prefix; one that has decoded on past the prefix and the window has
    forgotten rows the prefix's last positions need: refused, and the
    prompt prefills from its start.  Either way the stream is exact."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg, params = model[0], model[1]
    # a short token queue: the engine decodes AHEAD of its caller until
    # the queue is full, and under six test workers a donor of 5 tokens
    # had run past the window of 8 before its caller's `end` arrived
    core = DecodeSessionCore(cfg, max_len=96, params=params,
                             engine=DecodeEngineConfig(
                                 max_slots=2, prefix_cache_min_tokens=2,
                                 token_queue_depth=2))
    try:
        def hits():
            return core.engine.stats()["prefix"]["applied_hits"]
        short = [9, 8, 7, 6]
        a = _stream(core, short + [1], 3)           # donor ends at 4 + 1 + 2
        assert a == greedy_stream(cfg, short + [1], 3, max_len=96,
                                  params=params)
        b = _stream(core, short + [2, 3], 6)
        assert hits() == 1                          # 7 <= window: all there
        assert b == greedy_stream(cfg, short + [2, 3], 6, max_len=96,
                                  params=params)
        system = list(range(40, 60))                # 20 > window
        c = _stream(core, system + [1], 12)         # donor decodes to 32
        assert c == greedy_stream(cfg, system + [1], 12, max_len=96,
                                  params=params)
        d = _stream(core, system + [2, 3], 6)
        assert hits() == 1                          # refused: ring moved on
        assert d == greedy_stream(cfg, system + [2, 3], 6, max_len=96,
                                  params=params)
        assert core.engine.stats()["cache_copies"] == 0
    finally:
        core.engine.shutdown()


def test_gathered_prefix_is_exact_while_the_donor_stands_at_it(model):
    """`cache_gather_slot` of a donor that stands right at the prefix: the
    seeded batch-1 cache continues as the donor's own context would."""
    cfg, params, _, toks, want = model
    _, a = _walk(cfg, params, toks[:1], [4] * 7 + [1] * 2)   # 30 positions
    slots = jax.jit(cache_insert_slot)(init_slot_cache(cfg, 2, 128), a,
                                       jnp.int32(1))
    seeded = jax.jit(cache_gather_slot)(slots, jnp.int32(1), jnp.int32(29))
    assert int(seeded["pos"]) == 29
    for p in range(29, 44):
        lg, seeded = prefill_chunk_jit(params, toks[:1, p:p + 1], seeded,
                                       cfg=cfg)
        assert float(jnp.abs(lg[0] - want[0, p]).max()) < TOL, p
