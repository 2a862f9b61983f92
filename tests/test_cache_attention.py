"""`ops/cache_attention.py`: a decode step's attention over the cache blocks
a slot sees, against the dense form over the same rows
(`ops/eva_attention.py` `attend_two`), through the Pallas interpreter at a
tiny size (rings of 384 rows holding windows of 256, 256 summary rows of 4
positions), and the work list against the masks it is built from; then the
same for a CHUNK's 128 queries a lane (`attend_chunk_blocks`: two sets as a
summary layer hands them, one set as `attend_mha` does, against its `heads`),
and the host's count of the rows a chunk program fetches
(`models/generate.py` `CacheTraffic.chunk`) against the kernel's own list."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, init_kv_cache
from ray_tpu.models.generate import CacheTraffic, _ring_mask
from ray_tpu.ops import cache_attention as ca
from ray_tpu.ops.eva_attention import attend_two, summary_mask

RING, WINDOW, SUMS, CHUNK = 384, 256, 256, 4
L, HK, HD = 2, 2, 16


def _masks(pos):
    pos = jnp.asarray(pos)
    return (_ring_mask(pos, 1, RING, WINDOW, block=True),
            summary_mask(pos[:, None], SUMS, WINDOW, CHUNK))


def _world(slots: int, g: int, seed: int = 0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (slots, 1, HK, g, HD), jnp.float32) * 2
    arrays = [jax.random.normal(k, (L, slots, HK, HD, t), jnp.float32)
              for k, t in zip(keys[1:], (RING, RING, SUMS, SUMS))]
    return q, arrays


#: positions a slot, and which slots run (None: all)
CASES = {
    "first_row_of_a_window": ([512, 256], None),
    "last_row_of_a_window": ([511, 767], None),
    # window 2 is positions 512..767: ring columns 128..383
    # window 1 is positions 256..511: columns 256..383 then 0..127 (wraps)
    "a_range_that_wraps_the_rings_end": ([500, 400], None),
    "no_summary_visible": ([3, 255], None),
    "every_summary_visible": ([1023, 1020], None),
    "a_slot_that_stands": ([300, 700, 40], [True, False, True]),
    "the_first_slot_stands": ([300, 700], [False, True]),
    "all_slots_stand": ([300, 700], [False, False]),
    "grouped_queries": ([300, 700, 40], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_dense_form_over_the_blocks_a_slot_sees(
        case, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    pos, live = CASES[case]
    g = 2 if case == "grouped_queries" else 1
    q, (k_r, v_r, k_s, v_s) = _world(len(pos), g)
    m_r, m_s = _masks(pos)
    l = 1
    sets = [(k_r, v_r, m_r), (k_s, v_s, m_s)]
    assert ca.kernel_shape(q.shape, sets) and ca.engages(q.shape, sets)
    live = None if live is None else jnp.asarray(live)
    got = jax.jit(lambda q, l, *a: ca.attend_blocks(
        q, [(a[0], a[1], m_r), (a[2], a[3], m_s)], l, live))(
            q, l, k_r, v_r, k_s, v_s)
    want = attend_two(q, k_r[l], v_r[l], k_s[l], v_s[l], m_r, m_s)
    if live is not None:
        want = jnp.where(live[:, None, None, None, None], want, 0.0)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_the_work_list_names_the_blocks_whose_mask_has_a_set_row():
    pos = [500, 40, 700, 1023]
    live = jnp.asarray([True, True, False, True])
    masks = _masks(pos)
    item, runs, held, items = jax.jit(ca.block_work)(masks, live)
    item, runs, held = np.asarray(item), np.asarray(runs), np.asarray(held)
    nb = (RING + SUMS) // ca.BLOCK
    want = []
    for s in range(len(pos)):
        seen = np.concatenate([np.asarray(m)[s, 0].reshape(-1, ca.BLOCK)
                               .any(-1) for m in masks])
        mine = [s * nb + b for b in np.flatnonzero(seen)] if live[s] else []
        want += mine or [-1 - s * nb]      # a slot that stands: one item
    # slot 0 (500: window 1 wraps the ring): ring blocks 0, 2, a summary;
    # slot 1: ring block 0 alone; slot 2 stands; slot 3: 2 + 2 blocks
    assert want == [0, 2, 3, 5, -1 - 10, 15, 16, 18, 19]
    assert int(items) == len(want)
    assert np.where(runs > 0, item, -1 - item)[:len(want)].tolist() == want
    # only an item's own set moves: the other set's map repeats a block
    assert held[0, :len(want)].tolist() == [0, 2, 2, 5, 5, 15, 16, 16, 16]
    assert held[1, :len(want)].tolist() == [3, 3, 3, 3, 3, 3, 3, 18, 19]
    # ... and past the length every entry names a block of its set
    assert set(held[0, len(want):]) == {0} \
        and set(held[1, len(want):]) == {RING // ca.BLOCK}
    # the host's count of the same blocks, from positions
    for s, p in enumerate(pos):
        seen = [np.asarray(m)[s, 0].reshape(-1, ca.BLOCK).any(-1).sum()
                for m in masks]
        start = p // WINDOW * WINDOW
        assert [ca.fetched_blocks(start % RING, p - start + 1, RING),
                ca.fetched_blocks(0, start // CHUNK, SUMS)] == seen


def test_what_the_kernel_takes():
    q, (k_r, v_r, k_s, v_s) = _world(2, 1)
    m_r, m_s = _masks([3, 300])
    sets = [(k_r, v_r, m_r), (k_s, v_s, m_s)]
    assert ca.kernel_shape(q.shape, sets)
    assert not ca.kernel_shape((2, 8) + q.shape[2:], sets)  # no lane tile
    assert not ca.kernel_shape(q.shape, [(k_r[..., :200], v_r[..., :200],
                                          m_r[..., :200])])
    assert not ca.kernel_shape(q.shape, [(k_r[:, :, :, :12], v_r, m_r)])
    # this process lowers for the CPU: the dense form runs
    assert not ca.engages(q.shape, sets)
    # a chunk of whole lane tiles has a kernel of its own; a sink, a ragged
    # block or width has none, and one query a slot is still the step's
    chunk = (2, C) + q.shape[2:]
    assert ca.kernel_shape(chunk, sets) and ca.kernel_shape(chunk, sets[:1])
    assert not ca.kernel_shape(chunk, sets, sink=True)
    assert not ca.kernel_shape(q.shape, sets, sink=True)
    assert not ca.kernel_shape(chunk, [(k_r[..., :200], v_r[..., :200],
                                        None)])
    assert not ca.kernel_shape(chunk, [(k_r[:, :, :, :12], v_r, None)])
    assert not ca.engages(chunk, sets)
    # ... and takes as many heads a grid step as the budget holds: all of
    # a tiny model's, half of 32 heads of 128 over two sets (the byte
    # cell's), 2 of 4 where a head has 16 x 128 queries of 192
    assert ca._chunk_heads(chunk, sets) == HK
    bf = jnp.bfloat16
    wide = [(jax.ShapeDtypeStruct((8, 4, 32, 128, t), bf),) * 2 + (None,)
            for t in (2176, 1664)]
    assert ca._chunk_heads((4, 128, 32, 1, 128), wide) == 16
    tall = [(jax.ShapeDtypeStruct((2, 4, 4, 192, 9728), bf),
             jax.ShapeDtypeStruct((2, 4, 4, 128, 9728), bf), None)]
    assert ca._chunk_heads((4, 128, 4, 16, 192), tall) == 2


# ------------------------------------------------ a chunk's queries a lane

C = 128


def _chunk_masks(pos):
    """A summary layer's two masks for a chunk of C from each ``pos``."""
    pos = jnp.asarray(pos)
    return (_ring_mask(pos, C, RING, WINDOW, block=True),
            summary_mask(pos[:, None] + jnp.arange(C), SUMS, WINDOW, CHUNK))


def _heads(q, ck, cv, m):
    """`models/generate.py` `attend_mha`'s dense form over one set (no
    sink), float32."""
    scores = jnp.einsum("bskgd,bkdt->bskgt", q, ck) / jnp.sqrt(
        float(q.shape[-1]))
    scores = jnp.where(m[:, :, None, None, :], scores, -1e30)
    return jnp.einsum("bskgt,bkdt->bskgd", jax.nn.softmax(scores, axis=-1),
                      cv)


def _chunk_world(lanes, g, hd, vd, rows, hk=HK, seed=0):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1 + 2 * len(rows)))
    q = jax.random.normal(next(keys), (lanes, C, hk, g, hd), jnp.float32) * 2
    return q, [a for t in rows for a in (
        jax.random.normal(next(keys), (L, lanes, hk, hd, t), jnp.float32),
        jax.random.normal(next(keys), (L, lanes, hk, vd, t), jnp.float32))]


#: two sets (a ring and summaries): positions a lane, which lanes run
TWO_SETS = {
    # window 1 is positions 256..511: ring columns 256..383, then 0..127;
    # the chunk from 400 sees both sides of the seam, and summaries
    "a_ring_whose_visible_rows_wrap_the_seam": ([400, 640], None),
    "lanes_at_different_positions": ([0, 128, 896], None),
    # 200..327 lies in windows 0 and 1: the later queries see other rows
    "a_chunk_across_a_window_boundary": ([200, 712], None),
    "a_standing_lane": ([300, 700, 40], [True, False, True]),
    "the_first_lane_stands": ([300, 700], [False, True]),
    "every_lane_stands": ([300, 700], [False, False]),
}


@pytest.mark.parametrize("case", sorted(TWO_SETS))
def test_the_chunk_kernel_is_the_dense_form_over_two_sets(case, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    pos, live = TWO_SETS[case]
    q, (k_r, v_r, k_s, v_s) = _chunk_world(len(pos), 1, HD, HD, (RING, SUMS))
    m_r, m_s = _chunk_masks(pos)
    sets = [(k_r, v_r, m_r), (k_s, v_s, m_s)]
    assert ca.kernel_shape(q.shape, sets) and ca.engages(q.shape, sets)
    live = None if live is None else jnp.asarray(live)
    got = jax.jit(lambda q, l, *a: ca.attend_chunk_blocks(
        q, [(a[0], a[1], m_r), (a[2], a[3], m_s)], l, live))(
            q, 1, k_r, v_r, k_s, v_s)
    want = attend_two(q, k_r[1], v_r[1], k_s[1], v_s[1], m_r, m_s)
    if live is not None:
        want = jnp.where(live[:, None, None, None, None], want, 0.0)
        # a lane that stands is ONE item that runs nothing
        item, runs, _, items = ca.block_work([m_r, m_s], live)
        nb = (RING + SUMS) // ca.BLOCK
        for lane in np.flatnonzero(~np.asarray(live)):
            mine = np.asarray(item[:int(items)]) // nb == lane
            assert mine.sum() == 1 and not np.asarray(runs)[:int(items)][mine]
            assert not np.asarray(got[lane]).any()
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)


#: one set, as `attend_mha` hands it: (query heads a key-value head, key
#: width, value width, rows, the mask's kind, positions, which lanes run)
ONE_SET = {
    "six_query_heads_a_key_value_head": (6, 16, 16, 512, "full",
                                         [0, 200, 384], None),
    "heads_of_64": (1, 64, 64, 256, "full", [128, 17], None),
    "keys_of_192_values_of_128": (2, 192, 128, 384, "full", [250, 3], None),
    "a_window_ring": (2, 16, 16, 384, "window", [40, 300, 1000], None),
    "a_standing_lane_of_one_set": (2, 16, 16, 256, "full", [128, 0],
                                   [False, True]),
}


@pytest.mark.parametrize("case", sorted(ONE_SET))
def test_the_chunk_kernel_is_the_dense_form_over_one_set(case, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    g, hd, vd, rows, kind, pos, live = ONE_SET[case]
    q, (k, v) = _chunk_world(len(pos), g, hd, vd, (rows,))
    at = jnp.asarray(pos)
    mask = _ring_mask(at, C, rows, WINDOW) if kind == "window" else (
        jnp.arange(rows)[None, None, :]
        <= (at[:, None] + jnp.arange(C))[:, :, None])
    assert ca.engages(q.shape, [(k, v, mask)])
    live = None if live is None else jnp.asarray(live)
    got = jax.jit(lambda q, k, v: ca.attend_chunk_blocks(
        q, [(k, v, mask)], 0, live))(q, k, v)
    want = _heads(q, k[0], v[0], mask)
    if live is not None:
        want = jnp.where(live[:, None, None, None, None], want, 0.0)
    assert got.shape == want.shape == (len(pos), C, HK, g, vd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_one_mask_serves_every_row_of_a_lone_chunk_program(monkeypatch):
    """A batch of two rows at ONE position (``prefill_chunk`` of a batch):
    the mask ``[1, C, T]`` is every row's."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, (k, v) = _chunk_world(2, 2, HD, HD, (256,))
    mask = (jnp.arange(256)[None, :] <= (64 + jnp.arange(C))[:, None])[None]
    got = jax.jit(lambda q, k, v: ca.attend_chunk_blocks(
        q, [(k, v, mask)], 1))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_heads(q, k[1], v[1], mask)),
        atol=2e-5, rtol=0)


def test_the_work_list_of_a_chunk_names_the_blocks_some_query_sees():
    pos = [400, 0, 700, 200]
    live = jnp.asarray([True, True, False, True])
    masks = _chunk_masks(pos)
    item, runs, held, items = jax.jit(ca.block_work)(masks, live)
    item, runs = np.asarray(item), np.asarray(runs)
    nb = (RING + SUMS) // ca.BLOCK
    want = []
    for s in range(len(pos)):
        seen = np.concatenate([
            np.asarray(m)[s].reshape(C, -1, ca.BLOCK).any((0, 2))
            for m in masks])
        mine = [s * nb + b for b in np.flatnonzero(seen)] if live[s] else []
        want += mine or [-1 - s * nb]
    # lane 0 (400..527: windows 1 and 2): window 1's rows 256..527 lie in
    # ring columns 256..383 and 0..143, so all three ring blocks, and the
    # first window's 64 summaries for the queries past 512; lane 1: its
    # own rows alone; lane 2 stands; lane 3 (200..327): positions 0..327
    assert want == [0, 1, 2, 3, 5, -1 - 10, 15, 16, 17, 18]
    assert int(items) == len(want)
    assert np.where(runs > 0, item, -1 - item)[:len(want)].tolist() == want


def _tiny(kinds, **more):
    return TransformerConfig(
        vocab_size=64, d_model=32, n_layers=len(kinds), n_heads=4,
        n_kv_heads=2, head_size=16, d_ff=64, max_seq_len=1024,
        pos_emb="rope", rope_base=1e4, activation="swiglu", norm="rmsnorm",
        tie_embeddings=False, remat=False, layer_kinds=kinds,
        dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference", **more)


@pytest.mark.parametrize("kinds", ["summaries", "window_and_full"])
def test_the_hosts_count_of_a_chunks_rows_is_the_kernels_own_list(
        kinds, monkeypatch):
    """`CacheTraffic.chunk` from positions: where the kernel engages, the
    length of the work list the same masks give, x 128, summed over the
    layers; where it does not, every row of the lane's arrays.  Read rows
    count the REAL queries' alone."""
    from ray_tpu.models.generate import _row_inputs, cache_arrays
    if kinds == "summaries":
        cfg = _tiny(("eva",) * 2, sliding_window=WINDOW, window_chunk=C,
                    summary_chunk=CHUNK)
        sets = lambda m: [[m["eva"], m["summary"]]] * 2
        arrays_rows = 2 * (RING + SUMS)
    else:
        cfg = _tiny(("window", "full", "window"), sliding_window=WINDOW,
                    window_chunk=C)
        sets = lambda m: [[m["window"]], [m["full"]], [m["window"]]]
        arrays_rows = 2 * RING + 1024
    cache = init_kv_cache(cfg, 1, 1024)
    assert {a.shape[-1] for a in cache_arrays(cache).values()} <= {
        RING, SUMS, 1024}
    dense = CacheTraffic(cache, cfg, C).chunk
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    engaged = CacheTraffic(cache, cfg, C).chunk
    rng = np.random.default_rng(5)
    drawn = [0, 128, 255, 256, 383, 384, 896] + rng.integers(
        0, 1024 - C, 12).tolist()
    for pos in drawn:
        n_valid = int(rng.integers(1, C + 1))
        fetched, read = engaged(pos, n_valid)
        assert dense(pos, n_valid) == (arrays_rows, read)
        masks = _row_inputs(
            {"embed": {"tok": jnp.zeros((64, 32))}},
            jnp.zeros((1, C), jnp.int32), jnp.asarray([pos]), cfg, 1024)[2]
        blocks = sum(int(ca.block_work(ms, None)[3]) for ms in sets(masks))
        assert fetched == blocks * ca.BLOCK, pos
        # the rows some real query sees: the masks' own columns
        seen = sum(int(np.asarray(m)[0, :n_valid].any(0).sum())
                   for ms in sets(masks) for m in ms)
        assert read == seen, (pos, n_valid)
        assert read <= fetched <= arrays_rows


def test_latent_layers_chunks_are_counted_dense_where_no_kernel_reads_them():
    """A model of latent layers at a shape `ops/latent_attention.py`'s
    kernel does not take (latents of 16): every row of the lane's layer is
    moved, and a real query sees the rows up to its own
    (tests/test_sparse_index.py has the count where the kernel engages)."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        max_seq_len=64, pos_emb="rope", attention="mla", q_lora_rank=8,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, dtype=jnp.float32, attention_impl="reference")
    count = CacheTraffic(init_kv_cache(cfg, 1, 64), cfg, 32).chunk
    assert count(0, 32) == (64, 32) and count(32, 5) == (64, 37)
