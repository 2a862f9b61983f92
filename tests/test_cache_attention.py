"""`ops/cache_attention.py`: a decode step's attention over the cache blocks
a slot sees, against the dense form over the same rows
(`ops/eva_attention.py` `attend_two`), through the Pallas interpreter at a
tiny size (rings of 384 rows holding windows of 256, 256 summary rows of 4
positions), and the work list against the masks it is built from."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.generate import _ring_mask
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
    assert not ca.kernel_shape((2, 8) + q.shape[2:], sets)    # a chunk
    assert not ca.kernel_shape(q.shape, [(k_r[..., :200], v_r[..., :200],
                                          m_r[..., :200])])
    assert not ca.kernel_shape(q.shape, [(k_r[:, :, :, :12], v_r, m_r)])
    # this process lowers for the CPU: the dense form runs
    assert not ca.engages(q.shape, sets)
