"""The grouped matmul kernel (`ray_tpu/ops/grouped_matmul.py`) through the
Pallas interpreter on the CPU, against `jax.lax.ragged_dot`: the kernel's
arithmetic, its work list and its backward pass.  That the chip's compiler
takes it at the served shapes is `tests/test_chip_compile.py`'s; what it
costs there is `PERF.md`'s.

Tolerances: inputs are scaled so that outputs are of order 1.  In float32
both sides sum the same products in another order (1e-4); in bfloat16 both
accumulate in float32 and round once, so they differ by a rounding of the
output at most (2 ** -7 of values up to 4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops.moe import routed_ffn, sigmoid_route

TOL = {"float32": 1e-4, "bfloat16": 4 * 2.0 ** -7}

# name: (m, k, n, group sizes, layers in the stack or None, the layer)
CASES = {
    "empty_groups_start_middle_end": (32, 128, 256, [0, 0, 5, 0, 3, 0, 7, 0, 0],
                                      None, 0),
    "rows_past_the_last_group": (64, 128, 128, [4, 0, 9, 2], None, 0),
    "one_group_holds_most_rows": (64, 128, 256,
                                  [13, 1, 1, 4, 1, 0, 2, 1, 0, 38, 1, 2],
                                  None, 0),
    "groups_across_row_tiles": (256, 128, 128, [100, 60, 3, 0, 90], None, 0),
    "every_row_in_a_group": (128, 64, 128, [32, 32, 32, 32], None, 0),
    "a_single_pair": (4, 128, 256, [0, 1, 0], None, 0),
    "no_pair_at_all": (16, 128, 128, [0, 0, 0], None, 0),
    "m_not_a_multiple_of_the_row_tile": (300, 128, 256, [100, 0, 150, 3],
                                         None, 0),
    "a_token_of_four_pairs": (4, 64, 96, [1, 0, 2, 1], None, 0),
    "stack_middle_layer": (48, 128, 256, [0, 7, 0, 20, 5], 3, 1),
    "stack_last_layer": (48, 128, 256, [11, 0, 0, 1, 30], 4, 3),
    "glm_up": (32, 2048, 1536, [3, 0, 14], None, 0),
    "glm_down": (32, 1536, 2048, [0, 9, 8], 2, 1),
    "trinity": (16, 3072, 3072, [2, 0, 13], 2, 0),
}


def _inputs(m, k, n, sizes, n_layers, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(len(sizes) + m))
    lhs = jax.random.normal(k1, (m, k), jnp.float32).astype(dtype)
    shape = (len(sizes), k, n) if n_layers is None else \
        (n_layers, len(sizes), k, n)
    rhs = (jax.random.normal(k2, shape, jnp.float32) / k ** 0.5).astype(dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_ragged_dot(monkeypatch, case, dtype):
    """Rows of a group times the group's matrix, whatever the sizes: the
    kernel's rows that belong to a group equal `ragged_dot`'s."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    m, k, n, sizes, n_layers, layer = CASES[case]
    lhs, rhs, gs = _inputs(m, k, n, sizes, n_layers, jnp.dtype(dtype))
    if n_layers is None:
        got = jax.jit(gm.grouped_matmul)(lhs, rhs, gs)
        flat = rhs
    else:
        got = jax.jit(lambda a, st, l, g: gm.grouped_matmul(a, (st, l), g))(
            lhs, rhs, jnp.int32(layer), gs)
        flat = rhs[layer]
    assert got.shape == (m, n) and got.dtype == lhs.dtype
    want = jax.lax.ragged_dot(lhs.astype(jnp.float32),
                              flat.astype(jnp.float32), gs)
    rows = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(want[:rows]), atol=TOL[dtype])


@pytest.mark.parametrize("tm", [8, 16, 128])
@pytest.mark.parametrize("sizes", [
    [0, 0, 5, 0, 3, 0, 7, 0, 0], [13, 1, 1, 4, 1, 0, 2, 1, 0, 38, 1, 2],
    [100, 60, 3, 0, 90], [0, 0, 0], [256], [0, 1, 0], [8, 8, 8, 8]],
    ids=lambda s: "-".join(map(str, s)))
def test_work_list_names_each_group_with_rows_once_a_tile(sizes, tm):
    """An item for each (group that holds rows, row tile it reaches into),
    in order; no item for an empty group or for rows past the last group."""
    m_tiles = -(-256 // tm)
    group, tile, starts, ends, length = gm.work_list(
        jnp.asarray(sizes, jnp.int32), m_tiles, tm)
    assert np.asarray(ends).tolist() == np.cumsum(sizes).tolist()
    assert np.asarray(ends - starts).tolist() == sizes
    want, start = [], 0
    for g, size in enumerate(sizes):
        if size:
            want += [(g, t) for t in range(start // tm,
                                           (start + size - 1) // tm + 1)]
        start += size
    assert int(length) == len(want) <= group.shape[0] == tile.shape[0]
    got = list(zip(np.asarray(group).tolist(), np.asarray(tile).tolist()))
    assert got[:len(want)] == want
    # what lies past the list's end is never visited, and names real blocks
    assert all(0 <= g < len(sizes) and 0 <= t < m_tiles for g, t in got)


@pytest.mark.parametrize("shape", [
    (512, 2048, 1536, 2), (512, 1536, 2048, 2), (64, 2048, 1536, 2),
    (512, 3072, 3072, 2), (64, 3072, 3072, 2), (1024, 3072, 3072, 2),
    (512, 3072, 3072, 4), (4, 64, 96, 4), (20, 128, 200, 4),
    (512, 16384, 1024, 2)], ids=lambda s: "x".join(map(str, s)))
def test_tiles_follow_the_shapes_alone(shape):
    """Whole sublanes of rows, at most one MXU pass of them; an output tile
    that divides ``n`` in lanes of 128 (or is ``n``) and keeps the weight
    block in its budget wherever one lane tile does."""
    m, k, n, itemsize = shape
    tm, tn = gm.tiles(m, k, n, itemsize)
    assert tm % (32 // itemsize) == 0 and tm <= max(128, 32 // itemsize)
    assert tm >= min(m, 128)
    assert n % tn == 0 and (tn % 128 == 0 or tn == n)
    if n % 128 == 0 and k * 128 * itemsize <= gm._WEIGHT_BLOCK_BYTES:
        assert k * tn * itemsize <= gm._WEIGHT_BLOCK_BYTES
        assert tn == n or k * 2 * tn * itemsize > gm._WEIGHT_BLOCK_BYTES \
            or n % (2 * tn)


def _routed_by_ragged_dot(y, idx, w, w_in, w_out, w_gate):
    """`routed_ffn` as it was written over `jax.lax.ragged_dot`."""
    n, k = idx.shape
    pair = idx.reshape(-1)
    order = jnp.argsort(pair, stable=True)
    sizes = jnp.zeros((w_in.shape[0],), jnp.int32).at[pair].add(1)
    xs = y[order // k]
    z = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, sizes)) * \
        jax.lax.ragged_dot(xs, w_in, sizes)
    out = jax.lax.ragged_dot(z, w_out, sizes)[jnp.argsort(order)]
    return jnp.einsum("nkd,nk->nd", out.reshape(n, k, -1), w)


@pytest.mark.parametrize("path", ["kernel", "lowering_platform"])
def test_gradient_of_routed_ffn_is_the_ragged_dot_formulations(monkeypatch,
                                                               path):
    """`forward` of a sigmoid model stays differentiable: the backward pass
    of the grouped matmul is `ragged_dot`'s, whether the forward ran the
    kernel (interpreted here) or what the CPU's lowering chose."""
    if path == "kernel":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    d, f, n_experts, n = 64, 128, 6, 24
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    y = jax.random.normal(ks[0], (n, d))
    w_in, w_gate = (jax.random.normal(k, (n_experts, d, f)) / d ** 0.5
                    for k in ks[1:3])
    w_out = jax.random.normal(ks[3], (n_experts, f, d)) / f ** 0.5
    idx, w = sigmoid_route(y, jax.random.normal(ks[4], (d, n_experts)),
                           jnp.zeros((n_experts,)), 2)

    def loss(fn):
        return lambda y, a, b, c: (fn(y, a, b, c) ** 2).sum()
    got = jax.jit(jax.value_and_grad(loss(
        lambda y, a, b, c: routed_ffn(y, idx, w, a, b, c)[0]),
        argnums=(0, 1, 2, 3)))(y, w_in, w_out, w_gate)
    want = jax.jit(jax.value_and_grad(loss(
        lambda y, a, b, c: _routed_by_ragged_dot(y, idx, w, a, b, c)),
        argnums=(0, 1, 2, 3)))(y, w_in, w_out, w_gate)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_gradient_reaches_one_layer_of_a_stack(monkeypatch):
    """The ``(stack, layer)`` form: the cotangent of the stack is the
    layer's and zero elsewhere."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    lhs, stack, gs = _inputs(24, 64, 128, [5, 0, 12, 7], 3, jnp.float32)

    def total(a, st):
        return gm.grouped_matmul(a, (st, jnp.int32(1)), gs).sum()
    da, dst = jax.grad(total, argnums=(0, 1))(lhs, stack)
    ra, rflat = jax.grad(
        lambda a, b: jax.lax.ragged_dot(a, b, gs).sum(),
        argnums=(0, 1))(lhs, stack[1])
    np.testing.assert_allclose(np.asarray(da), np.asarray(ra), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dst[1]), np.asarray(rflat),
                               atol=1e-4)
    assert not np.asarray(dst[0]).any() and not np.asarray(dst[2]).any()


def test_the_lowering_platform_chooses_the_path(monkeypatch):
    """No flag: traced, the call holds BOTH the kernel and `ragged_dot`;
    lowered for the CPU it is `ragged_dot` alone (there a masked
    ``dot_general``), no kernel."""
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    lhs, rhs, gs = _inputs(32, 128, 256, [4, 0, 9], None, jnp.float32)
    text = str(jax.make_jaxpr(gm.grouped_matmul)(lhs, rhs, gs))
    assert "ragged_dot" in text and "pallas_call" in text
    lowered = jax.jit(gm.grouped_matmul).lower(lhs, rhs, gs).as_text()
    assert "dot_general" in lowered and "tpu_custom_call" not in lowered
    got = jax.jit(gm.grouped_matmul)(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.lax.ragged_dot(lhs, rhs, gs)),
                               atol=1e-5)
