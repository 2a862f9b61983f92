"""The selective scan of a Mamba-1 mixer (`ops/selective_scan.py`): its
three forms against a NumPy statement of the recurrence (a decay a channel a
state column): one token repeated, a chunk from a carried state across
boundaries with a ragged last chunk, a sequence; padded tokens, which neither
decay nor write; rows that stand, which keep a state bit for bit; the chunk
kernel through the interpreter against XLA's form; the step on layer ``l`` of
the stacked states of a cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as scan

B, S, D, N = 2, 19, 128, 8
TOL = dict(atol=2e-5, rtol=1e-5)


def _inputs(seed=0, b=B, s=S, d=D, n=N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    a = jax.random.normal(ks[0], (b, s, d))
    Bm, C = jax.random.normal(ks[1], (b, s, n)), \
        jax.random.normal(ks[2], (b, s, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, d)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[4], (n, d)) * 0.5)
    Dk = jax.random.normal(ks[5], (d,))
    return a, Bm, C, dt, A, Dk


def recurrence(a, Bm, C, dt, A, Dk, h=None, n_valid=None):
    """NumPy, float64, token by token: ``h[n, c] = exp(dt[c] A[n, c]) h[n, c]
    + dt[c] a[c] B[n]``, ``m[c] = sum_n h[n, c] C[n] + D[c] a[c]``."""
    a, Bm, C, dt, A, Dk = (np.asarray(t, np.float64)
                           for t in (a, Bm, C, dt, A, Dk))
    b, s, d = a.shape
    h = np.zeros((b, A.shape[0], d)) if h is None \
        else np.asarray(h, np.float64).copy()
    m = np.zeros((b, s, d))
    for i in range(b):
        for t in range(s if n_valid is None else int(n_valid[i])):
            h[i] = np.exp(dt[i, t][None] * A) * h[i] \
                + Bm[i, t][:, None] * (dt[i, t] * a[i, t])[None]
            m[i, t] = (h[i] * C[i, t][:, None]).sum(0) + Dk * a[i, t]
    return m, h


def test_sequence_is_the_recurrence():
    x = _inputs()
    m, h = scan.sequence(*x)
    want_m, want_h = recurrence(*x)
    np.testing.assert_allclose(m, want_m, **TOL)
    np.testing.assert_allclose(h, want_h, **TOL)
    assert m.dtype == h.dtype == jnp.float32


def test_step_token_by_token_is_the_recurrence():
    a, Bm, C, dt, A, Dk = _inputs(1)
    h = jnp.zeros((B, N, D))
    out = []
    for t in range(S):
        m, h = scan.step(a[:, t], Bm[:, t], C[:, t], dt[:, t], A, Dk, h)
        out.append(m)
    want_m, want_h = recurrence(a, Bm, C, dt, A, Dk)
    np.testing.assert_allclose(jnp.stack(out, 1), want_m, **TOL)
    np.testing.assert_allclose(h, want_h, **TOL)


@pytest.mark.parametrize("chunk", [4, 8])
def test_chunks_carry_the_state_and_a_ragged_last_one_is_padded(chunk):
    a, Bm, C, dt, A, Dk = _inputs(2)
    h = jnp.zeros((B, N, D))
    got = []
    for off in range(0, S, chunk):
        n = min(chunk, S - off)
        pad = lambda t: jnp.pad(t[:, off:off + n],
                                ((0, 0), (0, chunk - n), (0, 0)),
                                constant_values=7.0)    # padding is not zero
        m, h = scan.chunk(pad(a), pad(Bm), pad(C), pad(dt), A, Dk, h,
                          jnp.full((B,), n, jnp.int32))
        got.append(m[:, :n])
    want_m, want_h = recurrence(a, Bm, C, dt, A, Dk)
    np.testing.assert_allclose(jnp.concatenate(got, 1), want_m, **TOL)
    np.testing.assert_allclose(h, want_h, **TOL)


def test_rows_advance_by_their_valid_tokens_and_a_standing_row_not_at_all():
    a, Bm, C, dt, A, Dk = _inputs(3, b=3, s=8)
    h0 = jax.random.normal(jax.random.PRNGKey(9), (3, N, D))
    n_valid = jnp.asarray([8, 3, 0], jnp.int32)
    m, h = scan.chunk(a, Bm, C, dt, A, Dk, h0, n_valid)
    want_m, want_h = recurrence(a, Bm, C, dt, A, Dk, h0, n_valid)
    np.testing.assert_allclose(m[0], want_m[0], **TOL)
    np.testing.assert_allclose(m[1, :3], want_m[1, :3], **TOL)
    np.testing.assert_allclose(h[:2], want_h[:2], **TOL)
    assert np.array_equal(np.asarray(h[2]), np.asarray(h0[2]))  # bit for bit
    # the step's ``live``
    m1, h1 = scan.step(a[:, 0], Bm[:, 0], C[:, 0], dt[:, 0], A, Dk, h0,
                       live=jnp.asarray([True, False, True]))
    assert np.array_equal(np.asarray(h1[1]), np.asarray(h0[1]))
    assert not np.array_equal(np.asarray(h1[0]), np.asarray(h0[0]))


@pytest.mark.parametrize("d,c", [(128, 8), (256, 16), (640, 8)])
def test_the_chunk_kernel_through_the_interpreter_is_xlas_form(
        monkeypatch, d, c):
    """Whole lane tiles of channels (one block of 128, of 256, five of 128),
    whole turns of eight tokens; a padded row, a standing row."""
    a, Bm, C, dt, A, Dk = _inputs(4, b=3, s=c, d=d)
    h0 = jax.random.normal(jax.random.PRNGKey(5), (3, N, d))
    n_valid = jnp.asarray([c, c - 3, 0], jnp.int32)
    assert scan.kernel_shape(a.shape, h0)
    assert not scan.engages(a.shape, h0)            # a CPU: XLA's form
    plain = scan.chunk(a, Bm, C, dt, A, Dk, h0, n_valid)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    assert scan.engages(a.shape, h0)
    m, h = scan.chunk(a, Bm, C, dt, A, Dk, h0, n_valid)
    np.testing.assert_allclose(m[0], plain[0][0], **TOL)
    np.testing.assert_allclose(m[1, :c - 3], plain[0][1, :c - 3], **TOL)
    np.testing.assert_allclose(h, plain[1], **TOL)
    assert np.array_equal(np.asarray(h[2]), np.asarray(h0[2]))
    assert not np.asarray(m[2]).any()       # a standing row is not computed
    want_m, want_h = recurrence(a, Bm, C, dt, A, Dk, h0, n_valid)
    np.testing.assert_allclose(h[:2], want_h[:2], **TOL)


@pytest.mark.parametrize("shape,state,takes", [
    ((4, 128, 5120), (4, 16, 5120), True),      # the cell's chunk
    ((1, 1, 5120), (1, 16, 5120), False),       # one token: `step`
    ((4, 12, 5120), (4, 16, 5120), False),      # no whole turns of tokens
    ((4, 128, 5000), (4, 16, 5000), False),     # no whole lane tiles
    ((4, 128, 128), (4, 12, 128), False),       # no whole sublane tiles
])
def test_what_the_kernel_takes(shape, state, takes):
    assert scan.kernel_shape(
        shape, jax.ShapeDtypeStruct(state, jnp.float32)) is takes
    assert not scan.kernel_shape(
        shape, jax.ShapeDtypeStruct(state, jnp.bfloat16))


def test_step_in_place_advances_one_layer_of_the_stack():
    a, Bm, C, dt, A, Dk = _inputs(6, s=1)
    stack = jax.random.normal(jax.random.PRNGKey(3), (3, B, 1, N, D))
    live = jnp.asarray([True, False])
    m, new = scan.step_in_place(a[:, 0], Bm[:, 0], C[:, 0], dt[:, 0], A, Dk,
                                stack, jnp.int32(1), live)
    want_m, want_h = scan.step(a[:, 0], Bm[:, 0], C[:, 0], dt[:, 0], A, Dk,
                               stack[1, :, 0], live)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(new[1, :, 0]),
                                  np.asarray(want_h))
    for l in (0, 2):        # the other layers bit for bit
        assert np.array_equal(np.asarray(new[l]), np.asarray(stack[l]))
    assert np.array_equal(np.asarray(new[1, 1]), np.asarray(stack[1, 1]))


def test_gates_are_softplus_and_a_negative_rate():
    r = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 4), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (4, D))
    bias, a_log = jnp.full((D,), -3.0), jnp.log(jnp.arange(1.0, N + 1))
    dt, A = scan.gates(r, w, bias, jnp.broadcast_to(a_log[:, None], (N, D)))
    assert dt.dtype == A.dtype == jnp.float32
    np.testing.assert_allclose(
        dt, np.log1p(np.exp(np.asarray(r, np.float32)
                            @ np.asarray(w.astype(jnp.bfloat16), np.float32)
                            - 3.0)), rtol=2e-2, atol=1e-3)
    np.testing.assert_allclose(A[:, 0], -np.arange(1.0, N + 1), rtol=1e-6)
