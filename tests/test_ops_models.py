"""Ops + model tests (CPU reference paths; the Pallas kernel itself is
TPU-only: `tests/test_chip_compile.py` asks the chip's compiler, the
benchmark's train cells run it)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import (TransformerConfig, count_params, forward,
                            init_params, lm_loss, make_train_step)
from ray_tpu.ops import (apply_rotary, layernorm, multi_head_attention,
                         reference_attention, rmsnorm, rotary_angles)
from ray_tpu.parallel import (FSDP_TP_RULES, MeshSpec, create_mesh,
                              pytree_shardings)


def _output_and_grads(attend, q, k, v, live=slice(None), kernels=None):
    """``attend(q, k, v)`` and the gradients of the tests' loss (its squares
    summed over the ``live`` rows), from ONE compiled run: eager, the
    forward ran once for the output and again inside the gradient, every
    operation (or interpreted kernel) a compile of its own.  ``kernels``, a
    dict, takes the program's Pallas calls by name (`_pallas_calls`)."""
    def loss(*a):
        out = attend(*a)
        return (out[:, live].astype(jnp.float32) ** 2).sum(), out

    program = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)).trace(q, k, v)
    if kernels is not None:
        kernels.update(_pallas_calls(program.jaxpr.jaxpr))
    (_, out), grads = program.lower().compile()(q, k, v)
    return out, grads


def _reference_output_and_grads(q, k, v, causal=True, live=slice(None)):
    import functools
    return _output_and_grads(
        functools.partial(reference_attention, causal=causal), q, k, v, live)


def test_norms_match_numpy():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
    scale = jnp.ones((32,)) * 2.0
    y = rmsnorm(x, scale)
    ref = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * 2
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4)
    y2 = layernorm(x, jnp.ones((32,)), jnp.zeros((32,)))
    xa = np.asarray(x)
    ref2 = (xa - xa.mean(-1, keepdims=True)) / np.sqrt(
        xa.var(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(y2), ref2, rtol=1e-4)


def test_rotary_preserves_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 32))
    cos, sin = rotary_angles(16, 32)
    y = apply_rotary(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-4)
    # position 0 is the identity rotation
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-5)


def test_reference_attention_causality():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 2, 16))
    out1 = reference_attention(q, k, v, causal=True)
    # future keys must not affect past outputs
    k2 = k.at[:, 4:].set(0.0)
    v2 = v.at[:, 4:].set(0.0)
    out2 = reference_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :4]),
                               np.asarray(out2[:, :4]), rtol=1e-5)


def test_gqa_matches_expanded_mha():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 2, 16))
    out = reference_attention(q, k, v, causal=True)
    k_full = jnp.repeat(k, 2, axis=2)
    v_full = jnp.repeat(v, 2, axis=2)
    out_full = reference_attention(q, k_full, v_full, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_full),
                               rtol=1e-5)


@pytest.mark.parametrize("preset", ["llama", "gpt2"])
def test_model_trains(preset):
    if preset == "llama":
        cfg = TransformerConfig.tiny()
    else:
        cfg = TransformerConfig.tiny(pos_emb="learned", activation="gelu",
                                     norm="layernorm", tie_embeddings=True,
                                     n_kv_heads=None)
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    assert count_params(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert n_leaves == len(jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple)))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    step = jax.jit(make_train_step(cfg, optax.adamw(1e-3)))
    opt_state = optax.adamw(1e-3).init(params)
    batch = {"tokens": toks}
    losses = []
    for _ in range(8):
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_masked_loss():
    cfg = TransformerConfig.tiny()
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    full = lm_loss(params, {"tokens": toks}, cfg)
    masked = lm_loss(params, {"tokens": toks,
                              "mask": jnp.ones_like(toks)}, cfg)
    np.testing.assert_allclose(float(full), float(masked), rtol=1e-5)


def test_sharded_train_step_on_virtual_mesh():
    """Full train step jitted over an 8-device dp×tp mesh (the multichip
    path the driver dry-runs)."""
    cfg = TransformerConfig.tiny()
    mesh = create_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    shardings = pytree_shardings(axes, mesh, FSDP_TP_RULES)
    params = jax.device_put(params, shardings)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256)
    with jax.set_mesh(mesh):
        params2, opt_state, metrics = step(params, opt_state,
                                           {"tokens": toks})
    assert np.isfinite(float(metrics["loss"]))


def test_flash_kernel_interpret_mode_parity(monkeypatch):
    """The Pallas flash kernels (fwd + custom-VJP bwd) run through the
    interpreter and match reference attention — the off-chip proof of
    kernel logic (VERDICT r1: 'flash kernel unproven on hardware')."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (1, 128, 4, 32), jnp.float32)
    k = jax.random.normal(k2, (1, 128, 2, 32), jnp.float32)  # GQA
    v = jax.random.normal(k3, (1, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref, g_r = _reference_output_and_grads(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    g_f = jax.grad(lambda *a: (flash_attention(*a, causal=True) ** 2)
                   .sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


# `one_backward` held false: the dq and dkv kernels at a plan the one kernel
# would take
_TWO_KERNELS = {"one_backward": lambda plan: False}


# (batch, s_q, s_kv, heads, kv heads, head size, causal, block_q, block_k,
#  dtype, the module's names held otherwise) -> (query heads, kv heads) a
# grid step, and the backward the plan takes: the ONE kernel, or the TWO
_FLASH_CASES = {
    "one-head-a-step-d128": ((1, 256, 256, 1, 1, 128, True, 128, 128,
                              "float32", {}), (1, 1), "one"),
    "four-heads-a-step": ((1, 256, 256, 4, 4, 64, True, 128, 128,
                           "float32", {}), (4, 4), "one"),
    # 5 heads under a limit of 4 stand for gpt2-xl's 25 under 8: a block of
    # four, then one whose last three heads lie outside the array
    "odd-heads-5-for-25": ((1, 256, 256, 5, 5, 64, True, 128, 128,
                            "float32", {"_MAX_HEADS": 4}), (4, 4), "one"),
    "odd-heads-5-in-pairs": ((1, 256, 256, 5, 5, 64, True, 128, 128,
                              "float32", {"_MAX_HEADS": 2}), (2, 2), "one"),
    "odd-heads-all-5-in-a-step": ((1, 256, 256, 5, 5, 64, True, 128, 128,
                                   "float32", {}), (5, 5), "one"),
    "gqa-8-2": ((1, 256, 256, 8, 2, 64, True, 128, 128, "float32", {}),
                (8, 2), "one"),
    # two kv head blocks of 2, each kv head added to by its 4 query heads in
    # turn, tile after tile
    "gqa-16-4-two-head-blocks": ((1, 256, 256, 16, 4, 64, True, 128, 128,
                                  "float32", {}), (8, 2), "one"),
    "mqa-16-1-two-q-steps": ((1, 128, 128, 16, 1, 64, True, 128, 128,
                              "float32", {}), (8, 1), "two"),
    "gqa-4-2-d128": ((1, 256, 256, 4, 2, 128, True, 128, 128, "float32",
                      {}), (4, 2), "one"),
    "non-causal": ((1, 256, 256, 2, 2, 64, False, 128, 128, "float32",
                    {}), (2, 2), "one"),
    # all 3 heads in one block: 192 lanes, the array's whole width
    "non-causal-all-heads-192-lanes": ((1, 128, 256, 3, 3, 64, False, 128,
                                        128, "float32", {}), (3, 3), "one"),
    "s_q-less-than-s_kv": ((1, 128, 256, 2, 2, 64, True, 128, 128,
                            "float32", {}), (2, 2), "one"),
    "s_q-more-than-s_kv": ((1, 256, 128, 2, 2, 64, True, 128, 128,
                            "float32", {}), (2, 2), "one"),
    "s_q-more-odd-heads": ((1, 256, 128, 5, 5, 64, True, 128, 128,
                            "float32", {"_MAX_HEADS": 4}), (4, 4), "one"),
    "tile-128x256-batch-2": ((2, 256, 256, 4, 1, 64, True, 128, 256,
                              "float32", {}), (4, 1), "one"),
    # k, v (dkv: q, do) in major blocks smaller than the sequence: dead
    # blocks' indices are clamped and the state crosses grid steps
    "major-blocks-not-resident": ((1, 512, 512, 2, 2, 64, True, 128, 128,
                                   "float32",
                                   {"_VMEM_BLOCK_BUDGET": 1 << 20}),
                                  (2, 2), "two"),
    "major-blocks-s_q-more": ((1, 512, 256, 2, 2, 64, True, 128, 128,
                               "float32", {"_VMEM_BLOCK_BUDGET": 1 << 20}),
                              (2, 2), "two"),
    "bfloat16-gqa": ((1, 256, 256, 4, 2, 64, True, 128, 128, "bfloat16",
                      {}), (4, 2), "one"),
    "bfloat16-odd-heads-d128-scale": ((1, 256, 256, 3, 3, 128, True, 128,
                                       128, "bfloat16", {}), (3, 3), "one"),
    # the one kernel at 8 heads a step, and where the whole sequence is one
    # tile of no hardware multiple (ViT's 197 rows, not causal)
    "eight-heads-a-step": ((1, 256, 256, 8, 8, 64, True, 128, 128,
                            "float32", {}), (8, 8), "one"),
    "non-causal-197-rows": ((2, 197, 197, 4, 4, 64, False, 256, 256,
                             "float32", {}), (4, 4), "one"),
    "bfloat16-eight-heads-s_q-less": ((1, 128, 256, 8, 8, 64, True, 128, 128,
                                       "bfloat16", {}), (8, 8), "one"),
    # the two kernels where the one would run: they stay for the plans that
    # do not hold the query side (above), so they are held to the reference
    # over the same range of shapes
    "two-kernels-one-head-d128": ((1, 256, 256, 1, 1, 128, True, 128, 128,
                                   "float32", _TWO_KERNELS), (1, 1), "two"),
    "two-kernels-eight-heads": ((1, 256, 256, 8, 8, 64, True, 128, 128,
                                 "float32", _TWO_KERNELS), (8, 8), "two"),
    "two-kernels-odd-heads-5-for-25": ((1, 256, 256, 5, 5, 64, True, 128, 128,
                                        "float32",
                                        {"_MAX_HEADS": 4, **_TWO_KERNELS}),
                                       (4, 4), "two"),
    "two-kernels-gqa-8-2": ((1, 256, 256, 8, 2, 64, True, 128, 128,
                             "float32", _TWO_KERNELS), (8, 2), "two"),
    "two-kernels-s_q-less": ((1, 128, 256, 2, 2, 64, True, 128, 128,
                              "float32", _TWO_KERNELS), (2, 2), "two"),
    "two-kernels-s_q-more-odd-heads": ((1, 256, 128, 5, 5, 64, True, 128, 128,
                                        "float32",
                                        {"_MAX_HEADS": 4, **_TWO_KERNELS}),
                                       (4, 4), "two"),
    "two-kernels-non-causal-197-rows": ((2, 197, 197, 4, 4, 64, False, 256,
                                         256, "float32", _TWO_KERNELS),
                                        (4, 4), "two"),
    "two-kernels-bfloat16-gqa": ((1, 256, 256, 4, 2, 64, True, 128, 128,
                                  "bfloat16", _TWO_KERNELS), (4, 2), "two"),
    # what only a ONE-pass forward can get wrong: scores that rise along the
    # keys (`_rising`), so every tile after a query's first raises its
    # maximum and rescales what it holds: within a grid step, across major
    # blocks (k, v not resident), under dead rows that share the first tile
    # with live ones, and in the block of one head that 9 heads leave over
    # a block of 8 (gpt2-xl's 25 = 3 x 8 + 1)
    "rising-every-tile-rescales": ((1, 512, 512, 2, 2, 64, True, 128, 128,
                                    "float32", {}), (2, 2), "one"),
    "rising-major-blocks-not-resident": ((1, 512, 512, 2, 2, 64, True, 128,
                                          128, "float32",
                                          {"_VMEM_BLOCK_BUDGET": 1 << 20}),
                                         (2, 2), "two"),
    "rising-s_q-more-dead-rows-in-the-first-tile": (
        (1, 512, 256, 2, 2, 64, True, 256, 128, "float32", {}), (2, 2),
        "one"),
    "rising-odd-heads-9-a-block-of-one": ((1, 256, 256, 9, 9, 64, True, 128,
                                           128, "float32", {}), (8, 8),
                                          "one"),
    "rising-gqa-8-2-d128-bfloat16": ((1, 512, 512, 8, 2, 128, True, 128, 128,
                                      "bfloat16", {}), (8, 2), "one"),
}


def _rising(key, b, s_q, s_kv, h, h_kv, d, dt):
    """q and k whose scores rise along the keys, by about 6 every 128 of
    512 keys at head size 64: each new tile holds every query's new
    maximum, by far (``alpha`` near exp(-6))."""
    k1, k2 = jax.random.split(key)
    q = 1.0 + 0.1 * jax.random.normal(k1, (b, s_q, h, d), jnp.float32)
    ramp = (jnp.arange(1, s_kv + 1, dtype=jnp.float32) / s_kv)[:, None, None]
    k = 3.0 * (64 / d) ** 0.5 * ramp * (s_kv / 512) + 0.1 * jax.random.normal(
        k2, (b, s_kv, h_kv, d), jnp.float32)
    return q.astype(dt), k.astype(dt)


def _reference_lse(q, k, causal):
    """``[b, heads, s_q]``: the logarithm of each query's summed
    exponentials of its scaled scores, the last query on the last key."""
    (b, s_q, h, d), (s_kv, h_kv) = q.shape, k.shape[1:3]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, h // h_kv, axis=2),
                   precision=jax.lax.Precision.HIGHEST) * d ** -0.5
    if causal:
        sees = (jnp.arange(s_q)[:, None] + (s_kv - s_q)
                >= jnp.arange(s_kv)[None])
        s = jnp.where(sees, s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


@pytest.mark.parametrize("case", sorted(_FLASH_CASES))
def test_flash_kernel_cases_match_reference(monkeypatch, case):
    """Forward and all three gradients of the flash kernels (interpreter)
    against `reference_attention`: heads a grid step 1 and more, an odd head
    count whose last step lies half outside the array, GQA through index
    maps, head sizes 64 and 128 (a scale folded into the operand, and one
    that is not a power of two left on the scores), causal and not, s_q <,
    = and > s_kv, tiles the diagonal crosses beside tiles it does not, and
    major blocks that are not the whole sequence.  Each case names the
    backward its plan takes, and the program holds those kernels and no
    other: the one kernel wherever the whole query side is resident and a
    kv head block meets its query heads in one step (`one_backward`), the
    dq and dkv kernels elsewhere and, the predicate held false, over the
    one kernel's own range of shapes.  The forward's row statistics
    (``lse``, what the backward exponentiates against) are held to the
    reference too, not only ``o``; the ``rising-`` cases are the ones a
    one-pass forward has to rescale in every tile."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    (b, s_q, s_kv, h, h_kv, d, causal, bq, bk, dtype, held), heads, \
        backward = _FLASH_CASES[case]
    for name, value in held.items():
        monkeypatch.setattr(fa, name, value)
    dt = jnp.dtype(dtype)
    bq, bk = fa.fit_block(bq, s_q), fa.fit_block(bk, s_kv)
    plan = fa.make_plan(h, h_kv, d, s_q, s_kv, dt.itemsize, bq, bk)
    assert (plan.hq, plan.hk) == heads
    assert fa.one_backward(plan) == (backward == "one")
    assert (plan.major_k < s_kv and plan.major_q < s_q) == (
        "_VMEM_BLOCK_BUDGET" in held)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    v = jax.random.normal(k3, (b, s_kv, h_kv, d), dt)
    if case.startswith("rising-"):
        q, k = _rising(k1, b, s_q, s_kv, h, h_kv, d, dt)
    else:
        q = jax.random.normal(k1, (b, s_q, h, d), dt)
        k = jax.random.normal(k2, (b, s_kv, h_kv, d), dt)
    # rows that see no key (causal, s_q > s_kv): the kernel gives 0, the
    # reference a mean of v; compared on the rows that attend
    live = slice(max(s_q - s_kv, 0) if causal else 0, None)

    flash = lambda *a: fa.flash_attention(*a, causal=causal, block_q=bq,
                                          block_k=bk)
    # the statistics, from the forward alone (the gradient's program below
    # hands out `o` only): [b, head blocks, hq, s_q], 0 on rows that see no key
    _, lse = fa._flash_fwd(q.reshape(b, s_q, h * d),
                           k.reshape(b, s_kv, h_kv * d),
                           v.reshape(b, s_kv, h_kv * d), causal, d ** -0.5,
                           plan)
    lse = lse.reshape(b, -1, s_q)[:, :h]
    assert not np.asarray(lse[..., :live.start]).any()
    tol_lse = 2e-5 if dtype == "float32" else 2e-3
    np.testing.assert_allclose(
        np.asarray(lse[..., live]), np.asarray(_reference_lse(
            q.astype(jnp.float32), k.astype(jnp.float32), causal)[..., live]),
        atol=tol_lse, rtol=tol_lse)
    out_r, g_r = _reference_output_and_grads(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal, live)
    # the three kernels in one program (the forward that is no gradient's:
    # `test_flash_takes_a_whole_short_sequence_as_one_tile` and the parity
    # tests above)
    kernels = {}
    out, g_f = _output_and_grads(flash, q, k, v, live, kernels)
    assert kernels == {f"flash_attention_{k}": 1 for k in (
        ("fwd", "bwd") if backward == "one" else ("fwd", "dq", "dkv"))}
    assert out.dtype == dt and out.shape == q.shape
    assert not np.asarray(out[:, :live.start], np.float32).any()
    tol_o, tol_g = (2e-5, 5e-4) if dtype == "float32" else (3e-2, 0.25)
    np.testing.assert_allclose(np.asarray(out[:, live], np.float32),
                               np.asarray(out_r[:, live]),
                               atol=tol_o, rtol=tol_o)
    for a, r in zip(g_f, g_r):
        assert a.dtype == dt
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(r),
                                   atol=tol_g, rtol=tol_g)


@pytest.mark.parametrize("case", [
    "four-heads-a-step", "odd-heads-5-for-25", "gqa-8-2",
    "gqa-16-4-two-head-blocks", "s_q-less-than-s_kv", "s_q-more-odd-heads",
    "tile-128x256-batch-2", "non-causal-197-rows"])
def test_flash_one_backward_is_the_two_kernels_arithmetic(monkeypatch, case):
    """BOTH backwards on the same inputs, residuals and cotangent (the
    predicate held each way): the one kernel walks the dkv kernel's tiles
    and sums dq over the k tiles in the dq kernel's order, so dk and dv are
    its to the bit and dq to float32 rounding."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    (b, s_q, s_kv, h, h_kv, d, causal, bq, bk, dtype, held), _, backward = \
        _FLASH_CASES[case]
    assert dtype == "float32" and backward == "one"
    for name, value in held.items():
        monkeypatch.setattr(fa, name, value)
    bq, bk = fa.fit_block(bq, s_q), fa.fit_block(bk, s_kv)
    plan = fa.make_plan(h, h_kv, d, s_q, s_kv, 4, bq, bk)
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    q, do = (jax.random.normal(key, (b, s_q, h * d), jnp.float32)
             for key in keys[:2])
    k, v = (jax.random.normal(key, (b, s_kv, h_kv * d), jnp.float32)
            for key in keys[2:])
    scale = d ** -0.5
    o, lse = fa._flash_fwd(q, k, v, causal, scale, plan)

    def backward_of(one):
        monkeypatch.setattr(fa, "one_backward", lambda p: one)
        program = jax.jit(lambda *a: fa._flash_bwd(
            *a, causal, scale, plan)).trace(q, k, v, o, lse, do)
        assert sorted(_pallas_calls(program.jaxpr.jaxpr)) == (
            ["flash_attention_bwd"] if one
            else ["flash_attention_dkv", "flash_attention_dq"])
        return program.lower().compile()(q, k, v, o, lse, do)

    for name, a, r in zip(("dq", "dk", "dv"), backward_of(True),
                          backward_of(False)):
        assert float(jnp.abs(r).max()) > 0.1, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5,
                                   atol=1e-5, err_msg=name)


def _eqns(jaxpr):
    """The equations of ``jaxpr`` in program order, those of the jaxprs
    inside one (a kernel's body, a branch, a loop's body) right after it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("heads,kv_heads", [(8, 8), (8, 2)],
                         ids=["8-heads-of-64", "gqa-8-over-2"])
def test_flash_backward_issues_a_heads_products_one_head_ahead(
        heads, kv_heads):
    """The order of the one backward kernel's tile loop, which is all of its
    measured gain (module docstring): a body's matmuls go to the MXU in
    program order, so the two products of a head that wait for nothing (its
    scores and its ``dp``) are issued before the exponentials of the head
    BEFORE it (`_one_ahead`), and a head's three accumulations come after
    its own.  Before head ``k``'s `exp` the loop holds the two products of
    heads ``0 .. k + 1`` and the three accumulations of heads ``0 .. k - 1``;
    the chain head by head (scores, `exp`, ``dv +=``, ``dp``, ``ds``, ...)
    holds ``5 k + 1`` there.  An edit that puts the chain back fails here."""
    import importlib
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    d, s = 64, 512
    plan = fa.make_plan(heads, kv_heads, d, s, s, 2, 256, 256)
    assert (plan.hq, plan.hk) == (heads, kv_heads) and fa.one_backward(plan)
    q = jax.ShapeDtypeStruct((1, s, heads * d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, kv_heads * d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, plan.head_blocks, plan.hq, s), jnp.float32)
    program = jax.jit(lambda *a: fa._flash_bwd(
        *a, True, d ** -0.5, plan)).trace(q, kv, kv, q, lse, q)
    (call,) = [e for e in _eqns(program.jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "flash_attention_bwd"
    # the tile loop: the one loop of the kernel's body
    (loop,) = [e for e in call.params["jaxpr"].eqns
               if e.primitive.name in ("while", "scan")]
    order = [e.primitive.name
             for sub in jax.core.jaxprs_in_params(loop.params)
             for e in _eqns(sub) if e.primitive.name in ("dot_general", "exp")]
    assert order.count("exp") == heads
    assert order.count("dot_general") == 5 * heads
    before = [order[:i].count("dot_general")
              for i, name in enumerate(order) if name == "exp"]
    assert before == [2 * min(k + 2, heads) + 3 * k
                      for k in range(heads)], order


def _pallas_calls(jaxpr, times=1, found=None):
    """Kernel name -> calls a run of ``jaxpr`` makes (a scan's body counted
    by its length)."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + times
        inner = times * (eqn.params["length"]
                         if eqn.primitive.name == "scan" else 1)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, inner, found)
    return found


@pytest.mark.parametrize("case", ["one-device", "fsdp-4", "reference"])
def test_full_remat_runs_the_flash_forward_once_a_layer(monkeypatch, case):
    """Under ``remat=True`` a layer's checkpoint keeps the flash kernel's
    output and row statistics (`remat_policy`), so the gradient runs the
    forward kernel once a layer, as ``remat=False`` does, where
    `nothing_saveable` ran it twice; the same per shard under a mesh
    (`_flash_per_shard`'s `shard_map` passes the policy through) and under
    ``"dots"``.  The gradients are ``remat=False``'s (to the rounding of
    the CPU's own fusions: the arithmetic is the same).  Where attention is
    not the kernel the policy finds no name to save and the lowered
    gradient is `nothing_saveable`'s, character for character."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    import contextlib
    import dataclasses

    from ray_tpu.models import transformer
    layers = 2
    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_layers=layers, n_heads=2,
        max_seq_len=128, dtype=jnp.float32,
        attention_impl="reference" if case == "reference" else "flash")
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 128),
                                          0, 64)}
    mesh = contextlib.nullcontext() if case != "fsdp-4" else jax.set_mesh(
        create_mesh(MeshSpec(fsdp=4), devices=jax.devices()[:4]))

    def traced(remat):
        c = dataclasses.replace(cfg, remat=remat)
        return jax.jit(jax.grad(lambda p: lm_loss(p, batch, c))).trace(params)

    def parent():       # full remat as it was: a fresh trace under the patch
        monkeypatch.setattr(
            transformer, "remat_policy",
            lambda remat: jax.checkpoint_policies.nothing_saveable)
        return traced(True)

    with mesh:
        if case == "reference":
            ours = traced(True)
            assert "pallas_call" not in str(ours.jaxpr)
            assert ours.lower().as_text() == parent().lower().as_text()
            return
        programs = {remat: traced(remat) for remat in (True, False, "dots")}
        assert ("shard_map" in str(programs[True].jaxpr)) == (case == "fsdp-4")
        want = {f"flash_attention_{k}": layers for k in ("fwd", "bwd")}
        for remat, program in programs.items():
            assert _pallas_calls(program.jaxpr.jaxpr) == want, remat
        assert _pallas_calls(parent().jaxpr.jaxpr) == {
            **want, "flash_attention_fwd": 2 * layers}
        if case == "fsdp-4":    # the arithmetic is the one device's
            return
        got, ref = (programs[remat].lower().compile()(params)
                    for remat in (True, False))
    for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-4,
                                   atol=1e-6 * float(jnp.abs(r).max()))


# (batch, s_q, s_kv, heads, kv heads, head size, causal, dtype): sequences
# whose default tile is the WHOLE sequence, of a length that is no multiple
# of the lanes, of the sublanes, or of anything
_WHOLE_SEQUENCE_CASES = {
    "192-causal-12-heads": (1, 192, 192, 12, 12, 64, True, "float32"),
    "vit-197-non-causal-12-heads": (2, 197, 197, 12, 12, 64, False,
                                    "float32"),
    "197-causal": (1, 197, 197, 2, 2, 64, True, "float32"),
    "q-100-on-k-197-gqa": (1, 100, 197, 4, 2, 64, True, "float32"),
    "q-197-on-k-72-dead-rows": (1, 197, 72, 2, 2, 64, True, "float32"),
    "q-192-whole-k-384-in-tiles": (1, 192, 384, 2, 2, 64, True, "float32"),
    "q-256-in-a-tile-k-197-whole": (1, 256, 197, 2, 2, 64, False,
                                    "float32"),
    "200-gqa-d128-bfloat16": (1, 200, 200, 4, 2, 128, True, "bfloat16"),
    "8-rows": (1, 8, 8, 2, 2, 64, True, "float32"),
}


@pytest.mark.parametrize("case", sorted(_WHOLE_SEQUENCE_CASES))
def test_flash_takes_a_whole_short_sequence_as_one_tile(monkeypatch, case):
    """`multi_head_attention(impl="flash")` on sequences the default tile
    does not divide (192; ViT's 197 tokens; 100 queries on 197 keys): the
    whole sequence is one tile, whatever its length, forward and gradients
    as the reference's."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import multi_head_attention
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    b, s_q, s_kv, h, h_kv, d, causal, dtype = _WHOLE_SEQUENCE_CASES[case]
    tiles = (fa.fit_block(fa.DEFAULT_BLOCK_Q, s_q),
             fa.fit_block(fa.DEFAULT_BLOCK_K, s_kv))
    assert s_q in tiles or s_kv in tiles
    assert fa.tile_ok(tiles[0], s_q) and fa.tile_ok(tiles[1], s_kv)
    dt = jnp.dtype(dtype)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(k1, (b, s_q, h, d), dt)
    k = jax.random.normal(k2, (b, s_kv, h_kv, d), dt)
    v = jax.random.normal(k3, (b, s_kv, h_kv, d), dt)
    live = slice(max(s_q - s_kv, 0) if causal else 0, None)

    def loss(fn):
        return lambda *a: (fn(*a)[:, live].astype(jnp.float32) ** 2).sum()
    flash = lambda *a: multi_head_attention(*a, causal=causal, impl="flash")
    out_r, g_r = _reference_output_and_grads(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal, live)
    tol_o, tol_g = (2e-5, 5e-4) if dtype == "float32" else (3e-2, 0.25)
    out = flash(q, k, v)
    assert out.dtype == dt and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out[:, live], np.float32),
                               np.asarray(out_r[:, live]),
                               atol=tol_o, rtol=tol_o)
    g_f = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(r),
                                   atol=tol_g, rtol=tol_g)


@pytest.mark.parametrize("s_q,s_kv,ok", [
    (1024, 1024, True), (384, 384, True),       # tiles of 256 and of 128
    (192, 192, True), (197, 197, True),         # one tile, any length
    (197, 1024, True), (1024, 200, True), (8, 8, True),
    (320, 320, False), (1024, 320, False),      # only 64 divides 320
    (264, 264, False),                          # only 8 divides 264
    (4, 4, False), (1, 1024, False),            # under 8 rows
])
def test_flash_is_chosen_where_the_kernels_take_the_tiles(monkeypatch, s_q,
                                                          s_kv, ok):
    """The dispatcher's test and the kernels' own are one rule (`tile_ok`):
    what ``impl="auto"`` sends to the kernels on a TPU they run, and what
    `flash_attention` would refuse goes to the reference."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, s_q, 2, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, s_kv, 2, 64), jnp.bfloat16)
    assert attention._flash_ok(q, k) == ok
    if not ok:
        with pytest.raises(ValueError, match="a tile is a multiple of 128"):
            jax.eval_shape(fa.flash_attention, q, k, k)
    assert not attention._flash_ok(
        jax.ShapeDtypeStruct((1, s_q, 2, 96), jnp.bfloat16), k)


@pytest.mark.parametrize("shape,want", [
    # (heads, kv heads, head size, s_q, s_kv) -> (hq, hk, major_k, major_q)
    ((16, 16, 64, 1024, 1024), (8, 8, 1024, 1024)),     # gpt2-medium
    ((25, 25, 64, 1024, 1024), (8, 8, 1024, 1024)),     # gpt2-xl: 3 x 8 + 1
    ((12, 12, 64, 4096, 4096), (4, 4, 4096, 4096)),     # long context
    ((32, 8, 64, 2048, 2048), (8, 2, 2048, 2048)),      # GQA: 2 kv heads
    ((16, 4, 128, 2048, 2048), (4, 1, 2048, 2048)),     # one kv head a step
    ((48, 8, 128, 1024, 1024), (6, 1, 1024, 1024)),
    ((12, 12, 64, 65536, 65536), (2, 2, 8192, 8192)),   # not resident
], ids=lambda x: "x".join(map(str, x)))
def test_flash_plan_follows_the_shape(shape, want):
    """Heads a grid step and the resident rows come from the shape alone:
    lane-dense blocks (a multiple of 128 lanes), whole GQA groups, under
    the VMEM budget; residency before more heads."""
    import importlib
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    h, h_kv, d, s_q, s_kv = shape
    p = fa.make_plan(h, h_kv, d, s_q, s_kv, 2, 256, 256)
    assert (p.hq, p.hk, p.major_k, p.major_q) == want
    assert (p.hq * d) % 128 == 0 and (p.hk * d) % 128 == 0
    assert p.hq == p.hk * (h // h_kv) or (
        p.hk == 1 and (h // h_kv) % p.hq == 0)
    assert fa._block_bytes(p.hq, p.hk, d, 256, 256, p.major_k, p.major_q,
                           2) <= fa._VMEM_BLOCK_BUDGET


@pytest.mark.parametrize("shape,held,want", [
    # (heads, kv heads, head size, s_q, s_kv), the module's names held
    # otherwise -> the one kernel?  `tests/test_chip_compile.py`'s shapes:
    ((16, 16, 64, 1024, 1024), {}, True),       # gpt2-medium, both batches
    ((25, 25, 64, 1024, 1024), {}, True),       # gpt2-xl a chip
    ((12, 12, 64, 4096, 4096), {}, True),       # 4 heads a step, 20.1 MB
    ((32, 8, 64, 2048, 2048), {}, True),
    ((16, 4, 128, 2048, 2048), {}, True),
    ((12, 12, 64, 197, 197), {}, True),         # ViT: one tile
    # the dq and dkv kernels stay where a kv head meets its query heads in
    # two grid steps, where the query side is not resident, and where the
    # accumulator would not fit (14.4 MB at gpt2-medium's plan)
    ((16, 1, 64, 1024, 1024), {}, False),
    ((12, 12, 64, 65536, 65536), {}, False),
    ((16, 16, 64, 1024, 1024), {"_VMEM_LIMIT": 28 << 20}, False),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else None)
def test_flash_one_backward_follows_the_plan(monkeypatch, shape, held, want):
    """Which backward a call takes is a function of its plan alone
    (`one_backward`): the one kernel where the whole query side is resident,
    a kv head block meets all its query heads in one grid step, and the
    float32 accumulator of dq fits beside the dkv kernel's blocks."""
    import importlib
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    for name, value in held.items():
        monkeypatch.setattr(fa, name, value)
    h, h_kv, d, s_q, s_kv = shape
    p = fa.make_plan(h, h_kv, d, s_q, s_kv, 2,
                     fa.fit_block(fa.DEFAULT_BLOCK_Q, s_q),
                     fa.fit_block(fa.DEFAULT_BLOCK_K, s_kv))
    assert fa.one_backward(p) == want
    fits = fa._block_bytes(p.hq, p.hk, d, p.block_q, p.block_k, p.major_k,
                           p.major_q, 2, with_dq=True) <= fa._VMEM_LIMIT // 2
    # each case that keeps the two kernels keeps them for ONE reason
    assert (p.major_q < s_q, p.q_steps > 1, not fits).count(True) == (
        0 if want else 1)


def test_flash_kernel_runs_per_shard_under_a_mesh(monkeypatch):
    """A Mosaic kernel cannot be partitioned by the compiler, so under a
    mesh `multi_head_attention(impl="flash")` runs it per shard (batch over
    fsdp, heads over tp).  Values and gradients must match the reference
    computed without any mesh."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.attention import multi_head_attention
    from ray_tpu.parallel import MeshSpec, create_mesh

    mesh = create_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(k1, (2, 128, 4, 32), jnp.float32)
    k = jax.random.normal(k2, (2, 128, 2, 32), jnp.float32)  # GQA
    v = jax.random.normal(k3, (2, 128, 2, 32), jnp.float32)

    def loss(impl):
        return lambda *a: (multi_head_attention(*a, impl=impl) ** 2).sum()

    ref, g_ref = _reference_output_and_grads(q, k, v)
    sharding = NamedSharding(mesh, P("fsdp", None, "tp", None))
    with jax.set_mesh(mesh):
        qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
        out = jax.jit(lambda *a: multi_head_attention(*a, impl="flash"))(
            qs, ks, vs)
        g = jax.jit(jax.grad(loss("flash"), argnums=(0, 1, 2)))(qs, ks, vs)
    assert out.sharding.spec == P("fsdp", None, "tp", None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_kernel_interpret_mode_bf16(monkeypatch):
    """bf16 inputs through the kernels' production dtype path: the MXU
    dots take bf16 operands with fp32 accumulation, and the bwd kernels
    deliberately truncate p/ds to bf16 — the fp32 parity test above
    makes every one of those casts a no-op, so this case is what
    actually exercises them off-chip.  Mixed fp32-q/bf16-kv is included
    for the entry-point dtype normalization."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k1, (1, 128, 4, 32), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 128, 2, 32), jnp.bfloat16)  # GQA
    v = jax.random.normal(k3, (1, 128, 2, 32), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref, g_r = _reference_output_and_grads(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=3e-2, rtol=3e-2)

    loss_f = lambda *a: (flash_attention(*a, causal=True)
                         .astype(jnp.float32) ** 2).sum()
    g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), atol=0.25, rtol=0.25)

    # mixed dtypes: fp32 query against a bf16 KV cache must not trace-fail
    out_mixed = flash_attention(q.astype(jnp.float32), k, v, causal=True)
    assert out_mixed.dtype == jnp.float32


def test_one_hot_embed_parity():
    """embed_impl='one_hot' (MXU-matmul embedding, avoids the slow TPU
    scatter-add in gather's backward) matches the gather path in loss and
    gradients exactly at fp32."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.models.transformer import lm_loss

    base = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                n_kv_heads=2, max_seq_len=32, dtype=jnp.float32,
                remat=False, attention_impl="reference")
    c1 = TransformerConfig(**base)
    c2 = TransformerConfig(embed_impl="one_hot", **base)
    p, _ = init_params(jax.random.PRNGKey(0), c1)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, 64)}
    # compiled: eager, every operation of both passes compiles on its own
    l1, g1 = jax.jit(jax.value_and_grad(lambda pp: lm_loss(pp, batch, c1)))(p)
    l2, g2 = jax.jit(jax.value_and_grad(lambda pp: lm_loss(pp, batch, c2)))(p)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_chunked_lm_loss_parity():
    """Chunked cross entropy (one [b, chunk, vocab] logits block at a
    time) matches the full-logits loss in value AND gradients."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params, lm_loss

    base = dict(max_seq_len=64, attention_impl="reference",
                dtype=jnp.float32)
    cfg_full = TransformerConfig.tiny(**base)
    cfg_chunk = TransformerConfig.tiny(**base, loss_chunk=16)
    params, _ = init_params(jax.random.PRNGKey(0), cfg_full)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (2, 64)) > 0.2)

    for batch in ({"tokens": tokens},
                  {"tokens": tokens, "mask": mask}):
        lf, gf = jax.jit(jax.value_and_grad(lm_loss), static_argnums=2)(
            params, batch, cfg_full)
        lc, gc = jax.jit(jax.value_and_grad(lm_loss), static_argnums=2)(
            params, batch, cfg_chunk)
        np.testing.assert_allclose(float(lf), float(lc), rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(gf),
                        jax.tree_util.tree_leaves(gc)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)


def test_vit_learns_and_shards():
    """ViT family: tiny model learns a synthetic bars task; the same
    params shard over a dp×fsdp mesh via the shared logical-axis rules."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import (ViTConfig, init_vit_params,
                                make_vit_train_step, vit_forward)
    from ray_tpu.parallel import (FSDP_TP_RULES, MeshSpec, create_mesh,
                                  pytree_shardings)

    cfg = ViTConfig.tiny()
    key = jax.random.PRNGKey(0)
    params, axes = init_vit_params(key, cfg)  # axes validated by the
    # pytree_shardings call below (tuple leaves, same tree shape)

    @jax.jit        # thirty batches: one compile, not every operation's
    def make_batch(k):
        n = 64
        kk, kl = jax.random.split(k)
        labels = jax.random.randint(kl, (n,), 0, 4)
        imgs = jnp.zeros((n, 16, 16, 1))
        # class c -> a bright bar at row/col band c*4 (rows for even c,
        # cols for odd), plus noise
        for c in range(4):
            band = jnp.zeros((16, 16, 1))
            if c % 2 == 0:
                band = band.at[c * 4:(c * 4) + 4, :, :].set(1.0)
            else:
                band = band.at[:, c * 4:(c * 4) + 4, :].set(1.0)
            imgs = jnp.where((labels == c)[:, None, None, None],
                             band[None], imgs)
        imgs = imgs + 0.05 * jax.random.normal(kk, imgs.shape)
        return {"image": imgs, "label": labels}

    opt = optax.adam(3e-3)
    step = jax.jit(make_vit_train_step(cfg, opt))
    opt_state = opt.init(params)
    losses = []
    for i in range(30):
        batch = make_batch(jax.random.PRNGKey(100 + i))
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    eval_batch = make_batch(jax.random.PRNGKey(999))
    forward = jax.jit(lambda p, images: vit_forward(p, images, cfg))
    logits = forward(params, eval_batch["image"])
    acc = float((jnp.argmax(logits, -1) == eval_batch["label"]).mean())
    assert acc > 0.8, acc

    # sharded: the SAME jitted train step runs over a dp×fsdp mesh
    mesh = create_mesh(MeshSpec(dp=2, fsdp=-1))
    shardings = pytree_shardings(axes, mesh, FSDP_TP_RULES)
    sharded = jax.device_put(params, shardings)
    with jax.set_mesh(mesh):
        s_opt_state = opt.init(sharded)
        s_step = jax.jit(make_vit_train_step(cfg, opt))
        sharded, s_opt_state, m = s_step(sharded, s_opt_state,
                                         eval_batch)
        out = forward(sharded, eval_batch["image"])
    assert float(m["loss"]) > 0.0
    assert out.shape == (64, 4)


def test_grad_accumulation_matches_full_batch():
    """accum_steps microbatching must reproduce the full-batch step:
    lm_loss is a per-token mean, so the mean of equal-size microbatch
    grads equals the full-batch grad."""
    import optax

    cfg = TransformerConfig.tiny()
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    flat = jax.jit(make_train_step(cfg, opt))
    acc = jax.jit(make_train_step(cfg, opt, accum_steps=2))
    p1, s1 = params, opt_state
    p2, s2 = params, opt_state
    for i in range(3):
        p1, s1, m1 = flat(p1, s1, batch)
        p2, s2, m2 = acc(p2, s2, batch)
        # loss + grad_norm equality each step is the scale check (Adam
        # normalizes grads, so post-update params only diverge by fp
        # association noise amplified through m/sqrt(v) — bounded below)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=2e-4)
        np.testing.assert_allclose(float(m1["grad_norm"]),
                                   float(m2["grad_norm"]), rtol=2e-3)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        # |Adam update| <= ~lr per step; 3 steps of sign-noise bounds
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=4e-3)
    with pytest.raises(ValueError, match="divisible"):
        acc3 = jax.jit(make_train_step(cfg, opt, accum_steps=3))
        acc3(params, opt_state, batch)


def test_grad_accumulation_honors_mask():
    """accum path must split EVERY batch leaf AND weight microbatches
    by their valid-token counts: the mask here is deliberately UNEVEN
    across microbatches (rows 0-1 nearly full, rows 2-3 nearly empty),
    the case equal 1/accum weighting gets silently wrong (review
    finding r5)."""
    import optax

    cfg = TransformerConfig.tiny()
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.sgd(0.0)        # lr 0: isolate loss computation
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                cfg.vocab_size)
    mask = jnp.zeros((4, 64)).at[:2, :60].set(1.0).at[2:, :3].set(1.0)
    batch = {"tokens": tokens, "mask": mask}
    flat = jax.jit(make_train_step(cfg, opt))
    acc = jax.jit(make_train_step(cfg, opt, accum_steps=2))
    _, _, m1 = flat(params, opt_state, batch)
    _, _, m2 = acc(params, opt_state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-4)
    np.testing.assert_allclose(float(m1["grad_norm"]),
                               float(m2["grad_norm"]), rtol=2e-3)


def test_sharded_grad_accumulation_on_virtual_mesh():
    """accum_steps composes with dp×fsdp×tp shardings (the multichip
    path): microbatch scan + f32 grad carry over sharded params."""
    cfg = TransformerConfig.tiny()
    mesh = create_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params,
                            pytree_shardings(axes, mesh, FSDP_TP_RULES))
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt, accum_steps=2))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256)
    with jax.set_mesh(mesh):
        params, opt_state, metrics = step(params, opt_state,
                                          {"tokens": toks})
    assert np.isfinite(float(metrics["loss"]))
