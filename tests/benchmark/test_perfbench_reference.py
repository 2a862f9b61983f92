"""The comparison that decides ``correct``, at a size a test run can hold:
the program passes its limits, and the control (the plain reference computed
in fp8, the nearest precision below the bfloat16 the configurations state)
fails them.  On the chip, at the cells' own size, the same is read by
perfbench/tools/outputs_check.py."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from perfbench import manifest as mf
from perfbench import reference, verdict, weights
from perfbench.tools import rehearse

SEEDS = [3, 2**31 + 11, 77]
# every configuration of the rehearsal, whatever its family: one added
# there is compared here with no edit to this file
CONFIGS = [c["name"] for c in rehearse.manifest().data["configs"]]


@pytest.fixture(scope="module", params=CONFIGS)
def tiny(request):
    """(the configuration file's content, its family's JAX part)."""
    c = rehearse.manifest().config(request.param)
    return c, mf.family_of(c).model


def _limits(cell):
    return rehearse.manifest().limits(cell)


def test_reference_is_the_programs_function_in_float32(tiny):
    """Two independent implementations of the same published equations
    agree to float32 rounding when both compute in float32.  The program's
    configuration is the one the cells run, asked of the family."""
    from ray_tpu.models import forward
    c, model = tiny
    key = weights.key_of(5)
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 48), c)
    cfg = dataclasses.replace(
        model.model_config(c, "train", attention_impl="reference",
                           remat=False),
        dtype=jnp.float32, param_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = forward(params, toks, cfg)
    want = model.logits(params, toks, c)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert int(toks.max()) < mf.family_of(c).shapes.vocab(c)


@pytest.mark.parametrize("seed", SEEDS)
def test_training_program_passes_and_fp8_control_fails(tiny, seed):
    from ray_tpu.models import lm_loss
    c, model = tiny
    key = weights.key_of(seed)
    params = model.make(key, c, model.param_dtype(c, "train"))
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 64), c)
    cfg = model.model_config(c, "train", attention_impl="reference")
    l_ref, g_ref = model.loss_and_grad(params, toks, c)
    l_got, g_got = jax.value_and_grad(functools.partial(lm_loss, cfg=cfg))(
        params, {"tokens": toks})
    l_ctl, g_ctl = model.loss_and_grad(params, toks, c, "fp8")
    limits = _limits(c["name"] + ".train")
    program = {"grad_err": float(reference.tree_rel_error(g_got, g_ref))}
    control = {"grad_err": float(reference.tree_rel_error(g_ctl, g_ref))}
    # the loss itself is no precision test: fp8 moves it by 0.0001-0.03
    assert abs(float(l_got - l_ref)) < 0.01 and jnp.isfinite(l_ctl)
    sane = {"losses_finite": True}
    assert verdict.verdict(program, limits, sane)["correct"], program
    assert not verdict.verdict(control, limits, sane)["correct"], control
    assert control["grad_err"] > 3 * program["grad_err"]


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_program_passes_and_fp8_control_fails(tiny, seed):
    from ray_tpu.models import forward
    c, model = tiny
    key = weights.key_of(seed)
    params = model.make(key, c, model.param_dtype(c, "serve"))
    assert {x.dtype for x in jax.tree_util.tree_leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    toks = model.tokens(jax.random.fold_in(key, 2), (3, 40), c)
    cfg = model.model_config(c, "serve", attention_impl="reference")
    want = model.logits(params, toks, c)
    v = want.shape[-1]
    want = want.reshape(-1, v)
    got = forward(params, toks, cfg).reshape(-1, v)
    ctl = model.logits(params, toks, c, "fp8").reshape(-1, v)
    limits = _limits(c["name"] + ".serve-closed")
    program = {k: float(x) for k, x in reference.logit_numbers(
        got, want, got.argmax(-1)).items()}
    control = {k: float(x) for k, x in reference.logit_numbers(
        ctl, want, ctl.argmax(-1)).items()}
    sane = {"requests_completed": True}
    assert verdict.verdict(program, limits, sane)["correct"], program
    assert not verdict.verdict(control, limits, sane)["correct"], control
    assert control["logit_err"] > 3 * program["logit_err"]


def test_verdict_needs_every_number_finite_and_every_check():
    lim = {"a": 1.0, "b": 2.0}
    ok = {"x": True, "has_kernel": False}        # has_kernel is not judged
    assert verdict.verdict({"a": 0.5, "b": 2.0}, lim, ok)["correct"]
    assert not verdict.verdict({"a": 0.5}, lim, ok)["correct"]
    assert not verdict.verdict({"a": float("nan"), "b": 1}, lim, ok)["correct"]
    assert not verdict.verdict({"a": 0.5, "b": 2.1}, lim, ok)["correct"]
    assert not verdict.verdict({"a": 0.5, "b": 1}, lim, {"x": False})["correct"]
    assert not verdict.verdict({}, {}, ok)["correct"]
    rows = verdict.verdict({"a": 0.5, "b": 3.0}, lim, ok)["compared"]
    assert [(r["number"], r["limit"], r["inside"]) for r in rows] == [
        ("a", 1.0, True), ("b", 2.0, False)]


def test_weights_come_from_the_seed_alone(tiny):
    c, model = tiny
    a = model.make(weights.key_of(2**31 + 5), c, jnp.float32)
    b = model.make(weights.key_of(2**31 + 5), c, jnp.float32)
    other = model.make(weights.key_of(5), c, jnp.float32)
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    differs = jax.tree_util.tree_map(lambda x, y: bool((x != y).any()),
                                     a, other)
    assert any(jax.tree_util.tree_leaves(differs))
    # the layout is the one the program's own initialiser makes
    from ray_tpu.models import init_params
    cfg = model.model_config(c, "train")
    theirs = jax.eval_shape(lambda k: init_params(k, cfg)[0],
                            jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, a) == \
        jax.tree_util.tree_map(lambda x: x.shape, theirs)
