"""The comparison that decides ``correct``, at a size a test run can hold:
the program passes its limits, and the control (the plain reference computed
in fp8, the nearest precision below the bfloat16 the configurations state)
fails them.  On the chip, at the cells' own size, the same is read by
perfbench/tools/outputs_check.py."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import manifest as mf
from perfbench import chipside, reference, verdict, weights

REHEARSAL = os.path.join(mf.BENCH_DIR, "testdata", "rehearsal")
SEEDS = [3, 2**31 + 11, 77]


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(REHEARSAL, "configs", "tiny.json")) as f:
        return json.load(f)


def _limits(cell):
    with open(os.path.join(REHEARSAL, "limits", cell + ".json")) as f:
        return json.load(f)["limits"]


def test_reference_is_the_programs_function_in_float32(tiny):
    """Two independent implementations of the same published equations
    agree to float32 rounding when both compute in float32."""
    from ray_tpu.models import TransformerConfig, forward
    key = weights.key_of(5)
    params = weights.make(key, tiny, jnp.float32)
    toks = weights.tokens(jax.random.fold_in(key, 1), (2, 48), tiny)
    cfg = TransformerConfig(
        vocab_size=tiny["vocab_size"], d_model=tiny["n_embd"],
        n_layers=tiny["n_layer"], n_heads=tiny["n_head"],
        d_ff=tiny["n_inner"], max_seq_len=tiny["n_positions"],
        dtype=jnp.float32, param_dtype=jnp.float32,
        attention_impl="reference", remat=False)
    with jax.default_matmul_precision("highest"):
        got = forward(params, toks, cfg)
    want = reference.logits(params, toks, tiny)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert int(toks.max()) < tiny["published"]["vocab_size"]


@pytest.mark.parametrize("seed", SEEDS)
def test_training_program_passes_and_fp8_control_fails(tiny, seed):
    from ray_tpu.models import lm_loss
    key = weights.key_of(seed)
    params = weights.make(key, tiny, chipside.param_dtype(tiny, "train"))
    toks = weights.tokens(jax.random.fold_in(key, 1), (2, 64), tiny)
    cfg = chipside.model_config(tiny, "train", attention_impl="reference")
    l_ref, g_ref = reference.loss_and_grad(params, toks, tiny)
    l_got, g_got = jax.value_and_grad(functools.partial(lm_loss, cfg=cfg))(
        params, {"tokens": toks})
    l_ctl, g_ctl = reference.loss_and_grad(params, toks, tiny, "fp8")
    limits = _limits("tiny.train")
    program = {"grad_err": float(reference.tree_rel_error(g_got, g_ref))}
    control = {"grad_err": float(reference.tree_rel_error(g_ctl, g_ref))}
    assert abs(float(l_got - l_ref)) < 0.01 > abs(float(l_ctl - l_ref))
    sane = {"losses_finite": True}
    assert verdict.verdict(program, limits, sane)["correct"], program
    assert not verdict.verdict(control, limits, sane)["correct"], control
    assert control["grad_err"] > 3 * program["grad_err"]


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_program_passes_and_fp8_control_fails(tiny, seed):
    from ray_tpu.models import forward
    key = weights.key_of(seed)
    params = weights.make(key, tiny, chipside.param_dtype(tiny, "serve"))
    assert params["layers"]["wq"].dtype == jnp.bfloat16
    toks = weights.tokens(jax.random.fold_in(key, 2), (3, 40), tiny)
    cfg = chipside.model_config(tiny, "serve", attention_impl="reference")
    v = tiny["vocab_size"]
    want = reference.logits(params, toks, tiny).reshape(-1, v)
    got = forward(params, toks, cfg).reshape(-1, v)
    ctl = reference.logits(params, toks, tiny, "fp8").reshape(-1, v)
    limits = _limits("tiny.serve-closed")
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
    a = weights.make(weights.key_of(2**31 + 5), tiny, jnp.float32)
    b = weights.make(weights.key_of(2**31 + 5), tiny, jnp.float32)
    c = weights.make(weights.key_of(5), tiny, jnp.float32)
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((a["layers"]["wq"] == c["layers"]["wq"]).all())
    # the layout is the one the program's own initialiser makes
    from ray_tpu.models import init_params
    cfg = chipside.model_config(tiny, "train")
    theirs = jax.eval_shape(lambda k: init_params(k, cfg)[0],
                            jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, a) == \
        jax.tree_util.tree_map(lambda x: x.shape, theirs)
