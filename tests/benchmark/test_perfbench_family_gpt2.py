"""Family ``gpt2`` behind the seam: what it makes is, bit for bit, what
`perfbench/weights.py`, `reference.py` and `opsbytes.py` made before the
move (golden/gpt2.tiny.json was recorded from commit 26c1390 with the code
of `_record` below), and its counts are the ones made by hand."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import manifest as mf
from perfbench import opsbytes, weights
from perfbench.tools import rehearse

HERE = os.path.dirname(os.path.abspath(__file__))


def _sha(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


def _leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): _sha(x) for p, x in flat}


def _record(seed: int, c, model):
    key = weights.key_of(seed)
    row = {"weights." + name: _leaves(model.make(key, c, dt))
           for name, dt in (("float32", jnp.float32),
                            ("bfloat16", jnp.bfloat16))}
    params = model.make(key, c, jnp.float32)
    toks = model.tokens(jax.random.fold_in(key, 1), (2, 48), c)
    row["tokens"] = _sha(toks)
    row["logits"] = _sha(model.logits(params, toks, c))
    row["logits.fp8"] = _sha(model.logits(params, toks, c, "fp8"))
    row["loss"] = float(model.loss(params, toks, c)).hex()
    loss, g = model.loss_and_grad(params, toks, c)
    row["loss_and_grad.loss"] = float(loss).hex()
    row["grad_norm"] = float(jnp.sqrt(sum(
        jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(g)))).hex()
    row["grad"] = _leaves(g)
    return row


@pytest.mark.parametrize("seed", [3, 2**32 + 11])
def test_weights_and_reference_are_the_parents_bit_for_bit(seed):
    tiny = rehearse.manifest().config("tiny")
    with open(os.path.join(HERE, "golden", "gpt2.tiny.json")) as f:
        golden = json.load(f)[str(seed)]
    got = _record(seed, tiny, mf.family_of(tiny).model)
    assert set(got) == set(golden)
    for name in sorted(golden):
        assert got[name] == golden[name], name


# by hand, GPT-2 without projection biases, vocabulary padded to 50304:
# per layer 4 d^2 (q, k, v, o) + 2 d ff (in, out) + 4 d (two LayerNorms)
HAND = {
    "gpt2-medium": dict(
        d=1024, L=24, h=16, ff=4096,
        per_layer=4 * 1024 ** 2 + 2 * 1024 * 4096 + 4 * 1024,
        params=24 * (4 * 1024 ** 2 + 2 * 1024 * 4096 + 4 * 1024)
        + 50304 * 1024 + 1024 * 1024 + 2 * 1024),
    "gpt2-xl": dict(
        d=1600, L=48, h=25, ff=6400,
        per_layer=4 * 1600 ** 2 + 2 * 1600 * 6400 + 4 * 1600,
        params=48 * (4 * 1600 ** 2 + 2 * 1600 * 6400 + 4 * 1600)
        + 50304 * 1600 + 1024 * 1600 + 2 * 1600),
}


@pytest.fixture(scope="module")
def configs():
    m = mf.Manifest()
    return {c["name"]: m.config(c["name"]) for c in m.data["configs"]}


@pytest.fixture(scope="module")
def shapes():
    return mf.family("gpt2").shapes


@pytest.mark.parametrize("name", sorted(HAND))
def test_parameter_count(configs, shapes, name):
    c, hand = configs[name], HAND[name]
    assert mf.family_of(c).shapes is shapes
    assert (c["n_embd"], c["n_layer"], c["n_head"], c["n_inner"]) == (
        hand["d"], hand["L"], hand["h"], hand["ff"])
    assert c["n_embd"] // c["n_head"] == 64
    assert shapes.count_params(c) == hand["params"]
    assert {"gpt2-medium": 354_650_112,
            "gpt2-xl": 1_556_995_200}[name] == hand["params"]
    assert shapes.vocab(c) == 50257 and shapes.positions(c) == 1024


@pytest.mark.parametrize("name", sorted(HAND))
def test_train_and_decode_operations(configs, shapes, name):
    c, hand = configs[name], HAND[name]
    d, L, h = hand["d"], hand["L"], hand["h"]
    matmul = L * (4 * d * d + 2 * d * hand["ff"]) + 50304 * d
    assert shapes.train_flops_per_token(c, 1024) == (
        6 * matmul + 6 * L * h * 64 * 1024)
    assert shapes.decode_flops_per_token(c, 300) == (
        2 * matmul + 4 * L * h * 64 * 300)


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_step_bytes(configs, shapes, name):
    c, hand = configs[name], HAND[name]
    rows = 8 * 200.0
    assert shapes.decode_step_bytes(c, rows) == (
        2 * hand["params"] + 2 * hand["L"] * rows * hand["d"] * 2)


def test_flash_attention_cost_and_its_bound(configs, shapes):
    c = configs["gpt2-medium"]
    cost = shapes.flash_attention_cost(c, batch=32, seq_len=1024)
    one_matmul = 2 * 32 * 16 * 1024 * 1024 * 64 / 2
    assert cost["fwd_flops"] == 2 * one_matmul
    assert cost["bwd_flops"] == 5 * one_matmul
    tensor = 32 * 1024 * 16 * 64 * 2
    assert cost["fwd_bytes"] == 4 * tensor and cost["bwd_bytes"] == 8 * tensor
    peak = opsbytes.peaks("TPU v5 lite")
    r = opsbytes.roofline_seconds(cost["fwd_flops"], cost["fwd_bytes"], peak)
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(cost["fwd_flops"] / 197e12)
    assert opsbytes.roofline_seconds(1.0, 1e9, peak)["bound"] == "memory"
    # as the reader asks: the kernel by name, with the layers that call it
    assert shapes.kernels(c, 32, 1024) == {
        "flash_attention": dict(cost, calls=24)}
