"""Operations and bytes from shapes, against counts made by hand."""

import pytest

from perfbench import manifest as mf
from perfbench import opsbytes

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


@pytest.mark.parametrize("name", sorted(HAND))
def test_parameter_count(configs, name):
    c, hand = configs[name], HAND[name]
    assert (c["n_embd"], c["n_layer"], c["n_head"], c["n_inner"]) == (
        hand["d"], hand["L"], hand["h"], hand["ff"])
    assert c["n_embd"] // c["n_head"] == 64
    assert opsbytes.count_params(c) == hand["params"]
    assert {"gpt2-medium": 354_650_112,
            "gpt2-xl": 1_556_995_200}[name] == hand["params"]


@pytest.mark.parametrize("name", sorted(HAND))
def test_train_and_decode_operations(configs, name):
    c, hand = configs[name], HAND[name]
    d, L, h = hand["d"], hand["L"], hand["h"]
    matmul = L * (4 * d * d + 2 * d * hand["ff"]) + 50304 * d
    assert opsbytes.train_flops_per_token(c, 1024) == (
        6 * matmul + 6 * L * h * 64 * 1024)
    assert opsbytes.decode_flops_per_token(c, 300) == (
        2 * matmul + 4 * L * h * 64 * 300)


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_step_bytes(configs, name):
    c, hand = configs[name], HAND[name]
    rows = 8 * 200.0
    assert opsbytes.decode_step_bytes(c, rows) == (
        2 * hand["params"] + 2 * hand["L"] * rows * hand["d"] * 2)


def test_flash_attention_cost_and_its_bound(configs):
    c = configs["gpt2-medium"]
    cost = opsbytes.flash_attention_cost(c, batch=32, seq_len=1024)
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


def test_peaks_are_the_published_ones_and_an_unknown_kind_is_an_error():
    p = opsbytes.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "source"):
        with pytest.raises(KeyError):
            opsbytes.peaks(kind)
