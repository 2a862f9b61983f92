"""Family ``rope_gqa`` (the CPU rehearsal's second family): its counts
against counts made by hand and against the program's own arithmetic, and
its cache width against the cache the program allocates.  Its reference
against the program, its fp8 control and its two rehearsals are the
parametrised cases of test_perfbench_reference.py and
test_perfbench_rehearsal.py, which found it by the rehearsal's manifest."""

import jax
import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse

# by hand at the tiny size: d 64, 2 layers, 4 query heads over 2 key-value
# heads of 16, feed-forward 256 (up, gate, down), vocabulary 256, untied
PER_LAYER = 2 * 64 * 4 * 16 + 2 * 64 * 2 * 16 + 3 * 64 * 256
PARAMS = 2 * (PER_LAYER + 2 * 64) + 2 * 256 * 64 + 64


@pytest.fixture(scope="module")
def tiny():
    c = rehearse.manifest().config("tiny-rope")
    return c, mf.family_of(c)


def test_counts_by_hand(tiny):
    c, fam = tiny
    s = fam.shapes
    assert PER_LAYER == 61_440 and PARAMS == 155_968
    assert s.count_params(c) == PARAMS
    assert s.vocab(c) == 256 and s.positions(c) == 128
    matmul = 2 * PER_LAYER + 256 * 64
    assert s.train_flops_per_token(c, 64) == 6 * matmul + 6 * 2 * 4 * 16 * 64
    # a decode step gathers its embedding rows and reads every other
    # weight; a live cache row is 2 key-value heads of 16 wide, not 4
    assert s.decode_step_bytes(c, 100.0) == (
        2 * (PARAMS - 256 * 64) + 2 * 2 * 100.0 * 2 * 16 * 2)
    k = s.kernels(c, 4, 64)["flash_attention"]
    one = 2 * 4 * 4 * 64 * 64 * 16 / 2
    assert (k["fwd_flops"], k["bwd_flops"], k["calls"]) == (2 * one,
                                                            5 * one, 2)
    wide, narrow = 4 * 64 * 4 * 16 * 2, 4 * 64 * 2 * 16 * 2
    assert k["fwd_bytes"] == 2 * wide + 2 * narrow
    assert k["bwd_bytes"] == 4 * wide + 4 * narrow


def test_counts_are_the_programs_and_the_cache_is_as_wide(tiny):
    from ray_tpu.models import init_slot_cache
    from ray_tpu.models.transformer import count_params, flops_per_token
    c, fam = tiny
    cfg = fam.model.model_config(c, "serve")
    assert fam.shapes.count_params(c) == count_params(cfg)
    assert fam.shapes.train_flops_per_token(c, 64) == flops_per_token(cfg, 64)
    made = jax.eval_shape(lambda k: fam.model.make(k, c, cfg.param_dtype),
                          jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(made)) == PARAMS
    assert "pos" not in made["embed"] and "lm_head" in made
    cache = jax.eval_shape(lambda: init_slot_cache(cfg, 3, 128))
    per_row = (cache["k"].size + cache["v"].size) * 2 / (3 * 128)
    rows = fam.shapes.decode_step_bytes(c, 1.0) \
        - fam.shapes.decode_step_bytes(c, 0.0)
    assert rows == per_row
