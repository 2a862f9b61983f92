"""The engine derives the width of its ONE chunk program from the chip's
ridge point and the weights' item size (`serve/decode_session.py`
`prefill_chunk_width`, `util/device_profile.py` `ridge_rows`): the rule
as a table, and what an engine built on the CPU resolves and reports.
Tier-1, CPU: a device kind is NAMED to the rule, never attached.
"""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import TransformerConfig
from ray_tpu.serve.config import DecodeEngineConfig
from ray_tpu.serve.decode_session import (DecodeSessionCore,
                                          prefill_chunk_width,
                                          prefill_lane_count)
from ray_tpu.util.device_profile import (PEAK_HBM_GBPS, PEAK_TFLOPS,
                                         ridge_rows)


def _weights(dtype):
    return {"w": jax.ShapeDtypeStruct((64, 64), dtype),
            "layers": {"up": jax.ShapeDtypeStruct((2, 64, 256), dtype)}}


def test_every_named_chip_has_both_peaks():
    assert set(PEAK_HBM_GBPS) == set(PEAK_TFLOPS)


@pytest.mark.parametrize("kind,itemsize,rows", [
    ("TPU v5 lite", 2, 240.5),      # 197e12 * 2 / (2 * 819e9)
    ("TPU v5e", 4, 481.1),
    ("TPU v5p", 2, 166.0),
    ("TPU v6e", 2, 559.8),
    ("TPU v4", 2, 229.2),
])
def test_ridge_rows_of_the_published_peaks(kind, itemsize, rows):
    assert ridge_rows(itemsize, kind) == pytest.approx(rows, abs=0.05)


def test_ridge_rows_has_no_default():
    assert ridge_rows(2) is None            # the CPU: no published peaks
    with pytest.raises(KeyError, match="TPU v9"):
        ridge_rows(2, "TPU v9")


@pytest.mark.parametrize("pinned,dtype,capacity,kind,want", [
    (None, jnp.bfloat16, 4096, "TPU v5 lite", 128),   # under 240.5
    (None, jnp.float32, 4096, "TPU v5 lite", 256),    # under 481.1
    (None, jnp.bfloat16, 4096, "TPU v5p", 128),       # under 166.0
    (None, jnp.bfloat16, 4096, "TPU v6 lite", 512),   # under 559.8
    (None, jnp.bfloat16, 4096, None, 32),             # no peaks here
    (None, jnp.bfloat16, 24, None, 24),               # ... clamped
    (None, jnp.bfloat16, 100, "TPU v5 lite", 100),    # clamped
    (8, jnp.bfloat16, 4096, "TPU v5 lite", 8),        # a pinned value wins
    (1024, jnp.bfloat16, 4096, "TPU v5 lite", 1024),
    (64, jnp.bfloat16, 48, "TPU v5 lite", 48),        # ... and is clamped
    (0, jnp.bfloat16, 4096, None, 1),
])
def test_the_rule(pinned, dtype, capacity, kind, want):
    got = prefill_chunk_width(pinned, _weights(dtype), capacity, kind)
    assert got == want and type(got) is int


@pytest.mark.parametrize("chunk,dtype,kind,want", [
    (128, jnp.bfloat16, "TPU v5 lite", 4),    # 2 x 240.5 / 128 = 3.8
    (256, jnp.float32, "TPU v5 lite", 4),     # 2 x 481.1 / 256
    (128, jnp.bfloat16, "TPU v5p", 2),        # 2 x 166.0 / 128 = 2.6
    (512, jnp.bfloat16, "TPU v6 lite", 2),    # 2 x 559.8 / 512 = 2.2
    (32, jnp.bfloat16, "TPU v5 lite", 8),     # a pinned narrow chunk: capped
    (1024, jnp.bfloat16, "TPU v5 lite", 2),   # a pinned wide one: at least 2
    (32, jnp.bfloat16, None, 4),              # no peaks here
])
def test_the_lanes_follow_the_ridge_over_the_chunk(chunk, dtype, kind, want):
    """`prefill_lane_count`: the power of two nearest to twice the ridge
    over the chunk, from 2 to 8 (measured on a v5e at 128 rows: 2, 4 and 8
    lanes, `PERF.md` section 6, PR 41); 4 where no peaks are published."""
    got = prefill_lane_count(chunk, _weights(dtype), kind)
    assert got == want and type(got) is int


def test_item_size_is_the_weights_own_not_the_configs():
    """A few float32 leaves (norms, a router's bias) among bfloat16
    matrices do not move the width; `cfg.param_dtype` is not asked."""
    mixed = dict(_weights(jnp.bfloat16),
                 norm=jax.ShapeDtypeStruct((64,), jnp.float32))
    assert prefill_chunk_width(None, mixed, 4096, "TPU v5 lite") == 128


def _core(pinned, max_len=64, **cfg):
    cfg = TransformerConfig.tiny(dtype=jnp.float32,
                                 attention_impl="reference", **cfg)
    return DecodeSessionCore(
        cfg, max_len=max_len, seed=3,
        engine=DecodeEngineConfig(prefill_chunk_tokens=pinned, max_slots=2))


@pytest.mark.parametrize("pinned,max_len,cfg,want", [
    (None, 64, {"max_seq_len": 64}, 32),      # derived: the CPU keeps 32
    (None, 16, {"max_seq_len": 64}, 16),      # ... under a short cache
    (4, 64, {"max_seq_len": 64}, 4),          # pinned
    (48, 64, {"max_seq_len": 40, "pos_emb": "learned"}, 40),  # the table
])
def test_engine_holds_and_reports_the_resolved_width(pinned, max_len, cfg,
                                                     want):
    """`engine.ecfg.prefill_chunk_tokens` is the width in use as an int
    (the benchmark's warm-up and outputs check read it), `stats()` reports
    it, the caller's config object is left as it was given, and a prompt
    of width + 3 tokens runs two programs of that one shape."""
    core = _core(pinned, max_len, **cfg)
    try:
        eng = core.engine
        assert eng.ecfg.prefill_chunk_tokens == want
        assert type(eng.ecfg.prefill_chunk_tokens) is int
        assert eng.ecfg.max_slots == 2
        assert DecodeEngineConfig().prefill_chunk_tokens is None
        n = min(want + 3, eng._capacity - 2)
        out = core.handle({"op": "start",
                           "prompt": [1 + i % 50 for i in range(n)]})
        core.handle({"op": "end", "sid": out["sid"]})
        st = eng.stats()
        assert st["prefill_chunk_tokens"] == want
        assert st["prefill_chunks"] == -(-n // want)
        assert [s for s in st["program_shapes"]
                if s.startswith("prefill_chunk")] == [
                    f"prefill_chunk:1x{want}"]
    finally:
        core.engine.shutdown()
