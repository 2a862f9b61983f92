"""``cache.chunk_rows_fetched_share.batch``: of the cache rows the CHUNK
programs' attention moved from memory, the share a real query of theirs saw,
from the ``chunk_rows_fetched`` and ``chunk_rows_read`` the engine's
``engine:lanes`` ring spans carry beside ``programs`` and ``chunks``
(`ray_tpu/serve/decode_session.py` `_count_chunks`,
`ray_tpu/models/generate.py` `chunk_rows_fetched`).  The reader on hand-made
spans (with the keys, and a parent's without), its entry in the root
manifest, and the counter itself in an engine of a tiny model: on the
kernel's path (`ray_tpu/ops/cache_attention.py` `attend_chunk_blocks`,
through the interpreter) the blocks a chunk's queries see, off it every row
of the session's arrays.
"""

import types

import jax.numpy as jnp
import pytest

from perfbench import manifest as mf

NAME = "cache.chunk_rows_fetched_share.batch"
CELLS = ["evabyte.serve-bytedoc-closed",
         "trinity-large-preview.serve-mixed-closed",
         "mimo-v2-flash.serve-longreason-closed"]


def _run(events):
    return types.SimpleNamespace(stamps={"open": 10.0, "close": 55.0},
                                 _ring_spans=events)


def _span(end_s, **args):
    return {"name": "engine:lanes", "cat": "lanes", "ts": (end_s - 2) * 1e6,
            "dur": 2e6, "args": dict(args, deployment="bench")}


@pytest.mark.parametrize("counted,want", [
    (False, None),              # the parent: the keys are not there
    (True, 100.0 * 6400 / 7680)])
def test_reader_on_hand_made_spans(counted, want):
    read = mf.metric_reader(NAME)
    assert read(_run([])) is None
    more = lambda fetched, seen: {
        "chunk_rows_fetched": fetched, "chunk_rows_read": seen} \
        if counted else {}
    events = [_span(9.5, programs=9, chunks=9, **more(7, 1)),   # before
              _span(56.0, programs=1, chunks=1, **more(5, 5)),  # after
              {"name": "engine:lanes", "ts": 20e6, "dur": 2e6},  # no args
              {"name": "cache:rows", "ts": 20e6, "dur": 2e6,
               "args": {"chunk_rows_fetched": 10 ** 12}}]
    events += [_span(12.0 + 2 * i, programs=3, chunks=10,
                     **more(3840, 3200)) for i in range(2)]
    got = read(_run(events))
    assert got is None if want is None else got == pytest.approx(want)


def test_root_manifest_lists_it_and_has_no_problem():
    root = mf.Manifest()
    assert mf.problems(root) == []
    mine = [m for m in root.data["per_layer"] if m["name"] == NAME]
    assert mine == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "kernels",
        "moves": "serve_tok_s", "workloads": CELLS}]
    e2e = next(e for e in root.data["end_to_end"]
               if e["name"] == "serve_tok_s")
    assert set(CELLS) <= set(e2e["workloads"])


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_the_engine_counts_the_rows_its_chunk_programs_fetched(monkeypatch,
                                                               path):
    """One session of a tiny model (2 full layers of 256 rows) prefills 200
    tokens through chunks of 128: one whole chunk from 0, one of 72 real
    rows from 128.  `engine.stats()` and the ``engine:lanes`` spans carry
    both sums, and the reader gives their ratio."""
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import (ContinuousBatchingEngine,
                                              DecodeSessionCore)
    from ray_tpu.util import tracing
    if path == "kernel":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
        head_size=16, d_ff=64, max_seq_len=256, pos_emb="rope",
        rope_base=1e4, activation="swiglu", norm="rmsnorm",
        tie_embeddings=False, remat=False, dtype=jnp.float32,
        param_dtype=jnp.float32, attention_impl="reference")
    core = DecodeSessionCore(cfg, max_len=256, seed=3,
                             engine=DecodeEngineConfig(
                                 max_slots=2, prefill_chunk_tokens=128))
    try:
        before = len([e for e in tracing.span_events()
                      if e["name"] == "engine:lanes"])
        r = core.handle({"op": "start",
                         "prompt": [3 + i % 50 for i in range(200)]})
        assert "error" not in r, r
        core.handle({"op": "end", "sid": r["sid"]})
        stats = core.engine.stats()
    finally:
        core.engine.shutdown()
    assert (stats["prefill_programs"], stats["prefill_chunks"]) == (2, 2)
    # a real query of the first chunk sees rows 0..127, of the second 0..199
    assert stats["chunk_rows_read"] == 2 * (128 + 200)
    # the kernel moves the blocks either chunk's queries see (1, then 2);
    # the dense form every row of the session's arrays, twice
    want = 2 * (128 + 256) if path == "kernel" else 2 * (256 + 256)
    assert stats["chunk_rows_fetched"] == want
    spans = [e for e in tracing.span_events()
             if e["name"] == "engine:lanes"][before:]
    for key in ("programs", "chunks", "chunk_rows_read",
                "chunk_rows_fetched"):
        assert sum(e["args"].get(key, 0) for e in spans) \
            == {"programs": 2, "chunks": 2}.get(key, stats.get(key)), key
    share = mf.metric_reader(NAME)(types.SimpleNamespace(
        stamps={"open": 0.0, "close": 1e12}, _ring_spans=spans))
    assert share == pytest.approx(100.0 * 2 * 328 / want)
