"""``cache.rows_fetched_share.bytedoc``: of the cache rows the decode steps'
attention MOVED from memory, the share their live slots attended, from the
``rows_fetched`` the engine's ``cache:rows`` ring spans carry beside
``rows_read`` (`ray_tpu/serve/decode_session.py` `_dispatch`,
`ray_tpu/models/generate.py` `rows_fetched`).  The reader on hand-made spans,
its entry in the root manifest, and the counter itself in an engine of the
tiny byte model at rings of whole blocks: on the kernel's path
(`ray_tpu/ops/cache_attention.py`, through the interpreter) the blocks of the
kernel's own work list, off it every row of every slot.
"""

import dataclasses
import json
import os
import time
import types

import jax.numpy as jnp
import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse

NAME = "cache.rows_fetched_share.bytedoc"
CELL = "evabyte.serve-bytedoc-closed"


def _run(events):
    return types.SimpleNamespace(stamps={"open": 10.0, "close": 55.0},
                                 _ring_spans=events)


def _span(end_s, **args):
    return {"name": "cache:rows", "cat": "cache", "ts": (end_s - 2) * 1e6,
            "dur": 2e6, "args": dict(args, deployment="bench")}


def test_reader_on_hand_made_spans():
    read = mf.metric_reader(NAME)
    assert read(_run([])) is None
    # the parent of the PR that added the key: rows read, none counted as
    # fetched
    assert read(_run([_span(12.0, steps=10, rows_read=300,
                            summary_rows_read=100)])) is None
    events = [
        _span(9.5, steps=9, rows_read=1, rows_fetched=7),   # before the window
        _span(12.0, steps=10, rows_read=300, rows_fetched=400),
        _span(14.0, steps=10, rows_read=450, rows_fetched=600),
        _span(56.0, steps=10, rows_read=5, rows_fetched=5),     # after it
        {"name": "cache:rows", "ts": 20e6, "dur": 2e6},    # no arguments
        {"name": "engine:ahead", "ts": 20e6, "dur": 2e6,
         "args": {"rows_fetched": 10 ** 6}},
    ]
    assert read(_run(events)) == pytest.approx(75.0)


def test_root_manifest_lists_it_and_has_no_problem():
    root = mf.Manifest()
    assert mf.problems(root) == []
    assert root.data["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "kernels",
        "moves": "serve_tok_s", "workloads": [CELL]}
    e2e = next(e for e in root.data["end_to_end"]
               if e["name"] == "serve_tok_s")
    assert CELL in e2e["workloads"]


@pytest.mark.parametrize("path", ["kernel", "dense"])
def test_the_engine_counts_the_rows_its_steps_fetched(monkeypatch, path):
    """One session of the tiny byte model (2 summary layers; windows of 256
    in rings of 384 rows, 128 summary rows: whole blocks of 128) decodes
    from 250 through the window's edge in an engine of 3 slots."""
    from ray_tpu.models.generate import _eva_masks
    from ray_tpu.ops import cache_attention as ca
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import (ContinuousBatchingEngine,
                                              DecodeSessionCore)
    from ray_tpu.util import tracing
    if path == "kernel":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           "tiny-evabyte.json")) as f:
        c = json.load(f)
    max_len, slots, n = 512, 3, 250
    cfg = dataclasses.replace(
        mf.family_of(c).model.model_config(c, "serve"), dtype=jnp.float32,
        param_dtype=jnp.float32, sliding_window=256, window_chunk=128,
        max_seq_len=max_len)
    core = DecodeSessionCore(cfg, max_len=max_len, seed=3,
                             engine=DecodeEngineConfig(
                                 max_slots=slots, prefill_chunk_tokens=128))
    try:
        before = len([e for e in tracing.span_events()
                      if e["name"] == "cache:rows"])
        r = core.handle({"op": "start",
                         "prompt": [3 + i % 30 for i in range(n)]})
        assert "error" not in r, r
        got = len(r["token"])
        while got < 12:
            out = core.handle({"op": "next_chunk", "sid": r["sid"],
                               "max_tokens": 12 - got})
            assert "error" not in out, out
            got += len(out["tokens"])
        core.handle({"op": "end", "sid": r["sid"]})
        eng = core.engine
        for _ in range(500):          # the step in flight is read too
            if eng._flight is None:
                break
            time.sleep(0.01)
        cache = eng.stats()["cache"]
    finally:
        core.engine.shutdown()
    steps, layers = cache["steps"], cfg.n_layers
    assert steps >= 11
    pos = jnp.arange(n, n + steps)      # where the session stood, step by step
    if path == "kernel":
        masks = _eva_masks(cfg, pos, 1, max_len)
        _, runs, _, items = ca.block_work(
            [masks["eva"], masks["summary"]], None)
        assert int(items) == int(runs.sum())    # every item runs
        want = int(items) * ca.BLOCK * layers
        # 250..255: two ring blocks, no summary; 256 on: one and one
        assert int(items) == 2 * (256 - n) + 2 * (steps - (256 - n))
    else:
        want = steps * slots * (384 + max_len // cfg.summary_chunk) * layers
    assert cache["rows_fetched"] == want
    assert 0 < cache["rows_read"] <= cache["rows_fetched"]
    spans = [e for e in tracing.span_events()
             if e["name"] == "cache:rows"][before:]
    assert sum(e["args"].get("rows_fetched", 0) for e in spans) == want
    read = mf.metric_reader(NAME)
    share = read(types.SimpleNamespace(
        stamps={"open": 0.0, "close": 1e12}, _ring_spans=spans))
    assert share == pytest.approx(
        100.0 * cache["rows_read"] / cache["rows_fetched"])
