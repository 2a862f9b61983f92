"""``kda.state_fetched_share.think``: of the bytes of delta state the decode
steps' programs MOVED, the share the rule requires, from the
``state_bytes_fetched`` the engine's ``cache:rows`` ring spans carry beside
``state_bytes_moved`` (`ray_tpu/serve/decode_session.py` `_state_rows_of`,
`ray_tpu/models/generate.py` `state_fetched`).  The reader on hand-made
spans, its entry in the root manifest, and the counter itself in an engine of
the tiny Kimi model at heads of 128 x 128: on the kernel's path
(`ray_tpu/ops/delta_rule.py` `step_in_place`, through the interpreter) the
live slot's states once read and once written, off it every slot's three
times.
"""

import dataclasses
import json
import os
import types

import jax.numpy as jnp
import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse

NAME = "kda.state_fetched_share.think"
CELL = "kimi-linear-48b-a3b.serve-think-closed"


def _run(events):
    return types.SimpleNamespace(stamps={"open": 10.0, "close": 55.0},
                                 _ring_spans=events)


def _span(end_s, **args):
    return {"name": "cache:rows", "cat": "cache", "ts": (end_s - 2) * 1e6,
            "dur": 2e6, "args": dict(args, deployment="bench")}


@pytest.mark.parametrize("fetched,want", [
    (None, None),                   # the parent: the key is not there
    (lambda live: 2 * live, 100.0),             # the kernel's count
    (lambda live: 3 * 32, 62.8)])               # XLA's form, 32 slots
def test_reader_on_hand_made_spans(fetched, want):
    """The two counts at the cell's shape: 20 KDA layers, a state of
    2,170,880 B a slot a layer, 30.14 of 32 slots live a step."""
    read = mf.metric_reader(NAME)
    assert read(_run([])) is None
    per, lives = 20 * 2_170_880, (30, 31, 29, 30, 31, 30, 30)    # 30.14
    events = [_span(9.5, steps=9, state_bytes_moved=1,
                    state_bytes_fetched=7),                 # before the window
              _span(56.0, steps=1, state_bytes_moved=5,
                    state_bytes_fetched=5),                 # after it
              {"name": "cache:rows", "ts": 20e6, "dur": 2e6},   # no arguments
              {"name": "moe:load", "ts": 20e6, "dur": 2e6,
               "args": {"state_bytes_fetched": 10 ** 12}}]
    for i, live in enumerate(lives):
        more = {} if fetched is None else {
            "state_bytes_fetched": fetched(live) * per}
        events.append(_span(12.0 + 2 * i, steps=1, state_rows=20 * live,
                            state_bytes_moved=2 * live * per, **more))
    if fetched is None:
        events = [e for e in events
                  if "state_bytes_fetched" not in e.get("args", {})]
    got = read(_run(events))
    assert got is None if want is None else got == pytest.approx(
        want, abs=0.05)


def test_root_manifest_lists_it_and_has_no_problem():
    root = mf.Manifest()
    assert mf.problems(root) == []
    mine = [m for m in root.data["per_layer"] if m["name"] == NAME]
    assert mine == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "kernels",
        "moves": "serve_tok_s", "workloads": [CELL]}]
    e2e = next(e for e in root.data["end_to_end"]
               if e["name"] == "serve_tok_s")
    assert CELL in e2e["workloads"]


@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_the_engine_counts_the_state_its_steps_moved(monkeypatch, path):
    """One session of the tiny Kimi model (4 KDA layers; here 4 heads of 128
    x 128, a shape the kernel takes) decodes in an engine of 3 slots."""
    from ray_tpu.models.generate import position_bytes
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import (ContinuousBatchingEngine,
                                              DecodeSessionCore)
    from ray_tpu.util import tracing
    if path == "kernel":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                           "tiny-kimi-linear.json")) as f:
        c = json.load(f)
    max_len, slots = 96, 3      # (no whole block of rows: no other kernel)
    cfg = dataclasses.replace(
        mf.family_of(c).model.model_config(c, "serve"), dtype=jnp.float32,
        param_dtype=jnp.float32, kda_head_dim=128, max_seq_len=max_len)
    core = DecodeSessionCore(cfg, max_len=max_len, seed=3,
                             engine=DecodeEngineConfig(
                                 max_slots=slots, prefill_chunk_tokens=32))
    try:
        before = len([e for e in tracing.span_events()
                      if e["name"] == "cache:rows"])
        r = core.handle({"op": "start",
                         "prompt": [3 + i % 30 for i in range(40)]})
        assert "error" not in r, r
        got = len(r["token"])
        while got < 5:
            out = core.handle({"op": "next_chunk", "sid": r["sid"],
                               "max_tokens": 5 - got})
            assert "error" not in out, out
            got += len(out["tokens"])
        core.handle({"op": "end", "sid": r["sid"]})
    finally:
        core.engine.shutdown()
    cache = core.engine.stats()["cache"]    # the step in flight read too
    steps, per = cache["steps"], 4 * position_bytes(cfg)["delta"]
    assert steps >= 4 and per == 4 * (4 * 128 * 128 * 4 + 3 * 3 * 512 * 4)
    assert cache["state_bytes_moved"] == steps * 2 * per    # one live slot
    want = steps * (2 if path == "kernel" else 3 * slots) * per
    assert cache["state_bytes_fetched"] == want
    spans = [e for e in tracing.span_events()
             if e["name"] == "cache:rows"][before:]
    assert sum(e["args"].get("state_bytes_fetched", 0)
               for e in spans) == want
    share = mf.metric_reader(NAME)(types.SimpleNamespace(
        stamps={"open": 0.0, "close": 1e12}, _ring_spans=spans))
    assert share == pytest.approx(100.0 if path == "kernel"
                                  else 100.0 * 2 / (3 * slots))
