"""Dispatch-profiler units (PR-16 data-plane flight instruments):
wrap-once idempotence across engine restarts, the compile ledger
(novel-shape dispatches counted as compiles), device-time samples handed
in by the caller (the shim never waits for the device on a shape it has
seen: the engine keeps a step in flight) and their extrapolation, MFU
arithmetic against hand-computed analytic FLOPs, the peak-FLOPs
resolution order, and where the engine takes its samples."""

import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.core.config import GlobalConfig  # noqa: E402
from ray_tpu.models import (TransformerConfig,  # noqa: E402
                            decode_flops_per_token, engine_flops_table)
from ray_tpu.util.device_profile import (DispatchProfiler,  # noqa: E402
                                         peak_flops)


# ------------------------------------------------------------ wrap-once

def test_wrap_is_idempotent_across_engine_restarts():
    """The prefill chunk program is a module-level shared jit: every
    engine (re)start wraps it again.  A re-wrap must unwrap to the
    ORIGINAL underneath — stacking two shims would double-count every
    dispatch."""
    calls = []

    def fn(x):
        calls.append(1)
        return x

    p1 = DispatchProfiler()
    w1 = p1.wrap("prog", fn)
    # "engine restart": a fresh profiler wraps the already-wrapped fn
    p2 = DispatchProfiler()
    w2 = p2.wrap("prog", w1)
    assert w2._rt_profiled_inner is fn     # unwrapped, not stacked
    w2(jnp.ones((2, 2)))
    assert len(calls) == 1                 # the original ran once
    assert p2.snapshot(peak=1.0)[0]["dispatches"] == 1
    assert p1.snapshot(peak=1.0)[0]["dispatches"] == 0  # old shim idle

    # re-wrap within the SAME profiler must not stack either
    w3 = p1.wrap("prog", p1.wrap("prog", fn))
    w3(jnp.ones((2, 2)))
    assert p1.snapshot(peak=1.0)[0]["dispatches"] == 1


# -------------------------------------------------------- compile ledger

def test_compile_ledger_counts_novel_shapes():
    """A first-seen argument-shape dispatch pays XLA trace + compile:
    the ledger must count exactly the distinct shapes, bill their wall
    time as compile seconds, and keep them out of the steady-state
    device-time sample pool."""
    p = DispatchProfiler()
    f = p.wrap("prog", jax.jit(lambda x: x * 2))
    a, b = jnp.ones((1, 4)), jnp.ones((1, 8))
    for arg in (a, a, b, a, b):
        f(arg)
    row = p.snapshot(peak=1.0)[0]
    assert row["dispatches"] == 5
    assert row["compiles"] == 2 == row["shapes"]
    assert row["compile_s"] > 0
    assert p.total_compiles() == 2
    assert p.distinct_shapes() == 2


def test_shape_key_sees_scalar_statics():
    """Static scalars retrace jits too — a static int flipping per call
    is a compile storm the ledger must see."""
    p = DispatchProfiler()
    f = p.wrap("prog", lambda x, k: x)
    x = jnp.ones((2,))
    f(x, 1)
    f(x, 2)
    f(x, 1)
    assert p.snapshot(peak=1.0)[0]["compiles"] == 2


# ------------------------------------------------- device time and MFU

def test_wrap_waits_for_the_device_on_a_first_seen_shape_only(monkeypatch):
    """A loop that keeps a step in flight is emptied by every wait: the
    shim blocks on the dispatch that compiles, and on no other."""
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda out: (waits.append(1), real(out))[1])
    p = DispatchProfiler()
    f = p.wrap("prog", jax.jit(lambda x: x * 2))
    a, b = jnp.ones((1, 4)), jnp.ones((1, 8))
    for arg in (a,) * 25 + (b,) * 25:
        f(arg)
    assert len(waits) == 2
    row = p.snapshot(peak=1.0)[0]
    assert row["dispatches"] == 50 and row["compiles"] == 2
    # no sample was handed in: dispatch wall is the bound, and no mfu
    assert row["device_s"] == pytest.approx(row["wall_s"], abs=1e-5)


def test_device_seconds_extrapolation_and_mfu_arithmetic():
    p = DispatchProfiler()
    w = p.wrap("prog", lambda x: x)
    x = jnp.ones((2, 2))
    for _ in range(5):
        w(x)
    # the caller timed two of the five dispatches on the device
    p.note_device_seconds("prog", 0.002)
    p.note_device_seconds("prog", 0.004)
    p.set_flops_per_token("prog", 1e6)
    p.note_tokens("prog", 500)
    row = p.snapshot(peak=1e9)[0]
    assert row["device_s"] == pytest.approx(5 * 0.003)
    # mfu = tokens * flops_per_token / device_seconds / peak
    expect = 500 * 1e6 / row["device_s"] / 1e9
    assert row["mfu"] == pytest.approx(expect, rel=0.02)


def test_mfu_is_none_without_tokens_or_flops():
    p = DispatchProfiler()
    w = p.wrap("prog", lambda x: x)
    w(jnp.ones((2,)))
    assert p.snapshot(peak=1e9)[0]["mfu"] is None   # no flops, no toks
    p.set_flops_per_token("prog", 1e6)
    assert p.snapshot(peak=1e9)[0]["mfu"] is None   # still no tokens
    p.note_tokens("prog", 500)
    # ... and no device-time sample: a share of the peak over dispatch
    # walls would read far past it
    assert p.snapshot(peak=1e9)[0]["mfu"] is None
    p.note_device_seconds("prog", 0.01)
    assert p.snapshot(peak=1e9)[0]["mfu"] > 0


def test_decode_flops_per_token_matches_hand_computation():
    """Re-derive the analytic decode FLOPs for the tiny config straight
    from its fields: 2 FLOPs/MAC over qkvo + swiglu MLP + unembed, plus
    qk^T and probs.v reads against every cached position."""
    cfg = TransformerConfig.tiny()
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    ff, L = cfg.ff_dim, cfg.n_layers
    assert cfg.activation == "swiglu" and not cfg.n_experts
    per_layer = d * h * hd + 2 * d * hk * hd + h * hd * d + 3 * d * ff
    ctx = 64
    hand = 2 * (L * per_layer + cfg.vocab_size * d) + 4 * L * h * hd * ctx
    assert decode_flops_per_token(cfg, ctx) == hand

    table = engine_flops_table(cfg, max_len=2 * ctx)   # mid == ctx
    assert table["decode_step"] == hand
    assert table["prefill_chunk"] == hand
    assert table["cache_insert"] == 0.0     # byte movers: no MFU
    assert table["prefix_gather"] == 0.0
    # one row a program the engine wraps, none for a program it has not
    assert sorted(table) == ["cache_insert", "decode_step", "prefill_chunk",
                             "prefix_gather"]


def test_peak_flops_config_override_wins(monkeypatch):
    monkeypatch.setitem(GlobalConfig._values,
                        "device_profile_peak_flops", 123.0)
    assert peak_flops() == 123.0
    monkeypatch.setitem(GlobalConfig._values,
                        "device_profile_peak_flops", 0.0)
    assert peak_flops() is None  # the CPU has no published peak: no mfu


# ---------------------------------------------- engine integration seam

def test_engine_stats_carry_profile_and_phase_totals():
    """The serve engine's stats() must ship the profiler snapshot and
    the phase attribution table, and the profiler's prefill tokens must
    match the prompt lengths it actually prefilled (host-side count —
    the MFU numerator never costs a device sync)."""
    from ray_tpu.serve.decode_session import DecodeSessionCore

    cfg = TransformerConfig.tiny(max_seq_len=128, dtype=jnp.float32)
    core = DecodeSessionCore(cfg, max_len=128)
    try:
        prompt = [int(i) % cfg.vocab_size for i in range(17)]
        out = core.handle({"op": "start", "prompt": prompt})
        assert "sid" in out
        for _ in range(4):
            core.handle({"op": "next_chunk", "sid": out["sid"],
                         "max_tokens": 2})
        st = core.handle({"op": "stats"})["engine"]
        prof = {r["program"]: r for r in st["device_profile"]}
        assert prof["prefill_chunk"]["dispatches"] >= 1
        assert prof["prefill_chunk"]["tokens"] == len(prompt)
        assert prof["decode_step"]["dispatches"] >= 1
        assert prof["decode_step"]["compiles"] >= 1   # ledger alive
        ph = st["phase_totals"]
        # (the whole set is pinned in tests/test_tracing_spans.py)
        assert set(ph) >= {"queue", "admission", "prefill",
                           "decode_dispatch", "first_token",
                           "prefill_tail", "schedule", "admit_host",
                           "dispatch", "readback", "publish"}
        assert ph["prefill"] > 0 and ph["decode_dispatch"] > 0
        # the engine thread's own phases: every iteration passed
        # through each of them
        for key in ("schedule", "admit_host", "dispatch", "readback",
                    "publish"):
            assert ph[key] > 0, (key, ph)
        # wrap-once across restart: a second engine re-wraps the
        # module-level shared prefill chunk jit; its ledger starts
        # clean instead of inheriting a stacked shim
        core2 = DecodeSessionCore(cfg, max_len=128)
        try:
            p2 = {r["program"]: r
                  for r in core2.engine.stats()["device_profile"]}
            assert p2["prefill_chunk"]["dispatches"] == 0
        finally:
            core2.engine.shutdown()
    finally:
        core.engine.shutdown()


class _Out:
    """A step's output as `_read` sees it: done or not, then an array."""

    def __init__(self, ready):
        self._ready = ready

    def is_ready(self):
        return self._ready

    def __array__(self, dtype=None, copy=None):
        import numpy as np
        return np.zeros(4, np.int32)


@pytest.mark.parametrize("start,prev_waited,waited,sampled", [
    ("clock", False, True, True),    # the chip was free at the dispatch
    ("behind", True, True, True),    # ... or took the step as the last ended
    ("behind", False, True, False),  # the last one ended nobody knows when
    ("clock", False, False, False),  # this one ended nobody knows when
    (None, True, True, False),       # other programs ran between
], ids=["free_chip", "behind_a_waited_read", "behind_an_unwaited_read",
        "read_did_not_wait", "not_alone"])
def test_engine_samples_a_step_where_its_read_waits(start, prev_waited,
                                                    waited, sampled):
    """The engine's device-time sample is the time between two moments
    the host can place on the chip's clock: the step's start (its own
    dispatch on a free chip, or the return of a read that WAITED for the
    step before it, with nothing queued between) and the return of its
    own read, if that waited too."""
    from ray_tpu.serve import decode_session as ds
    from ray_tpu.util import fault_injection as fi

    cfg = TransformerConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    eng = ds.DecodeSessionCore(cfg, max_len=64).engine   # no thread yet
    t0 = time.perf_counter() - 0.25
    eng._read_end, eng._read_waited = t0, prev_waited
    step = ds._Step([], _Out(not waited), (0, 0),
                    {"clock": t0, "behind": ds._BEHIND, None: None}[start])
    eng._read(step, fi)
    row = {r["program"]: r for r in eng._prof.snapshot(peak=1.0)}
    st = eng._prof._stat("decode_step")
    assert st.sampled_n == int(sampled)
    if sampled:
        assert 0.25 <= st.sampled_s < 1.0
    assert eng._read_waited == waited and eng._read_end > t0
    assert row["decode_step"]["dispatches"] == 0

