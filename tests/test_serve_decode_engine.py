"""Continuous-batching decode engine (serve/decode_session.py).

The serve decode data plane: one fixed-slot batched KV cache + one
jitted decode step shared by all live sessions, iteration-level
admission, per-session token queues drained by the proxy's chunked
(``next_chunk``) SSE lane over sid-sticky routing.  Tier-1, CPU, tiny
model.
"""

import json
import threading
import time

import pytest

import ray_tpu
from greedy_reference import greedy_stream
from ray_tpu.core.config import GlobalConfig


def _tiny_cfg(max_seq_len=64):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    return TransformerConfig.tiny(max_seq_len=max_seq_len,
                                  attention_impl="reference",
                                  dtype=jnp.float32)


# ------------------------------------------------------- model-level units

def test_decode_step_slots_matches_batch1_decode():
    """The slot-batched decode step is numerically the batch-1 step: a
    session inserted into ANY slot, surrounded by garbage slots, decodes
    the same logits (and therefore the same argmax tokens)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import (cache_insert_slot, decode_step,
                                decode_step_slots, init_kv_cache,
                                init_params, init_slot_cache, prefill)
    cfg = _tiny_cfg()
    params, _ = init_params(jax.random.PRNGKey(3), cfg)
    prompt = jnp.asarray([[7, 11, 13, 17, 19]], jnp.int32)
    cache = init_kv_cache(cfg, 1, 64)
    logits, cache = prefill(params, prompt, cfg, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)

    slot_cache = init_slot_cache(cfg, 4, 64)
    slot_cache = cache_insert_slot(slot_cache, cache, jnp.int32(2))
    assert int(slot_cache["pos"][2]) == 5 and int(slot_cache["pos"][0]) == 0
    toks = jnp.zeros((4,), jnp.int32).at[2].set(tok[0])
    active = jnp.asarray([False, False, True, False])
    for _ in range(4):
        l1, cache = decode_step(params, tok, cache, cfg)
        ls, slot_cache = decode_step_slots(params, toks, slot_cache,
                                           active, cfg)
        np.testing.assert_allclose(np.asarray(ls[2]), np.asarray(l1[0]),
                                   rtol=2e-4, atol=2e-4)
        tok = jnp.argmax(l1, -1).astype(jnp.int32)
        stok = jnp.argmax(ls[2:3], -1).astype(jnp.int32)
        assert int(stok[0]) == int(tok[0])
        toks = toks.at[2].set(stok[0])
    # inactive slots never advance
    assert int(slot_cache["pos"][0]) == 0
    assert int(slot_cache["pos"][2]) == 9


@pytest.mark.parametrize("shape", [(4, 2), (4, 1), (3,)])
def test_decode_step_slots_takes_one_token_a_slot(shape):
    """A slot step feeds ONE token a slot: tokens of another shape (two
    columns a slot, a column axis of one, a slot too few) are refused by
    name, not broadcast into the batch."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (decode_step_slots, init_params,
                                init_slot_cache)
    cfg = _tiny_cfg()
    params, _ = init_params(jax.random.PRNGKey(3), cfg)
    cache = init_slot_cache(cfg, 4, 64)
    active = jnp.ones((4,), bool)
    with pytest.raises(ValueError, match="ONE token a slot"):
        decode_step_slots(params, jnp.zeros(shape, jnp.int32), cache,
                          active, cfg)


# ---------------------------------------------------- engine-level (no cluster)

@pytest.mark.parametrize("depth", [0, -1])
def test_a_token_queue_that_holds_no_token_is_refused(depth):
    """`token_queue_depth` under 1 is no configuration: a session admitted
    in a turn that dispatches no step never gets its first token into the
    carry and its caller waits for ever (`tests/test_engine_wakeups.py`
    found it); the settings refuse it where they are made."""
    import dataclasses

    from ray_tpu.serve.config import DecodeEngineConfig
    with pytest.raises(ValueError, match="token_queue_depth"):
        DecodeEngineConfig(token_queue_depth=depth)
    with pytest.raises(ValueError, match="token_queue_depth"):
        dataclasses.replace(DecodeEngineConfig(), token_queue_depth=depth)


@pytest.mark.parametrize("key", ["spec_draft", "spec_k", "spec_fail_disable"])
def test_the_settings_of_speculative_decoding_are_refused_by_name(key):
    """Engine settings arrive as the dataclass alone (`DecodeSessionCore`
    takes nothing else): a deployment that still names one of the three
    settings that went with speculative decoding fails where it builds
    them, with the key in the message, and no mapping gets past that."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    with pytest.raises(TypeError, match=key):
        DecodeEngineConfig(**{key: 2})
    with pytest.raises(TypeError, match="DecodeEngineConfig"):
        DecodeSessionCore(_tiny_cfg(), max_len=64, engine={key: 2})


def test_engine_token_parity_with_midstream_join_leave():
    """Acceptance: continuous-batched decode emits byte-identical token
    streams to sequential batch-1 decode for 3 concurrent fixed-seed
    sessions, with sessions joining and leaving mid-stream."""
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    engine = DecodeSessionCore(cfg, max_len=64, seed=3)
    prompts = [list(range(10)), [5, 6, 7], [9] * 12, [1, 2]]
    want = 12  # tokens per stream

    ref = [greedy_stream(cfg, p, want, max_len=64, seed=3)
           for p in prompts]

    def drain(sid, toks, n):
        while len(toks) < n:
            out = engine.handle({"op": "next_chunk", "sid": sid,
                                 "max_tokens": n - len(toks)})
            assert "error" not in out, out
            toks += out["tokens"]

    # staggered joins: s0 decodes alone, then s1 joins, s2 joins after
    # s0 LEAVES mid-everything, s3 joins last — every stream must still
    # match its sequential batch-1 reference exactly
    r0 = engine.handle({"op": "start", "prompt": prompts[0]})
    s0 = list(r0["token"])
    drain(r0["sid"], s0, 6)
    r1 = engine.handle({"op": "start", "prompt": prompts[1]})
    s1 = list(r1["token"])
    drain(r1["sid"], s1, 4)
    r2 = engine.handle({"op": "start", "prompt": prompts[2]})
    s2 = list(r2["token"])
    drain(r0["sid"], s0, want)
    assert engine.handle({"op": "end", "sid": r0["sid"]})["ended"]
    r3 = engine.handle({"op": "start", "prompt": prompts[3]})
    s3 = list(r3["token"])
    for sid, toks in ((r1["sid"], s1), (r2["sid"], s2), (r3["sid"], s3)):
        drain(sid, toks, want)
        engine.handle({"op": "end", "sid": sid})
    assert [s0, s1, s2, s3] == [r[:want] for r in ref]
    # engine actually batched: fewer steps than sequential would take
    st = engine.handle({"op": "stats"})["engine"]
    assert st["tokens"] >= 4 * (want - 1)
    assert st["steps"] < 4 * (want - 1)
    # every step, chunk program and slot insert consumed the cache it
    # was given: donation engaged, nothing was copied
    assert st["cache_copies"] == 0


@pytest.mark.parametrize("sid", [0, 7, "local:99", "nobody", None])
@pytest.mark.parametrize("op", ["next", "next_chunk", "end"])
def test_unknown_or_integer_sid_gets_a_reply_not_an_exception(op, sid):
    """One protocol, string sids: a sid the engine does not hold — an
    integer, a stranger, none at all — before any session and beside a
    live one gets the engine's unknown-session reply."""
    from ray_tpu.serve.decode_session import DecodeSessionCore
    core = DecodeSessionCore(_tiny_cfg(), max_len=64, seed=3)
    try:
        for live in (False, True):
            if live:
                mine = core.handle({"op": "start", "prompt": [1, 2, 3]})
            out = core.handle({"op": op, "sid": sid})
            if op == "end":
                assert out == {"ended": False}
            else:
                assert "unknown session" in out["error"], out
        assert core.handle({"op": "next", "sid": mine["sid"]})["token"]
        assert core.handle({"op": "end", "sid": mine["sid"]})["ended"]
    finally:
        core.engine.shutdown()


def test_engine_failed_step_fails_slot_holders_and_serves_on():
    """A donated step that raises may have consumed the slot cache: the
    engine fails EVERY session that holds a slot (not only the batch),
    forgets the prefixes the lost rows advertised, goes on with a fresh
    cache, and serves the next request what an undisturbed engine
    serves."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    ecfg = DecodeEngineConfig(max_slots=2, token_queue_depth=2)
    core = DecodeSessionCore(cfg, max_len=64, seed=3, engine=ecfg)
    ref = DecodeSessionCore(cfg, max_len=64, seed=3, engine=ecfg)
    prompt, want = [3, 1, 4, 1, 5, 9, 2, 6], 8

    def stream(c, sid, first, n):
        toks = list(first)
        while len(toks) < n:
            out = c.handle({"op": "next_chunk", "sid": sid,
                            "max_tokens": n - len(toks)})
            assert "error" not in out, out
            toks += out["tokens"]
        return toks

    r = ref.handle({"op": "start", "prompt": prompt})
    expect = stream(ref, r["sid"], r["token"], want)

    # a: decoding; b: holds the other slot but is PAUSED (its queue is
    # full, nobody drains it), so it is in no batch when the step fails
    b = core.handle({"op": "start", "prompt": [8, 8, 8]})
    eng = core.engine
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        with eng._cond:
            sb = eng.sessions[b["sid"]]
            if sb.slot is not None and len(sb.queue) >= 2:
                break
        time.sleep(0.01)
    a = core.handle({"op": "start", "prompt": prompt})
    real_step, raised = eng._step, threading.Event()

    def failing_step(params, tok, cache, active, *, cfg):
        if not raised.is_set():
            raised.set()
            # what a donated dispatch that dies leaves behind
            for leaf in (cache["k"], cache["v"], cache["pos"]):
                leaf.delete()
            raise RuntimeError("injected step failure")
        return real_step(params, tok, cache, active, cfg=cfg)

    eng._step = failing_step
    outs = {}
    for name, sid in (("a", a["sid"]), ("b", b["sid"])):
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            outs[name] = core.handle({"op": "next_chunk", "sid": sid,
                                      "max_tokens": 64, "timeout_s": 0.2})
            if "error" in outs[name]:
                break
    assert raised.is_set()
    for name in ("a", "b"):
        assert "decode engine step failed" in outs[name].get("error", ""), \
            (name, outs[name])
    # the engine thread is alive, the slots are free again, no prefix of
    # the lost cache is on offer, and the next request is served in full
    c = core.handle({"op": "start", "prompt": prompt})
    assert stream(core, c["sid"], c["token"], want) == expect
    st = core.handle({"op": "stats"})["engine"]
    assert st["prefix"]["applied_hits"] == 0
    assert st["cache_copies"] == 0
    assert eng._thread.is_alive()


# ------------------------------------------------------- one step ahead
#
# The engine dispatches step n+1 before it reads step n: positions,
# the queue bound and the live mask are kept at DISPATCH time, and only
# token values reach the host a step late.  Every stream below is held to
# the whole-prompt greedy reference.

def _core(max_len=64, **engine):
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    return DecodeSessionCore(_tiny_cfg(), max_len=max_len, seed=3,
                             engine=DecodeEngineConfig(**engine))


def _drain(core, sid, toks, n):
    while len(toks) < n:
        out = core.handle({"op": "next_chunk", "sid": sid,
                           "max_tokens": n - len(toks)})
        assert "error" not in out, out
        toks += out["tokens"]
        if out["done"]:
            break
    return toks


def _wait(cond, what, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.005)


def _churn_join_and_end(want):
    """Sessions join and end while the others' steps are in flight; the
    last one takes the slot the first one left."""
    core = _core(max_slots=3)
    prompts = [list(range(10)), [5, 6, 7], [9] * 12, [1, 2]]
    r = [core.handle({"op": "start", "prompt": prompts[0]})]
    s = [list(r[0]["token"])]
    _drain(core, r[0]["sid"], s[0], 3)
    r.append(core.handle({"op": "start", "prompt": prompts[1]}))
    s.append(list(r[1]["token"]))
    _drain(core, r[0]["sid"], s[0], 5)
    assert core.handle({"op": "end", "sid": r[0]["sid"]})["ended"]
    for p in prompts[2:]:
        r.append(core.handle({"op": "start", "prompt": p}))
        s.append(list(r[-1]["token"]))
    for i in (1, 2, 3):
        _drain(core, r[i]["sid"], s[i], want)
    return core, list(zip(prompts, s))


def _churn_end_in_flight_slot_reused_at_once(want):
    """ONE slot: `end` arrives exactly while a step that holds the slot is
    in flight (from inside the read of the step before it), and a session
    that was waiting takes the slot in the very next schedule, its insert
    ordered behind the dead session's last step by the donated cache."""
    core = _core(max_slots=1, token_queue_depth=6)
    eng = core.engine
    a = core.handle({"op": "start", "prompt": [3, 1, 4, 1, 5]})
    sess = eng.sessions[a["sid"]]

    def paused():
        return len(sess.queue) == 6 and eng._flight is None

    _wait(paused, "the slot never paused")
    sa = _drain(core, a["sid"], list(a["token"]), 4)
    b = core.handle({"op": "start", "prompt": [2, 7, 1, 8]})  # waits
    _wait(paused, "the slot never paused again")
    real_read, ended = eng._read, threading.Event()

    def read_and_end(step, fi):
        if not ended.is_set() and eng._flight is not None \
                and any(sess.sid == a["sid"]
                        for sess, _ in eng._flight.batch):
            assert eng.end(a["sid"])
            ended.set()
        return real_read(step, fi)

    eng._read = read_and_end
    _drain(core, a["sid"], sa, 7)    # room for two steps: the loop wakes
    assert ended.wait(30), "no step was ever in flight at a read"
    sb = _drain(core, b["sid"], list(b["token"]), want)
    assert eng.stats()["occupied_slots"] == 1
    return core, [([3, 1, 4, 1, 5], sa), ([2, 7, 1, 8], sb)]


def _churn_runs_into_max_len(want):
    """A session whose step in flight takes it to `max_len` is in no
    later dispatch, is done only once that last token is published, and
    its neighbour decodes on."""
    core = _core(max_len=32, max_slots=2)
    long, short = list(range(1, 21)), [4, 2]
    a = core.handle({"op": "start", "prompt": long})
    b = core.handle({"op": "start", "prompt": short})
    sa = _drain(core, a["sid"], list(a["token"]), 64)
    # the prefill's token, then one a position the cache had left
    assert len(sa) == 1 + 32 - len(long)
    out = core.handle({"op": "next_chunk", "sid": a["sid"]})
    assert out["tokens"] == [] and out["done"]
    sb = _drain(core, b["sid"], list(b["token"]), want)
    return core, [(long, sa), (short, sb)]


def _churn_paused_at_queue_depth_then_resumed(want):
    """A caller that stops polling: its slot pauses with exactly
    `token_queue_depth` tokens buffered (the one in flight counted), the
    loop goes quiet, and the stream resumes where it stopped from the
    carry entry the idle slot kept on the device."""
    core = _core(max_slots=2, token_queue_depth=2)
    eng = core.engine
    a = core.handle({"op": "start", "prompt": [8, 8, 8]})
    sess = eng.sessions[a["sid"]]
    _wait(lambda: len(sess.queue) == 2 and eng._flight is None,
          "the slot never paused")
    steps = eng.stats()["steps"]
    time.sleep(0.2)
    assert eng.stats()["steps"] == steps == 2 and sess.unread == 0
    sa = _drain(core, a["sid"], list(a["token"]), want)
    return core, [([8, 8, 8], sa)]


@pytest.mark.parametrize("churn", [
    _churn_join_and_end, _churn_end_in_flight_slot_reused_at_once,
    _churn_runs_into_max_len, _churn_paused_at_queue_depth_then_resumed],
    ids=lambda f: f.__name__[len("_churn_"):])
def test_step_ahead_streams_equal_the_greedy_reference(churn):
    want = 12
    core, streams = churn(want)
    try:
        for prompt, got in streams:
            assert got == greedy_stream(_tiny_cfg(), prompt, len(got),
                                        max_len=core.max_len, seed=3)
        st = core.handle({"op": "stats"})["engine"]
        assert st["steps_ahead"] > 0 and st["cache_copies"] == 0
    finally:
        core.engine.shutdown()


def test_steps_ahead_counter_and_span(monkeypatch):
    """On a steady batch every fused step but the first is dispatched
    before the one ahead of it is read, and the `engine:ahead` ring span
    carries the sums."""
    from ray_tpu.serve.decode_session import ContinuousBatchingEngine
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)

    def spans():
        return [e for e in tracing.span_events()
                if e["name"] == "engine:ahead"]

    before = len(spans())
    core = _core()
    try:
        prompt = [3, 1, 4, 1]
        r = core.handle({"op": "start", "prompt": prompt})
        got = _drain(core, r["sid"], list(r["token"]), 64)
        assert got == greedy_stream(_tiny_cfg(), prompt, len(got),
                                    max_len=64, seed=3)
        st = core.handle({"op": "stats"})["engine"]
        mine = spans()[before:]
        assert st["steps"] == 64 - len(prompt)
        assert st["steps_ahead"] == st["steps"] - 1
        assert all(e["cat"] == "ahead" for e in mine)
        assert sum(e["args"].get("steps", 0) for e in mine) == st["steps"]
        assert sum(e["args"].get("steps_ahead", 0) for e in mine) \
            == st["steps_ahead"]
    finally:
        core.engine.shutdown()


def test_stats_keep_what_the_benchmark_and_its_readers_take():
    """`engine.stats()` carries every key `perfbench` and the dashboards
    read, and none of a mode the engine does not have."""
    core = _core()
    try:
        r = core.handle({"op": "start", "prompt": [2, 7, 1]})
        _drain(core, r["sid"], list(r["token"]), 4)
        st = core.handle({"op": "stats"})["engine"]
        assert {"steps", "tokens", "prefill_chunks", "prefill_programs",
                "phase_totals", "program_shapes", "prefix", "steps_ahead",
                "prefill_lanes", "moe", "cache", "cache_copies",
                "device_profile"} <= set(st)
        assert "spec" not in st and st["prefill_lanes"] >= 2
        assert {p["program"] for p in st["device_profile"]} <= {
            "decode_step", "prefill_chunk", "cache_insert", "prefix_gather"}
        assert {"prefill", "decode_dispatch", "queue", "admission"} <= set(
            st["phase_totals"])
    finally:
        core.engine.shutdown()


def _tiny_with_state(state):
    """(cfg, params) of a two-layer float32 model whose cache holds
    ``state``: rows for the whole context alone (keys and values, or one
    array of latents), or beside them a window layer's ring (12 rows), a
    conv layer's state; for summary rows beside rings, which no full layer
    need stand by, the rehearsal's tiny byte model."""
    import dataclasses
    import os

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params
    if state == "eva":
        from perfbench import manifest as mf
        from perfbench.tools import rehearse
        with open(os.path.join(mf.ROOT, rehearse.REHEARSAL, "configs",
                               "tiny-evabyte.json")) as f:
            c = json.load(f)
        model = mf.family_of(c).model
        cfg = dataclasses.replace(
            model.model_config(c, "serve", attention_impl="reference"),
            dtype=jnp.float32, param_dtype=jnp.float32)
        return cfg, model.make(jax.random.PRNGKey(11), c, jnp.float32)
    cfg = TransformerConfig.tiny(
        attention_impl="reference", dtype=jnp.float32, **{
            "full": {},
            "window": dict(layer_kinds=("window", "full"), sliding_window=8,
                           window_chunk=4),
            "conv": dict(layer_kinds=("conv", "full"), conv_kernel=3),
            "latent": dict(attention="mla", q_lora_rank=24, kv_lora_rank=16,
                           qk_nope_head_dim=12, qk_rope_head_dim=8,
                           v_head_dim=16),
        }[state])
    return cfg, init_params(jax.random.PRNGKey(11), cfg)[0]


@pytest.mark.parametrize("state", ["full", "window", "eva", "conv",
                                   "latent"])
def test_a_session_stepped_to_max_len_ends_there(state):
    """For each kind of cached state (rows for the whole context, rings,
    rings beside summary rows, conv states, latents): a session decodes
    until its slot is full, one token a position the cache had left, and is
    done; its slot's ``pos`` stands AT ``max_len`` and no further, however
    many steps its neighbour goes on for (a full slot is in no later
    batch, and the one column a step still writes for it is clamped onto
    its last row); the neighbour, which runs to the end behind it, and a
    session that then reuses a slot that was full stream what they stream
    alone."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg, params = _tiny_with_state(state)
    assert {"full": set(cfg.kinds) == {"full"} and cfg.attention != "mla",
            "latent": cfg.attention == "mla"}.get(
                state, state in cfg.kinds), cfg.kinds
    max_len = 64
    core = DecodeSessionCore(cfg, max_len=max_len, params=params,
                             engine=DecodeEngineConfig(max_slots=2))

    def ref(prompt, n):
        return greedy_stream(cfg, prompt, n, max_len=max_len, params=params)

    try:
        v = cfg.vocab_size
        long = [(5 * i + 3) % v for i in range(53)]
        short = [(7 * i + 1) % v for i in range(6)]
        a = core.handle({"op": "start", "prompt": long})
        b = core.handle({"op": "start", "prompt": short})
        eng = core.engine
        sess_b = eng.sessions[b["sid"]]
        sa = _drain(core, a["sid"], list(a["token"]), 2 * max_len)
        # the prefill's token, then one a position the cache had left
        assert len(sa) == 1 + max_len - len(long)
        assert sa == ref(long, len(sa))
        out = core.handle({"op": "next_chunk", "sid": a["sid"]})
        assert out["tokens"] == [] and out["done"]
        sb = _drain(core, b["sid"], list(b["token"]), 12)
        assert sb == ref(short, 12)
        # every slot's column is written every step, live or not: b
        # decodes on to its own end (its queue holds what is left), and
        # after all its steps a's slot still stands where it ended
        _wait(lambda: sess_b.done and eng._flight is None,
              "the neighbour never reached the end")
        with eng._cond:
            assert eng._cache["pos"].tolist() == [max_len, max_len]
        for r in (a, b):
            core.handle({"op": "end", "sid": r["sid"]})
        again = core.handle({"op": "start", "prompt": short[::-1]})
        sc = _drain(core, again["sid"], list(again["token"]), 12)
        assert sc == ref(short[::-1], 12)
        assert eng.stats()["cache_copies"] == 0
    finally:
        core.engine.shutdown()


def test_step_fault_with_a_step_queued_behind_it(chaos_cleanup):
    """Chaos site ``serve.decode_step``: the read of step 3 raises (where
    an asynchronous device fault surfaces) with step 4 already queued
    behind it.  Both outputs are dropped: every session that holds a slot
    fails exactly once, no token of either step is published, and the
    engine serves the next request from a fresh cache and carry."""
    from ray_tpu.util import fault_injection as fi
    core = _core(max_slots=2, token_queue_depth=2)
    eng = core.engine
    prompt, want = [3, 1, 4, 1, 5, 9, 2, 6], 8
    fails = []
    real_fail = eng._fail_slots
    eng._fail_slots = lambda err: (fails.append(err), real_fail(err))[1]
    try:
        # both hold a slot and stand paused, nobody polls: the loop is
        # quiet while the plan is armed, then both decode on together
        a = core.handle({"op": "start", "prompt": prompt})
        b = core.handle({"op": "start", "prompt": [8, 8, 8]})
        sa, sb = eng.sessions[a["sid"]], eng.sessions[b["sid"]]
        _wait(lambda: len(sa.queue) == len(sb.queue) == 2
              and eng._flight is None, "the slots never paused")
        fi.arm([{"site": "serve.decode_step", "action": "error",
                 "match": {"nth": 3}}])
        steps = eng.steps
        with eng._cond:
            eng.ecfg.token_queue_depth = 64
            eng._cond.notify_all()
        _wait(lambda: sa.error and sb.error, "the fault never fired")
        assert len(fails) == 1 and "injected decode_step" in fails[0]
        # nobody has polled: what was published is what the queues hold,
        # and it is two steps short of what was dispatched
        assert eng.steps == steps + 2
        assert eng.ahead["steps"] == eng.steps + 2
        assert len(sa.queue) == len(sb.queue) == 4
        assert eng.tokens == len(sa.queue) + len(sb.queue)
        assert eng._flight is None
        for sid in (a["sid"], b["sid"]):
            out = core.handle({"op": "next_chunk", "sid": sid})
            assert "decode engine step failed" in out["error"], out
        c = core.handle({"op": "start", "prompt": prompt})
        got = _drain(core, c["sid"], list(c["token"]), want)
        assert got == greedy_stream(_tiny_cfg(), prompt, want, max_len=64,
                                    seed=3)
        st = core.handle({"op": "stats"})["engine"]
        assert st["cache_copies"] == 0 and len(fails) == 1
        assert eng._thread.is_alive()
    finally:
        eng.shutdown()


# ------------------------------------------------------------ lanes
#
# While two or more prompts prefill, ONE chunk program advances up to
# `prefill_lanes` of them over the engine's lane cache; a lone prompt keeps
# the batch-1 program over its own cache.  Every stream below is held to
# the whole-prompt greedy reference, every lane is free again at the end
# and the lane cache gone.

# -------------------------------------------------- chunked-prefill admission

def test_chunked_admission_token_parity_across_chunk_boundaries():
    """Acceptance: chunked admission emits byte-identical streams for
    prompt lengths straddling the chunk boundary (below, exact, above,
    multiple), including a mid-stream join under load — and the whole
    run compiles the one prefill chunk shape."""
    cfg = _tiny_cfg()
    want = 10
    prompts = [[5, 6, 7], [1, 2, 3, 4], [9, 8, 7, 6, 5],
               [3] * 8, [4] * 9]   # chunk=4: 3 | 4 | 5 | 8 | 9
    refs = [_greedy(p, want) for p in prompts]
    core = _core(prefill_chunk_tokens=4)
    # staggered: s0 streams alone, s1..s4 join while s0 is mid-stream
    r0 = core.handle({"op": "start", "prompt": prompts[0]})
    s0 = _drain(core, r0["sid"], list(r0["token"]), 5)
    mids = [core.handle({"op": "start", "prompt": p})
            for p in prompts[1:]]
    outs = [_drain(core, r["sid"], list(r["token"]), want)
            for r in mids]
    s0 = _drain(core, r0["sid"], s0, want)
    for r in (r0, *mids):
        core.handle({"op": "end", "sid": r["sid"]})
    assert [s0] + outs == refs
    st = core.handle({"op": "stats"})["engine"]
    assert st["prefill_chunks"] >= 5
    pf_shapes = [s for s in st["program_shapes"]
                 if s.startswith("prefill_chunk")]
    assert pf_shapes == ["prefill_chunk:1x4"], (
        f"admission must reuse the ONE fixed chunk shape (a remainder "
        f"is padded into it), compiled: {pf_shapes}")
    # 3 | 4 | 5 | 8 | 9 tokens: 1 + 1 + 2 + 2 + 3 programs, of which
    # those of 3, 5 and 9 end in a padded remainder
    assert (st["prefill_chunks"], st["prefill_tails"],
            st["prefill_pad_tokens"]) == (9, 3, 1 + 3 + 3)
    assert "distinct_program_shapes" in st


def test_chunked_admission_and_resume_share_program_shapes():
    """Satellite: a failover resume after chunked admissions adds NO
    new prefill program shape — admission and resume walk the same
    fixed-shape chunk programs, so resumes can never compile-storm."""
    want = 10
    prompt = [5, 6, 7, 8, 9]
    ref = _greedy(prompt, want)
    core = _core(prefill_chunk_tokens=4)
    r = core.handle({"op": "start", "prompt": prompt})
    _drain(core, r["sid"], list(r["token"]), want)
    core.handle({"op": "end", "sid": r["sid"]})
    shapes_before = set(
        core.handle({"op": "stats"})["engine"]["program_shapes"])
    # resume mid-stream at an awkward cut (prefix length 5+7=12: three
    # chunk blocks; the admission's 5 were one block and a padded one)
    rr = core.handle({"op": "resume", "prompt": prompt,
                      "generated": ref[:7]})
    assert rr["seq"] == 7
    toks = ref[:7] + list(rr["token"])
    toks = _drain(core, rr["sid"], toks, want)
    assert toks == ref
    core.handle({"op": "end", "sid": rr["sid"]})
    shapes_after = set(
        core.handle({"op": "stats"})["engine"]["program_shapes"])
    new = {s for s in shapes_after - shapes_before
           if s.startswith("prefill_chunk")}
    assert not new, f"resume compiled new prefill shapes: {new}"


def _prompt(i, n):
    return [(7 * i + 3 * j) % 200 + 1 for j in range(n)]


class _Together:
    """Callers that `start` at once, each on a thread of its own: the
    engine's chunk programs wait for a permit each (`step`), so a test
    decides what the loop has seen before its next program."""

    def __init__(self, core, **more):
        self.core, self.eng = core, core.engine
        sid, _ = self._stream(_prompt(99, 11), 2)     # warm: every program
        core.handle({"op": "end", "sid": sid})
        _wait(lambda: self.eng._flight is None and not self.eng._slots,
              "the warm-up session never left")
        self.base = dict(self.eng.stats())
        self.permits = threading.Semaphore(0)
        self.ran, self.came = [], 0      # programs run; come to the gate
        for attr in ("_chunk", "_chunk_lanes"):
            setattr(self.eng, attr, self._held(attr, getattr(self.eng, attr)))
        self.out, self.threads = {}, []

    def _held(self, attr, real):
        def program(*args, **kwargs):
            self.came += 1
            while not self.permits.acquire(timeout=0.2):
                assert not self.eng._shutdown, "shut down at the gate"
            self.ran.append(attr)
            return real(*args, **kwargs)
        return program

    def _stream(self, prompt, want):
        r = self.core.handle({"op": "start", "prompt": prompt})
        return r["sid"], _drain(self.core, r["sid"], list(r["token"]), want)

    def start(self, prompts, want=5):
        def call(i, prompt):
            try:
                self.out[i] = self._stream(prompt, want)[1]
            except Exception as e:
                self.out[i] = e
        for prompt in prompts:
            t = threading.Thread(target=call, daemon=True,
                                 args=(len(self.threads), prompt))
            self.threads.append(t)
            t.start()
            _wait(lambda: self.session(prompt) is not None,
                  "a caller never enqueued")
            self._settle()     # the loop has seen it, or stands at the gate

    def _settle(self):
        """Until the loop stands at the gate with its next chunk program,
        or has no prompt left to prefill."""
        def quiet():
            with self.eng._cond:
                return not any(not (s.ready or s.done)
                               for s in self.eng._prefilling)
        _wait(lambda: self.came > len(self.ran) or quiet(),
              "the loop never came to the gate")

    def step(self, n=1):
        """Let ``n`` chunk programs run, one at a time."""
        for _ in range(n):
            before = len(self.ran)
            self.permits.release()
            _wait(lambda: len(self.ran) > before, "no program ran")
            self._settle()

    def session(self, prompt):
        with self.eng._cond:
            return next((s for s in self.eng.sessions.values()
                         if s.ptoks == tuple(prompt)), None)

    def finish(self):
        self.permits.release(10_000)
        for t in self.threads:
            t.join(120)
            assert not t.is_alive()
        eng = self.eng
        _wait(lambda: not eng._prefilling and eng._pool is None
              and not any(eng._lane_sess), "a lane was never freed")
        st = eng.stats()
        assert st["cache_copies"] == 0
        return {k: st[k] - self.base[k]
                for k in ("prefill_chunks", "prefill_programs")}


def _lane_core(max_len=64, **engine):
    engine.setdefault("max_slots", 3)
    engine.setdefault("prefill_chunk_tokens", 4)
    return _core(max_len=max_len, **engine)


def _greedy(prompt, n, max_len=64):
    return greedy_stream(_tiny_cfg(), prompt, n, max_len=max_len, seed=3)


def test_more_sessions_than_lanes_stream_what_each_streams_alone():
    """Seven prompts of 5 to 30 tokens at once over 4 lanes and 3 slots:
    the first runs its first chunks alone on a batch-1 cache and enters a
    lane by the slot insert when the others join, three wait for a lane
    with no cache at all, finished lanes leave by the slot gather and wait
    for a slot as ever; fewer programs than chunks.  A lone session before
    and after them: a program a chunk, on the batch-1 program."""
    core = _lane_core()
    try:
        t = _Together(core)
        assert t.eng.stats()["prefill_lanes"] == 4
        assert t.base["prefill_programs"] == t.base["prefill_chunks"] == 3
        prompts = [_prompt(i, n)
                   for i, n in enumerate((23, 9, 30, 14, 5, 27, 18))]
        t.start(prompts[:1])
        t.step()                          # its first chunk: alone
        first = t.session(prompts[0])
        assert t.ran == ["_chunk"] and first.pcache is not None
        t.start(prompts[1:])
        t.step()      # its second was on its way before the others came
        assert t.ran == ["_chunk"] * 2 and first.poff == 8
        t.step()                          # the lanes program, full
        assert t.ran[-1] == "_chunk_lanes" and first.pcache is None
        assert first.lane == 0 and first.poff == 12
        assert [s.ptoks for s in t.eng._lane_sess] == [
            tuple(p) for p in prompts[:4]]
        assert all(t.session(p).lane is None and t.session(p).pcache is None
                   and t.session(p).poff == 0 for p in prompts[4:])
        d = t.finish()
        for i, prompt in enumerate(prompts):
            assert t.out[i] == _greedy(prompt, 5), i
        chunks = sum(-(-len(p) // 4) for p in prompts)
        assert d["prefill_chunks"] == chunks
        assert chunks / 4 <= d["prefill_programs"] < chunks / 2
        shapes = core.engine.stats()["program_shapes"]
        assert {"prefill_chunk:1x4", "prefill_chunk:4x4"} <= set(shapes)
        alone = _prompt(50, 30)
        sid, got = t._stream(alone, 5)
        assert got == _greedy(alone, 5)
        assert t.ran[-8:] == ["_chunk"] * 8
    finally:
        core.engine.shutdown()


def test_the_last_of_several_goes_back_to_a_cache_of_its_own():
    """Two prompts at once, one short: when it is done the long one leaves
    its lane for a batch-1 cache (the slot gather), the lane cache is
    dropped, and the rest of the prompt runs on the batch-1 program."""
    core = _lane_core()
    try:
        t = _Together(core)
        prompts = [_prompt(1, 30), _prompt(2, 6)]
        t.start(prompts)
        t.step(3)     # the long one alone, then two lanes programs
        assert t.ran == ["_chunk", "_chunk_lanes", "_chunk_lanes"]
        long = t.session(prompts[0])
        # ... and the loop stands before the next: out of its lane
        assert long.poff == 12 and long.lane is None
        assert long.pcache is not None and t.eng._pool is None
        t.step()
        assert t.ran[-1] == "_chunk" and long.poff == 16
        d = t.finish()
        assert [t.out[0], t.out[1]] == [_greedy(p, 5) for p in prompts]
        assert d == {"prefill_chunks": 8 + 2, "prefill_programs": 8}
    finally:
        core.engine.shutdown()


def test_a_prefix_seeded_session_and_one_at_the_capacity_edge_in_lanes():
    """A live donor of 29 tokens; two prompts at once, one of which shares
    27 of them and ends one short of the capacity (its one window is set
    back to 24 and runs three tokens again, in its lane), the other at
    position 0 beside it: the donor's rows reach the lane by the slot
    gather and the slot insert, and both stream what they stream alone."""
    core = _lane_core(max_len=32, prefix_cache_min_tokens=4, max_slots=3,
                      prefill_chunk_tokens=8)
    try:
        t = _Together(core)
        t.permits.release(4)
        donor = _prompt(5, 29)
        t._stream(donor, 2)                        # stays live
        edge = donor[:27] + _prompt(6, 4)
        other = _prompt(7, 13)
        hits = t.eng.stats()["prefix"]["applied_hits"]
        t.start([other, edge])
        t.step(2)
        assert t.ran[-1] == "_chunk_lanes"
        d = t.finish()
        assert t.out[0] == _greedy(other, 5, 32)
        assert t.out[1] == _greedy(edge, 2, 32)   # the cache ends there
        st = t.eng.stats()
        assert st["prefix"]["applied_hits"] == hits + 1
        assert st["prefix"]["tokens_reused"] >= 27
        # other: 2 chunks; edge: ONE window [24, 31); donor: 4, alone
        assert d["prefill_chunks"] == 4 + 2 + 1
    finally:
        core.engine.shutdown()


@pytest.mark.parametrize("how", ["ended", "reaped", "shed", "raised"])
def test_a_session_lost_mid_prompt_frees_its_lane_and_hurts_no_neighbour(
        how, chaos_cleanup):
    """Six long prompts at once: four hold lanes, two wait.  One of the
    four is ended by its client, or reaped, while the lanes program that
    carries it is in flight; or the replica drains (every prefilling
    session is shed); or the program raises (chaos site
    ``serve.prefill_chunk``: the sessions IN it fail, each with today's
    error, and the lane cache goes with them).  What is left streams what
    it streams alone, and every lane is free at the end."""
    from ray_tpu.exceptions import ReplicaUnavailableError
    from ray_tpu.util import fault_injection as fi
    core = _lane_core(session_idle_ttl_s=3600.0)
    try:
        t = _Together(core)
        prompts = [_prompt(i, 26 + i) for i in range(6)]
        t.start(prompts[:1])
        t.step()
        t.start(prompts[1:])
        t.step(2)                    # the first lanes program has run
        victim = t.session(prompts[1])
        assert victim.lane == 1 and victim.poff == 4
        lost = {1}
        if how == "ended":
            assert t.eng.end(victim.sid)
        elif how == "reaped":
            with t.eng._cond:
                victim.last_poll -= 7200.0
        elif how == "shed":
            assert t.eng.begin_drain() == 0
            lost = set(range(6))
        else:
            fi.arm([{"site": "serve.prefill_chunk", "action": "error",
                     "match": {"nth": 1}}])
            lost = {0, 1, 2, 3}
        t.step()
        if how in ("ended", "reaped"):
            # the lane is free at the next schedule and the first in the
            # queue takes it
            _wait(lambda: t.session(prompts[4]).lane == 1,
                  "the freed lane was not taken")
        t.finish()
        for i, prompt in enumerate(prompts):
            if i not in lost:
                assert t.out[i] == _greedy(prompt, 5), (how, i)
            elif how == "raised":
                assert isinstance(t.out[i], RuntimeError) and \
                    "chunked prefill failed" in str(t.out[i]) and \
                    "injected prefill_chunk" in str(t.out[i]), t.out[i]
            else:
                assert isinstance(t.out[i], ReplicaUnavailableError), (
                    how, i, t.out[i])
        assert t.eng._thread.is_alive()
        assert t.eng.stats()["reaped"] == (how == "reaped")
    finally:
        core.engine.shutdown()


def test_chunks_and_programs_counter_and_span(monkeypatch):
    """`engine:lanes` carries the chunks and the programs since the last
    span; their sums are the counters of `stats()`."""
    from ray_tpu.serve.decode_session import ContinuousBatchingEngine
    from ray_tpu.util import tracing
    monkeypatch.setattr(ContinuousBatchingEngine, "_MOE_SPAN_S", 0.0)

    def spans():
        return [e for e in tracing.span_events()
                if e["name"] == "engine:lanes"]

    before = len(spans())
    core = _lane_core()
    try:
        t = _Together(core)
        t.start([_prompt(i, 17) for i in range(3)])
        t.finish()
        st = core.engine.stats()
        mine = spans()[before:]
        assert all(e["cat"] == "lanes" for e in mine)
        assert sum(e["args"].get("chunks", 0) for e in mine) \
            == st["prefill_chunks"] == 3 + 3 * 5
        assert sum(e["args"].get("programs", 0) for e in mine) \
            == st["prefill_programs"] < st["prefill_chunks"]
    finally:
        core.engine.shutdown()


def test_step_ahead_under_more_callers_than_slots_and_cores():
    """Stress: twelve caller threads over three slots, a short switch
    interval, each streaming its prompt and ending early or running on,
    so joins, ends and reassignments land at every point of the loop's
    turn.  A lost update of a position, of the unread count or of a
    queue would show as a stream off the greedy reference, a token
    unaccounted for, or a slot never freed."""
    import sys
    core = _core(max_slots=3, max_waiting=16)
    eng = core.engine
    prompts = [[(3 * i + j) % 50 + 1 for j in range(2 + i % 5)]
               for i in range(12)]
    wants = [4 + (5 * i) % 17 for i in range(12)]
    got, errors = [None] * 12, []

    def caller(i):
        try:
            r = core.handle({"op": "start", "prompt": prompts[i]})
            got[i] = _drain(core, r["sid"], list(r["token"]), wants[i])
            assert core.handle({"op": "end", "sid": r["sid"]})["ended"]
        except BaseException as e:   # reported by the main thread
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=caller, args=(i,))
               for i in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    try:
        assert not errors and not any(t.is_alive() for t in threads), errors
        for i in range(12):
            assert got[i] == greedy_stream(_tiny_cfg(), prompts[i],
                                           wants[i], max_len=64, seed=3), i
        _wait(lambda: eng.stats()["occupied_slots"] == 0
              and eng._flight is None, "a slot was never freed")
        st = eng.stats()
        assert st["sessions"] == 0 and st["cache_copies"] == 0
        assert st["steps_ahead"] > 0
        # every dispatched step was read and published
        assert eng.ahead["steps"] == st["steps"]
    finally:
        eng.shutdown()


def test_engine_slot_reclamation_backpressure_and_lru():
    """Ended sessions vacate their slot between steps (a waiting/new
    session takes it over); with every slot held and the wait queue at
    its bound, `start` sheds with the typed ReplicaUnavailableError;
    abandoned finished sessions are LRU-evicted from the table."""
    from ray_tpu.exceptions import ReplicaUnavailableError
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    # token_queue_depth=4 pins occupancy: each session decodes 4 tokens
    # ahead then PAUSES holding its slot, so `occupied == 2` is a
    # stable state instead of a race against sessions running to cache
    # cap (chunked admission made joins fast enough to lose that race)
    core = DecodeSessionCore(
        cfg, max_len=64, seed=0, max_sessions=4,
        engine=DecodeEngineConfig(max_slots=2, max_waiting=0,
                                  token_queue_depth=4))
    a = core.handle({"op": "start", "prompt": [1, 2, 3]})
    b = core.handle({"op": "start", "prompt": [4, 5, 6]})
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if core.handle({"op": "stats"})["engine"]["occupied_slots"] == 2:
            break
        time.sleep(0.05)
    with pytest.raises(ReplicaUnavailableError):
        core.handle({"op": "start", "prompt": [7, 8]})
    # ending a session frees its slot for the next admission
    assert core.handle({"op": "end", "sid": a["sid"]})["ended"]
    c = None
    while time.monotonic() < deadline and c is None:
        try:
            c = core.handle({"op": "start", "prompt": [7, 8]})
        except ReplicaUnavailableError:
            time.sleep(0.05)
    assert c is not None, "freed slot was never granted to a new session"
    out = core.handle({"op": "next_chunk", "sid": c["sid"],
                       "max_tokens": 3})
    assert len(out["tokens"]) == 3
    # ended sid is forgotten
    assert "error" in core.handle({"op": "next", "sid": a["sid"]})
    # LRU: b was abandoned (never ended); un-pin the queue bound so it
    # runs to cache cap (its slot is reclaimed the moment it finishes),
    # then push the session TABLE past max_sessions — the abandoned
    # finished session is the eviction victim, so replica memory stays
    # bounded
    core.engine.ecfg.token_queue_depth = 64
    with core.engine._cond:
        core.engine._cond.notify_all()   # wake the paused loop
    while core.handle({"op": "stats"})["engine"]["occupied_slots"] > 1:
        assert time.monotonic() < deadline
        time.sleep(0.05)
    core.engine.ecfg.max_waiting = 2   # let the table fill past 4
    for i in range(3):
        core.handle({"op": "start", "prompt": [i + 1]})
    assert "error" in core.handle({"op": "next_chunk", "sid": b["sid"]})
    assert core.handle({"op": "stats"})["engine"]["sessions"] <= 4


def test_batch_leader_wakes_when_batch_fills():
    """Satellite: a full batch flushes immediately (condition-variable
    wake) instead of sleeping out batch_wait_timeout_s in 1 ms polls."""
    from ray_tpu.serve.batching import batch

    @batch(max_batch_size=4, batch_wait_timeout_s=30.0)
    def echo(items):
        return [(x, len(items)) for x in items]

    results = [None] * 4

    def call(i):
        results[i] = echo(i)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=25.0)
    took = time.monotonic() - t0
    assert all(r is not None for r in results), "a caller never returned"
    assert took < 20.0, (
        f"full batch took {took:.1f}s — leader slept out the timeout "
        f"instead of waking on the filling arrival")
    assert sorted(x for x, _ in results) == [0, 1, 2, 3]
    assert all(n == 4 for _, n in results), "batch did not coalesce"


# --------------------------------------------------------- full serving path

def _sse_events(resp):
    events = []
    for line in resp.iter_lines():
        if line.startswith(b"data: "):
            body = line[len(b"data: "):]
            events.append("DONE" if body == b"[DONE]"
                          else json.loads(body))
    return events


def _stream(addr, route, prompt, max_new, chunk=None, timeout=240):
    import requests
    body = {"prompt": prompt, "max_new_tokens": max_new}
    if chunk is not None:
        body["chunk_tokens"] = chunk
    with requests.post(f"{addr}{route}/stream", json=body,
                       stream=True, timeout=timeout) as r:
        assert r.status_code == 200, r.text
        return _sse_events(r)


@pytest.fixture(scope="module")
def engine_app():
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    from ray_tpu import serve
    serve.start()

    # NOTE: deployment classes must be SELF-CONTAINED (imports inside
    # methods, no module globals) — they are cloudpickled by value and
    # the test module is not importable inside replica workers

    @serve.deployment(max_concurrent_queries=8)
    class Gen:
        """Decode-session deployment that counts its own RPC arrivals —
        the round-trip-count acceptance assertion reads it back."""

        def __init__(self):
            import threading as _threading

            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.config import DecodeEngineConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            cfg = TransformerConfig.tiny(max_seq_len=64,
                                         attention_impl="reference",
                                         dtype=jnp.float32)
            self.core = DecodeSessionCore(
                cfg, max_len=64,
                engine=DecodeEngineConfig(chunk_linger_s=0.5))
            self.calls = 0
            self._lock = _threading.Lock()

        def engine_stats(self):
            return self.core.handle({"op": "stats"})

        def __call__(self, req):
            if req.get("op") == "calls":
                with self._lock:
                    return {"calls": self.calls}
            with self._lock:
                self.calls += 1
            return self.core.handle(req)

    @serve.deployment(max_concurrent_queries=8, num_replicas=2)
    class Gen2:
        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            cfg = TransformerConfig.tiny(max_seq_len=64,
                                         attention_impl="reference",
                                         dtype=jnp.float32)
            self.core = DecodeSessionCore(cfg, max_len=64)

        def __call__(self, req):
            return self.core.handle(req)

    @serve.deployment(max_concurrent_queries=8)
    class GenTinySlots:
        """One decode slot, zero wait queue: the second session must
        shed with the typed 503 path."""

        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.config import DecodeEngineConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            cfg = TransformerConfig.tiny(max_seq_len=64,
                                         attention_impl="reference",
                                         dtype=jnp.float32)
            # token_queue_depth=4: the session decodes 4 tokens ahead
            # then PAUSES holding its slot (instead of racing to the
            # cache cap and vacating) — occupancy is test-controlled
            self.core = DecodeSessionCore(
                cfg, max_len=64,
                engine=DecodeEngineConfig(max_slots=1, max_waiting=0,
                                          token_queue_depth=4))

        def __call__(self, req):
            return self.core.handle(req)

    serve.run(Gen.bind(), name="genc")
    serve.run(Gen2.bind(), name="gen2")
    serve.run(GenTinySlots.bind(), name="genbp")
    yield serve.api.http_address()
    serve.shutdown()
    ray_tpu.shutdown()


def _calls(addr, route):
    import requests
    return requests.post(f"{addr}{route}", json={"op": "calls"},
                         timeout=60).json()["calls"]


def test_stream_rpc_count_one_round_trip_per_chunk(engine_app):
    """Acceptance: streaming N tokens costs ≤ 1 router round trip per
    `next_chunk` of N tokens — start + ceil((max_new-1)/chunk) chunk
    drains + end, NOT one RPC per token."""
    addr = engine_app
    _stream(addr, "/genc", [3, 1, 4, 1, 5], 8)   # warmup: compiles
    before = _calls(addr, "/genc")
    events = _stream(addr, "/genc", [2, 7, 1, 8], 33, chunk=16)
    toks = [e for e in events if isinstance(e, dict) and "token" in e]
    assert len(toks) == 33
    assert events[-1] == "DONE"
    assert not any(isinstance(e, dict) and "error" in e for e in events)
    delta = _calls(addr, "/genc") - before
    # start + 2 chunked drains (16+16 tokens) + end
    assert delta <= 4, (
        f"{delta} replica RPCs for a 33-token stream — the chunked "
        f"lane must amortize transport over next_chunk batches")


def test_sticky_routing_two_replicas_concurrent_streams(engine_app):
    """With num_replicas=2 a session's next_chunk/end must land on the
    replica that owns its KV cache (sid-sticky routing) — without it,
    round-robin hands the sid to the wrong replica and streams die with
    'unknown session'."""
    addr = engine_app
    _stream(addr, "/gen2", [1, 2, 3], 4)   # warmup
    results, errs = [], []

    def one(i):
        try:
            events = _stream(addr, "/gen2",
                             [(3 * i + j) % 250 for j in range(6)], 12)
            bad = [e for e in events
                   if isinstance(e, dict) and "error" in e]
            toks = [e for e in events
                    if isinstance(e, dict) and "token" in e]
            results.append((len(toks), bad, events))
        except Exception as e:   # noqa: BLE001
            errs.append(repr(e))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    assert not errs, errs
    assert len(results) == 4
    for ntoks, bad, events in results:
        assert not bad, f"stream leaked a routing error: {bad}"
        assert ntoks == 12, events


def test_engine_metrics_and_spans_exported(engine_app):
    """Observability satellite: the engine loop feeds the occupancy
    histogram + token counter; its per-REQUEST span reaches the merged
    timeline, its per-step work does not (that goes to `phase_totals`
    and, in a profiler trace, to the `engine:` host annotations — one
    ring span per step would evict everything else)."""
    from ray_tpu import state
    _stream(engine_app, "/genc", [1, 2, 3, 4], 10)
    text = state.cluster_metrics_text()
    # replica-process registries are not scraped cluster-wide (known
    # exposition limit), but the span path IS cluster-wide
    deadline = time.monotonic() + 30
    names = set()
    while time.monotonic() < deadline:
        tl = state.timeline()
        names = {ev.get("name", "") for ev in tl.get("traceEvents", [])}
        if "serve_admission::genc" in names:
            break
        time.sleep(0.5)
    assert "serve_admission::genc" in names, \
        sorted(n for n in names if n.startswith("serve"))
    assert not any(n.startswith(("serve_decode_step::",
                                 "serve_prefill_chunk::"))
                   for n in names), sorted(names)
    assert isinstance(text, str)  # exposition path stays alive


def test_admission_backpressure_is_http_503_retry_after(engine_app):
    """Satellite: decode-slot exhaustion raises the typed
    ReplicaUnavailableError INSIDE the replica; the proxy unwraps it
    from the remote task error and maps it to 503 + Retry-After, like
    the zero-replica shed path."""
    import requests
    addr = engine_app
    first = requests.post(f"{addr}/genbp",
                          json={"op": "start", "prompt": [1, 2, 3]},
                          timeout=240).json()
    assert "sid" in first, first
    deadline = time.monotonic() + 120
    while True:   # wait out the admission lag of the first session
        r = requests.post(f"{addr}/genbp",
                          json={"op": "start", "prompt": [4, 5, 6]},
                          timeout=240)
        if r.status_code == 503 or time.monotonic() > deadline:
            break
        # the slot wasn't taken yet (engine still compiling/admitting):
        # this start won a slotless race window — release and retry
        if r.status_code == 200 and "sid" in r.json():
            requests.post(f"{addr}/genbp",
                          json={"op": "end", "sid": r.json()["sid"]},
                          timeout=60)
        time.sleep(0.2)
    assert r.status_code == 503, (r.status_code, r.text)
    assert "Retry-After" in r.headers
    requests.post(f"{addr}/genbp",
                  json={"op": "end", "sid": first["sid"]}, timeout=60)


def test_engine_metrics_registered_in_process():
    """The engine's counter/histogram land in the replica process's own
    registry (scraped wherever that process's /metrics is exposed)."""
    from ray_tpu import metrics
    from ray_tpu.serve.decode_session import DecodeSessionCore
    core = DecodeSessionCore(_tiny_cfg(), max_len=64, seed=1)
    r = core.handle({"op": "start", "prompt": [1, 2, 3]})
    out = core.handle({"op": "next_chunk", "sid": r["sid"],
                       "max_tokens": 4})
    assert len(out["tokens"]) == 4
    core.handle({"op": "end", "sid": r["sid"]})
    text = metrics.prometheus_text()
    assert "ray_tpu_serve_tokens_total" in text
    assert "ray_tpu_serve_decode_batch_occupancy" in text


def test_prefill_chunk_counter_exported():
    """The chunk counter lands in the process registry."""
    from ray_tpu import metrics
    from ray_tpu.serve.decode_session import DecodeSessionCore
    core = DecodeSessionCore(_tiny_cfg(), max_len=64, seed=1)
    r = core.handle({"op": "start", "prompt": [1, 2, 3]})
    out = core.handle({"op": "next_chunk", "sid": r["sid"],
                       "max_tokens": 8})
    assert len(out["tokens"]) >= 1
    core.handle({"op": "end", "sid": r["sid"]})
    text = metrics.prometheus_text()
    assert "ray_tpu_serve_prefill_chunks_total" in text
    assert "ray_tpu_serve_spec" not in text


# ------------------------------------------------------------------- chaos

@pytest.fixture
def chaos_cleanup():
    import os

    from ray_tpu.util import fault_injection as fi
    yield
    fi.disarm()
    GlobalConfig.update({"chaos_plan": ""})
    os.environ.pop("RAY_TPU_CHAOS_PLAN", None)


def test_chaos_replica_failure_midstream_recovers(engine_app,
                                                  chaos_cleanup):
    """Chaos acceptance (upgraded by the failover layer): an injected
    replica failure mid-stream is RECOVERED — the stream completes with
    its full token count and zero error events (pre-failover this test
    asserted an in-band SSE error; the proxy's replay journal now
    retries/resumes instead of surfacing the fault), the engine loop
    keeps serving the OTHER session, and after the injected-error
    window fresh streams stay clean.

    The plan is armed at RUNTIME (PR-2's controller KV + pubsub path)
    before the chaos deployment starts, so its replica worker boots
    already armed — the nth counter is then driven only by this test's
    requests (the regex filters every other deployment out)."""
    import requests

    from ray_tpu import chaos, serve
    chaos.apply([{"site": "serve.request",
                  "match": {"nth": 4, "regex": "^chaosgen$"},
                  "action": "error"}])
    try:
        @serve.deployment(max_concurrent_queries=8)
        class ChaosGen:
            def __init__(self):
                import jax.numpy as jnp

                from ray_tpu.models import TransformerConfig
                from ray_tpu.serve.decode_session import \
                    DecodeSessionCore
                cfg = TransformerConfig.tiny(max_seq_len=64,
                                             attention_impl="reference",
                                             dtype=jnp.float32)
                self.core = DecodeSessionCore(cfg, max_len=64)

            def __call__(self, req):
                return self.core.handle(req)

        serve.run(ChaosGen.bind(), name="chaosgen")
        addr = engine_app
        # survivor session, held open across the injected failure
        # (request #1 on the replica)
        surv = requests.post(f"{addr}/chaosgen",
                             json={"op": "start", "prompt": [9, 9, 9]},
                             timeout=240).json()
        assert "sid" in surv, surv
        # victim stream: start (#2), first chunk (#3), second chunk
        # (#4) ← injected error → the failover client retries the op
        # (the session is intact — the fault fired at request entry)
        # and the stream completes as if nothing happened
        events = _stream(addr, "/chaosgen", [1, 2, 3], 20, chunk=4)
        assert events[-1] == "DONE", \
            "mid-stream failure must keep the SSE framing intact"
        errors = [e for e in events
                  if isinstance(e, dict) and "error" in e]
        assert not errors, \
            f"failover must hide the injected fault, got: {errors}"
        toks = [e for e in events if isinstance(e, dict) and "token" in e]
        assert len(toks) == 20, \
            f"recovered stream must carry ALL tokens, got {len(toks)}"
        # the engine loop survived for the other session
        out = requests.post(
            f"{addr}/chaosgen",
            json={"op": "next_chunk", "sid": surv["sid"],
                  "max_tokens": 5}, timeout=240).json()
        assert out.get("tokens") and "error" not in out, out
        requests.post(f"{addr}/chaosgen",
                      json={"op": "end", "sid": surv["sid"]}, timeout=60)
        # and fresh streams are clean (the nth rule is spent)
        events = _stream(addr, "/chaosgen", [4, 5, 6], 8)
        assert [e for e in events
                if isinstance(e, dict) and "token" in e] and \
            not [e for e in events
                 if isinstance(e, dict) and "error" in e]
    finally:
        chaos.clear()
        serve.delete("chaosgen")
