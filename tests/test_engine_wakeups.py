"""Who the decode engine wakes, and when (serve/decode_session.py).

A caller in ``next_chunk`` waits on its SESSION's condition over the
engine's one lock.  A step's publish wakes it for the first token it can
take and when what it asked for is there, never for another session's
tokens; a drain wakes the loop only where it un-paused a slot.  With one
condition for all (every publish and every drain a ``notify_all``) 32
callers of one replica woke each other some 190 times a step, and the
loop waited for the interpreter behind them (PERF.md, PR 40).  Tier-1,
CPU, tiny model.
"""

import threading
import time

import pytest

from greedy_reference import greedy_stream


def _core(max_len=256, **engine):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = TransformerConfig.tiny(max_seq_len=max_len,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    return cfg, DecodeSessionCore(
        cfg, max_len=max_len, seed=3,
        engine=DecodeEngineConfig(max_slots=2, **engine))


def _wait(cond, what, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.005)


def _counted(sess):
    """`sess.cond.wait` with every return counted: True for a wake-up,
    False for a time-out."""
    wakes, wait = [], sess.cond.wait

    def counted(timeout=None):
        woke = wait(timeout)
        wakes.append(woke)
        return woke
    sess.cond.wait = counted
    return wakes


def _resume(eng, depth=64):
    with eng._cond:
        eng.ecfg.token_queue_depth = depth
        eng._cond.notify_all()


def test_a_lingering_caller_is_woken_once_for_all_it_asked_for():
    """Both slots stand paused at 2 tokens.  A caller asks session b for
    40 and lingers; the loop goes on and publishes 38 steps of two
    sessions: b's caller is woken ONCE, when the 40 are there, and the
    stream is the reference's."""
    cfg, core = _core(chunk_linger_s=20.0, token_queue_depth=2)
    eng = core.engine
    try:
        a = core.handle({"op": "start", "prompt": [3, 1, 4]})
        b = core.handle({"op": "start", "prompt": [8, 8, 8]})
        sa, sb = eng.sessions[a["sid"]], eng.sessions[b["sid"]]
        _wait(lambda: len(sa.queue) == len(sb.queue) == 2
              and eng._flight is None, "the slots never paused")
        wakes, out = _counted(sb), {}
        t = threading.Thread(target=lambda: out.update(core.handle(
            {"op": "next_chunk", "sid": b["sid"], "max_tokens": 40})))
        t.start()
        _wait(lambda: sb.want == 40 and sb.cond._waiters,
              "the caller never waited")
        _resume(eng)
        t.join(30)
        assert not t.is_alive()
        assert wakes == [True]
        assert list(b["token"]) + out["tokens"] == greedy_stream(
            cfg, [8, 8, 8], 41, max_len=256, seed=3)
        # a's tokens were published all the while, and woke nobody
        assert len(sa.queue) >= 40 and not sa.cond._waiters
    finally:
        eng.shutdown()


def test_a_publish_wakes_for_the_first_token_the_last_asked_for_and_the_end():
    """`_EngineSession.wake`: a caller that found nothing is woken by the
    first token (its linger starts there), sleeps through the next ones,
    and is woken when what it asked for is there or nothing more comes."""
    from ray_tpu.serve.decode_session import _EngineSession
    lock = threading.RLock()
    s = _EngineSession("local:0", None, lock)
    woken = []
    s.cond.notify_all = lambda: woken.append(len(s.queue))
    with lock:
        s.want = 5
        for tok in range(7):
            was_empty = not s.queue
            s.queue.append(tok)
            s.wake(was_empty)
        assert woken == [1, 5, 6, 7]     # nobody took them: each is enough
        del woken[:]
        s.queue.clear()
        s.want = 64
        s.queue.append(7)
        s.wake(was_empty=True)
        s.queue.append(8)
        s.wake(was_empty=False)
        s.done = True
        s.wake(was_empty=False)
        assert woken == [1, 2]           # the first, then the end


@pytest.mark.parametrize("how", ["end", "drain", "fault"])
def test_what_ends_a_session_wakes_its_waiting_caller(how):
    """`end` from another caller, a replica that begins to drain, and a
    failed step each return a caller that lingers for more at once, not
    at its linger's end."""
    cfg, core = _core(token_queue_depth=1, chunk_linger_s=60.0)
    eng = core.engine
    try:
        b = core.handle({"op": "start", "prompt": [8, 8, 8]})
        sb = eng.sessions[b["sid"]]
        _wait(lambda: len(sb.queue) == 1 and eng._flight is None,
              "the slot never paused")
        tok, out = sb.queue[0], {}
        t = threading.Thread(target=lambda: out.update(core.handle(
            {"op": "next_chunk", "sid": b["sid"], "max_tokens": 4})))
        t.start()
        _wait(lambda: sb.cond._waiters, "the caller never waited")
        t0 = time.monotonic()
        if how == "end":
            assert core.handle({"op": "end", "sid": b["sid"]})["ended"]
        elif how == "drain":
            eng.begin_drain()
        else:
            eng._fail_slots("injected")
        t.join(10)
        assert not t.is_alive() and time.monotonic() - t0 < 5
        if how == "end":
            assert out == {"tokens": [tok], "done": True, "seq": 1}
        elif how == "drain":
            assert out["migrating"] and out["tokens"] == [tok]
        else:
            assert out == {"error": "injected", "done": True}
    finally:
        eng.shutdown()


def test_a_drain_wakes_the_loop_only_where_it_unpaused_a_slot():
    """The loop sleeps with every slot paused.  A caller that takes
    tokens from a full queue wakes it; the callers in `start` and the
    loop are NOT notified by a drain of a queue that was not full."""
    cfg, core = _core(token_queue_depth=4)
    eng = core.engine
    try:
        b = core.handle({"op": "start", "prompt": [8, 8, 8]})
        sb = eng.sessions[b["sid"]]
        _wait(lambda: len(sb.queue) == 4 and eng._flight is None,
              "the slot never paused")
        notified, notify = [], eng._cond.notify_all

        def counted():
            notified.append(len(sb.queue))
            notify()
        eng._cond.notify_all = counted
        got = core.handle({"op": "next_chunk", "sid": b["sid"],
                           "max_tokens": 1})["tokens"]
        assert len(got) == 1 and notified == [3]     # 4 were held: full
        _wait(lambda: len(sb.queue) == 4 and eng._flight is None,
              "the loop did not go on")
        with eng._cond:
            eng.ecfg.token_queue_depth = 64    # nobody told: still asleep
            del notified[:]
            got += core.handle({"op": "next_chunk", "sid": b["sid"],
                                "max_tokens": 2})["tokens"]
            assert notified == []                    # 4 of 64: not full
        eng._cond.notify_all = notify
        assert list(b["token"]) + got == greedy_stream(
            cfg, [8, 8, 8], 4, max_len=256, seed=3)
    finally:
        eng.shutdown()
