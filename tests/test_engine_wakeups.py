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


# ---------------------------------------- what the loop's own turn waited for
# (`phase_totals`: lock_wait, long_read; `stats()["waits"]`; PR 51)

def _two_decoding(core, eng):
    """Two sessions that hold a slot and stand paused at 2 tokens with
    nobody polling: the loop sleeps, and decodes on when `_resume`d."""
    a = core.handle({"op": "start", "prompt": [3, 1, 4]})
    b = core.handle({"op": "start", "prompt": [8, 8, 8]})
    sa, sb = eng.sessions[a["sid"]], eng.sessions[b["sid"]]
    _wait(lambda: len(sa.queue) == len(sb.queue) == 2
          and eng._flight is None, "the slots never paused")
    return sa, sb


def _long_reads():
    from ray_tpu.util import tracing
    return [e for e in tracing.span_events()
            if e["name"] == "engine:long_read"]


def test_an_undisturbed_loop_waits_for_no_lock_and_no_read():
    """50 steps of two sessions with no caller at the lock: the loop's
    every acquisition is the one `acquire(False)`, no read is long, and the
    wall seconds of `schedule` are the thread's own CPU seconds or more."""
    cfg, core = _core(max_len=512, token_queue_depth=2)
    eng = core.engine
    try:
        _two_decoding(core, eng)
        before, waits, spans = dict(eng.phase_s), dict(eng.waits), \
            len(_long_reads())
        steps = eng.steps
        _resume(eng, depth=400)
        _wait(lambda: eng.steps >= steps + 50, "the loop did not go on")
        assert eng.waits == waits == {"lock_waits": 0, "long_reads": 0}
        assert eng.phase_s["lock_wait"] == before["lock_wait"] == 0.0
        assert eng.phase_s["long_read"] == before["long_read"] == 0.0
        assert len(_long_reads()) == spans
        ph = eng.phase_totals()
        assert 0 < ph["schedule_cpu"] <= ph["schedule"] + 1e-6, ph
        # `schedule` alone: `thread_time()` is a system call
        assert [k for k in ph if k.endswith("_cpu")] == ["schedule_cpu"]
        assert set(eng.stats()["waits"]) == {
            "lock_waits", "long_reads", "gc_collections", "late_wakeups"}
    finally:
        eng.shutdown()


def test_a_held_lock_is_the_loops_lock_wait():
    """A thread that holds the engine's lock for 50 ms while two sessions
    decode: the loop stands at its next acquisition for what is left of
    them, counted once or more and in seconds."""
    cfg, core = _core(max_len=512, token_queue_depth=2)
    eng = core.engine
    try:
        _two_decoding(core, eng)
        steps = eng.steps
        _resume(eng, depth=400)
        _wait(lambda: eng.steps >= steps + 5, "the loop did not go on")
        assert eng.waits["lock_waits"] == 0
        with eng._cond:
            held = eng.steps
            time.sleep(0.05)
            # it stood still: at most the step whose publish was under way
            assert eng.steps <= held + 1
        _wait(lambda: eng.steps >= held + 5, "the loop did not go on")
        assert eng.waits["lock_waits"] >= 1
        assert 0.02 <= eng.phase_s["lock_wait"] <= 0.5
        assert eng.phase_totals()["lock_wait"] == round(
            eng.phase_s["lock_wait"], 6)
        assert eng.stats()["waits"]["lock_waits"] >= 1
    finally:
        eng.shutdown()


def test_a_stalled_read_is_one_long_read_span():
    """Chaos site ``serve.decode_step`` with a delay of 300 ms (it stands
    where a device or a transfer would stall the read): ONE ring span
    `engine:long_read` with what the loop can say of it, and the seconds
    in `long_read`.  The sleeping thread lets the interpreter go, so no
    late wake-up stands beside it: the rule's verdict is the device."""
    from ray_tpu.util import fault_injection as fi
    cfg, core = _core(max_len=512, token_queue_depth=2)
    eng = core.engine
    try:
        _two_decoding(core, eng)
        spans = len(_long_reads())
        fi.arm([{"site": "serve.decode_step", "action": "delay",
                 "delay_s": 0.3, "match": {"nth": 3}}])
        steps = eng.steps
        _resume(eng, depth=400)
        _wait(lambda: eng.steps >= steps + 10, "the loop did not go on")
        assert eng.waits["long_reads"] == 1
        assert 0.3 <= eng.phase_s["long_read"] < 2.0
        assert eng.phase_s["long_read"] <= eng.phase_s["readback"]
        (ev,) = _long_reads()[spans:]
        assert ev["cat"] == "stall"
        args = ev["args"]
        assert 300.0 <= args["waited_ms"] < 2000.0 and args["live"] == 2
        assert abs(ev["dur"] * 1e-3 - args["waited_ms"]) < 1.0
        assert steps <= args["step"] <= steps + 3
        assert not args.get("late_wakeups")     # zero is left out
        assert eng.stats()["waits"]["long_reads"] == 1
    finally:
        fi.disarm()
        eng.shutdown()
