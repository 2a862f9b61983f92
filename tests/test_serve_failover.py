"""Decode-stream failover: live sessions survive replica death and drain.

The proxy/router layer journals every emitted token (serve/failover.py);
when a session's owner replica dies (chaos kill, node death) or drains,
the stream is re-admitted on a healthy replica via a teacher-forced
prefix prefill (``{"op": "resume"}`` → the engine's chunked admission) and
deduped by seq — the client sees a stall, never an error and never a
repeated/dropped token (greedy decode makes replay deterministic).

Tier-1: chaos-plan lints, journal/seq-dedupe units over a scripted
transport, Retry-After-honoring handle retries, teacher-forced replay
parity (fixed seeds), the idle-session leak reaper, chaos mid-stream
replica kill with byte-identical recovery (×2), controlled drain
handoff with zero dropped sessions, and eager client-disconnect
cancellation.  `slow`: `drain_node` of a node hosting live streams
(×2, fixed seeds).
"""

import json
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core.config import GlobalConfig

slow = pytest.mark.slow


def _tiny_cfg(max_seq_len=64):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    return TransformerConfig.tiny(max_seq_len=max_seq_len,
                                  attention_impl="reference",
                                  dtype=jnp.float32)


def _wait_for(cond, timeout=60.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.25)
    pytest.fail(f"timed out waiting for {msg}")


# ------------------------------------------------- chaos plan validation

def test_chaos_validate_plan_lints():
    """`ray-tpu chaos validate` satellite: a typoed site, bad regex, or
    conflicting `once` rules would silently never fire (or misfire) —
    the linter catches each class up front."""
    from ray_tpu.util.fault_injection import validate_plan
    ok = [{"site": "serve.request", "action": "crash",
           "match": {"nth": 3, "regex": "^gen$"}, "once": True},
          {"site": "serve.session_failover", "action": "error"},
          {"site": "rpc.send", "action": "delay", "delay_s": 0.1}]
    assert validate_plan(ok) == []
    issues = validate_plan([
        {"site": "serve.requset", "action": "crash"},       # typo
        {"site": "serve.request", "action": "evict"},       # wrong site
        {"site": "rpc.send", "action": "drop",
         "match": {"regex": "("}},                          # bad regex
        {"site": "rpc.send", "action": "drop",
         "match": {"nth": 1, "prob": 0.5}},                 # conflict
        {"site": "rpc.send", "action": "drop", "once": True,
         "max_fires": 3},                                   # conflict
        {"site": "rpc.send", "action": "drop", "id": "x"},
        {"site": "rpc.send", "action": "drop", "id": "x"},  # dup id
        {"site": "rpc.send", "action": "drop", "matches": {}},  # typo key
    ])
    text = "\n".join(issues)
    assert "unknown site" in text
    assert "no-op at site" in text
    assert "bad regex" in text
    assert "'nth' and 'prob' conflict" in text
    assert "'once' conflicts with max_fires" in text
    assert "duplicate rule id 'x'" in text
    assert "unknown key 'matches'" in text
    assert not validate_plan([])  # empty plan is vacuously fine
    assert validate_plan({"site": "x"})  # not a list


def test_chaos_validate_cli(tmp_path, capsys):
    """The CLI subcommand lints OFFLINE (no cluster) and fails fast on
    a plan that would misfire."""
    from ray_tpu.scripts.cli import main
    good = tmp_path / "good.json"
    good.write_text(json.dumps(
        [{"site": "serve.request", "action": "error",
          "match": {"nth": 2}}]))
    main(["chaos", "validate", str(good)])
    assert "OK" in capsys.readouterr().out
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"site": "nope", "action": "error"}]))
    with pytest.raises(SystemExit):
        main(["chaos", "validate", str(bad)])
    assert "unknown site" in capsys.readouterr().out


# ------------------------------------- failover client (scripted transport)

def test_failover_session_replica_death_resume():
    """Owner dies mid-stream → the journal resumes the session on a
    sibling (teacher-forced replay of prompt + delivered tokens), the
    spliced stream has no duplicate and no missing token, and follow-up
    ops stick to the NEW owner."""
    from ray_tpu.exceptions import ActorDiedError
    from ray_tpu.serve.failover import FailoverSession
    seen = []
    state = {"n": 0}

    def call(payload, sticky=None):
        seen.append((payload["op"], sticky))
        op = payload["op"]
        if op == "start":
            return {"sid": "A#1:0", "token": [10], "seq": 0}
        if op == "next_chunk":
            state["n"] += 1
            if state["n"] == 1:
                return {"tokens": [11, 12], "seq": 1, "done": False}
            if state["n"] == 2:
                raise ActorDiedError("aa", "chaos kill")
            return {"tokens": [14, 15], "seq": 4, "done": True}
        if op == "resume":
            assert payload["prompt"] == [1, 2]
            assert payload["generated"] == [10, 11, 12]
            return {"sid": "B#2:0", "token": [13], "seq": 3}
        raise AssertionError(op)

    s = FailoverSession(call, {"op": "start", "prompt": [1, 2]},
                        deployment="t", transient_retries=0)
    out = s.start()
    assert s.journal == [10] and out["sid"] == "A#1:0"
    assert s.next_tokens(4) == {"tokens": [11, 12], "done": False}
    assert s.next_tokens(4) == {"tokens": [13], "done": False}
    assert s.failovers == 1
    assert s.next_tokens(4) == {"tokens": [14, 15], "done": True}
    assert s.journal == [10, 11, 12, 13, 14, 15]
    # post-failover ops (including the final end) stick to the NEW owner
    s.end()
    assert seen[-1] == ("end", "B#2")
    stickies = [st for op_, st in seen if op_ == "next_chunk"]
    assert stickies == ["A#1", "A#1", "B#2"]


def test_failover_session_drain_migrate_dedupe_and_gap():
    """The three splice paths: a ``migrating`` reply hands off with
    reason=drain before the next fetch; an overlapping reply is deduped
    by seq; a FORWARD seq gap (destructive pop whose reply was lost)
    triggers a resume that regenerates the lost tokens."""
    from ray_tpu.serve.failover import FailoverSession
    script = []
    resumes = []

    def call(payload, sticky=None):
        op = payload["op"]
        if op == "start":
            return {"sid": "A:0", "token": [5], "seq": 0}
        if op == "resume":
            resumes.append(list(payload["generated"]))
            g = len(payload["generated"])
            return {"sid": f"B:{g}", "token": [100 + g], "seq": g}
        if op == "next_chunk":
            return script.pop(0)
        return {"ended": True}

    # drain handoff: buffered tokens ride the migrating reply
    s = FailoverSession(call, {"op": "start", "prompt": [9]},
                        deployment="t", transient_retries=0)
    s.start()
    script.append({"tokens": [6, 7], "seq": 1, "migrating": True})
    assert s.next_tokens(4)["tokens"] == [6, 7]
    # next fetch resumes FIRST (reason=drain): the replay carries the
    # whole journal, and no next_chunk hits the drained owner
    out = s.next_tokens(4)
    assert out["tokens"] == [103]
    assert resumes == [[5, 6, 7]]
    assert s.journal == [5, 6, 7, 103]

    # overlap dedupe: a reply re-carrying already-journaled tokens
    script.append({"tokens": [7, 103, 42], "seq": 2, "done": False})
    assert s.next_tokens(4)["tokens"] == [42]
    assert s.journal == [5, 6, 7, 103, 42]

    # forward gap: seq jumped past the journal → resume regenerates
    script.append({"tokens": [77], "seq": 9, "done": False})
    out = s.next_tokens(4)
    assert out["tokens"] == [105]       # resumed at journal len 5
    assert resumes == [[5, 6, 7], [5, 6, 7, 103, 42]]
    assert s.journal == [5, 6, 7, 103, 42, 105]
    assert s.failovers == 2


def test_failover_session_exhaustion_surfaces_stream_failed():
    """Recovery is bounded: when every resume attempt fails, the typed
    StreamFailedError surfaces (the SSE lane turns it into the in-band
    error event)."""
    from ray_tpu.exceptions import WorkerCrashedError
    from ray_tpu.serve.failover import FailoverSession, StreamFailedError
    calls = {"resume": 0}

    def call(payload, sticky=None):
        if payload["op"] == "start":
            return {"sid": "A:0", "token": [1], "seq": 0}
        if payload["op"] == "resume":
            calls["resume"] += 1
            raise WorkerCrashedError("still dead")
        raise WorkerCrashedError("owner gone")

    s = FailoverSession(call, {"op": "start", "prompt": [1]},
                        deployment="t", attempts=3,
                        failover_timeout_s=0.0, transient_retries=0)
    s.start()
    with pytest.raises(StreamFailedError):
        s.next_tokens(4)
    assert calls["resume"] == 3   # the attempts floor was honored


# ----------------------------------------- Retry-After in call_with_retry

def test_call_with_retry_honors_retry_after(monkeypatch):
    """Satellite: a typed shed (503) carries a server-sent Retry-After;
    retries are spaced by full-jitter delays sampled from it instead of
    the fixed cadence, and a sticky request never burns retries on it."""
    from ray_tpu.exceptions import ReplicaUnavailableError
    from ray_tpu.serve import handle as handle_mod
    sleeps = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
    monkeypatch.setattr(handle_mod.api, "get",
                        lambda ref, timeout=None: ref)

    class Router:
        def __init__(self, sheds):
            self.sheds = sheds
            self.calls = 0

        def assign_request(self, name, args, kwargs, method=None,
                           timeout_s=60.0, sticky_replica_id=None):
            self.calls += 1
            if self.calls <= self.sheds:
                raise ReplicaUnavailableError(name, retry_after_s=0.25)
            return {"ok": self.calls}, "r1"

        def complete(self, name, rid):
            pass

        def _refresh(self, force=False):
            pass

    r = Router(sheds=2)
    out = handle_mod.call_with_retry(r, "d", (), {}, timeout_s=30.0)
    assert out == {"ok": 3} and r.calls == 3
    assert len(sleeps) == 2, "each shed must be spaced, not hammered"
    # full jitter sampled from the Retry-After envelope (0.25 * 2**n,
    # capped at 4x), never the fixed serve_backoff cadence ceiling
    assert all(0.0 <= s <= 1.0 for s in sleeps), sleeps

    # sticky ops never re-route/retry on a shed: the session owner is
    # gone and only the failover client may act on that
    r2 = Router(sheds=10)
    sleeps.clear()
    with pytest.raises(ReplicaUnavailableError):
        handle_mod.call_with_retry(r2, "d", (), {}, timeout_s=5.0,
                                   sticky_replica_id="dead#1")
    assert r2.calls == 1 and not sleeps


# ------------------------------------ teacher-forced replay parity (seeds)

def test_prefill_chunked_matches_whole_prompt_prefill():
    """models satellite: the bounded-compile prefix prefill (fixed-size
    chunk programs, the remainder one more, padded) produces the same
    last-position argmax and the same continuation as the whole-prompt
    prefill, for a prefix length that has a remainder."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (decode_step, init_kv_cache, init_params,
                                prefill, prefill_chunked)
    cfg = _tiny_cfg()
    params, _ = init_params(jax.random.PRNGKey(7), cfg)
    prefix = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9]],
                         jnp.int32)   # 13 = 3 chunks of 4 + a tail of 1
    lr, cr = prefill(params, prefix, cfg, init_kv_cache(cfg, 1, 64))
    ls, cs = prefill_chunked(params, prefix, cfg,
                             init_kv_cache(cfg, 1, 64), chunk=4)
    assert int(cs["pos"]) == int(cr["pos"]) == 13
    tok_r = jnp.argmax(lr, -1).astype(jnp.int32)
    tok_s = jnp.argmax(ls, -1).astype(jnp.int32)
    assert int(tok_r[0]) == int(tok_s[0])
    # the caches agree where it matters: identical greedy continuations
    for _ in range(4):
        lr, cr = decode_step(params, tok_r, cr, cfg)
        ls, cs = decode_step(params, tok_s, cs, cfg)
        tok_r = jnp.argmax(lr, -1).astype(jnp.int32)
        tok_s = jnp.argmax(ls, -1).astype(jnp.int32)
        assert int(tok_r[0]) == int(tok_s[0])


def test_engine_resume_replay_parity():
    """Acceptance satellite: an engine slot seeded via teacher-forced
    prefill of prompt+prefix produces a token-identical continuation vs
    an uninterrupted step-by-step session (fixed seeds), for several
    cut points including mid-chunk ones."""
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    want = 16
    prompt = [5, 6, 7]
    core = DecodeSessionCore(cfg, max_len=64, seed=3)
    r = core.handle({"op": "start", "prompt": prompt})
    assert r["seq"] == 0
    ref = list(r["token"])
    while len(ref) < want:
        out = core.handle({"op": "next_chunk", "sid": r["sid"],
                           "max_tokens": want - len(ref)})
        assert "error" not in out, out
        assert out["seq"] == len(ref)
        ref += out["tokens"]
    core.handle({"op": "end", "sid": r["sid"]})

    from ray_tpu.serve.config import DecodeEngineConfig

    # engines to resume INTO: the defaults, and ones of other slot counts
    # and chunk widths — chunked teacher-forced admission must keep the
    # replayed continuation byte-identical whatever the batch's shape
    engines = {1: None, 7: None,
               12: DecodeEngineConfig(max_slots=2),
               6: DecodeEngineConfig(prefill_chunk_tokens=4,
                                     max_slots=3)}
    for cut, engine in engines.items():
        fresh = DecodeSessionCore(cfg, max_len=64, seed=3,
                                  engine=engine)
        rr = fresh.handle({"op": "resume", "prompt": prompt,
                           "generated": ref[:cut]})
        assert "error" not in rr, rr
        assert rr["seq"] == cut
        toks = ref[:cut] + list(rr["token"])
        while len(toks) < want:
            out = fresh.handle({"op": "next_chunk", "sid": rr["sid"],
                               "max_tokens": want - len(toks)})
            assert "error" not in out, out
            toks += out["tokens"]
        assert toks == ref, f"cut={cut}: {toks} != {ref}"
        fresh.handle({"op": "end", "sid": rr["sid"]})
        if fresh.engine is not None:
            fresh.engine.shutdown()


# --------------------------------------------------- session leak reaper

def test_engine_idle_reaper_evicts_abandoned_sessions():
    """Satellite: a session whose client stops polling past
    session_idle_ttl_s is evicted and its slot reclaimed; a polled
    session survives."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg(max_seq_len=256)
    # the ttl is many polls long: under six test workers one turn of the
    # loop below (a 0.2 s wait, the stats, a 0.1 s sleep) has taken more
    # than a second, and at a ttl of 1.0 the POLLED session was reaped too
    core = DecodeSessionCore(
        cfg, max_len=256, seed=1,
        engine=DecodeEngineConfig(max_slots=2, token_queue_depth=4,
                                  session_idle_ttl_s=4.0))
    dead = core.handle({"op": "start", "prompt": [1, 2, 3]})
    live = core.handle({"op": "start", "prompt": [4, 5, 6]})
    deadline = time.monotonic() + 60
    # keep polling `live`, abandon `dead` — only the abandoned one reaps
    reaped = False
    while time.monotonic() < deadline and not reaped:
        out = core.handle({"op": "next_chunk", "sid": live["sid"],
                           "max_tokens": 2, "timeout_s": 0.2})
        assert "error" not in out, out
        st = core.handle({"op": "stats"})["engine"]
        reaped = st["reaped"] >= 1
        time.sleep(0.1)
    assert reaped, "idle session was never reaped"
    out = core.handle({"op": "next_chunk", "sid": dead["sid"]})
    assert "error" in out, "reaped session must be forgotten"
    out = core.handle({"op": "next_chunk", "sid": live["sid"],
                       "max_tokens": 2, "timeout_s": 5.0})
    assert "error" not in out, "polled session must survive the reaper"
    st = core.handle({"op": "stats"})["engine"]
    assert st["live_sessions"] == 1
    core.handle({"op": "end", "sid": live["sid"]})


# ------------------------------------------------------- cluster fixture

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


def _tokens(events):
    return [e["token"][0] for e in events
            if isinstance(e, dict) and "token" in e]


def _errors(events):
    return [e for e in events if isinstance(e, dict) and "error" in e]


def _alive_replicas():
    from ray_tpu import state
    return [r for r in state.list_actors()
            if "ServeReplica" in (r.get("class_name") or "")
            and r.get("state") == "ALIVE"]


@pytest.fixture(scope="module")
def failover_app():
    ray_tpu.init(num_cpus=6, object_store_memory=256 * 1024 * 1024)
    from ray_tpu import serve
    serve.start()

    # NOTE: deployment classes must be SELF-CONTAINED (imports inside
    # methods, no module globals) — they are cloudpickled by value

    @serve.deployment(max_concurrent_queries=8, num_replicas=2)
    class SGen:
        """Two replicas, SAME seed: greedy decode is deterministic, so
        any replica produces the identical stream — the failover
        acceptance compares streams across replica generations."""

        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.config import DecodeEngineConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            cfg = TransformerConfig.tiny(max_seq_len=64,
                                         attention_impl="reference",
                                         dtype=jnp.float32)
            self.core = DecodeSessionCore(
                cfg, max_len=64, seed=5,
                engine=DecodeEngineConfig(chunk_linger_s=0.01))

        def engine_stats(self):
            return self.core.handle({"op": "stats"})

        def __call__(self, req):
            return self.core.handle(req)

    @serve.deployment(max_concurrent_queries=8, num_replicas=1)
    class LGen:
        """Single replica with a roomy cache: the disconnect test needs
        a stream long enough to out-live the client."""

        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.config import DecodeEngineConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            cfg = TransformerConfig.tiny(max_seq_len=512,
                                         attention_impl="reference",
                                         dtype=jnp.float32)
            self.core = DecodeSessionCore(
                cfg, max_len=512, seed=5,
                engine=DecodeEngineConfig(chunk_linger_s=0.01))

        def __call__(self, req):
            return self.core.handle(req)

    serve.run(SGen.bind(), name="failgen")
    serve.run(SGen.bind(), name="draingen")
    serve.run(LGen.bind(), name="leakgen")
    yield serve.api.http_address()
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def chaos_cleanup():
    import os

    from ray_tpu.util import fault_injection as fi
    yield
    fi.disarm()
    GlobalConfig.update({"chaos_plan": ""})
    os.environ.pop("RAY_TPU_CHAOS_PLAN", None)


# ---------------------------------------- acceptance: chaos replica kill

@pytest.mark.parametrize("run", [1, 2])
def test_chaos_midstream_replica_kill_stream_byte_identical(
        failover_app, chaos_cleanup, run):
    """Acceptance: a chaos mid-stream replica KILL (worker process
    dies) yields the byte-identical full token stream a no-fault run
    produces — zero user-visible errors, no duplicate/missing tokens —
    because the proxy journal resumes the session on the surviving
    replica.  Run twice with fixed seeds."""
    import requests

    from ray_tpu import chaos
    addr = failover_app

    def poke_and_count():
        # the heal loop piggybacks on router metric reports, so the
        # wait must generate traffic (run 2 waits out run 1's heal)
        try:
            requests.post(f"{addr}/failgen", json={"op": "stats"},
                          timeout=60)
        except Exception:
            pass
        return len(_alive_replicas())

    _wait_for(lambda: poke_and_count() >= 5, 180.0,
              "all replicas ALIVE (incl. healed crash victim)")
    prompt = [2, 7, 1, 8, 2, 8]
    # no-fault reference, twice: also proves replica determinism (the
    # two streams may land on different replicas)
    ref = _tokens(_stream(addr, "/failgen", prompt, 24, chunk=4))
    assert len(ref) == 24
    assert _tokens(_stream(addr, "/failgen", prompt, 24, chunk=4)) == ref
    # request #3 on the stream's owner replica (start, chunk, chunk →
    # crash) — `once` claims through the controller so exactly one
    # replica cluster-wide takes the hit
    chaos.apply([{"id": f"failkill-{run}", "site": "serve.request",
                  "match": {"nth": 3, "regex": "^failgen$"},
                  "action": "crash", "once": True}])
    try:
        events = _stream(addr, "/failgen", prompt, 24, chunk=4)
    finally:
        chaos.clear()
    assert events[-1] == "DONE"
    assert not _errors(events), \
        f"failover must hide the replica death: {_errors(events)}"
    toks = _tokens(events)
    assert toks == ref, (
        f"recovered stream diverged: {toks} != {ref} — failover must "
        f"be invisible (no dup/drop/divergence)")


# -------------------------------------- acceptance: drain with live stream

def _router_call(name):
    """FailoverSession transport over this process's serve router —
    the same call_with_retry + TaskError-unwrap closure the HTTP proxy
    uses, minus the SSE framing, so tests can pace the stream."""
    from ray_tpu import serve
    from ray_tpu.exceptions import ReplicaUnavailableError, TaskError
    from ray_tpu.serve.handle import call_with_retry
    router = serve.api._state["router"]

    def call(payload, sticky=None):
        try:
            return call_with_retry(router, name, (payload,), {},
                                   timeout_s=60.0,
                                   sticky_replica_id=sticky)
        except TaskError as e:
            if isinstance(e.cause, ReplicaUnavailableError):
                raise e.cause from None
            raise
    return call


def _replica_handle(name, replica_id):
    from ray_tpu import api as core_api
    from ray_tpu import serve
    snap = core_api.get(
        serve.api._state["controller"].snapshot.remote(-1), timeout=30.0)
    for rep in snap["table"][name]["replicas"]:
        if rep["id"] == replica_id:
            return rep["handle"]
    raise AssertionError(f"replica {replica_id} not in table")


def test_drain_handoff_migrates_live_stream_zero_dropped(failover_app):
    """Acceptance: a replica entering drain mode mid-stream hands its
    live session to the sibling with zero dropped sessions and a
    token-identical stream — the `migrating` reply carries the buffered
    tokens, the resume replays the journal, and the drained replica
    reports zero live sessions for the controller's stop gate."""
    from ray_tpu import api as core_api
    from ray_tpu.serve.failover import FailoverSession
    call = _router_call("draingen")
    prompt = [3, 1, 4, 1, 5]
    want = 20

    def run_stream(pause_after=None, on_pause=None):
        sess = FailoverSession(call, {"op": "start", "prompt": prompt},
                               deployment="draingen")
        out = sess.start()
        assert sess.journal == out["token"], out
        while len(sess.journal) < want and not sess.done:
            if pause_after is not None and on_pause is not None \
                    and len(sess.journal) >= pause_after:
                on_pause(sess)
                pause_after = None
            sess.next_tokens(min(4, want - len(sess.journal)))
        sess.end()
        return sess

    ref = run_stream().journal[:want]
    assert len(ref) == want

    drained = {}

    def trigger_drain(sess):
        owner = sess._sticky
        h = _replica_handle("draingen", owner)
        n = core_api.get(h.prepare_drain.remote(), timeout=60.0)
        drained.update(owner=owner, handle=h, live_at_drain=n)

    sess = run_stream(pause_after=6, on_pause=trigger_drain)
    assert drained, "drain was never triggered"
    assert drained["live_at_drain"] >= 1
    assert sess.journal[:want] == ref, (
        f"migrated stream diverged: {sess.journal[:want]} != {ref}")
    assert sess.failovers >= 1, "the session never actually migrated"
    assert sess._sticky != drained["owner"], \
        "resumed session must live on a DIFFERENT replica"
    # the drained replica reports zero live sessions — the controller's
    # stop gate (zero dropped sessions) is satisfied
    st = core_api.get(drained["handle"].drain_status.remote(),
                      timeout=30.0)
    assert st["live_sessions"] == 0, st
    # migration observability: counted in THIS process (the failover
    # client ran here), with the drain reason
    from ray_tpu import metrics
    text = metrics.prometheus_text()
    assert "ray_tpu_serve_sessions_migrated_total" in text
    assert 'reason="drain"' in text


# ------------------------------------- eager client-disconnect cancellation

def test_client_disconnect_cancels_session_eagerly(failover_app):
    """Satellite: the proxy detects a vanished SSE client and cancels
    the session (end + slot reclaim) instead of decoding to max_tokens
    into a full queue; the idle TTL (120s default) is NOT the mechanism
    that fires here."""
    import requests
    addr = failover_app
    max_new = 400

    def live_sessions():
        r = requests.post(f"{addr}/leakgen", json={"op": "stats"},
                          timeout=60)
        return r.json().get("engine", {}).get("live_sessions", 0)

    r = requests.post(
        f"{addr}/leakgen/stream",
        json={"prompt": [1, 2, 3], "max_new_tokens": max_new,
              "chunk_tokens": 8},
        stream=True, timeout=240)
    assert r.status_code == 200
    # read just the start event, then vanish
    for line in r.iter_lines():
        if line.startswith(b"data: "):
            break
    r.close()
    _wait_for(lambda: live_sessions() == 0, 45.0,
              "eager cancel of the disconnected client's session")
    st = requests.post(f"{addr}/leakgen", json={"op": "stats"},
                       timeout=60).json()["engine"]
    assert st["tokens"] < max_new - 20, (
        f"proxy decoded {st['tokens']} tokens for a vanished client — "
        f"disconnect must cancel eagerly")


# ------------------------------------------- slow: real node drain ×2

@slow
@pytest.mark.parametrize("run", [1, 2])
def test_drain_node_with_live_streams_zero_dropped(run):
    """Acceptance (slow): `ray-tpu drain` of a node hosting replicas
    with LIVE streams completes with zero dropped sessions — every
    stream finishes full-length, token-identical to its no-fault
    reference, with no user-visible error."""
    from ray_tpu import serve, state
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.driver import get_global_core
    from ray_tpu.serve.failover import FailoverSession
    cluster = Cluster()
    try:
        # n1 (2 CPU) hosts serve's controller/proxy but can never fit a
        # 3-CPU replica: replicas land on n2/n3
        n1 = cluster.add_node(num_cpus=2)
        cluster.connect(n1)
        serve.start()
        n2 = cluster.add_node(num_cpus=6)
        n3 = cluster.add_node(num_cpus=6)

        @serve.deployment(num_replicas=2, max_concurrent_queries=8,
                          ray_actor_options={"num_cpus": 3.0})
        class DGen:
            def __init__(self):
                import jax.numpy as jnp

                from ray_tpu.models import TransformerConfig
                from ray_tpu.serve.config import DecodeEngineConfig
                from ray_tpu.serve.decode_session import \
                    DecodeSessionCore
                cfg = TransformerConfig.tiny(max_seq_len=64,
                                             attention_impl="reference",
                                             dtype=jnp.float32)
                self.core = DecodeSessionCore(
                    cfg, max_len=64, seed=5,
                    engine=DecodeEngineConfig(chunk_linger_s=0.01))

            def __call__(self, req):
                return self.core.handle(req)

        serve.run(DGen.bind(), name="dgen")
        _wait_for(lambda: len(_alive_replicas()) == 2, 120.0,
                  "two live replicas")
        call = _router_call("dgen")
        prompts = [[3, 1, 4, 1], [2, 7, 1, 8, 2]]
        want = 30

        def full_stream(prompt, pace=0.0):
            sess = FailoverSession(call,
                                   {"op": "start", "prompt": prompt},
                                   deployment="dgen",
                                   failover_timeout_s=90.0)
            out = sess.start()
            assert sess.journal == out["token"], out
            fetch = 2 if pace else 4   # paced streams span the drain
            while len(sess.journal) < want and not sess.done:
                sess.next_tokens(min(fetch, want - len(sess.journal)))
                if pace:
                    time.sleep(pace)
            sess.end()
            return sess.journal[:want]

        refs = [full_stream(p) for p in prompts]
        assert all(len(r) == want for r in refs)

        results, errors = [None] * len(prompts), []

        def one(i):
            try:
                results[i] = full_stream(prompts[i], pace=0.4)
            except Exception as e:    # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        time.sleep(1.5)   # streams in flight before the drain lands
        target = next(
            r["node_id"] for r in _alive_replicas()
            if r.get("node_id") and r["node_id"] != n1.node_id)
        core = get_global_core()
        reply = core.controller.call(
            "drain_node", {"node_id": target, "timeout_s": 90.0,
                           "wait": True}, timeout=150.0)
        for t in threads:
            t.join(timeout=240.0)
        assert reply.get("outcome") == "completed", reply
        assert not errors, \
            f"zero dropped sessions required, got: {errors}"
        for i, ref in enumerate(refs):
            assert results[i] == ref, (
                f"stream {i} diverged across the drain: "
                f"{results[i]} != {ref}")
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()
