"""Transparent decode-stream failover: the proxy-side replay journal.

The continuous-batching engine (`decode_session.py`) pins a session's KV
cache to ONE replica — when that replica dies (chaos kill, node death)
or its node drains, the cache is gone.  But the routing layer driving
the stream has observed *every* emitted token, and greedy decode is
exactly deterministic: prompt + tokens-delivered-so-far fully determine
the rest of the stream.  So the proxy keeps a per-session **replay
journal** (prompt, emitted token ids, monotonic seq) and, on owner
failure, re-admits the session on a healthy replica with a
teacher-forced prefix prefill (``{"op": "resume"}``), resuming at the
next seq.  Resume IS chunked admission since PR-6: the target engine's
thread walks the replay prefix through the same fixed-shape chunk
programs every admission uses (``models.prefill_chunk_jit`` →
``models.cache_insert_slot``), so a resume never stalls the healthy
replica's live streams and never compiles a new program.
The client sees a stall — never an error, never a repeated or dropped
token.

Seq accounting makes the splice airtight:

* every engine reply stamps the seq of its first token; the journal
  length is the next seq the client expects;
* a reply overlapping the journal (a resume replayed after a partial
  read) is deduped by skipping the overlap;
* a reply AHEAD of the journal means a destructive ``next_chunk`` pop
  whose reply was lost in flight (proxy timeout, connection reset
  after the replica popped) — those tokens are unrecoverable from that
  session, so the gap triggers a resume, which regenerates them.

Failure classification:

* ``ReplicaUnavailableError`` from sid-sticky routing (owner out of the
  table) or a typed replica-death error → resume, reason
  ``replica_death``;
* a ``migrating`` reply (the owner's engine entered drain mode — the
  serve controller evacuating the replica before stopping it) → resume,
  reason ``drain``;
* any other request failure is retried on the same owner first (the
  session may be fine — e.g. an injected transient error); if it
  persists, or a seq gap is detected, resume with reason ``error``.

Chaos site ``serve.session_failover`` fires at the top of every
recovery attempt so the chaos suite can attack the failover path
itself.  Every migration counts
``ray_tpu_serve_sessions_migrated_total{reason}``, observes the
client-visible stall in ``ray_tpu_serve_session_failover_seconds``,
and records a ``serve_session_failover`` span.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

#: payload keys that are per-call transport details, not generation
#: parameters — everything else from the start payload is replayed
#: verbatim on resume so sampling-param-style extras survive failover
_NON_REPLAY_KEYS = ("op", "prompt", "generated", "sid", "max_tokens",
                    "timeout_s")


class StreamFailedError(RuntimeError):
    """Recovery exhausted: every resume attempt failed.  The SSE lane
    surfaces this as the in-band error event (the pre-failover
    behavior, now reserved for genuinely unrecoverable streams)."""


class FailoverSession:
    """One decode stream with transparent failover.

    ``call`` is the transport: ``call(payload: dict, sticky:
    Optional[str]) -> dict``, raising on RPC failure — the HTTP proxy
    passes a closure over its Router + ``call_with_retry``; tests pass
    scripted fakes.  The session itself is transport-agnostic and
    jax-free, so the journal/dedupe/resume logic is unit-testable
    without a cluster."""

    def __init__(self, call: Callable[..., Any], start_payload: Dict[str, Any],
                 *, deployment: str = "", attempts: Optional[int] = None,
                 failover_timeout_s: Optional[float] = None,
                 transient_retries: int = 2):
        self._call = call
        self._payload = dict(start_payload)
        self._name = deployment or "decode"
        self._attempts = attempts
        self._timeout = failover_timeout_s
        self._transient_retries = max(0, int(transient_retries))
        self.journal: List[int] = []   # every token delivered, in order
        self.sid: Optional[str] = None
        self.done = False
        self.failovers = 0
        self._sticky: Optional[str] = None
        self._migrate_pending = False

    # ------------------------------------------------------------- lifecycle

    def start(self) -> Any:
        """Issue the start op; returns the raw reply for the caller to
        emit.  A dict without ``"error"`` IS a session: its sid pins the
        owner and its token opens the journal.  Anything else (an error
        reply) passes through, and the session stays unstarted
        (``sid is None``)."""
        out = self._call(self._payload, None)
        if not isinstance(out, dict) or "error" in out:
            return out
        self.sid = out.get("sid")
        self._sticky = self._owner_of(self.sid)
        self.journal.extend(out.get("token") or ())
        self.done = bool(out.get("done"))
        return out

    def next_tokens(self, max_tokens: int) -> Dict[str, Any]:
        """Fetch the next chunk, riding out owner death/drain/transient
        failures.  Returns ``{"tokens": [...], "done": bool}`` with
        journal-deduped tokens (possibly empty on a server-side wait
        timeout); raises :class:`StreamFailedError` only when recovery
        is exhausted."""
        transient_left = self._transient_retries
        while True:
            if self._migrate_pending:
                self._migrate_pending = False
                out = self._failover("drain")
            else:
                try:
                    out = self._call({"op": "next_chunk", "sid": self.sid,
                                      "max_tokens": max(1, int(max_tokens))},
                                     self._sticky)
                except Exception as e:   # noqa: BLE001
                    reason = self._death_reason(e)
                    if reason is not None:
                        out = self._failover(reason)
                    elif transient_left > 0:
                        # the session may be intact (injected error,
                        # blip): retry the same owner before resuming
                        transient_left -= 1
                        time.sleep(0.05)
                        continue
                    else:
                        out = self._failover("error")
            if not isinstance(out, dict):
                raise StreamFailedError(
                    f"protocol violation from {self._name}: {out!r}")
            if "error" in out:
                # unknown sid (engine restarted/evicted) or engine
                # failure: the journal can still replay it elsewhere
                out = self._failover("error")
            fresh = self._consume(out)
            if fresh is None:          # seq gap: tokens lost in flight
                out = self._failover("error")
                fresh = self._consume(out)
                if fresh is None:
                    raise StreamFailedError(
                        f"seq gap persisted across resume of "
                        f"{self._name} stream")
            if out.get("migrating") and not self.done:
                # buffered tokens delivered; owner is evacuating — line
                # up the resume before the next fetch
                self._migrate_pending = True
            if fresh or self.done:
                return {"tokens": fresh, "done": self.done}
            # empty non-terminal reply (server-side wait timeout or a
            # drain handoff with nothing buffered): loop — the migrate
            # flag above or the next poll makes progress

    def end(self) -> None:
        """Release the replica-side session; never raises (a dead owner
        has nothing to free)."""
        if self.sid is None:
            return
        try:
            self._call({"op": "end", "sid": self.sid}, self._sticky)
        except Exception:
            pass

    # -------------------------------------------------------------- internals

    @staticmethod
    def _owner_of(sid: Any) -> Optional[str]:
        """Engine sids are ``<replica_id>:<n>`` — the prefix pins every
        follow-up op to the owning replica."""
        if isinstance(sid, str) and ":" in sid:
            return sid.rsplit(":", 1)[0]
        return None

    @staticmethod
    def _death_reason(e: BaseException) -> Optional[str]:
        """Classify an RPC failure that kills the session outright."""
        from ..exceptions import ReplicaUnavailableError, TaskError
        from .handle import is_replica_down_error
        if is_replica_down_error(e):
            return "replica_death"
        if isinstance(e, ReplicaUnavailableError):
            return "replica_death"   # sticky owner out of the table
        if isinstance(e, TaskError) and isinstance(
                getattr(e, "cause", None), ReplicaUnavailableError):
            return "drain"           # owner shedding: engine draining
        return None

    def _consume(self, out: Dict[str, Any]) -> Optional[List[int]]:
        """Splice a reply into the journal by seq.  Returns the deduped
        fresh tokens, or None on a forward gap (lost destructive pop)."""
        toks = list(out.get("tokens") if out.get("tokens") is not None
                    else out.get("token") or ())
        if "seq" not in out:
            raise StreamFailedError(
                f"protocol violation from {self._name}: no seq in {out!r}")
        seq = out["seq"]
        if seq > len(self.journal):
            return None
        fresh = toks[len(self.journal) - seq:]
        self.journal.extend(fresh)
        if out.get("done"):
            self.done = True
        return fresh

    def _failover(self, reason: str) -> Dict[str, Any]:
        """Re-admit the session on a healthy replica via teacher-forced
        replay of the journal; returns the resume reply (which carries
        the next token, seq-stamped at the journal length)."""
        from ..core.config import GlobalConfig
        from ..core.runtime_metrics import (SERVE_FAILOVER_LATENCY,
                                            SERVE_SESSIONS_MIGRATED)
        from ..util import fault_injection as fi
        from ..util import tracing
        from ..util.backoff import ExponentialBackoff
        t0 = time.time()
        if fi.ACTIVE is not None:
            act = fi.ACTIVE.point("serve.session_failover", self._name)
            if act is not None:
                if act["action"] in ("delay", "latency"):
                    time.sleep(max(0.0, act["delay_s"]))
                else:
                    raise StreamFailedError(
                        f"chaos: injected session_failover failure for "
                        f"{self._name}")
        attempts = max(1, self._attempts or
                       GlobalConfig.serve_session_failover_attempts)
        timeout = self._timeout if self._timeout is not None else \
            GlobalConfig.serve_session_failover_timeout_s
        # attempts is a FLOOR, the timeout a wall-clock budget for fast
        # rejections: while a dead node's replacement replica boots,
        # every resume sheds instantly with the typed 503 — counting
        # those against a small attempt budget would give up seconds
        # before the replacement comes up
        deadline = time.monotonic() + max(0.0, timeout)
        bo = ExponentialBackoff(base=0.05, cap=2.0)
        payload = {"op": "resume",
                   "prompt": list(self._payload.get("prompt") or ()),
                   "generated": list(self.journal)}
        payload.update({k: v for k, v in self._payload.items()
                        if k not in _NON_REPLAY_KEYS})
        last_err: Optional[BaseException] = None
        tries = 0
        while True:
            tries += 1
            try:
                out = self._call(payload, None)
            except Exception as e:   # noqa: BLE001
                last_err = e
                out = None
            if isinstance(out, dict) and "error" not in out \
                    and out.get("sid") is not None:
                self.sid = out["sid"]
                self._sticky = self._owner_of(self.sid)
                self.failovers += 1
                now = time.time()
                SERVE_SESSIONS_MIGRATED.inc(tags={"reason": reason})
                SERVE_FAILOVER_LATENCY.observe(
                    now - t0, {"deployment": self._name})
                tracing.record_span(
                    f"serve_session_failover::{self._name}", "serve",
                    t0, now, reason=reason, deployment=self._name,
                    resumed_at=len(self.journal), new_sid=str(self.sid))
                return out
            if out is not None:
                last_err = StreamFailedError(f"resume rejected: {out!r}")
            if tries >= attempts and time.monotonic() >= deadline:
                raise StreamFailedError(
                    f"decode-stream failover exhausted for {self._name} "
                    f"(reason={reason}, resumed_at={len(self.journal)}, "
                    f"tries={tries}): {last_err!r}") from last_err
            time.sleep(bo.next_delay())
