"""Message-passing layer: length-framed msgpack RPC over asyncio TCP.

Plays the role of the reference's gRPC layer (/root/reference/src/ray/rpc/
grpc_server.h, grpc_client.h) for the control plane.  The protocol is
symmetric: either end of a connection can issue calls, which is how
long-poll-free pubsub pushes work (the controller calls back into
subscribers, cf. /root/reference/src/ray/pubsub/publisher.h's batched
long-poll design — TCP lets us push directly instead).

Frame layout: 4-byte little-endian length, then msgpack ``[seq, kind, method,
data]`` where kind is REQUEST/REPLY/ERROR/NOTIFY.  ``data`` is
msgpack-serializable (callers pre-pickle rich Python values).
"""

from __future__ import annotations

import asyncio
import struct
import threading
import time
import traceback
from collections import deque as _deque
from typing import Any, Awaitable, Callable, Dict, Optional, Tuple

import msgpack

REQUEST, REPLY, ERROR, NOTIFY = 0, 1, 2, 3
_LEN = struct.Struct("<I")
MAX_FRAME = 1 << 31

# ------------------------------------------------------ dispatch attribution
# Per-process table of where RPC-handler time goes (reference: the
# per-method gRPC server stats of grpc_server.h + event_stats.cc).  Lives
# HERE because this module hosts every server's dispatch loop and sits
# below ray_tpu.util/metrics in the import graph — runtime_metrics folds
# the table into Prometheus at scrape time, and the controller/nodelet
# `rpc_attribution` handlers serve it raw.  Cost per dispatch: two
# perf_counter reads and one dict update under a plain dict (asyncio
# single-threaded per loop; cross-thread readers tolerate torn snapshots).

#: latency histogram bucket upper bounds (seconds) for the attribution
#: table — fixed so p50/p99 estimates survive serialization
DISPATCH_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

_dispatch_stats: Dict[str, dict] = {}


def _note_dispatch(method: str, dur_s: float, bytes_in: int,
                   bytes_out: int, error: bool) -> None:
    st = _dispatch_stats.get(method)
    if st is None:
        st = _dispatch_stats[method] = {
            "count": 0, "errors": 0, "total_s": 0.0, "max_s": 0.0,
            "bytes_in": 0, "bytes_out": 0,
            "buckets": [0] * (len(DISPATCH_BUCKETS) + 1)}
    st["count"] += 1
    if error:
        st["errors"] += 1
    st["total_s"] += dur_s
    if dur_s > st["max_s"]:
        st["max_s"] = dur_s
    st["bytes_in"] += bytes_in
    st["bytes_out"] += bytes_out
    lo = 0
    for i, b in enumerate(DISPATCH_BUCKETS):
        if dur_s <= b:
            lo = i
            break
    else:
        lo = len(DISPATCH_BUCKETS)
    st["buckets"][lo] += 1


def _bucket_quantile(buckets, q: float) -> float:
    """Estimate a latency quantile from the fixed bucket counts (upper
    bound of the bucket holding the q-th sample; +Inf bucket reports the
    last finite bound)."""
    total = sum(buckets)
    if not total:
        return 0.0
    want = q * total
    seen = 0
    for i, c in enumerate(buckets):
        seen += c
        if seen >= want:
            return DISPATCH_BUCKETS[min(i, len(DISPATCH_BUCKETS) - 1)]
    return DISPATCH_BUCKETS[-1]


def dispatch_stats() -> Dict[str, dict]:
    """Snapshot of this process's per-op dispatch table (value copies:
    safe to serialize while dispatches keep landing)."""
    return {m: dict(st, buckets=list(st["buckets"]))
            for m, st in _dispatch_stats.items()}


def attribution_rows(stats: Optional[Dict[str, dict]] = None) -> list:
    """The dispatch table as rows sorted by total handler time (the
    'where does control-plane time go' view), with derived avg/p50/p99."""
    stats = dispatch_stats() if stats is None else stats
    rows = []
    for op, st in stats.items():
        n = st["count"] or 1
        rows.append({
            "op": op, "count": st["count"], "errors": st["errors"],
            "total_s": round(st["total_s"], 6),
            "avg_ms": round(st["total_s"] / n * 1e3, 3),
            "p50_ms": round(_bucket_quantile(st["buckets"], 0.5) * 1e3, 3),
            "p99_ms": round(_bucket_quantile(st["buckets"], 0.99) * 1e3, 3),
            "max_ms": round(st["max_s"] * 1e3, 3),
            "bytes_in": st["bytes_in"], "bytes_out": st["bytes_out"],
        })
    rows.sort(key=lambda r: -r["total_s"])
    return rows


def reset_dispatch_stats() -> None:
    _dispatch_stats.clear()


# ------------------------------------------------------- priority RPC lanes
# Every inbound REQUEST/NOTIFY is classified into one of three lanes and
# dispatched from per-connection lane queues in strict priority order, so
# a controller digesting a bulk kv_put flood still STARTS heartbeat
# handlers immediately (the overload-resilience half of the reference's
# control-store design — arXiv:1712.05889 §4.2; replicated Redis absorbs
# this for the reference, our single asyncio loop must self-protect).
# REPLY/ERROR frames never queue: a client's pending-call futures resolve
# straight from the read loop regardless of inbound request backlog.

#: dispatch priority order (index == priority, 0 highest)
LANES = ("liveness", "control", "bulk")

#: ops whose timeliness IS cluster health: heartbeats, liveness probes,
#: HA leases, flow-control credit grants.  Never queued behind anything.
#: NB: "ping" stays in the control lane — sync_borrows uses its reply as
#: a FIFO fence behind ref_inc notifies, which only holds same-lane.
_LIVENESS_OPS = frozenset({
    "heartbeat", "ha_lease", "ha_status", "peer_probe",
    "probe_peer_now", "credit_request", "drain_status"})

#: high-volume payload/telemetry ops: blob ships, trace/metrics pushes,
#: pubsub fan-in, observability pulls.  Everything else (leases, actor
#: FSM, WAL-backed mutations, ...) defaults to the "control" lane.
_BULK_OPS = frozenset({
    "kv_put", "trace_append", "trace_dump", "publish", "task_state",
    "task_state_batch",
    "serve_metrics", "metrics_text", "metrics_history", "task_spans",
    "tail_log", "node_stats", "stats", "chaos_injected", "report_event",
    "pub_batch"})


def lane_for(method: str) -> str:
    """Lane classification for an RPC op (pubsub pushes count as bulk)."""
    if method in _LIVENESS_OPS:
        return "liveness"
    if method in _BULK_OPS or method.startswith("pub:"):
        return "bulk"
    return "control"


def _new_lane_stats() -> Dict[str, dict]:
    return {lane: {"depth": 0, "queued_bytes": 0, "dispatched": 0,
                   "queued_s": 0.0, "queued_s_max": 0.0}
            for lane in LANES}


#: per-process lane table (all connections fold in here — the per-lane
#: depth/latency gauges the attribution plumbing and the overload
#: watermark evaluator read)
_lane_stats: Dict[str, dict] = _new_lane_stats()


def lane_stats() -> Dict[str, dict]:
    """Snapshot of this process's per-lane queue table (value copies)."""
    return {lane: dict(st) for lane, st in _lane_stats.items()}


def _bulk_cap() -> int:
    """In-flight bulk-dispatch bound per connection (config-read at use:
    this module sits below core.config in the import graph)."""
    try:
        from .config import GlobalConfig as _cfg
        return max(1, _cfg.rpc_bulk_inflight)
    except Exception:
        return 64


def reset_lane_stats() -> None:
    # mutate in place: live connections may still decrement depth for
    # items they enqueued before the reset
    for st in _lane_stats.values():
        st.update(depth=0, queued_bytes=0, dispatched=0,
                  queued_s=0.0, queued_s_max=0.0)

# Armed fault-injection plan (util/fault_injection.py sets/clears this —
# this module sits below ray_tpu.util in the import graph and cannot
# import it at module scope).  None == chaos disabled: hot paths pay one
# module-global None check and nothing else.
_chaos = None


def _jitter() -> float:
    """Full-jitter multiplier for Retry-After sleeps."""
    import random
    return random.uniform(0.5, 1.5)


class RpcError(Exception):
    pass


class ConnectionLost(RpcError):
    pass


def _pack(seq: int, kind: int, method: str, data: Any) -> bytes:
    payload = msgpack.packb([seq, kind, method, data], use_bin_type=True)
    return _LEN.pack(len(payload)) + payload


class Connection:
    """One bidirectional peer connection."""

    def __init__(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter,
                 handlers: Dict[str, Callable[["Connection", Any], Awaitable[Any]]]):
        self.reader = reader
        self.writer = writer
        self.handlers = handlers
        self._seq = 0
        self._pending: Dict[int, asyncio.Future] = {}
        self._closed = False
        self._send_lock = asyncio.Lock()
        self.on_close: Optional[Callable[["Connection"], None]] = None
        self.peer_info: Dict[str, Any] = {}  # set by handshake handlers
        # "host:port" this end DIALED (empty on accepted conns) — the
        # chaos layer's peer label, so a fault plan can sever the A→B
        # direction of a link while B→A keeps working
        self.peer_label: str = ""
        # Priority lane queues: the read loop ENQUEUES inbound
        # REQUEST/NOTIFY frames, the pump STARTS their dispatches in
        # lane-priority order (handlers still run concurrently — many
        # are long-polls).  Bulk dispatches are additionally bounded
        # in-flight so a blob flood cannot swamp the loop.
        self._lanes: Dict[str, "deque"] = {ln: _deque() for ln in LANES}
        self._lane_wake = asyncio.Event()
        self._lane_holds: Dict[str, float] = {}   # lane -> perf_counter until
        self._bulk_inflight = 0
        self._pump_task = asyncio.ensure_future(self._lane_pump())
        self._task = asyncio.ensure_future(self._read_loop())

    @property
    def closed(self):
        return self._closed

    async def _send(self, frame: bytes):
        # A peer that dies mid-send surfaces as a raw OS error from the
        # transport (ConnectionResetError/BrokenPipeError).  Callers all
        # handle RpcError — an untranslated escape here kills whole
        # supervision loops (a chaos-crashed worker took the driver's
        # _lease_loop down with it, losing the task retry).
        try:
            async with self._send_lock:
                self.writer.write(frame)
                await self.writer.drain()
        except (ConnectionResetError, BrokenPipeError, OSError) as e:
            raise ConnectionLost(f"send failed: {e}") from e

    async def call(self, method: str, data: Any = None, timeout: Optional[float] = None) -> Any:
        if self._closed:
            raise ConnectionLost(f"connection closed (calling {method})")
        self._seq += 1
        seq = self._seq
        fut = asyncio.get_event_loop().create_future()
        self._pending[seq] = fut
        if _chaos is not None and await self._chaos_send(method):
            # frame "lost on the wire": the request hangs to its timeout
            # exactly as a real drop would
            pass
        else:
            await self._send(_pack(seq, REQUEST, method, data))
        try:
            return await asyncio.wait_for(fut, timeout)
        finally:
            self._pending.pop(seq, None)

    async def _chaos_send(self, method: str) -> bool:
        """Apply an armed ``rpc.send`` rule; True == drop the frame."""
        act = await _chaos.async_point("rpc.send", method,
                                       peer=self.peer_label)
        if act is None:
            return False
        if act["action"] == "sever":
            await self._shutdown()
            raise ConnectionLost(f"chaos: connection severed ({method})")
        if act["action"] == "error":
            raise RpcError(f"chaos: injected send error ({method})")
        return act["action"] == "drop"

    async def notify(self, method: str, data: Any = None):
        if self._closed:
            raise ConnectionLost(f"connection closed (notifying {method})")
        if _chaos is not None and await self._chaos_send(method):
            return
        await self._send(_pack(0, NOTIFY, method, data))

    async def _read_loop(self):
        try:
            while True:
                head = await self.reader.readexactly(4)
                (length,) = _LEN.unpack(head)
                if length > MAX_FRAME:
                    raise RpcError(f"frame too large: {length}")
                payload = await self.reader.readexactly(length)
                seq, kind, method, data = msgpack.unpackb(payload, raw=False)
                if kind in (REQUEST, NOTIFY):
                    lane = lane_for(method)
                    st = _lane_stats[lane]
                    st["depth"] += 1
                    st["queued_bytes"] += length
                    self._lanes[lane].append(
                        (seq if kind == REQUEST else 0, method, data,
                         length, time.perf_counter()))
                    self._lane_wake.set()
                elif kind in (REPLY, ERROR):
                    fut = self._pending.pop(seq, None)
                    if fut is not None and not fut.done():
                        if kind == REPLY:
                            fut.set_result(data)
                        else:
                            fut.set_exception(RpcError(data))
        except (asyncio.IncompleteReadError, ConnectionResetError, BrokenPipeError, OSError):
            pass
        except asyncio.CancelledError:
            pass
        finally:
            await self._shutdown()

    def _pop_next(self):
        """Highest-priority dispatchable item, or (None, None).

        A lane is skipped while chaos holds it (``rpc.lane_starve``) or,
        for bulk, while the in-flight dispatch cap is reached — lower
        lanes keep flowing, which is the whole point."""
        now = time.perf_counter()
        for lane in LANES:
            q = self._lanes[lane]
            if not q:
                continue
            if lane == "bulk" and self._bulk_inflight >= _bulk_cap():
                continue
            hold = self._lane_holds.get(lane)
            if hold is not None:
                if hold > now:
                    continue
                # hold served: admit ONE item before re-evaluating chaos,
                # so a persistent latency rule THROTTLES the lane (one
                # dispatch per delay_s) instead of starving it outright
                del self._lane_holds[lane]
            elif _chaos is not None:
                act = _chaos.point("rpc.lane_starve", lane,
                                   peer=self.peer_label)
                if act is not None and act.get("delay_s"):
                    self._lane_holds[lane] = now + act["delay_s"]
                    continue
            return q.popleft(), lane
        return None, None

    def _hold_timeout(self) -> Optional[float]:
        """Seconds until the earliest chaos lane-hold on a NON-EMPTY
        lane expires (None: nothing time-gated, wait for the event)."""
        now = time.perf_counter()
        pending = [until - now for lane, until in self._lane_holds.items()
                   if until > now and self._lanes[lane]]
        return max(0.0, min(pending)) if pending else None

    async def _lane_pump(self):
        """Start queued dispatches in lane-priority order.  Dispatches
        themselves run as independent tasks (handlers long-poll); only
        the START order and the bulk in-flight bound are serialized
        here."""
        try:
            while True:
                item, lane = self._pop_next()
                if item is None:
                    self._lane_wake.clear()
                    item, lane = self._pop_next()  # re-check: lost-wakeup
                    if item is None:
                        timeout = self._hold_timeout()
                        try:
                            await asyncio.wait_for(self._lane_wake.wait(),
                                                   timeout)
                        except asyncio.TimeoutError:
                            pass
                        continue
                seq, method, data, length, t_enq = item
                st = _lane_stats[lane]
                st["depth"] -= 1
                st["queued_bytes"] -= length
                waited = time.perf_counter() - t_enq
                st["dispatched"] += 1
                st["queued_s"] += waited
                if waited > st["queued_s_max"]:
                    st["queued_s_max"] = waited
                fut = asyncio.ensure_future(
                    self._dispatch(seq, method, data, length))
                if lane == "bulk":
                    self._bulk_inflight += 1
                    fut.add_done_callback(self._bulk_done)
        except asyncio.CancelledError:
            pass

    def _bulk_done(self, _fut) -> None:
        self._bulk_inflight -= 1
        self._lane_wake.set()   # a bulk slot freed: re-check the queues

    async def _dispatch(self, seq: int, method: str, data: Any,
                        nbytes: int = 0):
        handler = self.handlers.get(method)
        t0 = time.perf_counter()
        bytes_out = 0
        error = False
        try:
            if handler is None:
                raise RpcError(f"no handler for method {method!r}")
            result = await handler(self, data)
            if seq:
                frame = _pack(seq, REPLY, method, result)
                bytes_out = len(frame)
                await self._send(frame)
        except Exception:
            error = True
            if seq:
                try:
                    await self._send(_pack(seq, ERROR, method, traceback.format_exc()))
                except Exception:
                    pass
        finally:
            _note_dispatch(method, time.perf_counter() - t0, nbytes,
                           bytes_out, error)

    async def _shutdown(self):
        if self._closed:
            return
        self._closed = True
        self._pump_task.cancel()
        # un-count still-queued items so the module lane table doesn't
        # leak depth/bytes from connections that died with a backlog
        for lane, q in self._lanes.items():
            st = _lane_stats[lane]
            while q:
                _s, _m, _d, length, _t = q.popleft()
                st["depth"] -= 1
                st["queued_bytes"] -= length
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(ConnectionLost("peer disconnected"))
        self._pending.clear()
        try:
            self.writer.close()
        except Exception:
            pass
        if self.on_close:
            try:
                self.on_close(self)
            except Exception:
                pass

    async def close(self):
        self._task.cancel()
        await self._shutdown()


class RpcServer:
    """Accepts connections; all connections share one handler table."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self.host = host
        self.port = port
        self.handlers: Dict[str, Callable] = {}
        self.connections: set[Connection] = set()
        self._server: Optional[asyncio.AbstractServer] = None

    def handler(self, name: str):
        def deco(fn):
            self.handlers[name] = fn
            return fn
        return deco

    def register(self, name: str, fn):
        self.handlers[name] = fn

    async def start(self):
        self._server = await asyncio.start_server(self._accept, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        return self

    async def _accept(self, reader, writer):
        conn = Connection(reader, writer, self.handlers)
        self.connections.add(conn)
        conn.on_close = self.connections.discard

    async def stop(self):
        if self._server:
            self._server.close()              # no new connections
        for conn in list(self.connections):
            await conn.close()
        if self._server:
            # Python 3.12 waits here for every accepted connection to be
            # closed, so they are closed first
            await self._server.wait_closed()


def parse_endpoints(addr) -> list:
    """``"h1:p1,h2:p2"`` (or a list of such / (host, port) pairs) →
    ``[(host, port), ...]``.  Controller addresses grew into lists with
    HA: the leader plus its hot standby(s)."""
    if isinstance(addr, (list, tuple)) and addr \
            and not isinstance(addr[0], str):
        return [(h, int(p)) for h, p in addr]
    parts = addr if isinstance(addr, (list, tuple)) else str(addr).split(",")
    out = []
    for part in parts:
        part = str(part).strip()
        if not part:
            continue
        host, port = part.rsplit(":", 1)
        out.append((host, int(port)))
    return out


async def connect_leader(endpoints, handlers=None, retries: int = 30,
                         probe_timeout: float = 3.0,
                         deadline_s: Optional[float] = None):
    """Dial the LEADER controller among ``endpoints``.

    Each round probes every endpoint with ``ha_status`` and follows
    leader/standby hints it returns (so a standby added after this
    process booted is still discovered).  Returns ``(conn, endpoint,
    status_dict)``.  A peer without an ``ha_status`` handler is treated
    as a leader (pre-HA controller, plain test server)."""
    from ..util.backoff import ExponentialBackoff
    from .config import GlobalConfig as _cfg
    eps = list(dict.fromkeys(parse_endpoints(endpoints)))
    bo = ExponentialBackoff(base=0.05,
                            cap=_cfg.rpc_connect_backoff_cap_s)
    deadline = None if deadline_s is None \
        else asyncio.get_event_loop().time() + deadline_s
    last = None
    for _attempt in range(max(1, retries)):
        for ep in list(eps):
            try:
                conn = await connect(*ep, handlers, retries=1)
            except (ConnectionLost, OSError) as e:
                last = e
                continue
            try:
                st = await conn.call("ha_status", {}, timeout=probe_timeout)
            except RpcError as e:
                if "no handler" in str(e):
                    return conn, ep, {}   # pre-HA peer: it IS the leader
                await conn.close()
                last = e
                continue
            except (asyncio.TimeoutError, OSError) as e:
                await conn.close()
                last = e
                continue
            if not isinstance(st, dict):
                return conn, ep, {}
            for hint in list(st.get("standbys") or []) \
                    + ([st.get("leader")] if st.get("leader") else []):
                try:
                    for e2 in parse_endpoints(hint):
                        if e2 not in eps:
                            eps.append(e2)
                except (ValueError, AttributeError):
                    pass
            if st.get("role", "leader") == "leader":
                return conn, ep, st
            await conn.close()
            last = ConnectionLost(f"{ep[0]}:{ep[1]} is {st.get('role')}")
        if deadline is not None \
                and asyncio.get_event_loop().time() > deadline:
            break
        await asyncio.sleep(bo.next_delay())
    raise ConnectionLost(
        f"no leader controller among {parse_endpoints(endpoints)}: {last}")


async def connect(host: str, port: int,
                  handlers: Optional[Dict[str, Callable]] = None,
                  retries: int = 1, retry_delay: float = 0.02) -> Connection:
    # Subscribers transparently accept coalesced event frames (the
    # publisher batches bursts — controller._flush_pubs).
    if handlers and "pub_batch" not in handlers \
            and any(k.startswith("pub:") for k in handlers):
        async def _pub_batch(conn, data, _h=handlers):
            for ch, ev in data.get("events", []):
                h = _h.get("pub:" + ch)
                if h is not None:
                    await h(conn, ev)
            # overflow at the publisher dropped this subscriber's oldest
            # events: tell it which channels need a snapshot resync
            rs = _h.get("pub:_resync")
            if rs is not None:
                for ch in data.get("resync", ()):
                    await rs(conn, ch)
            return True
        handlers = {**handlers, "pub_batch": _pub_batch}
    # Capped exponential backoff with FULL jitter between attempts: a
    # restarted controller comes back to staggered redials, not a
    # thundering herd of every nodelet/driver waking on the same fixed
    # 20 ms tick (utils/backoff.py; the reference's gcs_rpc_client
    # reconnect spreads the same way).
    from ..util.backoff import ExponentialBackoff
    from .config import GlobalConfig as _cfg
    bo = ExponentialBackoff(base=retry_delay,
                            cap=_cfg.rpc_connect_backoff_cap_s)
    last = None
    for attempt in range(max(1, retries)):
        if _chaos is not None:
            act = await _chaos.async_point("rpc.connect", f"{host}:{port}")
            if act is not None and act["action"] in ("error", "drop"):
                last = OSError("chaos: connect refused")
                await asyncio.sleep(bo.next_delay())
                continue
        try:
            reader, writer = await asyncio.open_connection(host, port)
            conn = Connection(reader, writer, handlers or {})
            conn.peer_label = f"{host}:{port}"
            return conn
        except OSError as e:
            last = e
            await asyncio.sleep(bo.next_delay())
    raise ConnectionLost(f"cannot connect to {host}:{port}: {last}")


class EventLoopThread:
    """A dedicated asyncio loop on a daemon thread.

    Drivers and workers are synchronous user code; all their networking runs
    here (the reference gets the same split from the C++ core worker's asio
    io_service running on its own thread).
    """

    def __init__(self, name: str = "ray-tpu-io"):
        self.loop = asyncio.new_event_loop()
        self._lag_ewma = 0.0   # seconds; see loop_lag_monitor
        self._lag_max = 0.0
        self._thread = threading.Thread(target=self._run, name=name, daemon=True)
        self._thread.start()

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.create_task(loop_lag_monitor(self))
        self.loop.run_forever()

    def lag_stats(self) -> Dict[str, float]:
        """Event-loop scheduling lag (reference: asio event_stats,
        src/ray/common/event_stats.cc — how late handlers run vs when they
        were ready)."""
        return {"ewma_ms": self._lag_ewma * 1000.0,
                "max_ms": self._lag_max * 1000.0}

    def run(self, coro, timeout: Optional[float] = None):
        fut = asyncio.run_coroutine_threadsafe(coro, self.loop)
        return fut.result(timeout)

    def spawn(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop)

    def stop(self):
        # Cancel and DRAIN pending tasks (read loops, lag monitor, lease
        # loops) before stopping: bare loop.stop() leaves them pending
        # and every driver exit spews "Task was destroyed but it is
        # pending!" warnings from their GC.
        async def _drain():
            tasks = [t for t in asyncio.all_tasks()
                     if t is not asyncio.current_task()]
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

        try:
            asyncio.run_coroutine_threadsafe(
                _drain(), self.loop).result(timeout=1.0)
        except Exception:
            pass  # a stuck task must not block process exit
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=2)


async def loop_lag_monitor(owner, interval: float = 0.25):
    """Measure how late the loop wakes from a timed sleep — a saturated or
    blocked loop (sync work on the async thread) shows up as lag.  Works
    for EventLoopThread and for server processes (owner just needs
    `_lag_ewma`/`_lag_max` attributes)."""
    import time as _time
    while True:
        t0 = _time.monotonic()
        await asyncio.sleep(interval)
        lag = max(0.0, _time.monotonic() - t0 - interval)
        owner._lag_ewma = 0.9 * owner._lag_ewma + 0.1 * lag
        owner._lag_max = max(owner._lag_max, lag)


class BlockingClient:
    """Synchronous facade over a Connection living on an EventLoopThread.

    When constructed via ``connect`` it remembers its endpoint and redials
    on entry if the connection has dropped — the client half of controller
    fault tolerance (a restarted controller resumes at the same address;
    reference: GCS clients retry through gcs_rpc_client.h).

    Constructed via ``connect_ha`` it additionally holds the controller
    ADDRESS LIST (leader + hot standbys): a failed call transparently
    replays against whichever endpoint currently leads (epoch-stamped, so
    a deposed leader the client stumbles onto fences itself), and
    ``_not_leader`` replies from a standby/fenced controller reroute
    instead of surfacing."""

    def __init__(self, loop_thread: EventLoopThread, conn: Connection,
                 endpoint: Optional[Tuple[str, int]] = None, handlers=None):
        self._lt = loop_thread
        self.conn = conn
        self._endpoint = endpoint
        self._handlers = handlers
        self._redial_lock = threading.Lock()
        self._ha = False
        self._endpoints: list = [endpoint] if endpoint else []
        self._epoch = 0
        self._fail_fast = False
        #: called with this client after a successful HA redial — owners
        #: re-establish connection-scoped state (pubsub subscriptions)
        self.on_reconnect = None

    @classmethod
    def connect(cls, loop_thread: EventLoopThread, host: str, port: int,
                handlers=None, retries: int = 50):
        conn = loop_thread.run(connect(host, port, handlers, retries=retries))
        return cls(loop_thread, conn, endpoint=(host, port), handlers=handlers)

    @classmethod
    def connect_ha(cls, loop_thread: EventLoopThread, addr,
                   handlers=None, retries: int = 50):
        """Connect to the leader among a controller address list
        (``"h1:p1,h2:p2"``); the client follows leadership from then on."""
        eps = parse_endpoints(addr)
        conn, ep, st = loop_thread.run(
            connect_leader(eps, handlers, retries=retries))
        bc = cls(loop_thread, conn, endpoint=ep, handlers=handlers)
        bc._ha = True
        bc._endpoints = eps
        bc._absorb_status(st)
        return bc

    def _absorb_status(self, st: dict):
        if not isinstance(st, dict):
            return
        self._epoch = max(self._epoch, int(st.get("epoch", 0) or 0))
        for hint in list(st.get("standbys") or []):
            try:
                for ep in parse_endpoints(hint):
                    if ep not in self._endpoints:
                        self._endpoints.append(ep)
            except (ValueError, AttributeError):
                pass

    def fail_fast(self):
        """Disable failover retries (shutdown path: a dead controller
        must not cost the full failover budget on the way out)."""
        self._fail_fast = True

    def endpoints(self):
        return list(self._endpoints)

    async def aconn(self) -> Connection:
        """Current connection, redialed ON THE LOOP when dead — for the
        owner's async internals (actor-wait polls, pubsub re-subscribes)
        that share this client.  Never touches the sync redial lock: the
        sync path blocks a caller thread on `_lt.run(...)` INTO this
        loop, so acquiring its lock here could deadlock the loop."""
        if not self.conn.closed:
            return self.conn
        if not self._ha or self._fail_fast:
            raise ConnectionLost("controller connection closed")
        conn, ep, st = await connect_leader(
            self._endpoints, self._handlers, retries=5, deadline_s=5.0)
        if self.conn.closed:
            self.conn, self._endpoint = conn, ep
            self._absorb_status(st)
            cb = self.on_reconnect
            if cb is not None:
                try:
                    cb(self)
                except Exception:
                    pass
        else:
            # lost a redial race against the sync path: keep the winner
            await conn.close()
        return self.conn

    def _ensure_conn(self, reprobe: bool = False):
        if not reprobe and (not self.conn.closed or self._endpoint is None):
            return
        cb = None
        with self._redial_lock:
            if self.conn.closed or reprobe:
                if self._ha and not self._fail_fast:
                    from .config import GlobalConfig as _cfg
                    old = self.conn
                    conn, ep, st = self._lt.run(connect_leader(
                        self._endpoints, self._handlers, retries=1000,
                        deadline_s=_cfg.ha_client_failover_timeout_s))
                    self.conn, self._endpoint = conn, ep
                    self._absorb_status(st)
                    if not old.closed and old is not conn:
                        try:
                            self._lt.run(old.close())
                        except Exception:
                            pass
                    cb = self.on_reconnect
                else:
                    self.conn = self._lt.run(connect(
                        *self._endpoint, self._handlers, retries=10))
                    cb = self.on_reconnect
        if cb is not None:
            try:
                cb(self)
            except Exception:
                pass

    def call(self, method: str, data: Any = None, timeout: Optional[float] = None):
        if not self._ha:
            self._ensure_conn()
            return self._lt.run(self.conn.call(method, data, timeout=timeout),
                                timeout=None if timeout is None else timeout + 5)
        from .config import GlobalConfig as _cfg
        import time as _time
        deadline = _time.monotonic() + _cfg.ha_client_failover_timeout_s
        from ..util.backoff import ExponentialBackoff
        bo = ExponentialBackoff(base=0.05, cap=0.5)
        reprobe = False
        while True:
            try:
                self._ensure_conn(reprobe=reprobe)
                reprobe = False
                payload = data
                if type(data) is dict and "_ha_epoch" not in data:
                    payload = {**data, "_ha_epoch": self._epoch}
                r = self._lt.run(
                    self.conn.call(method, payload, timeout=timeout),
                    timeout=None if timeout is None else timeout + 5)
            except (ConnectionLost, OSError) as e:
                # leader died mid-call: replay against the new leader
                if self._fail_fast or _time.monotonic() > deadline:
                    raise
                _time.sleep(bo.next_delay())
                continue
            if type(r) is dict and r.get("_overload"):
                # typed pushback: the controller shed this bulk op under
                # overload — honor Retry-After with full jitter (same
                # spread-the-herd rationale as the reconnect backoff)
                ra = float(r.get("retry_after_s") or 1.0)
                remaining = deadline - _time.monotonic()
                if self._fail_fast or remaining <= 0:
                    from ..exceptions import ControlPlaneOverloadError
                    raise ControlPlaneOverloadError(method, ra)
                _time.sleep(min(remaining, ra * _jitter() + bo.next_delay()))
                continue
            if type(r) is dict and r.get("_not_leader"):
                self._epoch = max(self._epoch, int(r.get("epoch", 0) or 0))
                hint = r.get("leader")
                if hint:
                    try:
                        for ep in parse_endpoints(hint):
                            if ep not in self._endpoints:
                                self._endpoints.append(ep)
                    except (ValueError, AttributeError):
                        pass
                if self._fail_fast or _time.monotonic() > deadline:
                    raise RpcError(
                        f"controller at {self._endpoint} is not the "
                        f"leader (epoch {self._epoch}) and no leader "
                        f"emerged in time (calling {method})")
                reprobe = True
                _time.sleep(bo.next_delay())
                continue
            return r

    def notify(self, method: str, data: Any = None):
        self._ensure_conn()
        try:
            return self._lt.run(self.conn.notify(method, data))
        except (ConnectionLost, OSError):
            if not self._ha or self._fail_fast:
                raise
            self._ensure_conn()
            return self._lt.run(self.conn.notify(method, data))

    def close(self):
        try:
            self._lt.run(self.conn.close())
        except Exception:
            pass
