"""Cluster-wide tracing: one span primitive, two sinks (reference:
core_worker/profiling.cc profile events -> GCS, surfaced by `ray timeline`
/ python/ray/_private/state.py:414 chrome_tracing_dump).

``span`` / ``record_span`` are the one way any runtime process (driver,
controller, nodelet, worker) marks an interval:

* **The ring** — a bounded per-process buffer of Chrome-trace events on
  the wall clock, so spans of different processes line up.  It keeps a
  bound PER CATEGORY: a category that floods (one span per served
  request) evicts only its own oldest spans, never the three ``setup``
  spans of the process.  A per-process flush loop ships what was
  recorded since the last flush (``flush_batch`` -> the controller's
  ``trace_append``; cost in proportion to the new spans, not to the ring),
  ``state.timeline()`` merges every process's spans into one Chrome-trace
  JSON, and at exit each process writes its ring to
  ``<session_dir>/spans/<kind>-<pid>.json`` so a finished session keeps
  its timeline (``ray-tpu timeline --session-dir``).  Beside it go the
  **op maps** of the programs the process compiled
  (``<session_dir>/programs/<kind>-<pid>.<program>.json``, from
  `util/device_profile.py`'s compile ledger: instruction name ->
  ``op_name`` path, what places a profiler trace's device ops in the
  model), each marked on the timeline by one ``program:compiled`` span.

* **The profiler's host plane** — where JAX is already imported in the
  process, ``span`` also enters ``jax.profiler.TraceAnnotation(name)``:
  the span then lands in a `jax.profiler` trace on the device lines'
  clock.  Names meant for that plane are ``<layer>:<phase>``, lower case.
  This module never imports JAX itself.

A span that repeats every iteration of a hot loop passes ``into=(dict,
key)``: its seconds are added to that accumulator and it goes to the
annotation only, never to the ring; with ``cpu=True`` the thread's own CPU
seconds of the interval go to ``dict[key + "_cpu"]`` beside them (wall less
CPU: the thread was runnable and did not run, or slept).

**The host watch** — one a process, started and stopped with the flush
loop's claim (`claim_flusher` / `release_flusher`): a ``gc.callbacks`` hook
(``host:gc``: every collection's seconds, a ring span and a profiler
annotation for those of `GC_SPAN_FLOOR_S` or more) and a thread that sleeps
`WATCH_TICK_S` and records ``host:late_wakeup`` when it wakes
`LATE_WAKEUP_S` or more late (the interpreter was held, or the host did not
run the process).  `host_totals` has their cumulative sums.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from ..core.config import GlobalConfig

#: a ring holds at most this many categories; later ones share "other"
MAX_CATEGORIES = 16
#: a flush the controller did not take is followed by a re-ship of the
#: whole ring; flush loops wait this long before it
RESHIP_PAUSE_S = 1.0
#: a collection shorter than this is counted and leaves no ring span
GC_SPAN_FLOOR_S = 1e-3
#: the host watch's sleep, and how late a wake-up is before it is recorded
#: (a replica's full collections at a window's start take 0.09 s)
WATCH_TICK_S = 0.02
LATE_WAKEUP_S = 0.05

_PLAIN = (str, int, float, bool)


class SpanRing:
    """Chrome-trace events by category, each category its own bounded
    deque.  The process's ring and the controller's copy of it are the
    same structure, so both keep the same spans."""

    def __init__(self, per_category: Optional[int] = None):
        self.per_category = per_category or max(
            16, GlobalConfig.trace_buffer_size // 4)
        self._cats: Dict[str, deque] = {}

    def add(self, ev: dict) -> None:
        cat = ev.get("cat") or "task"
        ring = self._cats.get(cat)
        if ring is None:
            if len(self._cats) >= MAX_CATEGORIES:
                cat = "other"
            ring = self._cats.setdefault(
                cat, deque(maxlen=self.per_category))
        ring.append(ev)

    def extend(self, events: Iterable[dict]) -> None:
        for ev in events:
            self.add(ev)

    def events(self) -> List[dict]:
        out = [ev for ring in self._cats.values() for ev in ring]
        out.sort(key=lambda e: e.get("ts", 0))
        return out

    def __len__(self) -> int:
        return sum(len(ring) for ring in self._cats.values())


_span_lock = threading.Lock()
_ring: Optional[SpanRing] = None
_pending: List[dict] = []     # recorded since the last flush
_reship = False               # the controller lost our history
_recorded = 0                 # spans ever recorded here
_filed = -1                   # `_recorded` when the span file was written
_proc = {"kind": "proc", "node": ""}
_programs: Dict[str, List[dict]] = {}   # program -> its op maps, one a shape
_unmapped: List[tuple] = []             # (program, make, t0, seconds) owed
_flusher_claimed = False
_host = {"gc_s": 0.0, "gc_collections": 0,
         "late_wakeup_s": 0.0, "late_wakeups": 0}
_host_owed: deque = deque()   # collections the ring has yet to be given
_drain_lock = threading.Lock()          # one drainer of it at a time
_gc_open: Optional[tuple] = None        # (perf_counter, wall, annotation)
_watch: Optional[Tuple[threading.Thread, threading.Event]] = None


def configure(kind: str, node_id: str = "") -> None:
    """Set this process's identity for span attribution (called once by
    the driver core, worker runtime, nodelet, and controller)."""
    _proc["kind"] = kind
    _proc["node"] = (node_id or "")[:8]


def claim_flusher() -> bool:
    """First caller owns the flush loop for this process (a worker
    process hosts both a WorkerRuntime and a lazy CoreClient; only one
    may flush or they'd race on the pending batch)."""
    global _flusher_claimed
    with _span_lock:
        if _flusher_claimed:
            return False
        _flusher_claimed = True
    _start_host_watch()
    return True


def release_flusher() -> None:
    """Claimant is shutting down (driver disconnect): let the NEXT
    runtime in this process own the flush loop again.  Without this, a
    process doing init() -> shutdown() -> init() (every test after the
    first in a pytest invocation) silently loses its span flusher and
    the second cluster's timeline never sees driver spans."""
    global _flusher_claimed
    with _span_lock:
        _flusher_claimed = False
    _stop_host_watch()


# ----------------------------------------------------------- the host watch

def host_totals() -> Dict[str, float]:
    """Cumulative, of this process since its first claim: seconds and count
    of garbage collections, and of the watch thread's late wake-ups."""
    return dict(_host)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """`gc.callbacks` hook.  It runs on whichever thread's allocation
    started the collection, possibly INSIDE `record_span` under
    `_span_lock`: it takes no lock and records no span itself, the
    finished collection waits in `_host_owed` for `_drain_host`."""
    global _gc_open
    if phase == "start":
        ann = _annotation("host:gc")
        if ann is not None:
            ann.__enter__()
        _gc_open = (time.perf_counter(), time.time(), ann)
        return
    if _gc_open is None:        # hooked while a collection was under way
        return
    (t0, wall, ann), _gc_open = _gc_open, None
    if ann is not None:
        ann.__exit__(None, None, None)
    took = time.perf_counter() - t0
    _host["gc_s"] += took
    _host["gc_collections"] += 1
    if took >= GC_SPAN_FLOOR_S:
        _host_owed.append((wall, wall + took, info.get("generation"),
                           info.get("collected")))


def _drain_host() -> None:
    """The collections `_on_gc` left, into the ring as ``host:gc`` spans
    (the watch thread every tick; every flush and the span file too, so a
    collection of a process's last second is kept).  One drainer at a time:
    a collection the watch thread has taken and not yet recorded (it waits
    for `_span_lock`) is in neither place, and a flush that found nothing
    owed would ship a batch without it."""
    with _drain_lock:
        while _host_owed:
            t0, t1, generation, collected = _host_owed.popleft()
            record_span("host:gc", "host", t0, t1, generation=generation,
                        collected=collected)


def _watch_loop(stop: threading.Event) -> None:
    while True:
        t = time.perf_counter()
        stopped = stop.wait(WATCH_TICK_S)
        late = time.perf_counter() - t - WATCH_TICK_S
        if late >= LATE_WAKEUP_S and not stopped:
            _host["late_wakeup_s"] += late
            _host["late_wakeups"] += 1
            now = time.time()
            record_span("host:late_wakeup", "host", now - late, now,
                        late_ms=round(1e3 * late, 3))
        _drain_host()
        if stopped:
            return


def _start_host_watch() -> None:
    global _watch
    if _watch is not None or not GlobalConfig.trace_enabled:
        return
    stop = threading.Event()
    thread = threading.Thread(target=_watch_loop, args=(stop,),
                              name="rt-host-watch", daemon=True)
    _watch = (thread, stop)
    gc.callbacks.append(_on_gc)
    thread.start()


def _stop_host_watch() -> None:
    global _watch
    if _watch is None:
        return
    (thread, stop), _watch = _watch, None
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    stop.set()
    thread.join(timeout=1.0)


def _buffer() -> SpanRing:
    global _ring
    if _ring is None:
        _ring = SpanRing()
    return _ring


def proc_label() -> str:
    node = _proc["node"]
    return f"{_proc['kind']}@{node}" if node else _proc["kind"]


def proc_key() -> str:
    return f"{_proc['kind']}:{_proc['node']}:{os.getpid()}"


def record_span(name: str, cat: str, start_s: float, end_s: float,
                **args: Any) -> None:
    """Record one span in the ring (wall-clock seconds in, Chrome µs
    out).  Argument values other than plain scalars are kept as text."""
    if not GlobalConfig.trace_enabled:
        return
    ev = {
        "name": name, "cat": cat, "ph": "X",
        "ts": start_s * 1e6, "dur": max(0.0, end_s - start_s) * 1e6,
        "pid": proc_label(), "tid": str(os.getpid()),
        "args": {k: (v if type(v) in _PLAIN else str(v))
                 for k, v in args.items() if v},
    }
    global _recorded, _reship
    with _span_lock:
        ring = _buffer()
        ring.add(ev)
        _recorded += 1
        _pending.append(ev)
        if len(_pending) > ring.per_category * MAX_CATEGORIES:
            # nobody flushes here (or the controller is away): the ring
            # is the bound, the next flush ships it whole
            _pending.clear()
            _reship = True


def _annotation(name: str):
    """`jax.profiler.TraceAnnotation(name)` where JAX is already in the
    process, else None.  Costs a fraction of a microsecond while no
    profiler trace is being taken."""
    prof = sys.modules.get("jax.profiler")
    return prof.TraceAnnotation(name) if prof is not None else None


class span:
    """One interval, as a context manager: a `jax.profiler` host
    annotation where JAX is loaded, and either a ring span (wall clock;
    the default) or, with ``into=(accumulator, key)``, seconds added to
    ``accumulator[key]`` and nothing in the ring; ``cpu=True`` then adds
    the calling thread's own CPU seconds (`time.thread_time`) to
    ``accumulator[key + "_cpu"]``."""

    __slots__ = ("name", "cat", "args", "into", "cpu", "start", "_cpu0",
                 "_ann")

    def __init__(self, name: str, cat: str = "task",
                 into: Optional[Tuple[Dict[str, float], str]] = None,
                 cpu: bool = False, **args: Any):
        self.name = name
        self.cat = cat
        self.args = args
        self.into = into
        self.cpu = cpu

    def __enter__(self):
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.start = time.perf_counter() if self.into is not None \
            else time.time()
        if self.cpu:
            self._cpu0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        if self.into is not None:
            acc, key = self.into
            if self.cpu:    # read INSIDE the wall interval: never above it
                acc[key + "_cpu"] = acc.get(key + "_cpu", 0.0) \
                    + time.thread_time() - self._cpu0
            acc[key] = acc.get(key, 0.0) + time.perf_counter() - self.start
        else:
            record_span(self.name, self.cat, self.start, time.time(),
                        **self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)


def span_events() -> List[dict]:
    """Snapshot of this process's ring, oldest first."""
    with _span_lock:
        return _buffer().events()


def flush_batch() -> Optional[dict]:
    """The request of the next ``trace_append`` call to the controller:
    the spans recorded since the last flush, or the whole ring with
    ``reset`` after :func:`mark_dirty`; None when there is nothing to
    ship.  A caller whose RPC fails calls :func:`mark_dirty`."""
    global _pending, _reship
    _drain_host()
    with _span_lock:
        if _reship:
            spans, reset = _buffer().events(), True
        elif _pending:
            spans, reset = _pending, False
        else:
            return None
        _pending, _reship = [], False
    return {"key": proc_key(), "spans": spans, "reset": reset}


async def flush_sent(call) -> None:
    """Make and await a flush loop's ``trace_append`` call (``call()``
    returns the awaitable).  Anything but the controller's ``True`` — no
    connection, the call lost, shed in a brownout, answered by a standby
    — means the controller lacks that batch: the whole ring goes again,
    after a pause and not every tick."""
    import asyncio
    try:
        ok = await call()
    except Exception:
        ok = False
    if ok is not True:
        mark_dirty()
        await asyncio.sleep(RESHIP_PAUSE_S)


def mark_dirty() -> None:
    """The controller does not have (all of) this process's spans — it
    restarted, a standby was promoted, or a flush RPC failed: the next
    flush re-ships the whole ring."""
    global _reship
    with _span_lock:
        _reship = True


# ------------------------------------------------------------ span files

def span_file(session_dir: str) -> str:
    return os.path.join(session_dir, "spans",
                        f"{_proc['kind']}-{os.getpid()}.json")


def record_program(program: str, make: Callable[[], dict], t0: float,
                   seconds: float) -> None:
    """Keep one executable JAX loaded for ``program`` at ``t0``, for this
    process's program files.  ``make()`` gives its op map
    (`device_profile.op_map` over the executable's text, which took
    ``seconds`` to take): it is called where the maps are read or written
    (`program_maps`, `write_span_file`) and NOT here, because the caller
    stands inside a compile of a program's warm-up and the map of a large
    program takes tenths of a second."""
    with _span_lock:
        _unmapped.append((program, make, t0, seconds))


def _map_pending() -> None:
    """Make the op maps still owed, each marked by its ``program:compiled``
    span at the time of its compile; an executable JAX loaded again (the
    same module and shape) adds nothing."""
    with _span_lock:
        todo = list(_unmapped)
        del _unmapped[:]
    for program, make, t0, seconds in todo:
        began = time.time()
        try:
            entry = make()
        except Exception:
            continue
        seconds += time.time() - began
        with _span_lock:
            maps = _programs.setdefault(program, [])
            if any((m["module"], m["shape"])
                   == (entry["module"], entry["shape"]) for m in maps):
                continue
            maps.append(entry)
        record_span("program:compiled", "setup", t0, t0 + seconds,
                    program=program, module=entry["module"],
                    instructions=len(entry["instructions"]),
                    named=entry["named"], seconds=round(seconds, 4))


def program_maps() -> Dict[str, List[dict]]:
    """program -> the op maps of this process's programs, oldest first."""
    _map_pending()
    with _span_lock:
        return {p: list(maps) for p, maps in _programs.items()}


def program_file(session_dir: str, program: str) -> str:
    return os.path.join(session_dir, "programs",
                        f"{_proc['kind']}-{os.getpid()}.{program}.json")


def _write_json(path: str, body: Any) -> bool:
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(body, f)
        os.replace(tmp, path)
    except (OSError, TypeError, ValueError):
        return False
    return True


def write_span_file(session_dir: Optional[str]) -> Optional[str]:
    """Write this process's ring to its file under ``<session_dir>/spans``
    and its programs' op maps to ``<session_dir>/programs`` (called where
    a process makes its final flush).  Skipped when nothing was recorded
    since the last write (a map comes with a span).  Never raises: the
    process is on its way out."""
    global _filed
    if not session_dir:
        return None
    _map_pending()
    _drain_host()
    with _span_lock:
        if _recorded == _filed or _ring is None:
            return None
        events, _filed = _ring.events(), _recorded
        programs = {p: list(maps) for p, maps in _programs.items()}
    for program, maps in programs.items():
        _write_json(program_file(session_dir, program),
                    {"program": program, "maps": maps})
    path = span_file(session_dir)
    return path if _write_json(path, events) else None


def write_span_file_on_sigterm(session_dir: str) -> None:
    """For the processes whose only way out is SIGTERM (nodelet,
    controller): on the running asyncio loop, write the span file when
    the signal comes, then die of it as before."""
    import asyncio
    import signal

    def on_term() -> None:
        write_span_file(session_dir)
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)

    try:
        asyncio.get_running_loop().add_signal_handler(signal.SIGTERM,
                                                      on_term)
    except (NotImplementedError, RuntimeError):
        pass


def read_span_files(session_dir: str) -> List[dict]:
    """Every process's span file of a session, merged, oldest first."""
    events: List[dict] = []
    spans_dir = os.path.join(session_dir, "spans")
    try:
        names = sorted(os.listdir(spans_dir))
    except OSError:
        return events
    for name in names:
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(spans_dir, name)) as f:
                events.extend(json.load(f))
        except (OSError, ValueError):
            continue
    events.sort(key=lambda e: e.get("ts", 0))
    return events


def chrome_trace(events: List[dict]) -> Dict[str, Any]:
    """``events`` (already ordered) as a Chrome-trace dict with one
    ``process_name`` metadata record per distinct pid."""
    pids: List[Any] = []
    for e in events:
        p = e.get("pid")
        if p not in pids:
            pids.append(p)
    meta = [{"ph": "M", "name": "process_name", "pid": p, "tid": 0,
             "args": {"name": str(p)}} for p in pids]
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def cluster_trace_events() -> List[dict]:
    """Every process's flushed lifecycle spans plus every node's legacy
    finished-task spans — the flat-list form the dashboard consumes
    (``state.timeline()`` wraps the same spans as a Chrome-trace dict)."""
    try:
        from .. import state
        return state._trace_span_events() + state._node_task_span_events()
    except Exception:
        return span_events()   # not connected: this process's ring only
