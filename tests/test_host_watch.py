"""util/tracing.py's host watch: what the interpreter and the host took
from a process, on the ring's clock.  ``host:gc`` (a `gc.callbacks` hook:
every collection's seconds, a ring span for those of a millisecond or more)
and ``host:late_wakeup`` (a thread that sleeps 20 ms and notes a wake-up
50 ms late), one watch a process, started and stopped with the flush
loop's claim; and `span(..., cpu=True)`, a thread's own CPU seconds beside
an interval's wall seconds.  CPU only, no runtime."""

import gc
import os
import threading
import time
from collections import deque

import pytest

from ray_tpu.core.config import GlobalConfig
from ray_tpu.util import tracing


@pytest.fixture
def watch(monkeypatch):
    """An empty ring, zeroed totals and nobody holding the flush loop's
    claim; the automatic collector is off, so the only collections are
    the test's own."""
    tracing.release_flusher()
    for name, value in (("_ring", None), ("_pending", []),
                        ("_reship", False), ("_recorded", 0),
                        ("_filed", -1), ("_host_owed", deque()),
                        ("_host", dict.fromkeys(tracing._host, 0))):
        monkeypatch.setattr(tracing, name, value)
    gc.collect()
    gc.disable()
    try:
        yield tracing
    finally:
        gc.enable()
        tracing.release_flusher()


def _spans(name):
    tracing.flush_batch()       # the ring is given what the hook left
    return [e for e in tracing.span_events() if e["name"] == name]


def _watch_threads():
    return [t for t in threading.enumerate()
            if t.name == "rt-host-watch" and t.is_alive()]


def _cyclic_heap(n=200_000):
    heap = [[] for _ in range(n)]
    for cell in heap:
        cell.append(cell)
    return heap


def test_a_full_collection_leaves_one_span_and_its_seconds(watch):
    assert tracing.claim_flusher()
    heap = _cyclic_heap()
    before = tracing.host_totals()
    del heap
    t0 = time.time()
    found = gc.collect()
    t1 = time.time()
    after = tracing.host_totals()
    assert after["gc_collections"] == before["gc_collections"] + 1
    assert 1e-3 <= after["gc_s"] - before["gc_s"] <= t1 - t0 + 1e-3
    (ev,) = _spans("host:gc")
    assert ev["cat"] == "host" and ev["tid"] == str(os.getpid())
    assert ev["args"]["generation"] == 2
    assert ev["args"]["collected"] == found >= 200_000
    assert t0 - 1e-3 <= ev["ts"] * 1e-6 <= t1
    assert abs(ev["dur"] * 1e-6 - (after["gc_s"] - before["gc_s"])) < 1e-3


def test_a_young_collection_is_counted_and_leaves_no_span(watch):
    assert tracing.claim_flusher()
    before = tracing.host_totals()
    junk = [[] for _ in range(50)]
    for cell in junk:
        cell.append(cell)
    del junk
    gc.collect(0)
    after = tracing.host_totals()
    assert after["gc_collections"] == before["gc_collections"] + 1
    assert 0 < after["gc_s"] - before["gc_s"] < tracing.GC_SPAN_FLOOR_S
    assert _spans("host:gc") == []


def test_a_held_interpreter_leaves_a_late_wakeup(watch):
    """ONE C call that never lets the interpreter go (a loop of short ones
    would hand it over every 5 ms): the watch thread, due after 20 ms,
    wakes when the call returns."""
    assert tracing.claim_flusher()
    n, held = 2_000_000, 0.0
    while held < 0.15:          # sized by doubling: the box may be loaded
        n *= 2
        time.sleep(0.03)        # the watch thread is asleep in its tick
        t = time.perf_counter()
        sum(range(n))
        held = time.perf_counter() - t
    deadline = time.monotonic() + 5.0
    while not tracing.host_totals()["late_wakeups"] \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    totals = tracing.host_totals()
    assert totals["late_wakeups"] >= 1
    late = _spans("host:late_wakeup")
    assert late and late[0]["cat"] == "host"
    worst = max(e["args"]["late_ms"] for e in late)
    assert 50.0 <= worst <= 1e3 * held + 50.0
    assert abs(sum(e["args"]["late_ms"] for e in late)
               - 1e3 * totals["late_wakeup_s"]) < 1.0
    # the span covers the overrun
    ev = max(late, key=lambda e: e["dur"])
    assert abs(ev["dur"] * 1e-3 - ev["args"]["late_ms"]) < 1.0


def test_trace_disabled_starts_no_thread_and_counts_nothing(
        watch, monkeypatch):
    monkeypatch.setattr(GlobalConfig, "trace_enabled", False)
    assert tracing.claim_flusher()
    assert _watch_threads() == [] and tracing._on_gc not in gc.callbacks
    heap = _cyclic_heap(50_000)
    del heap
    gc.collect()
    sum(range(3_000_000))
    assert tracing.host_totals() == dict.fromkeys(tracing._host, 0)
    monkeypatch.setattr(GlobalConfig, "trace_enabled", True)
    assert _spans("host:gc") == [] and _spans("host:late_wakeup") == []


def test_claim_and_release_twice_leave_one_watch_then_none(watch):
    assert tracing.claim_flusher()
    assert not tracing.claim_flusher()      # the process's second runtime
    assert len(_watch_threads()) == 1
    assert gc.callbacks.count(tracing._on_gc) == 1
    tracing.release_flusher()
    assert _watch_threads() == [] and tracing._on_gc not in gc.callbacks
    assert tracing.claim_flusher()
    assert len(_watch_threads()) == 1
    assert gc.callbacks.count(tracing._on_gc) == 1
    tracing.release_flusher()
    tracing.release_flusher()
    assert _watch_threads() == [] and tracing._on_gc not in gc.callbacks


def test_a_collection_inside_record_span_does_not_take_its_lock(
        watch, monkeypatch):
    """A collection can start on an allocation INSIDE `record_span`, on a
    thread that holds the plain `_span_lock`: the hook must leave the
    finished collection for a later drain and never record it there."""
    monkeypatch.setattr(tracing, "GC_SPAN_FLOOR_S", 0.0)
    assert tracing.claim_flusher()
    add = tracing.SpanRing.add

    def collecting_add(self, ev):
        if ev["name"] == "planted":
            assert tracing._span_lock.locked()
            gc.collect()
        add(self, ev)

    monkeypatch.setattr(tracing.SpanRing, "add", collecting_add)
    now = time.time()
    t = threading.Thread(target=tracing.record_span, daemon=True,
                         args=("planted", "task", now, now + 1e-3))
    t.start()
    t.join(timeout=10.0)
    assert not t.is_alive(), "record_span never returned: the hook took " \
                             "the span lock"
    names = [e["name"] for e in tracing.span_events()]
    assert "planted" in names
    assert len(_spans("host:gc")) >= 1


def test_a_flush_waits_for_a_drain_that_holds_a_collection(
        watch, monkeypatch):
    """A drainer that has taken a collection and not yet recorded it (it
    waits for `_span_lock`, say) holds it where neither `_host_owed` nor the
    ring shows it: a flush beside it waits for that drain, and ships the
    collection."""
    record, taken, go = tracing.record_span, threading.Event(), \
        threading.Event()

    def held_record(*args, **kwargs):
        taken.set()
        assert go.wait(10.0)
        record(*args, **kwargs)

    monkeypatch.setattr(tracing, "record_span", held_record)
    tracing._host_owed.append((1.0, 1.5, 2, 7))
    batch = []
    drain = threading.Thread(target=tracing._drain_host, daemon=True)
    flush = threading.Thread(
        target=lambda: batch.append(tracing.flush_batch()), daemon=True)
    drain.start()
    assert taken.wait(10.0) and not tracing._host_owed
    flush.start()
    flush.join(timeout=0.2)
    assert flush.is_alive() and not batch   # behind the drain, not past it
    go.set()
    for t in (drain, flush):
        t.join(timeout=10.0)
        assert not t.is_alive()
    assert [e["name"] for e in batch[0]["spans"]] == ["host:gc"]


def test_the_last_seconds_collection_reaches_the_span_file(
        watch, tmp_path):
    """The hook's deque is drained by the span file's writer too: a
    collection between the watch's last tick and the process's end is
    kept."""
    assert tracing.claim_flusher()
    tracing.release_flusher()               # no watch thread to drain it
    gc.callbacks.append(tracing._on_gc)
    try:
        heap = _cyclic_heap()
        del heap
        gc.collect()
    finally:
        gc.callbacks.remove(tracing._on_gc)
    assert len(tracing._host_owed) == 1
    path = tracing.write_span_file(str(tmp_path))
    assert path is not None
    assert [e["name"] for e in tracing.read_span_files(str(tmp_path))
            ].count("host:gc") == 1


# ------------------------------------------- a thread's own CPU seconds

def test_span_cpu_seconds_beside_wall_seconds(watch):
    """Asleep, a thread's interval has wall seconds and no CPU seconds;
    computing, nearly all of both; and never more CPU than wall."""
    acc = {}
    with tracing.span("engine:schedule", into=(acc, "slept"), cpu=True):
        time.sleep(0.05)
    assert acc["slept"] >= 0.05 and 0 <= acc["slept_cpu"] < 0.02
    with tracing.span("engine:schedule", into=(acc, "worked"), cpu=True):
        t = time.perf_counter()
        while time.perf_counter() - t < 0.05:
            sum(range(1000))
    assert 0.02 <= acc["worked_cpu"] <= acc["worked"]
    with tracing.span("engine:readback", into=(acc, "plain")):
        pass
    assert "plain_cpu" not in acc
    assert tracing.span_events() == []      # `into` never reaches the ring
