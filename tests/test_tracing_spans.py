"""util/tracing.py: the one span primitive, its two sinks, the per-category
ring, the flush that ships what is new, and the span files of a finished
session.  CPU only; the cluster cases start a one-node runtime."""

import json
import os
import subprocess
import sys
import time

import pytest

from ray_tpu.core.config import GlobalConfig
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_ring(monkeypatch):
    """The module's ring state, emptied for one test and put back."""
    for name, value in (("_ring", None), ("_pending", []),
                        ("_reship", False), ("_recorded", 0),
                        ("_filed", -1), ("_programs", {}),
                        ("_unmapped", [])):
        monkeypatch.setattr(tracing, name, value)
    return tracing


def _record(n, cat, name="s"):
    t = time.time()
    for i in range(n):
        tracing.record_span(f"{name}{i}", cat, t, t + 1e-3, i=i + 1)


# ------------------------------------------------------------- the two sinks

def test_span_lands_in_the_profilers_host_plane(tmp_path, fresh_ring):
    """With JAX in the process a span is also a TraceAnnotation: a
    `jax.profiler` trace taken around it holds it in /host:CPU, where the
    benchmark's reduction looks for `<layer>:<phase>` names."""
    import jax
    import jax.numpy as jnp

    from perfbench import xplane
    acc = {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("engine:publish", "serve", into=(acc, "publish")):
            jnp.ones((8, 8)).sum().block_until_ready()
        with tracing.span("proxy:request", "serve", rid="r1"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    raw = xplane.read(xplane.find(str(tmp_path)))
    by_name = {name: (a, b) for a, b, name in raw["spans"]}
    assert {"engine:publish", "proxy:request"} <= set(by_name)
    a, b = by_name["proxy:request"]
    assert 0.002 <= b - a < 1.0
    # and each went to its own second sink
    assert acc["publish"] > 0
    assert [e["name"] for e in tracing.span_events()] == ["proxy:request"]


def test_into_adds_seconds_and_skips_the_ring(fresh_ring):
    acc = {"publish": 1.0}
    for _ in range(3):
        with tracing.span("engine:publish", into=(acc, "publish")):
            time.sleep(0.001)
    assert 1.003 <= acc["publish"] < 2.0
    assert tracing.span_events() == [] and tracing.flush_batch() is None


def test_trace_disabled_turns_ring_spans_off_not_accumulators(
        fresh_ring, monkeypatch):
    monkeypatch.setattr(GlobalConfig, "trace_enabled", False)
    acc = {}
    with tracing.span("proxy:request", "serve"):
        pass
    with tracing.span("engine:schedule", into=(acc, "schedule")):
        pass
    _record(3, "serve")
    assert tracing.span_events() == [] and tracing.flush_batch() is None
    assert acc["schedule"] >= 0.0 and "schedule" in acc


# ------------------------------------------------------- ring and its flush

def test_flush_of_a_full_ring_carries_only_the_new_spans(fresh_ring):
    """The flush costs what was recorded since the last one: with the
    category full, k new spans make a batch of k spans, and its bytes on
    the wire are those of k spans, not of the ring."""
    import msgpack
    bound = tracing.SpanRing().per_category
    _record(bound + 50, "serve")
    first = tracing.flush_batch()
    assert len(first["spans"]) == bound + 50 and first["reset"] is False
    assert len(tracing.span_events()) == bound          # the ring is full
    assert tracing.flush_batch() is None                # nothing new
    for k in (1, 7, 100):
        _record(k, "serve", name="new")
        batch = tracing.flush_batch()
        assert [e["name"] for e in batch["spans"]] == \
            [f"new{i}" for i in range(k)]
        assert batch["reset"] is False and batch["key"] == tracing.proc_key()
        per_span = len(msgpack.packb(batch["spans"])) / k
        whole = len(msgpack.packb(tracing.span_events()))
        assert per_span * k < whole * (k + 5) / bound


def test_a_flood_of_one_category_leaves_the_others_in_place(fresh_ring):
    t = time.time()
    tracing.record_span("setup:chip_open", "setup", t, t + 2.0,
                        worker_pid=os.getpid())
    tracing.record_span("exec::f", "exec", t, t + 0.1)
    bound = tracing.SpanRing().per_category
    _record(3 * bound, "serve")
    events = tracing.span_events()
    names = [e["name"] for e in events]
    assert "setup:chip_open" in names and "exec::f" in names
    assert sum(1 for e in events if e["cat"] == "serve") == bound
    # the newest of the flooding category are the ones kept
    assert f"s{3 * bound - 1}" in names and "s0" not in names
    # and the total stays bounded whatever the number of categories
    for c in range(3 * tracing.MAX_CATEGORIES):
        tracing.record_span("x", f"cat{c}", t, t)
    assert len(tracing._buffer()._cats) <= tracing.MAX_CATEGORIES + 1
    assert len(tracing.span_events()) <= bound * (tracing.MAX_CATEGORIES + 1)


def test_mark_dirty_reships_the_whole_ring_once(fresh_ring):
    _record(5, "task")
    assert len(tracing.flush_batch()["spans"]) == 5
    _record(2, "task", name="late")
    tracing.mark_dirty()                 # the controller lost our history
    batch = tracing.flush_batch()
    assert batch["reset"] is True and len(batch["spans"]) == 7
    assert tracing.flush_batch() is None
    _record(1, "task", name="after")
    batch = tracing.flush_batch()
    assert batch["reset"] is False and len(batch["spans"]) == 1


def test_pending_is_bounded_where_nobody_flushes(fresh_ring, monkeypatch):
    monkeypatch.setattr(GlobalConfig, "trace_buffer_size", 64)
    bound = tracing.SpanRing().per_category
    assert bound == 16
    _record(bound * tracing.MAX_CATEGORIES + 10, "serve")
    assert len(tracing._pending) <= bound * tracing.MAX_CATEGORIES
    batch = tracing.flush_batch()        # the ring itself, not a backlog
    assert batch["reset"] is True and len(batch["spans"]) == bound


def test_controller_keeps_each_process_in_the_same_ring(fresh_ring):
    """`trace_append` batches land in a per-process SpanRing: a reset
    replaces it, an append extends it, categories keep their own bound."""
    from ray_tpu.core.controller import Controller
    c = Controller.__new__(Controller)
    c.trace_log = {}
    t = time.time() * 1e6
    ev = lambda name, cat: {"name": name, "cat": cat, "ts": t, "dur": 1}
    c._trace_append({"key": "w:1", "reset": False,
                     "spans": [ev("setup:chip_open", "setup")]})
    bound = c.trace_log["w:1"].per_category
    c._trace_append({"key": "w:1", "reset": False,
                     "spans": [ev(f"q{i}", "serve")
                               for i in range(bound + 9)]})
    names = [e["name"] for e in c.trace_log["w:1"].events()]
    assert "setup:chip_open" in names and len(names) == bound + 1
    c._trace_append({"key": "w:1", "reset": True,
                     "spans": [ev("only", "task")]})
    assert [e["name"] for e in c.trace_log["w:1"].events()] == ["only"]


def test_flush_of_a_full_ring_at_the_chat_cells_rate_is_cheap(fresh_ring):
    """Not a timing of the chip: the host cost of one flush tick with the
    ring full, at 25 new spans a tick (100 a second, the rate at which the
    chat cell's replica recorded before this ring existed).  The old flush
    serialised the whole ring, 9.9 ms measured on this CPU; the bound here
    is loose enough for a loaded test box."""
    import msgpack
    bound = tracing.SpanRing().per_category
    for cat in ("serve", "exec", "fetch", "put"):
        _record(bound, cat)
    tracing.flush_batch()
    worst = 0.0
    for _ in range(20):
        _record(25, "serve", name="tick")
        t0 = time.perf_counter()
        batch = tracing.flush_batch()
        msgpack.packb(batch)
        worst = max(worst, time.perf_counter() - t0)
        assert len(batch["spans"]) == 25
    assert worst < 5e-3, worst


# ------------------------------------------------------------ span files

def test_span_files_merge_through_timeline_session_dir(fresh_ring, tmp_path,
                                                       monkeypatch):
    session = tmp_path / "session_x"
    t = time.time()
    monkeypatch.setitem(tracing._proc, "kind", "worker")
    tracing.record_span("setup:actor_init", "setup", t + 1, t + 2)
    path = tracing.write_span_file(str(session))
    assert path == str(session / "spans" / f"worker-{os.getpid()}.json")
    assert tracing.write_span_file(str(session)) is None    # nothing new
    other = [{"name": "setup:worker_spawn", "cat": "setup", "ph": "X",
              "ts": t * 1e6, "dur": 5e5, "pid": "nodelet@ab",
              "tid": "1", "args": {}}]
    (session / "spans" / "nodelet-1.json").write_text(json.dumps(other))
    (session / "spans" / "broken-2.json").write_text("{not json")
    merged = tracing.read_span_files(str(session))
    assert [e["name"] for e in merged] == ["setup:worker_spawn",
                                           "setup:actor_init"]
    out = tmp_path / "tl.json"
    done = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "timeline",
         "--session-dir", str(session), "-o", str(out)],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert done.returncode == 0, done.stderr[-2000:]
    assert "2 spans" in done.stdout
    dump = json.loads(out.read_text())
    names = [e["name"] for e in dump["traceEvents"] if e.get("ph") == "X"]
    assert names == ["setup:worker_spawn", "setup:actor_init"]
    assert {e["args"]["name"] for e in dump["traceEvents"]
            if e.get("ph") == "M"} == {"nodelet@ab", tracing.proc_label()}


def test_finished_session_keeps_its_timeline(tmp_path):
    """A driver script runs a task and an actor and shuts down; afterwards,
    with no cluster up, the session directory's span files hold the
    driver's, the nodelet's and the workers' spans."""
    script = tmp_path / "drive.py"
    script.write_text(
        "import ray_tpu\n"
        "from ray_tpu import api\n"
        "ray_tpu.init(num_cpus=2)\n"
        "@ray_tpu.remote\n"
        "def f(x):\n"
        "    return x + 1\n"
        "@ray_tpu.remote\n"
        "class A:\n"
        "    def g(self):\n"
        "        return 2\n"
        "a = A.remote()\n"
        "assert ray_tpu.get([f.remote(1), a.g.remote()], timeout=120) "
        "== [2, 2]\n"
        "ray_tpu.kill(a)\n"
        "print('SESSION', api._local_cluster.session_dir)\n"
        "ray_tpu.shutdown()\n")
    done = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert done.returncode == 0, done.stdout + done.stderr[-3000:]
    session = next(ln.split()[1] for ln in done.stdout.splitlines()
                   if ln.startswith("SESSION"))
    files = sorted(os.listdir(os.path.join(session, "spans")))
    kinds = {f.split("-")[0] for f in files}
    assert {"driver", "nodelet", "worker"} <= kinds, files
    names = {e["name"] for e in tracing.read_span_files(session)}
    assert {"submit::f", "exec::f", "setup:worker_spawn"} <= names, names


def test_shutdown_waits_for_a_worker_that_is_writing_its_files(tmp_path):
    """A serve replica owes its programs' op maps when the session ends
    and takes half a second and more to make them and to write its ring
    (on the chip: PERF.md, PR 41).  The sweep that ends a session gives
    an orphaned worker the grace a stopping nodelet gives, so the span
    file and the program file of a worker that needs a second are whole
    when `shutdown` returns."""
    script = tmp_path / "drive.py"
    script.write_text(
        "import ray_tpu\n"
        "from ray_tpu import api\n"
        "ray_tpu.init(num_cpus=2)\n"
        "@ray_tpu.remote\n"
        "class A:\n"
        "    def owe(self):\n"
        "        import os, time\n"
        "        from ray_tpu.util import tracing\n"
        "        def make():\n"
        "            time.sleep(1.0)\n"
        "            return {'module': 'jit_slow', 'shape': 's',\n"
        "                    'instructions': {}, 'named': 0}\n"
        "        tracing.record_program('slow', make, time.time(), 0.0)\n"
        "        return os.getpid()\n"
        "a = A.remote()\n"
        "print('PID', ray_tpu.get(a.owe.remote(), timeout=120))\n"
        "print('SESSION', api._local_cluster.session_dir)\n"
        "ray_tpu.shutdown()\n")
    done = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert done.returncode == 0, done.stdout + done.stderr[-3000:]
    said = dict(ln.split() for ln in done.stdout.splitlines()
                if ln.startswith(("PID", "SESSION")))
    session, pid = said["SESSION"], said["PID"]
    assert f"worker-{pid}.json" in os.listdir(
        os.path.join(session, "spans"))
    assert os.listdir(os.path.join(session, "programs")) \
        == [f"worker-{pid}.slow.json"]
    assert "program:compiled" in {
        e["name"] for e in tracing.read_span_files(session)}


# ------------------------------------------------------- the engine thread

def test_engine_phase_totals_first_token_and_prefill_tail():
    """The engine at tiny size: `phase_totals` carries the engine thread's
    own phases beside the pipeline's, `first_token` (enqueued -> first
    token exists) covers the queue wait and the prefill inside it, and a
    prompt of one chunk plus three tokens spends prefill seconds in the
    ONE padded program that carries its remainder.  None of it touches the
    ring: the per-step work records no span."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = TransformerConfig.tiny(max_seq_len=128, dtype=jnp.float32)
    ecfg = DecodeEngineConfig(max_slots=2)
    core = DecodeSessionCore(cfg, max_len=128, engine=ecfg)
    try:
        chunk = core.engine.ecfg.prefill_chunk_tokens
        before = {e["name"] for e in tracing.span_events()}
        out = core.handle({"op": "start",
                           "prompt": [1 + i % 50 for i in range(chunk + 3)]})
        got = len(out["token"])
        while got < 6:
            more = core.handle({"op": "next_chunk", "sid": out["sid"],
                                "max_tokens": 4})
            got += len(more["tokens"])
        core.handle({"op": "end", "sid": out["sid"]})
        ph = core.engine.phase_totals()
        assert {"schedule", "admit_host", "dispatch", "readback",
                "publish", "first_token", "prefill_tail", "queue",
                "admission", "prefill", "decode_dispatch",
                # what the host's turn waited for (PR 51)
                "schedule_cpu", "lock_wait", "long_read", "gc",
                "late_wakeup"} == set(ph)
        # a thread's CPU seconds of an interval never pass its wall
        # seconds (both clocks tick in nanoseconds; rounding: 1 us)
        assert 0 <= ph["schedule_cpu"] <= ph["schedule"] + 1e-6, ph
        assert ph["first_token"] > 0 and ph["first_token"] >= ph["queue"]
        assert 0 < ph["prefill_tail"] < ph["prefill"]
        # one whole chunk, then the three tokens as one more of its shape
        st = core.engine.stats()
        assert [s for s in st["program_shapes"]
                if s.startswith("prefill_chunk")] == [
                    f"prefill_chunk:1x{chunk}"]
        assert (st["prefill_chunks"], st["prefill_tails"],
                st["prefill_pad_tokens"]) == (2, 1, chunk - 3)
        new = {e["name"] for e in tracing.span_events()} - before
        assert not any(n.startswith(("serve_decode_step", "engine:",
                                     "serve_prefill_chunk"))
                       for n in new), new
    finally:
        core.engine.shutdown()


# ------------------------------------------------- hops and worker set-up

@pytest.fixture(scope="module")
def served():
    import ray_tpu
    from ray_tpu import serve
    ray_tpu.init(num_cpus=4)

    @serve.deployment(name="hop")
    class Hop:
        def __call__(self, req):
            return {"got": req}

    serve.run(Hop.bind())
    try:
        yield serve.api.http_address() + "/hop"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _timeline_until(pred, timeout_s=30.0):
    from ray_tpu import state
    deadline = time.monotonic() + timeout_s
    while True:
        events = [e for e in state.timeline()["traceEvents"]
                  if e.get("ph") == "X"]
        if pred(events) or time.monotonic() > deadline:
            return events
        time.sleep(0.3)


def test_proxy_and_replica_spans_of_a_request_share_its_id(served):
    """The generic HTTP path: `proxy:request` with `proxy:route` inside it
    in the proxy, `serve_queue::` and `serve_exec::` in the replica, all
    four under the id the proxy minted — and the payload the deployment
    sees is the client's, untouched."""
    import requests
    body = {"op": "echo", "x": 1}
    assert requests.post(served, json=body, timeout=60).json() == \
        {"got": body}

    def whole(events):
        rids = [e["args"].get("rid") for e in events
                if e["name"] == "proxy:request"]
        return any(sum(1 for e in events
                       if e.get("args", {}).get("rid") == r) >= 4
                   for r in rids if r)
    events = _timeline_until(whole)
    req = [e for e in events if e["name"] == "proxy:request"][-1]
    rid = req["args"]["rid"]
    mine = {e["name"]: e for e in events
            if e.get("args", {}).get("rid") == rid}
    assert set(mine) == {"proxy:request", "proxy:route",
                         "serve_queue::hop", "serve_exec::hop"}, set(mine)
    route, ex = mine["proxy:route"], mine["serve_exec::hop"]
    assert route["ts"] == req["ts"] and route["dur"] <= req["dur"]
    assert req["ts"] <= ex["ts"] and \
        ex["ts"] + ex["dur"] <= req["ts"] + req["dur"] + 1e3
    assert ex["args"]["op"] == "echo" and req["args"]["status"] == 200
    assert req["tid"] != ex["tid"]          # two processes, one id


def test_worker_setup_spans_reach_the_timeline(served):
    """`setup:worker_spawn` from the nodelet for every worker it started,
    `setup:actor_init` from the replica's worker; no `setup:chip_open` on a
    CPU node, where no worker holds a chip."""
    events = _timeline_until(
        lambda ev: any(e["name"] == "setup:actor_init" for e in ev))
    spawn = [e for e in events if e["name"] == "setup:worker_spawn"]
    init = [e for e in events if e["name"] == "setup:actor_init"
            and e["args"].get("deployment") == "hop"]
    assert spawn and all(e["cat"] == "setup" and e["dur"] > 0
                         and e["pid"].startswith("nodelet@")
                         for e in spawn)
    assert len(init) == 1 and init[0]["pid"].startswith("worker@")
    pids = {e["args"]["worker_pid"] for e in spawn}
    assert init[0]["args"]["worker_pid"] in pids
    assert not any(e["name"] == "setup:chip_open" for e in events)
