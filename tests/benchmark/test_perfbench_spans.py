"""perfbench/spans.py: the reductions of the program's own spans on
hand-made intervals and events, and the readers built on them in a
rehearsed cell on the CPU."""

import copy
import json
import os

import pytest

from perfbench import manifest as mf
from perfbench import spans
from perfbench.tools import rehearse

REHEARSAL = os.path.join("perfbench", "testdata", "rehearsal")
MS = 1e-3


def test_a_hole_goes_to_the_engine_phase_open_in_it():
    """A hole under two handler spans and one `engine:` span is that engine
    phase's, whatever the handler threads had open."""
    holes = [(10 * MS, 12 * MS)]
    sp = [(0.0, 1.0, "handle:start"), (5 * MS, 40 * MS, "handle:next_chunk"),
          (9 * MS, 13 * MS, "engine:schedule")]
    out = spans.idle_by_phase(holes, sp)
    assert out["schedule"] == pytest.approx(2 * MS)
    assert out["total"] == pytest.approx(2 * MS)
    assert all(out[k] == 0.0 for k in ("admit", "dispatch", "readback",
                                       "publish", "none"))


def test_a_hole_is_cut_at_the_phases_edges_and_the_parts_add_up():
    """One device hole usually spans several phases in turn: read-back ends,
    publish, schedule, admit, dispatch, the next program starts.  Each phase
    gets its own part, the time between two phases and the time the thread
    waited go to ``none``, and the parts make the total."""
    holes = [(0.0, 10 * MS),            # under four phases and two seams
             (20 * MS, 20.04 * MS),     # 40 us: under the floor
             (30 * MS, 33 * MS),        # the engine thread waited
             (40 * MS, 41 * MS)]        # inside one long readback
    sp = [(-1 * MS, 1 * MS, "engine:readback"),
          (1 * MS, 3 * MS, "engine:publish"),
          (3.5 * MS, 5 * MS, "engine:schedule"),
          (5 * MS, 8 * MS, "engine:admit"),
          (8.25 * MS, 11 * MS, "engine:dispatch"),
          (39 * MS, 45 * MS, "engine:readback"),
          (0.0, 50 * MS, "handle:next_chunk")]
    out = spans.idle_by_phase(holes, sp)
    want = {"readback": 1 + 1, "publish": 2, "schedule": 1.5, "admit": 3,
            "dispatch": 1.75, "none": 0.5 + 0.25 + 3}
    for k, ms in want.items():
        assert out[k] == pytest.approx(ms * MS), k
    assert out["total"] == pytest.approx(14 * MS)        # 40 us left out
    assert sum(out[k] for k in spans.ENGINE_PHASES) + out["none"] == \
        pytest.approx(out["total"])


def test_cut_leaves_no_piece_across_an_edge():
    pieces = spans.cut([(0.0, 10.0), (20.0, 30.0)],
                       [(2.0, 4.0, "a"), (4.0, 25.0, "b"), (40.0, 41.0, "c")])
    assert pieces == [(0.0, 2.0), (2.0, 4.0), (4.0, 10.0), (20.0, 25.0),
                      (25.0, 30.0)]
    assert spans.cut([], [(0.0, 1.0, "a")]) == []
    assert spans.cut([(0.0, 1.0)], []) == [(0.0, 1.0)]


def _event(name, ts_ms, dur_ms, **args):
    return {"name": name, "cat": "serve", "ph": "X", "ts": ts_ms * 1e3,
            "dur": dur_ms * 1e3, "pid": "p", "tid": "1", "args": args}


def test_hops_join_the_proxys_and_the_replicas_spans_on_the_request_id():
    events = [
        # a whole next_chunk call: route 2, queue 1.5, exec 50, reply 3
        _event("proxy:request", 1000, 56.5, rid="a"),
        _event("proxy:route", 1000, 2, rid="a"),
        _event("serve_queue::bench", 1002, 1.5, rid="a", op="next_chunk"),
        _event("serve_exec::bench", 1003.5, 50, rid="a", op="next_chunk"),
        # another, slower on the way back
        _event("proxy:request", 2000, 70, rid="b"),
        _event("proxy:route", 2000, 4, rid="b"),
        _event("serve_queue::bench", 2004, 2.5, rid="b", op="next_chunk"),
        _event("serve_exec::bench", 2006.5, 50, rid="b", op="next_chunk"),
        # a start call, a call before the window, and one with a span lost
        _event("proxy:request", 3000, 500, rid="c"),
        _event("proxy:route", 3000, 9, rid="c"),
        _event("serve_queue::bench", 3009, 9, rid="c", op="start"),
        _event("serve_exec::bench", 3018, 400, rid="c", op="start"),
        _event("proxy:request", 10, 60, rid="d"),
        _event("proxy:route", 10, 9, rid="d"),
        _event("serve_queue::bench", 19, 9, rid="d", op="next_chunk"),
        _event("serve_exec::bench", 28, 40, rid="d", op="next_chunk"),
        _event("proxy:request", 2500, 60, rid="e"),
        _event("serve_exec::bench", 2510, 40, rid="e", op="next_chunk"),
        _event("serve_admission::bench", 2510, 40, rid=""),
    ]
    out = spans.hops(events, "next_chunk", 0.5, 5.0)
    assert out["route"] == pytest.approx([2, 4])
    assert out["queue"] == pytest.approx([1.5, 2.5])
    assert out["reply"] == pytest.approx([3, 13.5])
    assert spans.hops([], "next_chunk", 0.0, 9.0) == \
        {"route": [], "queue": [], "reply": []}


class _Run:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_setup_span_is_the_chip_holders_and_phase_delta_needs_its_keys():
    events = [_event("setup:worker_spawn", 0, 800, worker_pid=11),
              _event("setup:worker_spawn", 0, 1200, worker_pid=42),
              _event("setup:chip_open", 0, 1900, worker_pid=42)]
    run = _Run(worker={"pid": 42}, _ring_spans=events)
    assert spans.setup_span_s(run, "setup:worker_spawn") == \
        pytest.approx(1.2)
    assert spans.setup_span_s(run, "setup:chip_open") == pytest.approx(1.9)
    assert spans.setup_span_s(run, "setup:actor_init") is None
    counters = {"before": {"phase_totals": {"queue": 1.0, "schedule": 2.0,
                                            "publish": 0.5}},
                "after": {"phase_totals": {"queue": 1.5, "schedule": 2.25,
                                           "publish": 1.0}}}
    run = _Run(raw={"counters": counters})
    assert spans.phase_delta(run, "schedule", "publish") == \
        pytest.approx(0.75)
    assert spans.phase_delta(run, "first_token") is None   # the parent
    assert spans.phase_delta(_Run(raw={}), "queue") is None


def test_readers_give_nothing_for_a_program_without_the_spans():
    """The parent of the PR that added the spans: an untraced run with the
    old counters and no span files.  Every new reader returns None."""
    run = _Run(raw={"counters": {"before": {"phase_totals": {"queue": 1.0},
                                            "steps": 0, "starts": 0},
                                 "after": {"phase_totals": {"queue": 2.0},
                                           "steps": 9, "starts": 3}}},
               trace=None, worker={"pid": 1}, _ring_spans=[],
               stamps={"open": 0.0, "close": 9.0, "start": 0.0})
    m = mf.Manifest()
    new = [x["name"] for x in m.data["per_layer"]
           if x["source"] == "program_span"
           or x["name"].startswith("engine.")]
    assert len(new) >= 19
    for name in new:
        if name in ("engine.batch_mean.batch", "engine.admit_wait_ms.chat"):
            continue
        assert mf.metric_reader(name)(run) is None, name


def _manifest_with_the_new_metrics(tmp_path) -> str:
    """The rehearsal's tiny manifest plus every per-layer entry of the real
    one that it lacks, moved to the tiny cell in the same position."""
    real = mf.Manifest().data
    tiny = json.load(open(os.path.join(mf.ROOT, REHEARSAL,
                                       "BENCHMARK.json")))
    cell = {r["name"]: t["name"]
            for r, t in zip(real["workloads"], tiny["workloads"])}
    have = {x["name"] for x in tiny["per_layer"]}
    for x in real["per_layer"]:
        if x["name"] not in have:
            x = copy.deepcopy(x)
            x["workloads"] = [cell[w] for w in x["workloads"]]
            tiny["per_layer"].append(x)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(tiny))
    return str(path)


@pytest.mark.parametrize("index,must,must_not", [
    (1, ("proxy.route_ms.batch", "replica.queue_ms.batch",
         "proxy.reply_ms.batch", "engine.host_ms_per_step.batch",
         "setup.worker_spawn_s"), ("engine.idle_pct.schedule.batch",)),
    (2, ("engine.first_token_ms.chat", "engine.prefill_tail_share.chat",
         "engine.host_ms_per_step.chat", "setup.worker_spawn_s"),
     ("engine.idle_pct.none.chat",)),
])
def test_new_readers_in_a_rehearsed_served_cell(tmp_path, index, must,
                                                must_not):
    """A traced rehearsal of each served kind: the counter and ring-span
    readers find their numbers in a real run; the trace readers find no
    device plane on the CPU and leave their metrics out; no chip opens
    here, so `setup.chip_open_s` is left out too."""
    manifest = _manifest_with_the_new_metrics(tmp_path)
    cell = json.load(open(manifest))["workloads"][index]["name"]
    lines = rehearse.rehearse(cell, 1, seed=2**31 + 99 + index,
                              manifest_path=manifest)
    m = lines[-1]["metrics"]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    for name in must:
        assert name in m and m[name]["value"] >= 0, (name, sorted(m))
    for name in must_not + ("setup.chip_open_s",):
        assert name not in m, name
    phases = next(ln["setup_phases"] for ln in lines if "setup_phases" in ln)
    assert 0 < m["setup.worker_spawn_s"]["value"] < phases["worker_ready_s"]
    if index == 1:
        hops_ms = sum(m[k]["value"] for k in must[:3])
        assert 0 < hops_ms and m["proxy.overhead_ms.batch"]["value"] > 0
    else:
        assert 0 < m["engine.prefill_tail_share.chat"]["value"] <= 100.5
        assert m["engine.first_token_ms.chat"]["value"] > \
            m["engine.admit_wait_ms.chat"]["value"]
