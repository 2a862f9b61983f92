"""What the host's turn waited for (PR 51): the eleven readers over
`perfbench/host_waits.py` on hand-made counters and ring spans, their
entries in the root manifest, the readers in a rehearsed served cell of each
kind, `perfbench.tools.stalls` on hand-made spans, and the trap the new
annotations' names avoid: `spans.idle_by_phase` credits an overlap to the
first ``engine:`` annotation by name, so an annotation ``engine:lock_wait``
nested in ``engine:schedule`` would have moved idle time from ``schedule``
to ``none``; ``wait:lock`` and ``host:gc`` are not looked at there.
"""

import copy
import json
import os
import types

import pytest

from perfbench import manifest as mf
from perfbench import spans, xplane
from perfbench.tools import rehearse, stalls

CLOSED = ["gpt2-xl.serve-batch-closed", "glm-4.7-flash.serve-agent-closed",
          "trinity-large-preview.serve-mixed-closed",
          "lfm2-8b-a1b.serve-reason-closed",
          "mimo-v2-flash.serve-longreason-closed",
          "evabyte.serve-bytedoc-closed", "glm-5.2.serve-longdoc-closed",
          "kimi-linear-48b-a3b.serve-think-closed"]
CHAT = ["gpt2-medium.serve-chat-open"]

# name -> (unit, source, layer)
BASES = {
    "engine.lock_wait_ms_per_step": ("ms", "program_counter",
                                     "decode engine"),
    "engine.schedule_blocked_share": ("%", "program_counter",
                                      "decode engine"),
    "host.gc_pause_pct": ("%", "program_counter", "runtime"),
    "host.late_wakeup_ms": ("ms", "program_span", "runtime"),
    "engine.long_read_ms": ("ms", "program_counter", "decode engine"),
}
ELEVEN = {base + suffix: BASES[base] for base in BASES
          for suffix in (".batch", ".chat")}
ELEVEN["loadgen.late_wakeup_ms.chat"] = ("ms", "program_span",
                                         "load generator")

HOLDER = 4242


def _totals(**keys):
    base = {"schedule": 0.0, "dispatch": 0.0, "publish": 0.0, "queue": 0.0}
    return dict(base, **keys)


def _run(before=None, after=None, steps=(100, 300), t=(10.0, 55.0),
         events=()):
    raw = {}
    if before is not None:
        raw["counters"] = {
            "before": {"t": t[0], "steps": steps[0], "phase_totals": before},
            "after": {"t": t[1], "steps": steps[1], "phase_totals": after}}
    return types.SimpleNamespace(
        raw=raw, stamps={"open": 10.0, "close": 55.0},
        worker={"pid": HOLDER}, _ring_spans=list(events))


def _late(end_s, late_ms, pid=HOLDER):
    return {"name": "host:late_wakeup", "cat": "host",
            "ts": (end_s - late_ms * 1e-3) * 1e6, "dur": late_ms * 1e3,
            "pid": "worker@ab", "tid": str(pid),
            "args": {"late_ms": late_ms}}


NEW = dict(lock_wait=0.0, schedule_cpu=0.0, long_read=0.0, gc=0.0,
           late_wakeup=0.0)


@pytest.mark.parametrize("name", sorted(ELEVEN))
def test_the_parents_counters_give_none(name):
    """No counters at all, and counters without the new keys (the parent of
    the PR that added them): every reader leaves its metric out."""
    read = mf.metric_reader(name)
    assert read(_run()) is None
    old = _run(_totals(schedule=1.0), _totals(schedule=2.0),
               events=[_late(20.0, 80.0), _late(21.0, 80.0, os.getpid())])
    assert read(old) is None


@pytest.mark.parametrize("suffix", [".batch", ".chat"])
def test_counter_readers_on_hand_made_counters(suffix):
    run = _run(
        _totals(schedule=1.0, **dict(NEW, lock_wait=0.5, schedule_cpu=0.8,
                                     long_read=0.25, gc=2.0)),
        _totals(schedule=3.0, **dict(NEW, lock_wait=0.9, schedule_cpu=1.3,
                                     long_read=0.55, gc=2.9)))

    def read(base):
        return mf.metric_reader(base + suffix)(run)
    # 0.4 s over 200 steps
    assert read("engine.lock_wait_ms_per_step") == pytest.approx(2.0)
    # 2.0 s of wall, 0.5 of them the thread's own
    assert read("engine.schedule_blocked_share") == pytest.approx(75.0)
    # 0.9 s of 45
    assert read("host.gc_pause_pct") == pytest.approx(2.0)
    assert read("engine.long_read_ms") == pytest.approx(300.0)
    # a quiet run of a program that has the instrument reads 0, not None
    quiet = _run(_totals(schedule=1.0, **NEW), _totals(schedule=2.0, **NEW))
    for base in ("engine.lock_wait_ms_per_step", "host.gc_pause_pct",
                 "engine.long_read_ms", "host.late_wakeup_ms"):
        assert mf.metric_reader(base + suffix)(quiet) == 0.0, base
    assert mf.metric_reader("engine.schedule_blocked_share" + suffix)(
        quiet) == pytest.approx(100.0)
    # no step, or no second of schedule, in the window: nothing to divide by
    still = _run(_totals(**NEW), _totals(**NEW), steps=(100, 100))
    assert mf.metric_reader("engine.lock_wait_ms_per_step" + suffix)(
        still) is None
    assert mf.metric_reader("engine.schedule_blocked_share" + suffix)(
        still) is None


def test_late_wakeup_readers_take_their_own_process_and_the_window():
    me = os.getpid()
    events = [
        _late(9.9, 500.0),                  # ended before the window
        _late(12.0, 60.0), _late(30.0, 190.5),
        _late(55.5, 700.0),                 # ended after it
        _late(20.0, 90.0, pid=777),         # the proxy's
        _late(25.0, 3100.0, pid=me), _late(8.0, 50.0, pid=me),
        {"name": "host:gc", "cat": "host", "ts": 20e6, "dur": 9e4,
         "tid": str(HOLDER), "args": {"generation": 2}},
        {"name": "host:late_wakeup", "ts": 21e6, "dur": 6e4,
         "tid": str(HOLDER)},                                # no arguments
    ]
    run = _run(_totals(**NEW), _totals(**NEW), events=events)
    for suffix in (".batch", ".chat"):
        assert mf.metric_reader("host.late_wakeup_ms" + suffix)(run) \
            == pytest.approx(250.5)
    assert mf.metric_reader("loadgen.late_wakeup_ms.chat")(run) \
        == pytest.approx(3100.0)


def test_root_manifest_lists_the_eleven_and_has_no_problem():
    root = mf.Manifest()
    assert mf.problems(root) == []
    listed = {x["name"]: x for x in root.data["per_layer"]}
    for name, (unit, source, layer) in ELEVEN.items():
        chat = name.endswith(".chat")
        assert listed[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer,
            "moves": "ttft_p95_ms" if chat else "serve_tok_s",
            "workloads": CHAT if chat else CLOSED}, name
    # the closed cells are the served cells that report `serve_tok_s`
    assert CLOSED == listed["engine.idle_pct.schedule.batch"]["workloads"]
    for cell in CLOSED + CHAT:
        mine = {x["name"] for x in root.metrics_for(cell, True)} \
            & set(ELEVEN)
        assert len(mine) == (6 if cell in CHAT else 5), (cell, mine)


# ---------------------------------------------------------------- the trap

def _idle_sums(annotations):
    """`idle_by_phase` of three idle holes under hand-made ``engine:``
    phases, with ``annotations`` beside them."""
    phases = [(0.000, 0.010, "engine:schedule"),
              (0.010, 0.014, "engine:admit"),
              (0.014, 0.016, "engine:dispatch"),
              (0.016, 0.030, "engine:readback"),
              (0.030, 0.032, "engine:publish"),
              (0.040, 0.050, "engine:schedule"),
              (0.000, 0.050, "handle:next_chunk")]
    holes = [(0.002, 0.009),        # all of it under schedule
             (0.0135, 0.0165),      # admit, dispatch, readback
             (0.031, 0.045)]        # publish, none, schedule
    return spans.idle_by_phase(holes, phases + annotations)


def test_idle_by_phase_does_not_look_at_the_new_annotations():
    plain = _idle_sums([])
    assert plain["schedule"] == pytest.approx(0.007 + 0.005)
    assert plain["none"] == pytest.approx(0.008)
    nested = _idle_sums([(0.003, 0.008, "wait:lock"),
                         (0.004, 0.006, "host:gc"),
                         (0.041, 0.044, "wait:lock"),
                         (0.033, 0.039, "host:gc")])
    assert nested == pytest.approx(plain)
    assert set(plain) == set(spans.ENGINE_PHASES) | {"none", "total"}
    # the trap: the same wait under an `engine:` name sorts before
    # `engine:schedule`, matches no phase, and moves its idle time to `none`
    trapped = _idle_sums([(0.003, 0.008, "engine:lock_wait")])
    assert trapped["schedule"] == pytest.approx(plain["schedule"] - 0.005)
    assert trapped["none"] == pytest.approx(plain["none"] + 0.005)
    # ... while the idle gaps' labels do name the new annotations
    labels = xplane.attribute_gaps(
        [(0.002, 0.009)], [(0.000, 0.010, "engine:schedule"),
                           (0.003, 0.008, "wait:lock")])
    assert labels == {"engine:schedule+wait:lock": pytest.approx(0.007)}
    for name in ("wait:lock", "host:gc"):
        assert xplane._SPAN.match(name)


# ------------------------------------------------------------ the stalls tool

def test_stalls_tool_gives_the_rules_verdict_a_long_read():
    def ev(name, t, ms, tid="12", **args):
        return {"name": name, "ts": t * 1e6, "dur": ms * 1e3,
                "pid": "worker@ab" if tid == "12" else "driver",
                "tid": tid, "args": args}
    events = [
        ev("proxy:request", 0.0, 5.0),
        ev("host:gc", 1.00, 90.0, generation=2, collected=10),
        ev("host:late_wakeup", 1.02, 70.0, late_ms=70.0),
        ev("engine:long_read", 1.00, 300.0, step=5, live=2, waited_ms=300.0,
           late_wakeups=1),
        ev("engine:long_read", 5.00, 2800.0, step=50, live=2,
           waited_ms=2800.0),
        ev("host:late_wakeup", 5.5, 3100.0, tid="7", late_ms=3100.0),
    ]
    text = stalls.lines(events)
    assert text[0].startswith("driver pid 7: host:gc 0 x 0.0 ms, "
                              "host:late_wakeup 1 x 3100.0 ms")
    assert text[2].startswith("worker@ab pid 12: host:gc 1 x 90.0 ms")
    reads = [ln for ln in text if " live: " in ln]
    assert reads[0].endswith(
        "step 5, 2 live: interpreter/host (host:gc, host:late_wakeup)")
    # the driver's late wake-up is another process's: not beside this read
    assert reads[1].endswith("step 50, 2 live: device/transfer")
    # ... and a late wake-up names the other processes that woke late with
    # it: all of them = the host stood still, none = this interpreter
    late = [ln for ln in text if ln.endswith("other processes")]
    assert len(late) == 2 and all(
        ln.endswith("host:late_wakeup with 0 of 1 other processes")
        for ln in late)
    both = events + [ev("host:late_wakeup", 1.03, 60.0, tid="7",
                        late_ms=60.0)]
    text = stalls.lines(both)
    assert any(ln.endswith("with 1 of 1 other processes") for ln in text)
    # ... and the long read beside it was the HOST's, no interpreter's
    assert [ln for ln in text if " live: " in ln][0].endswith(
        "step 5, 2 live: host (host:gc, host:late_wakeup)")
    assert stalls.lines([]) == ["no span"]
    assert "no host:gc" in stalls.lines([ev("proxy:request", 0, 1)])[0]


# ------------------------------------------------- in a rehearsed served cell

def _manifest_with_the_eleven(tmp_path) -> str:
    """The rehearsal's tiny manifest plus the eleven entries of the real
    one, each moved to the tiny cells that report what it moves."""
    real = {x["name"]: x for x in mf.Manifest().data["per_layer"]}
    tiny = json.load(open(os.path.join(mf.ROOT, rehearse.REHEARSAL,
                                       "BENCHMARK.json")))
    cells = {x["name"]: x["workloads"] for x in tiny["end_to_end"]
             if "workloads" in x}
    for name in ELEVEN:
        x = copy.deepcopy(real[name])
        x["workloads"] = cells[x["moves"]]
        tiny["per_layer"].append(x)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(tiny))
    return str(path)


@pytest.mark.parametrize("cell,suffix", [("tiny.serve-closed", ".batch"),
                                         ("tiny.serve-open", ".chat")])
def test_the_readers_in_a_rehearsed_served_cell(tmp_path, cell, suffix):
    """A traced rehearsal of each served kind, on the CPU: every one of the
    cell's new metrics is in the line with a finite value (timings mean
    nothing here; the shares stay shares)."""
    manifest = _manifest_with_the_eleven(tmp_path)
    lines = rehearse.rehearse(cell, 1, seed=2**31 + 151,
                              manifest_path=manifest)
    m = lines[-1]["metrics"]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    mine = sorted(n for n in ELEVEN if n.endswith(suffix))
    assert len(mine) == (5 if suffix == ".batch" else 6)
    for name in mine:
        assert name in m and 0.0 <= m[name]["value"] < 1e7, (name, m)
        assert m[name]["unit"] == ELEVEN[name][0]
    assert m["engine.schedule_blocked_share" + suffix]["value"] <= 100.0
    assert m["host.gc_pause_pct" + suffix]["value"] <= 100.0
    assert not any(n in m for n in ELEVEN if not n.endswith(suffix))
