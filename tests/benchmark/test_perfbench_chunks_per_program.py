"""``engine.chunks_per_program.batch`` / ``.chat``: chunks of prompts the
engine consumed over the chunk programs that consumed them, from its
``engine:lanes`` ring spans (`ray_tpu/serve/decode_session.py`
`_count_chunks`).  The readers on hand-made spans, their entries in the root
manifest, and the whole path (engine -> span file -> reader) in a rehearsed
served cell under the rehearsal's manifest with the entry appended (a PR that
changes the program adds files to the benchmark and edits none).
"""

import json
import os
import types

import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse

NAMES = ("engine.chunks_per_program.batch", "engine.chunks_per_program.chat")
CLOSED = ["gpt2-xl.serve-batch-closed", "glm-4.7-flash.serve-agent-closed",
          "trinity-large-preview.serve-mixed-closed",
          "lfm2-8b-a1b.serve-reason-closed",
          "mimo-v2-flash.serve-longreason-closed"]
CHAT = ["gpt2-medium.serve-chat-open"]


def _run(events):
    return types.SimpleNamespace(stamps={"open": 10.0, "close": 55.0},
                                 _ring_spans=events)


def _span(end_s, **args):
    return {"name": "engine:lanes", "cat": "lanes", "ts": (end_s - 2) * 1e6,
            "dur": 2e6, "args": dict(args, deployment="bench")}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_hand_made_spans(name):
    read = mf.metric_reader(name)
    # the parent of the PR that added the span, or a run with no ring
    assert read(_run([])) is None
    assert read(_run([{"name": "engine:ahead", "ts": 12e6, "dur": 2e6,
                       "args": {"steps": 10, "steps_ahead": 9}}])) is None
    # a window in which no prompt was prefilled
    assert read(_run([_span(20.0)])) is None
    events = [
        _span(9.5, programs=100, chunks=100),       # ended before the window
        _span(12.0, programs=50, chunks=196),
        _span(14.0, programs=30, chunks=90),
        _span(16.0, programs=20, chunks=20),
        _span(18.0),                 # a zero argument is absent from a span
        _span(56.0, programs=100, chunks=400),      # ended after it
        {"name": "engine:lanes", "ts": 20e6, "dur": 2e6},    # no arguments
    ]
    assert read(_run(events)) == pytest.approx(306 / 100)
    # prompts one at a time, or a speculating engine: a chunk a program
    assert read(_run([_span(30.0, programs=40, chunks=40)])) == 1.0


def test_root_manifest_lists_both_and_has_no_problem():
    root = mf.Manifest()
    assert mf.problems(root) == []
    got = {x["name"]: x for x in root.data["per_layer"] if x["name"] in NAMES}
    assert set(got) == set(NAMES)
    for name, moves, cells in ((NAMES[0], "serve_tok_s", CLOSED),
                               (NAMES[1], "ttft_p95_ms", CHAT)):
        assert got[name] == {
            "name": name, "unit": "chunks/program", "better": "higher",
            "source": "program_span", "layer": "decode engine",
            "moves": moves, "workloads": cells}
    # every cell listed reports the end-to-end metric the ratio moves
    for x in got.values():
        e2e = next(e for e in root.data["end_to_end"]
                   if e["name"] == x["moves"])
        assert set(x["workloads"]) <= set(e2e["workloads"])


def test_rehearsed_cell_reports_the_ratio(tmp_path, cell="tiny.serve-closed",
                                          name=NAMES[0]):
    """A traced rehearsal under the rehearsal's manifest with this PR's
    entry appended: the engine's spans reach the reader through the
    session's span files, and the ratio lies between a chunk a program and
    the engine's lanes.  (The `.chat` reader is the same code over the same
    span; the open rehearsal is `test_perfbench_engine_ahead.py`'s.)"""
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL,
                           "BENCHMARK.json")) as f:
        data = json.load(f)
    e2e = next(w for w in data["end_to_end"] if cell in w.get("workloads",
                                                             [cell]))
    data["per_layer"].append({
        "name": name, "unit": "chunks/program", "better": "higher",
        "source": "program_span", "layer": "decode engine",
        "moves": e2e["name"], "workloads": [cell]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(data))
    line = rehearse.rehearse(cell, 1, 2718281828, seconds=5.0,
                             manifest_path=str(path))[-1]
    assert line["correct"], line
    assert line["metrics"]["compiles_in_window"]["value"] == 0.0, line
    value = line["metrics"][name]["value"]
    assert 1.0 <= value <= 8.0, line["metrics"][name]
