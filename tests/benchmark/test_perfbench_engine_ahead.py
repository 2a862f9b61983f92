"""``engine.ahead_share.batch`` / ``.chat``: the share of the engine's fused
decode steps that were dispatched while the step before them had not been
read, from its ``engine:ahead`` ring spans (`ray_tpu/serve/decode_session.py`
`_dispatch`).  The readers on hand-made spans, their entries in the root
manifest, and the whole path (engine -> span file -> reader) in two rehearsed
served cells under the rehearsal's manifest with the two entries appended (a
PR that changes the program adds files to the benchmark and edits none).
"""

import json
import os
import types

import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse

NAMES = ("engine.ahead_share.batch", "engine.ahead_share.chat")
CLOSED = ["gpt2-xl.serve-batch-closed", "glm-4.7-flash.serve-agent-closed",
          "trinity-large-preview.serve-mixed-closed",
          "lfm2-8b-a1b.serve-reason-closed"]
CHAT = ["gpt2-medium.serve-chat-open"]


def _run(events):
    return types.SimpleNamespace(stamps={"open": 10.0, "close": 55.0},
                                 _ring_spans=events)


def _span(end_s, **args):
    return {"name": "engine:ahead", "cat": "ahead", "ts": (end_s - 2) * 1e6,
            "dur": 2e6, "args": dict(args, deployment="bench")}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_hand_made_spans(name):
    read = mf.metric_reader(name)
    # the parent of the PR that added the span, or a run with no ring
    assert read(_run([])) is None
    assert read(_run([{"name": "cache:rows", "ts": 12e6, "dur": 2e6,
                       "args": {"steps": 10, "rows_read": 300}}])) is None
    # a window in which no fused step was dispatched
    assert read(_run([_span(20.0)])) is None
    events = [
        _span(9.5, steps=100, steps_ahead=1),       # ended before the window
        _span(12.0, steps=130, steps_ahead=128),
        _span(14.0, steps=70, steps_ahead=70),
        _span(16.0, steps=50),       # a zero argument is absent from a span
        _span(56.0, steps=100, steps_ahead=100),    # ended after it
        {"name": "engine:ahead", "ts": 20e6, "dur": 2e6},    # no arguments
    ]
    assert read(_run(events)) == pytest.approx(100.0 * 198 / 250)
    # a speculating engine: steps, none of them ahead
    assert read(_run([_span(30.0, steps=40)])) == 0.0


def test_root_manifest_lists_both_and_has_no_problem():
    root = mf.Manifest()
    assert mf.problems(root) == []
    got = {x["name"]: x for x in root.data["per_layer"] if x["name"] in NAMES}
    assert set(got) == set(NAMES)
    for name, moves, cells in ((NAMES[0], "serve_tok_s", CLOSED),
                               (NAMES[1], "ttft_p95_ms", CHAT)):
        assert got[name] == {
            "name": name, "unit": "%", "better": "higher",
            "source": "program_span", "layer": "decode engine",
            "moves": moves, "workloads": cells}
    # every cell listed reports the end-to-end metric the share moves
    for x in got.values():
        e2e = next(e for e in root.data["end_to_end"]
                   if e["name"] == x["moves"])
        assert set(x["workloads"]) <= set(e2e["workloads"])


@pytest.mark.parametrize("cell,name", [("tiny.serve-closed", NAMES[0]),
                                       ("tiny.serve-open", NAMES[1])])
def test_rehearsed_cell_reports_the_share(tmp_path, cell, name):
    """A traced rehearsal under the rehearsal's manifest with this PR's
    entries appended: the engine's spans reach the reader through the
    session's span files, and an untraced run leaves the metric out."""
    with open(os.path.join(mf.ROOT, rehearse.REHEARSAL,
                           "BENCHMARK.json")) as f:
        data = json.load(f)
    e2e = next(w for w in data["end_to_end"] if cell in w.get("workloads",
                                                             [cell]))
    data["per_layer"].append({
        "name": name, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "decode engine",
        "moves": e2e["name"], "workloads": [cell]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(data))
    line = rehearse.rehearse(cell, 1, 2718281828, seconds=5.0,
                             manifest_path=str(path))[-1]
    assert line["correct"], line
    value = line["metrics"][name]["value"]
    # one caller at a time or several: whenever a batch outlives two
    # iterations its second step goes out before the first is read
    assert 0.0 < value <= 100.0, line["metrics"][name]
