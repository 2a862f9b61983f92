"""BENCHMARK.json against the contract, as far as it shows without a run,
and the rule that the harness is driven by data."""

import json
import os
import re

import pytest

from perfbench import manifest as mf

REHEARSAL = os.path.join(mf.BENCH_DIR, "testdata", "rehearsal")


def _manifests():
    return {"root": mf.Manifest(),
            "rehearsal": mf.Manifest(
                os.path.join(REHEARSAL, "BENCHMARK.json"),
                os.path.join(REHEARSAL, "traffic"))}


@pytest.mark.parametrize("which", ["root", "rehearsal"])
def test_manifest_has_no_problem(which):
    assert mf.problems(_manifests()[which]) == []


def test_issue_names_letter_for_letter():
    d = mf.Manifest().data
    # gap_p95_ms is read per layer: its run-to-run spread is over half of
    # the widest bound an end-to-end metric may have (PERF.md, PR 24)
    assert {m["name"] for m in d["end_to_end"]} == {
        "train_tok_s", "serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert "gap_p95_ms" in {m["name"] for m in d["per_layer"]}
    assert {c["name"] for c in d["configs"]} == {"gpt2-medium", "gpt2-xl"}
    allowed = {"gpt2-medium.train-1024", "gpt2-xl.serve-batch-closed",
               "gpt2-medium.serve-chat-open", "gpt2-xl.train-fsdp4"}
    cells = {w["name"]: w for w in d["workloads"]}
    assert set(cells) <= allowed and cells
    assert [n for n, w in cells.items() if w["chips"] == 4] in (
        [], ["gpt2-xl.train-fsdp4"])
    assert d["command"][:3] == ["python3", "-m", "perfbench"]
    assert len(json.dumps(d)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"]
                                  for w in mf.Manifest().data["workloads"]])
def test_every_cell_finds_its_files_by_name(cell):
    m = mf.Manifest()
    w = m.cell(cell)
    c, t = m.config(w["config"]), m.traffic(w["traffic"])
    assert c["name"] == w["config"]
    assert callable(mf.kind_module(t["kind"]).run)
    assert set(m.limits(cell))                      # a limit for `correct`
    for trace in (False, True):
        specs = m.metrics_for(cell, trace)
        assert specs
        for s in specs:
            assert callable(mf.metric_reader(s["name"]))
    e2e = {s["name"] for s in m.metrics_for(cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    # every per-layer metric of the cell moves a metric the cell reports
    assert {s["moves"] for s in m.metrics_for(cell, True)} <= e2e


def test_a_broken_manifest_is_seen(tmp_path):
    d = json.loads(open(mf.Manifest().path).read())
    d["workloads"][0]["chips"] = 2
    d["per_layer"][0]["moves"] = "no_such_metric"
    d["end_to_end"][0]["unit"] = "tokens per second"
    for w in d["workloads"]:
        w["chips"] = 4 if w["chips"] != 2 else 2
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(d))
    found = " ".join(mf.problems(mf.Manifest(str(p))))
    for part in ("chips 2", "moves unknown", "unit 'tokens per second'",
                 "four-chip cells"):
        assert part in found, found


def test_the_harness_names_no_cell_and_no_configuration():
    """A later PR adds cells as files and entries; code that branched on a
    name would need an edit for each."""
    d = mf.Manifest().data
    names = [w["name"] for w in d["workloads"]] \
        + [c["name"] for c in d["configs"]] \
        + [w["traffic"] for w in d["workloads"]]
    rx = re.compile("|".join(re.escape(n) for n in names))
    for base, _dirs, files in os.walk(mf.BENCH_DIR):
        if "testdata" in base:
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(base, f)).read()
                assert not rx.search(text), (base, f, rx.search(text))
