"""BENCHMARK.json against the contract, as far as it shows without a run,
and the rule that the harness is driven by data."""

import json
import os
import re
import subprocess
import sys

import pytest

from perfbench import manifest as mf
from perfbench.tools import rehearse


def _manifests():
    return {"root": mf.Manifest(), "rehearsal": rehearse.manifest()}


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


def _harness_sources():
    """(path, text) of every Python file of the harness and its tests that
    is no family's own: not under perfbench/families/, not a family's tests
    (test_perfbench_family_<family>.py)."""
    for top in mf.Manifest().data["paths"]:
        for base, _dirs, files in os.walk(os.path.join(mf.ROOT, top)):
            if os.path.join("perfbench", "families") in base:
                continue
            for f in sorted(files):
                if f.endswith(".py") \
                        and not f.startswith("test_perfbench_family_"):
                    path = os.path.join(base, f)
                    with open(path) as src:
                        yield os.path.relpath(path, mf.ROOT), src.read()


def _config_files():
    for m in _manifests().values():
        for c in m.data["configs"]:
            yield m.config(c["name"])


def test_the_harness_names_no_cell_no_configuration_and_no_family():
    """A later PR adds cells, configurations and families as files and
    entries; code that branched on a name would need an edit for each."""
    d = mf.Manifest().data
    names = [w["name"] for w in d["workloads"]] \
        + [c["name"] for c in d["configs"]] \
        + [w["traffic"] for w in d["workloads"]]
    quoted = {c["family"] for c in _config_files()} \
        | set(os.listdir(os.path.join(mf.BENCH_DIR, "families")))
    rx = re.compile("|".join(
        [re.escape(n) for n in names]
        + ["[\"']" + re.escape(n) + "[\"']" for n in sorted(quoted)]))
    for path, text in _harness_sources():
        if path.startswith("tests") or "testdata" in path:
            continue               # the tests name what they test
        assert not rx.search(text), (path, rx.search(text))


# keys of a configuration file that say nothing of the architecture
GENERAL_KEYS = {"name", "family", "source", "reduced", "changed", "assumed",
                "departures", "deployment", "precision", "limits"}


def test_only_a_family_reads_the_keys_of_its_architecture():
    """Every other key of every configuration file, and ``published``
    with them, is some family's: quoted anywhere else in the harness or its
    tests, a configuration of another family could not pass there."""
    keys = {k for c in _config_files() for k in c} - GENERAL_KEYS
    assert {"n_embd", "n_head", "n_layer", "n_inner", "n_positions",
            "vocab_size", "published"} <= keys
    rx = re.compile("[\"'](" + "|".join(map(re.escape, sorted(keys)))
                    + ")[\"']")
    found = [(path, m.group(1)) for path, text in _harness_sources()
             if path != os.path.relpath(__file__, mf.ROOT)
             for m in [rx.search(text)] if m]
    assert not found, found


def test_a_familys_shapes_load_without_jax():
    """The runner stays off JAX until the window has closed (importing it
    is seconds of set-up), and it asks the family what the traffic may
    draw before that."""
    names = sorted({c["family"] for c in _config_files()})
    code = ("import sys; from perfbench import manifest as mf; "
            f"[mf.family(n).shapes for n in {names!r}]; "
            "sys.exit('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], cwd=mf.ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]


def _broken_config(monkeypatch, edit):
    """The root manifest with its first configuration's file rewritten."""
    d = json.loads(open(mf.Manifest().path).read())
    with open(os.path.join(mf.ROOT, d["configs"][0]["file"])) as f:
        body = json.load(f)
    edit(body)
    monkeypatch.setattr(mf.Manifest, "config",
                        lambda self, name, _orig=mf.Manifest.config:
                        body if name == d["configs"][0]["name"]
                        else _orig(self, name))
    return " ".join(mf.problems(mf.Manifest()))


def test_a_configuration_without_family_is_seen(monkeypatch):
    found = _broken_config(monkeypatch, lambda body: body.pop("family"))
    assert "names no family" in found, found


def test_an_unknown_family_is_seen(monkeypatch):
    found = _broken_config(monkeypatch,
                           lambda body: body.update(family="no-such"))
    assert "family 'no-such' has no perfbench/families/no-such/shapes.py" \
        in found and "no-such/model.py" in found, found
    with pytest.raises(mf.ManifestError, match="no-such"):
        mf.family("no-such").shapes


def test_a_family_short_of_a_function_is_seen(tmp_path, monkeypatch):
    """A family copied without its ``loss_and_grad`` and ``kernels``."""
    src = os.path.join(mf.BENCH_DIR, "families", "gpt2")
    monkeypatch.setattr(mf, "BENCH_DIR", str(tmp_path))
    os.makedirs(tmp_path / "families" / "short")
    for part, drop in (("model", "loss_and_grad"), ("shapes", "kernels")):
        with open(os.path.join(src, part + ".py")) as f:
            text = f.read().replace(f"def {drop}(", f"def _{drop}(")
        (tmp_path / "families" / "short" / (part + ".py")).write_text(text)
    found = " ".join(mf._family_problems("c", {"family": "short"}))
    assert "model.py lacks loss_and_grad()" in found, found
    assert "shapes.py lacks kernels()" in found, found
