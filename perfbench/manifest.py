"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found here by the name the manifest gives it:

    configs/<config>.json    the sizes as run, their source, what was
                             changed, and ``family``: whose sizes they are
    traffic/<mix>.json       kind (which generator) and its parameters
    metrics/<name>.py        ``read(run) -> float | None``
    kinds/<kind>.py          ``run(ctx) -> dict`` for a traffic kind
    families/<family>/       the one place that knows an architecture: the
                             keys of its configuration files and what
                             follows from them (`FAMILY_INTERFACE`)

No function here, in the runner, in a kind or in a reader branches on a
cell's, a configuration's or a family's name, and none but a family's own
reads a key of a configuration file that describes the architecture.
"""

from __future__ import annotations

import ast
import functools
import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


# What a family gives, every function taking the configuration file's
# content ``c`` (a dict).  ``shapes.py`` imports no JAX: the runner, which
# stays off JAX until the window has closed, and the metric readers load it.
# ``model.py`` needs JAX and is loaded in the chip holder alone.
FAMILY_INTERFACE = {
    "shapes": (
        "vocab",                  # (c) -> n: traffic draws token ids below n
        "positions",              # (c) -> positions a cache row may hold
        "count_params",           # (c) -> parameters held
        "train_flops_per_token",  # (c, seq_len): forward and backward,
                                  # recomputation not counted
        "decode_step_bytes",      # (c, live_rows, bytes_per_el=2): bytes a
                                  # decode step must read, weights and cache
        "kernels",                # (c, batch, seq_len) -> {kernel: one
                                  # call's operations and bytes by pass, and
                                  # "calls": the layers that call it a step}
    ),
    "model": (
        "model_config",   # (c, use, **overrides) -> the program's model
                          # configuration, use = "train" | "serve", in the
                          # precision the file states for it
        "param_dtype",    # (c, use) -> the type the weights are held in
        "make",           # (key, c, dtype) -> weights from the key, in the
                          # layout the program takes; jitted by the caller
        "tokens",         # (key, shape, c) -> token ids the traffic may draw
        "logits",         # (params, tokens, c, precision="float32"|"fp8")
        "loss",           # the same arguments -> mean next-token loss
        "loss_and_grad",  # the same arguments -> (loss, gradient tree)
    ),
}


class ManifestError(ValueError):
    pass


class Family:
    """``families/<name>/``, each part loaded by path when first asked for."""

    def __init__(self, name: str):
        self.name = name

    def path(self, part: str) -> str:
        return os.path.join(BENCH_DIR, "families", self.name, part + ".py")

    def _load(self, part: str):
        path = self.path(part)
        if not os.path.exists(path):
            raise ManifestError(f"family {self.name!r} has no {path}")
        return _load_by_path(f"perfbench_family_{self.name}_{part}", path)

    @functools.cached_property
    def shapes(self):
        return self._load("shapes")

    @functools.cached_property
    def model(self):
        return self._load("model")


@functools.lru_cache(maxsize=None)
def family(name: str) -> Family:
    return Family(name)


def family_of(config: Dict[str, Any]) -> Family:
    """The family a configuration file's content names."""
    if "family" not in config:
        raise ManifestError(
            f"configuration {config.get('name')!r} names no family")
    return family(config["family"])


def _load_by_path(module_name: str, path: str):
    """Names hold dots and dashes, so a file the manifest names is loaded
    by path and not imported by name."""
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", module_name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """One parsed BENCHMARK.json.  ``root`` is the directory its relative
    paths start from, ``traffic_dir`` where mixes are looked up (the CPU
    rehearsal keeps a tiny manifest of its own under testdata/)."""

    def __init__(self, path: Optional[str] = None,
                 traffic_dir: Optional[str] = None):
        self.path = path or os.path.join(ROOT, "BENCHMARK.json")
        self.root = ROOT
        self.traffic_dir = traffic_dir or os.path.join(BENCH_DIR, "traffic")
        with open(self.path) as f:
            self.data: Dict[str, Any] = json.load(f)

    # ------------------------------------------------------------ look-ups
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r} in {self.path}; it has "
            f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise ManifestError(f"no configuration {name!r} in {self.path}")

    def traffic(self, name: str) -> Dict[str, Any]:
        with open(os.path.join(self.traffic_dir, name + ".json")) as f:
            return json.load(f)

    def limits(self, cell: str) -> Dict[str, float]:
        """limits/<cell>.json: the limit of each number ``correct``
        compares, with the readings it was set from."""
        path = os.path.join(os.path.dirname(self.traffic_dir), "limits",
                            cell + ".json")
        with open(path) as f:
            return json.load(f)["limits"]

    def metrics_for(self, cell: str, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports in this kind of run: its
        end-to-end metrics with ``--trace 0``, its per-layer ones with
        ``--trace 1``."""
        if not trace:
            return [m for m in self.data["end_to_end"]
                    if cell in m.get("workloads", [cell])]
        reported = {m["name"] for m in self.metrics_for(cell, False)}
        return [m for m in self.data["per_layer"]
                if cell in m.get("workloads", [cell])
                and m["moves"] in reported]


def metric_reader(name: str):
    """``read`` of metrics/<name>.py."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"metric {name!r} has no reader {path}")
    return _load_by_path("perfbench_metric_" + name, path).read


def kind_module(kind: str):
    name = kind.replace("-", "_")
    return importlib.import_module(f"perfbench.kinds.{name}")


# ------------------------------------------------------------- validation

def _family_problems(config: str, body: Dict[str, Any]) -> List[str]:
    """A configuration file without ``family``, a family without its
    files, a file short of a function of `FAMILY_INTERFACE`.  The files are
    parsed, not run: this imports no JAX."""
    if "family" not in body:
        return [f"config {config}: its file names no family"]
    fam, out = family(body["family"]), []
    for part, needed in FAMILY_INTERFACE.items():
        path = fam.path(part)
        if not os.path.exists(path):
            out.append(f"config {config}: family {fam.name!r} has no "
                       f"{os.path.relpath(path, ROOT)}")
            continue
        with open(path) as f:
            defined = {n.name for n in ast.parse(f.read()).body
                       if isinstance(n, ast.FunctionDef)}
        out += [f"config {config}: family {fam.name!r}: {part}.py lacks "
                f"{fn}()" for fn in needed if fn not in defined]
    return out


def problems(m: Manifest) -> List[str]:
    """Everything about the manifest that the contract would refuse and
    that can be seen without a run.  Empty when sound."""
    d, out = m.data, []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(d) != keys:
        out.append(f"keys {sorted(d)} are not exactly {sorted(keys)}")
        return out
    for p in d["paths"]:
        if not os.path.isdir(os.path.join(m.root, p)):
            out.append(f"path {p!r} is not a directory")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in d["paths"])

    def name_ok(kind: str, n: str) -> None:
        if not _NAME.match(n):
            out.append(f"{kind} name {n!r} has characters outside the rule")

    configs = {}
    for c in d["configs"]:
        name_ok("config", c["name"])
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config {c['name']}: keys {sorted(c)}")
        if c["name"] in configs:
            out.append(f"config {c['name']} twice")
        configs[c["name"]] = c
        if not under_paths(c["file"]) or not os.path.exists(
                os.path.join(m.root, c["file"])):
            out.append(f"config file {c['file']!r} missing or outside paths")
        else:
            body = m.config(c["name"])
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                out.append(f"config {c['name']}: reduced differs from file")
            out += _family_problems(c["name"], body)
        for k in c["reduced"]:
            name_ok("reduced key", k)
    cells, pairs = {}, set()
    for w in d["workloads"]:
        name_ok("workload", w["name"])
        name_ok("traffic", w["traffic"])
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w['name']}: keys {sorted(w)}")
        if w["name"] != f"{w['config']}.{w['traffic']}":
            out.append(f"workload {w['name']} is not <config>.<mix>")
        if w["config"] not in configs:
            out.append(f"workload {w['name']}: unknown config")
        if (w["config"], w["traffic"]) in pairs or w["name"] in cells:
            out.append(f"workload {w['name']} twice")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            out.append(f"workload {w['name']}: why of {len(w['why'])} chars")
        try:
            t = m.traffic(w["traffic"])
            kind_module(t["kind"])
        except (OSError, KeyError, ImportError) as e:
            out.append(f"workload {w['name']}: traffic file or kind: {e!r}")
    for c in configs:
        if not any(w["config"] == c for w in cells.values()):
            out.append(f"config {c} is used by no cell")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        out.append(f"{four} four-chip cells of {len(cells)}")

    e2e = {}
    for x in d["end_to_end"]:
        name_ok("metric", x["name"])
        extra = set(x) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra or "bound" not in x:
            out.append(f"metric {x['name']}: keys {sorted(x)}")
        if x["source"] not in ("host_clock", "device_trace"):
            out.append(f"metric {x['name']}: source {x['source']}")
        if not 0 < x.get("bound", 0) <= 0.1:
            out.append(f"metric {x['name']}: bound {x.get('bound')}")
        e2e[x["name"]] = x
    if "setup_s" not in e2e:
        out.append("no setup_s among end_to_end")
    names = set(e2e)
    for x in d["per_layer"]:
        name_ok("metric", x["name"])
        extra = set(x) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra or "bound" in x:
            out.append(f"metric {x['name']}: keys {sorted(x)}")
        if x["name"] in names:
            out.append(f"metric {x['name']} twice")
        names.add(x["name"])
        if x["source"] not in _SOURCES:
            out.append(f"metric {x['name']}: source {x['source']}")
        if x["moves"] not in e2e:
            out.append(f"metric {x['name']} moves unknown {x['moves']}")
            continue
        moved_in = e2e[x["moves"]].get("workloads", list(cells))
        for w in x.get("workloads", moved_in):
            if w not in cells:
                out.append(f"metric {x['name']}: unknown cell {w}")
            elif w not in moved_in:
                out.append(f"metric {x['name']}: cell {w} does not report "
                           f"{x['moves']}")
    for x in list(e2e.values()) + d["per_layer"]:
        if not _UNIT.match(x["unit"]):
            out.append(f"metric {x['name']}: unit {x['unit']!r}")
        if x["better"] not in ("lower", "higher"):
            out.append(f"metric {x['name']}: better {x['better']!r}")
        try:
            metric_reader(x["name"])
        except ManifestError as e:
            out.append(str(e))
    for w in cells:
        if len(m.metrics_for(w, False)) < 2:
            out.append(f"cell {w} reports fewer than two end-to-end metrics")
        if not m.metrics_for(w, True):
            out.append(f"cell {w} reports no per-layer metric")
    if not 1 <= d["run_seconds"] <= 51:
        out.append(f"run_seconds {d['run_seconds']}")
    return out
