"""perfbench/parts.py: device time by model part on hand-made events and
maps, and the ``device.share.*`` readers on a run that has no map files."""

import json
import os

import pytest

from perfbench import manifest as mf
from perfbench import parts

MS = 1e-3

PATHS = [
    ("jit(step)/jvp()/while/body/closed_call/attention/dot_general",
     ("attention", "forward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/ffn/mul",
     ("ffn", "backward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/exp", ("attention", "recompute")),
    ("jit(fused_step)/while/body/projections/norm/mul", ("norm", "forward")),
    ("jit(step)/while/body/closed_call/transpose(jvp(head))/add_any",
     ("head", "backward")),
    ("jit(step)/jvp()/while/body/dynamic_update_slice",
     ("unnamed", "forward")),
    ("jit(head)/add", ("unnamed", "forward")),
    ("", ("unnamed", "forward")),
]


@pytest.mark.parametrize("path,want", PATHS)
def test_place(path, want):
    assert parts.place(path) == want


def test_the_benchmarks_parts_are_the_programs():
    from ray_tpu.util import device_profile
    assert parts.PARTS == device_profile.MODEL_PARTS
    for path, (part, direction) in PATHS:
        got = device_profile.part_of(path)
        assert (got[0] or parts.UNNAMED, got[1]) == (part, direction)


def _devices():
    """One device, two programs that both own a ``fusion.3``; the step's
    ``while`` holds two of its operations; 1 ms of the step has no
    operation running."""
    ops = [
        # jit_fused_step(7): 0 .. 10 ms
        (0 * MS, 8 * MS, "while.2"),
        (1 * MS, 4 * MS, "fusion.3"),               # attention, in the loop
        (4 * MS, 7 * MS, "fusion.9"),               # ffn, in the loop
        (8 * MS, 9 * MS, "copy.1"),                 # no metadata
        # jit_prefill_chunk(8): 20 .. 26 ms
        (20 * MS, 24 * MS, "fusion.3"),             # experts here
        (24 * MS, 26 * MS, "tpu_custom_call:grouped_matmul.5"),
    ]
    modules = [(0 * MS, 10 * MS, "jit_fused_step(7)"),
               (20 * MS, 26 * MS, "jit_prefill_chunk(8)")]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}


MAPS = {
    "jit_fused_step": [{
        "while.2": "jit(fused_step)/while",
        "fusion.3": "jit(fused_step)/while/body/attention/dot_general",
        "fusion.9": "jit(fused_step)/while/body/ffn/dot_general",
        "copy.1": ""}],
    "jit_prefill_chunk": [
        # two compiled shapes: the second knows this trace's operations
        {"fusion.77": "jit(prefill_chunk)/head/dot_general"},
        {"fusion.3": "jit(prefill_chunk)/while/body/experts/gather",
         "grouped_matmul.5": "jit(prefill_chunk)/while/body/experts/"
                             "grouped_matmul/pallas_call"}],
}


def test_same_named_ops_of_two_programs_land_in_their_own_parts():
    r = parts.by_part(_devices(), MAPS)
    s = r["seconds"]
    assert r["total_s"] == pytest.approx(16 * MS)
    assert s[("jit_fused_step", "attention", "forward")] == \
        pytest.approx(3 * MS)
    assert s[("jit_prefill_chunk", "experts", "forward")] == \
        pytest.approx(6 * MS)
    # the kernel is looked up without `xplane`'s mark
    assert r["ops"][("jit_prefill_chunk", "experts", "forward")] == \
        pytest.approx({"fusion.3": 4 * MS, "grouped_matmul.5": 2 * MS})


def test_a_while_is_not_counted_over_its_body():
    r = parts.by_part(_devices(), MAPS)
    s = r["seconds"]
    # 8 ms of ``while.2`` less the 6 ms of the two operations inside it
    assert s[("jit_fused_step", "unnamed", "forward")] == \
        pytest.approx(2 * MS + 1 * MS)       # its own time and copy.1
    assert s[("jit_fused_step", "ffn", "forward")] == pytest.approx(3 * MS)
    assert sum(s.values()) == pytest.approx(15 * MS)
    assert r["idle_s"] == pytest.approx(1 * MS)


OVERLAPS = [
    # nested: the parent keeps what its children leave
    ([(0, 8, "while"), (1, 4, "a"), (4, 7, "b")], [2, 3, 3]),
    # an asynchronous copy that a later operation outlasts: every instant
    # once (`xplane.self_times` gives 0 + 2 + 9 = 11 of a union of 12)
    ([(0, 10, "w"), (2, 4, "copy-start"), (3, 12, "fusion")], [2, 1, 9]),
    # given in any order, answered in the order given
    ([(5, 6, "late"), (0, 2, "early")], [1, 2]),
    ([], []),
]


@pytest.mark.parametrize("events,want", OVERLAPS)
def test_own_times_add_up_to_the_union(events, want):
    from perfbench import xplane
    got = parts.own_times(events)
    assert got == pytest.approx(want)
    assert sum(got) == pytest.approx(
        xplane.total(xplane.union((a, b) for a, b, _ in events)))


def test_no_map_is_all_unnamed():
    r = parts.by_part(_devices(), {})
    assert {k[1] for k in r["seconds"]} == {"unnamed"}


class _Run:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _traced_run(tmp_path, monkeypatch, maps):
    """A run whose trace is `_devices` and whose session left ``maps``."""
    from perfbench import spans, xplane
    session = tmp_path / "session"
    (session / "programs").mkdir(parents=True)
    for i, (module, found) in enumerate(maps.items()):
        body = {"program": f"p{i}", "maps": [
            {"module": module, "shape": str(j), "instructions": m}
            for j, m in enumerate(found)]}
        (session / "programs" / f"worker-1.p{i}.json").write_text(
            json.dumps(body))
    monkeypatch.setattr(spans, "session_dir", lambda run: str(session))
    monkeypatch.setattr(xplane, "find", lambda d: d)
    monkeypatch.setattr(xplane, "read",
                        lambda path: {"devices": _devices(), "spans": []})
    return _Run(trace={"busy_s": 1.0}, raw={"trace": {"dir": "t"}})


def test_parts_and_unnamed_add_up_to_100(tmp_path, monkeypatch):
    run = _traced_run(tmp_path, monkeypatch, MAPS)
    s = parts.shares(run)
    assert sum(s[p] for p in parts.PARTS) + s["unnamed"] == \
        pytest.approx(100.0)
    assert s["attention"] == pytest.approx(100 * 3 / 16)
    assert s["experts"] == pytest.approx(100 * 6 / 16)
    # the loop's own time, the copy and the millisecond with no op running
    assert s["unnamed"] == pytest.approx(100 * 4 / 16)
    assert s["recompute"] == 0.0
    assert parts.share(run, "ffn") == pytest.approx(100 * 3 / 16)


SHARES = sorted(x["name"] for x in mf.Manifest().data["per_layer"]
                if x["name"].startswith("device.share."))


def test_the_manifest_has_the_seventeen_shares():
    assert len(SHARES) == 17
    for x in mf.Manifest().data["per_layer"]:
        if x["name"] in SHARES:
            assert (x["unit"], x["source"], x["layer"]) == \
                ("%", "device_trace", "model programs")
            assert x["name"].split(".")[2] in parts.PARTS + (
                "recompute", "unnamed")


@pytest.mark.parametrize("name", SHARES)
def test_share_readers_give_nothing_without_map_files(name, tmp_path,
                                                      monkeypatch):
    """The parent of the PR that added the maps: a traced run whose session
    left no ``programs/``, and an untraced run of the change."""
    read = mf.metric_reader(name)
    assert read(_traced_run(tmp_path, monkeypatch, {})) is None
    assert read(_Run(trace=None)) is None


@pytest.mark.parametrize("name", SHARES)
def test_share_readers_read_their_part(name, tmp_path, monkeypatch):
    run = _traced_run(tmp_path, monkeypatch, MAPS)
    want = parts.shares(run)[name.split(".")[2]]
    assert mf.metric_reader(name)(run) == pytest.approx(want)
    assert os.path.exists(os.path.join(mf.BENCH_DIR, "metrics",
                                       name + ".py"))
