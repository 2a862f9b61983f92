"""The reduction from a profiler trace to numbers: the interval arithmetic on
intervals made by hand, and the whole of it on a small trace recorded on a
TPU v5e (perfbench/tools/record_fixture.py) and kept beside the benchmark."""

import os

import pytest

from perfbench import manifest as mf
from perfbench import xplane

FIXTURE = os.path.join(mf.BENCH_DIR, "testdata", "small.xplane.pb")


def test_union_total_gaps():
    merged = xplane.union([(5, 6), (0, 2), (1, 3), (3, 4), (5.5, 5.8)])
    assert merged == [(0, 4), (5, 6)]
    assert xplane.total(merged) == 5
    assert xplane.gaps(merged) == [(4, 5)]
    assert xplane.union([]) == [] and xplane.gaps([]) == []


def test_subtract_is_what_the_other_does_not_cover():
    coll = [(0, 10), (20, 30)]
    rest = [(2, 3), (8, 12), (19, 21), (25, 26)]
    assert xplane.subtract(coll, rest) == [
        (0, 2), (3, 8), (21, 25), (26, 30)]
    assert xplane.subtract(coll, []) == coll
    assert xplane.subtract([(0, 1)], [(0, 1)]) == []


def test_gaps_are_named_by_the_spans_open_in_them():
    holes = [(0.0, 0.00001), (1.0, 1.5), (2.0, 2.25), (3.0, 3.1)]
    spans = [(0.9, 1.6, "handle:next_chunk"), (1.2, 1.3, "handle:start"),
             (1.9, 2.5, "handle:next_chunk"), (5.0, 6.0, "handle:end")]
    got = xplane.attribute_gaps(holes, spans)
    assert got == pytest.approx({
        "under_50us": 0.00001,
        "handle:next_chunk+handle:start": 0.5,
        "handle:next_chunk": 0.25,
        "none": 0.1})


def test_reduce_on_intervals_made_by_hand():
    raw = {"devices": {
        "/device:TPU:0": {
            "ops": [(0.0, 1.0, "fusion.1"), (1.0, 1.5, "all-gather.2"),
                    (2.0, 3.0, "fusion.1"), (3.0, 3.5, "all-gather.2")],
            "modules": [(0.0, 1.5, "jit_step(7)"), (2.0, 3.5, "jit_step(7)")]},
        "/device:TPU:1": {
            "ops": [(0.0, 1.5, "fusion.1"), (2.0, 3.5, "fusion.1")],
            "modules": [(0.0, 1.5, "jit_step(7)"),
                        (2.0, 3.5, "jit_step(7)")]}},
        "spans": [(1.4, 2.1, "train:block"), (3.9, 4.0, "train:step")]}
    r = xplane.reduce(raw)
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["window_s"] == pytest.approx(4.0)
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(2.5)]
    assert r["collective_exposed_s"] == pytest.approx(0.5)    # (1.0 + 0) / 2
    step = xplane.program(r, r"^jit_step$")
    assert step == pytest.approx(
        {"count": 2, "device_s": 3.0, "mean_gap_s": 0.5})
    assert r["idle_gaps"] == [["train:block", pytest.approx(0.5)]]
    assert xplane.op_seconds(r, "all-gather") == pytest.approx(0.5)
    with pytest.raises(xplane.NoDevicePlane):
        xplane.reduce({"devices": {}, "spans": []})


def test_a_while_around_a_collective_does_not_hide_it():
    """The op line nests: a ``while`` spans its body.  Own time goes to the
    body's operations, and only operations that hold no other count as
    compute that can hide a collective."""
    ops = [(0.0, 10.0, "while.1"), (0.0, 4.0, "fusion.2"),
           (4.0, 6.0, "all-gather.3"), (6.0, 10.0, "fusion.2"),
           (10.0, 11.0, "all-reduce.4")]
    assert sorted(xplane.self_times(ops)) == sorted([
        (0.0, "while.1", False), (4.0, "fusion.2", True),
        (2.0, "all-gather.3", True), (4.0, "fusion.2", True),
        (1.0, "all-reduce.4", True)])
    r = xplane.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [(0.0, 11.0, "jit_step(1)")]}}, "spans": []})
    assert r["busy_s"] == pytest.approx(11.0)
    assert r["collective_exposed_s"] == pytest.approx(3.0)
    assert r["ops"]["fusion.2"] == pytest.approx(8.0)
    assert r["ops"]["while.1"] == pytest.approx(0.0)


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce(xplane.read(FIXTURE))


def test_recorded_trace_busy_idle_and_programs(recorded):
    r = recorded
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    prog = xplane.program(r, r"^jit_small_program$")
    assert prog["count"] == 3
    # the three executions are the busy time, the 20 ms sleeps the idle time
    assert prog["device_s"] == pytest.approx(r["busy_s"], rel=0.05)
    assert prog["mean_gap_s"] > 0.02
    idle = 1 - r["busy_s"] / r["window_s"]
    assert 0.5 < idle < 1.0


def test_recorded_trace_kernel_sum_and_gap_names(recorded):
    r = recorded
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"], rel=0.05)
    assert r["top_ops"][0][1] >= r["top_ops"][-1][1]
    assert len(r["top_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    named = dict(r["idle_gaps"])
    # the device waits between steps while no benchmark span is open (the
    # sleep) or while the next step's span has just opened
    assert set(named) <= {"none", "train:step", "under_50us"}
    assert sum(named.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=0.35)
