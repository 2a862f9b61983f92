"""One run of one cell: start the runtime without the TPU probe, let the
cell's traffic kind bring its one chip holder up through the normal entry
point and drive it for the window, then reduce what came back to the
metrics BENCHMARK.json names for the cell and print the result line.

This process never touches JAX's backends (`ray_tpu.init` pins it to the
CPU); the device in the result line is what the chip holder reported.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, Optional

from perfbench import manifest as mf
from perfbench import opsbytes
from perfbench import verdict

OUT_DIR = os.path.join(mf.ROOT, ".perfbench_out")


class BenchFailure(SystemExit):
    """No result: the message goes to stderr, the exit code is 1."""


class Ctx:
    """What a traffic kind's ``run(ctx)`` is given."""

    def __init__(self, *, cell, config, traffic, seed, seconds, trace,
                 extra=None, rehearsal=False):
        self.rehearsal = rehearsal
        self.cell, self.config, self.traffic = cell, config, traffic
        self.family = mf.family_of(config)
        self.seed, self.seconds = seed, seconds
        self.chips = cell["chips"]
        self.extra: Dict[str, Any] = extra or {}
        self.trace_dir = self.scratch("trace") if trace else None

    def scratch(self, name: str) -> str:
        return os.path.join(OUT_DIR, self.cell["name"], name)


class Run:
    """Everything one run produced, as the metric readers see it."""

    def __init__(self, ctx: Ctx, raw: Dict[str, Any], t_start: float,
                 t_init: float):
        self.ctx, self.raw = ctx, raw
        self.config, self.traffic = ctx.config, ctx.traffic
        self.family = ctx.family
        self.seconds, self.chips = ctx.seconds, ctx.chips
        self.stamps = dict(raw["stamps"], start=t_start, init=t_init)
        self.worker = raw["worker"]
        self.device = raw["worker"]["device"]
        self._trace = None

    @property
    def window_s(self) -> float:
        return self.stamps["close"] - self.stamps["open"]

    @property
    def trace(self):
        """The reduced profiler trace, or None in an untraced run."""
        if self._trace is None and self.raw.get("trace"):
            from perfbench import xplane
            try:
                self._trace = xplane.reduce_dir(self.raw["trace"]["dir"])
            except xplane.NoDevicePlane:
                if not self.ctx.rehearsal:     # the CPU has no such plane
                    raise
        return self._trace

    def phases(self) -> Dict[str, float]:
        return phases(self.stamps)

    def peaks(self) -> Dict[str, float]:
        from perfbench import opsbytes
        return opsbytes.peaks(self.device["kind"])


def _process_start() -> float:
    import psutil
    return psutil.Process().create_time()


def start_runtime(chips: int, rehearsal: Optional[Dict[str, Any]]) -> None:
    """The runtime with the chips advertised by configuration, not by the
    probe child (`core/nodelet_main.py`): nothing opens the chip until the
    cell's own worker does."""
    import ray_tpu
    os.environ["PYTHONPATH"] = mf.ROOT + os.pathsep + os.environ.get(
        "PYTHONPATH", "")
    # the chip holder inherits this.  With libtpu's default the TPU client
    # pins its premapped host buffer for 6.4 s of every start (`jax.devices()`
    # 8.3 s against 1.9 s) and gives it back for 3.5 s at exit, and both
    # times drift by seconds from run to run on this host (PERF.md, PR 24):
    # the one phase of set-up that did not repeat.  No cell moves more than
    # a few kilobytes between host and device at a time.
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))
    kwargs: Dict[str, Any] = {"num_tpus": chips}
    config = {"tpu_chips_per_host_override": chips,
              "serve_request_timeout_s": 900.0}
    if rehearsal:
        kwargs = dict(rehearsal["init_kwargs"])
        config.pop("tpu_chips_per_host_override")
    ray_tpu.init(system_config=config, **kwargs)
    total = ray_tpu.cluster_resources()
    if total.get("TPU") != float(chips):
        raise BenchFailure(f"perfbench: the node advertises {total}, not "
                           f"TPU: {chips}")


def stop_runtime() -> Dict[str, Any]:
    """Shut the runtime down, wait until every process of the session is
    reaped, then until the host is quiet again.  Tearing a chip holder down
    stalls the whole host for seconds (PERF.md, PR 21), also after the
    process is gone; a run that ended before that was over would hand the
    stall to whatever starts next, as part of ITS set-up."""
    import ray_tpu
    from ray_tpu import api, serve
    from ray_tpu.core.node import session_processes
    t0 = time.time()
    session_dir = api._local_cluster.session_dir
    serve.shutdown()
    ray_tpu.shutdown()
    deadline = time.time() + 60.0
    while (alive := session_processes(session_dir)) \
            and time.time() < deadline:
        time.sleep(0.05)
    reaped = time.time()
    quiet = wait_until_quiet()
    return {"left": alive, "reaped_s": reaped - t0,
            "quiet_s": time.time() - reaped, **quiet}


def wait_until_quiet(tick: float = 0.02, need_s: float = 2.0,
                     late_s: float = 0.01, cap_s: float = 20.0
                     ) -> Dict[str, float]:
    """Sleep in ticks until ``need_s`` seconds of ticks in a row woke less
    than ``late_s`` late, ``cap_s`` at most."""
    t0 = time.perf_counter()
    good_since, worst, stalled = t0, 0.0, 0.0
    while True:
        t = time.perf_counter()
        time.sleep(tick)
        now = time.perf_counter()
        lag = now - t - tick
        worst = max(worst, lag)
        if lag > late_s:
            good_since = now
            stalled += lag
        if now - good_since >= need_s or now - t0 >= cap_s:
            return {"worst_lag_ms": 1e3 * worst, "stalled_s": stalled}


def phases(stamps: Dict[str, float]) -> Dict[str, float]:
    """Set-up by phase on the host clock, seconds."""
    s = stamps
    return {"runtime_up_s": s["init"] - s["start"],
            "worker_ready_s": s["worker_ready"] - s["init"],
            "weights_s": s["weights"] - s["worker_ready"],
            "warmup_s": s["warm"] - s["weights"],
            "settle_s": s["open"] - s["warm"],
            "setup_s": s["open"] - s["start"]}


def main(argv=None, rehearsal: Optional[Dict[str, Any]] = None,
         extra: Optional[Dict[str, Any]] = None):
    """``rehearsal`` is for tests/benchmark alone (a tiny manifest and a
    node whose ``TPU`` is a stand-in token); ``extra`` is for the tools
    beside this file (sweep, outputs check).  The command line has neither:
    no option of it can make a run small or move it off the chip."""
    t_start = _process_start() if argv is None and not rehearsal \
        else time.time()
    ap = argparse.ArgumentParser(prog="python3 -m perfbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rehearsal = rehearsal or {}
    m = mf.Manifest(rehearsal.get("manifest"), rehearsal.get("traffic_dir"))
    cell = m.cell(args.workload)
    if not rehearsal:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            raise BenchFailure(
                "perfbench: JAX_PLATFORMS=cpu: the node would hand the "
                "cell's worker the CPU; a run needs the TPU")
        if os.environ.get("RAY_TPU_PALLAS_INTERPRET"):
            raise BenchFailure("perfbench: RAY_TPU_PALLAS_INTERPRET is a "
                               "test-only switch; unset it")
    ctx = Ctx(cell=cell, config=m.config(cell["config"]),
              traffic=m.traffic(cell["traffic"]), seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), extra=extra,
              rehearsal=bool(rehearsal))
    shutil.rmtree(os.path.join(OUT_DIR, cell["name"]), ignore_errors=True)
    os.makedirs(os.path.join(OUT_DIR, cell["name"]), exist_ok=True)

    start_runtime(cell["chips"], rehearsal)
    t_init = time.time()
    try:
        raw = mf.kind_module(ctx.traffic["kind"]).run(ctx)
    finally:
        down = stop_runtime()
    if down["left"]:
        raise BenchFailure("perfbench: processes outlived shutdown: "
                           f"{down['left']}")
    if extra and ("rows" in raw or extra.get("raw")):
        return raw

    run = Run(ctx, raw, t_start, t_init)
    device = run.device
    if not rehearsal and (device["platform"] != "tpu"
                          or device["count"] != cell["chips"]):
        raise BenchFailure(
            f"perfbench: the chip holder ran on {device}, not on "
            f"{cell['chips']} TPU chip(s)")
    print(json.dumps({"setup_phases": run.phases(),
                      "chip_holder_pid": run.worker["pid"],
                      "compile_cache_dir": run.worker["compile_cache_dir"],
                      "teardown": down}), flush=True)

    v = verdict.verdict(raw.get("numbers", {}), m.limits(cell["name"]),
                        raw.get("sanity", {}))
    print(json.dumps({"compared": v["compared"], "sanity": v["sanity"]}),
          flush=True)

    metrics: Dict[str, Dict[str, Any]] = {}
    for spec in m.metrics_for(cell["name"], bool(args.trace)):
        try:
            value = mf.metric_reader(spec["name"])(run)
        except opsbytes.UnknownDevice:
            if not rehearsal:            # the CPU is in no table of peaks
                raise
            value = None
        if value is not None:
            metrics[spec["name"]] = {"value": float(value),
                                     "unit": spec["unit"]}
    line: Dict[str, Any] = {
        "correct": v["correct"],
        "attempted": raw["attempted"], "failed": raw["failed"],
        "metrics": metrics,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": device["memory_peak_bytes"]}}
    if args.trace and run.trace is not None:
        line["device"]["busy_s"] = run.trace["busy_s"]
        line["device"]["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["top_ops"][:10],
                             "idle_gaps": run.trace["idle_gaps"][:10]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
