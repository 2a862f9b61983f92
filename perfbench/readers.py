"""What several metric readers share.  A reader is ``read(run)``; it returns
None where its source has nothing (an untraced run, another traffic kind),
and the runner then leaves the metric out of the line."""

from __future__ import annotations

import statistics
from typing import Optional

from perfbench import xplane

# program names as the trace's ``XLA Modules`` line has them
TRAIN_STEP = r"^jit_step$"
DECODE_STEP = r"^jit_fused_step$"
PREFILL_CHUNK = r"^jit_prefill_chunk$"
# the train step has no Pallas kernel but the flash attention ones
FLASH_KERNELS = r"^tpu_custom_call:"


def phase(run, name: str) -> float:
    return run.phases()[name]


def idle_share(run) -> Optional[float]:
    t = run.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def hbm_peak_gb(run) -> float:
    return run.device["memory_peak_bytes"] / 1e9


def program_ms(run, pattern: str) -> Optional[float]:
    """Device milliseconds per execution of a program."""
    if run.trace is None:
        return None
    p = xplane.program(run.trace, pattern)
    return 1e3 * p["device_s"] / p["count"] if p["count"] else None


def median_step_s(run) -> Optional[float]:
    m = run.raw.get("train")
    if not m or len(m["step_ends"]) < 2:
        return None
    ends = [run.stamps["open"]] + m["step_ends"]
    return statistics.median(b - a for a, b in zip(ends, ends[1:]))


def counters_delta(run, key: str) -> Optional[float]:
    c = run.raw.get("counters", {})
    if "before" not in c:
        return None
    return c["after"][key] - c["before"][key]


def decode_step_bytes(run) -> Optional[float]:
    """Bytes a decode step of this run's traffic must read: the weights and
    the live cache rows at the mean batch and the mean depth a session is
    at over its life (prompt plus half its output)."""
    steps = counters_delta(run, "steps")
    if not steps:
        return None
    batch = counters_delta(run, "tokens") / steps
    reqs = [r for r in run.raw["requests"] if r.arrivals]
    depth = statistics.mean(len(r.prompt) + len(r.tokens) / 2 for r in reqs)
    return run.family.shapes.decode_step_bytes(run.config, batch * depth)
