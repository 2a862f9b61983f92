"""selective_scan_roofline.deepreason: The selective-scan chunk kernel's
share of its roofline (`ray_tpu/ops/selective_scan.py` `chunk`,
``selective_scan_chunk`` in a trace: a chunk program's scan, one call a mamba
layer, the state held on the chip across the chunk's tokens): the least time
the chip could take for the traced calls (the family's `kernels`: at the mean
LIVE rows a chunk program carried, a call's operands once, 6 operations a
float of state a token) over the kernel's summed device time.  The kernel is
bound by the vector and transcendental units (an ``exp`` a float of state a
token: 10.5 M a row a layer), NOT by memory, so its share of a MEMORY
roofline is a floor's: it says how far the kernel is from free, not how far
from its own bound.  None in an untraced run, where no operation of the trace
is the kernel (XLA's form: a CPU, shapes the kernel refuses, the parent of
the PR that added it) and where the family counts no such kernel.
"""

from perfbench import opsbytes, readers, spans, xplane

KERNEL = r"^tpu_custom_call:selective_scan_chunk"


def read(run):
    if run.trace is None:
        return None
    programs = xplane.program(run.trace, readers.PREFILL_CHUNK)["count"]
    kernel_s = xplane.op_seconds(run.trace, KERNEL)
    if not programs or not kernel_s:
        return None
    t0, t1 = run.stamps["open"], run.stamps["close"]
    ran = chunks = 0
    for e in spans.ring_spans(run):
        if e.get("name") == "engine:lanes" \
                and t0 <= (e["ts"] + e["dur"]) * 1e-6 <= t1:
            ran += e.get("args", {}).get("programs", 0)
            chunks += e.get("args", {}).get("chunks", 0)
    # the chunk programs' shapes as the engine names them: "<name>:PxC"
    rows = max((int(s.rsplit("x", 1)[-1]) for s in
                run.raw["counters"]["after"].get("program_shapes", ())
                if s.startswith("prefill_chunk:")), default=0)
    if not ran or not chunks or not rows:
        return None
    cost = run.family.shapes.kernels(run.config, chunks / ran, rows).get(
        "selective_scan_chunk")
    if not cost:
        return None
    least = opsbytes.roofline_seconds(
        cost["chunk_flops"], cost["chunk_bytes"], run.peaks())["seconds"] \
        * cost["calls"]
    return 100.0 * least / (kernel_s / programs)
