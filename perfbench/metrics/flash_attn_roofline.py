"""flash_attn_roofline: The flash attention kernels' share of their roofline:
the least time the chip could take for their operations and bytes
(the family's `kernels`: forward and both backward kernels, all the layers
that call them, one optimizer step, the forward counted once although remat
runs it twice) over the kernels' summed device time per step.
"""

from perfbench import opsbytes, readers, xplane


def read(run):
    if run.trace is None or not run.raw.get("train"):
        return None
    steps = xplane.program(run.trace, readers.TRAIN_STEP)["count"]
    kernel_s = xplane.op_seconds(run.trace, readers.FLASH_KERNELS)
    if not steps or not kernel_s:
        return None
    t = run.traffic
    cost = run.family.shapes.kernels(
        run.config, t["sequences_per_step"], t["seq_len"])["flash_attention"]
    peak, layers = run.peaks(), cost["calls"]
    least = sum(opsbytes.roofline_seconds(
        cost[p + "_flops"], cost[p + "_bytes"], peak)["seconds"]
        for p in ("fwd", "bwd")) * layers / run.device["count"]
    return 100.0 * least / (kernel_s / steps)
