"""flash_attn_roofline: The flash attention kernels' share of their roofline:
the least time the chip could take for their operations and bytes
(opsbytes.flash_attention_cost, forward and both backward kernels, all
layers, one optimizer step, the forward counted once although remat runs it
twice) over the kernels' summed device time per step.
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
    cost = opsbytes.flash_attention_cost(
        run.config, t["sequences_per_step"], t["seq_len"])
    peak, layers = run.peaks(), run.config["n_layer"]
    least = sum(opsbytes.roofline_seconds(
        cost[p + "_flops"], cost[p + "_bytes"], peak)["seconds"]
        for p in ("fwd", "bwd")) * layers / run.device["count"]
    return 100.0 * least / (kernel_s / steps)
