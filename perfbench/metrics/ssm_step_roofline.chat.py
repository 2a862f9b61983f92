"""ssm_step_roofline.chat: The state-space step kernel's share of its
roofline (`ray_tpu/ops/ssd.py` `step_in_place`, ``ssd_step`` in a trace: the
decode step's state update, one call a layer over the stacked states where
they lie): the least time the chip could take for one step's calls (the
family's `kernels`: at the run's mean batch of LIVE slots, a live slot's
float32 state once in and once out and 5 operations a float of it, all the
layers that call it) over the kernel's summed device time a step.  Memory
bound.  None in an untraced run, where no operation of the trace is the
kernel (XLA's form of the step: a CPU, shapes the kernel refuses, the parent
of the PR that added it) and where the family counts no such kernel.
"""

from perfbench import opsbytes, readers, xplane

KERNEL = r"^tpu_custom_call:ssd_step"


def read(run):
    if run.trace is None:
        return None
    steps = xplane.program(run.trace, readers.DECODE_STEP)["count"]
    kernel_s = xplane.op_seconds(run.trace, KERNEL)
    served = readers.counters_delta(run, "steps")
    if not steps or not kernel_s or not served:
        return None
    batch = readers.counters_delta(run, "tokens") / served
    cost = run.family.shapes.kernels(run.config, batch, 1).get("ssd_step")
    if not cost:
        return None
    least = opsbytes.roofline_seconds(
        cost["step_flops"], cost["step_bytes"], run.peaks())["seconds"] \
        * cost["calls"]
    return 100.0 * least / (kernel_s / steps)
