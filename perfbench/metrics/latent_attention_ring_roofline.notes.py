"""latent_attention_ring_roofline.notes: The latent cache kernel's share of
its roofline where it reads a RING (`ray_tpu/ops/latent_attention.py`
`attend_cache` over a window layer's ring of ``kv_lora + rope`` values a
row, masked by the position a column holds; ``latent_attention_cache`` in a
trace, told from the full layers' calls of the same kernel by the
``window_latent`` scope it stands in), IN THE DECODE STEP: the least time the
chip could take for one step's calls (the family's `kernels`: at the run's
mean batch of LIVE slots, a live slot's ``window`` ring rows once, its
heads' queries in and latent rows out, two dots a row a head; all the
sliding layers) over the kernel's summed device time a step.  Memory bound
at a step's one query a slot.  None in an untraced run, where the program
left no map or none with the scope, where no kernel ran in it (XLA's forms:
a CPU, shapes the kernel refuses, the parent) and where the family counts no
such kernel.
"""

import re

from perfbench import opsbytes, readers, scopes, xplane

SCOPE, KERNEL = "window_latent", "latent_attention_ring"


def read(run):
    step = re.compile(readers.DECODE_STEP)
    found = scopes.seconds(
        run, SCOPE, lambda program, path: bool(step.search(program))
        and path.endswith("latent_attention_cache/pallas_call"))
    if found is None or not found[0]:
        return None
    steps = xplane.program(run.trace, readers.DECODE_STEP)["count"]
    served = readers.counters_delta(run, "steps")
    if not steps or not served:
        return None
    batch = readers.counters_delta(run, "tokens") / served
    cost = run.family.shapes.kernels(run.config, batch, 1).get(KERNEL)
    if not cost:
        return None
    least = opsbytes.roofline_seconds(
        cost["step_flops"], cost["step_bytes"], run.peaks())["seconds"] \
        * cost["calls"]
    return 100.0 * least / (found[0] / steps)
