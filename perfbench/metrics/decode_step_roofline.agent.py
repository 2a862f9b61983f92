"""decode_step_roofline.agent: The decode step's share of its memory
roofline where the bytes depend on the routing: the family's
``decode_step_bytes`` (weights outside the routed experts once, the head,
the latents of the live rows at the run's mean batch and depth) WITH the
experts a layer the run's live rows touched, as the engine counted them
(``moe:load``), over the HBM peak, over the step's device time.  The count
is a floor: a slot that is not live still computes, and what its token
touched is not counted.  Memory bound.
"""

import statistics

from perfbench import moe_load, readers


def read(run):
    ms = readers.program_ms(run, readers.DECODE_STEP)
    touched = moe_load.experts_touched_per_layer_step(run)
    steps = readers.counters_delta(run, "steps")
    if not ms or touched is None or not steps:
        return None
    batch = readers.counters_delta(run, "tokens") / steps
    reqs = [r for r in run.raw["requests"] if r.arrivals]
    depth = statistics.mean(len(r.prompt) + len(r.tokens) / 2 for r in reqs)
    nbytes = run.family.shapes.decode_step_bytes(
        run.config, batch * depth, experts_touched=touched)
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / (ms / 1e3)
