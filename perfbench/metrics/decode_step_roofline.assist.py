"""decode_step_roofline.assist: The WHOLE decode step's share of its memory
roofline for a model whose layer is two latent-attention sublayers, two dense
feed-forwards and one routed branch with identity experts: the family's FLOOR
of a step's bytes (bf16 weights outside the routed experts once, the head's
slice among them; of each routing layer's HELD experts those the window's
live rows TOUCHED, as the engine counted them (``moe:load``: an identity
expert has nothing to read); the latents of the live rows, a row a SUBLAYER:
`longcat_flash/shapes.py` ``decode_step_bytes``) over the HBM peak, over the
step's device time.  The floor is taken at the run's mean batch and the mean
depth its slots stood at (a latent layer's rows are linear in the depth).
Memory bound.  None where the family counts no identity experts
(``zero_experts``: every other family has a reader of its own), where the
engine wrote no ``moe:load`` span, and in an untraced run.
"""

import statistics

from perfbench import moe_load, readers


def read(run):
    ms = readers.program_ms(run, readers.DECODE_STEP)
    steps = readers.counters_delta(run, "steps")
    shapes = run.family.shapes
    if not ms or not steps or not hasattr(shapes, "zero_experts"):
        return None
    touched = moe_load.experts_touched_per_layer_step(run)
    depths = [len(r.prompt) + i for r in run.raw["requests"] if r.arrivals
              for i in range(len(r.tokens))]
    if touched is None or not depths:
        return None
    batch = readers.counters_delta(run, "tokens") / steps
    nbytes = shapes.decode_step_bytes(
        run.config, batch * statistics.mean(depths), experts_touched=touched)
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / (ms / 1e3)
