"""decode_step_roofline.notes: The decode step's share of its memory roofline
for a model of TWO LATENT SHAPES: the family's FLOOR of a step's bytes (bf16
weights outside the routed experts once, the indexers and the head among
them; the held experts a layer the run's live rows touched, as the engine
counted them (``moe:load``); of the cache, for a slot at depth t, the
``min(t, index_topk)`` chosen latents and t index keys on every full layer
and the ``min(t, window)`` ring rows on every sliding layer, never the arrays
the program reads to get them) over the HBM peak, over the step's device
time.  The floor is taken at the run's mean batch and over the depths its
slots really stood at (one an emitted token: neither ``min`` is linear in the
depth).  Memory bound.  None where the family's ``decode_step_bytes`` takes
no depths or no counted experts, where the family counts no ring
(``ring_rows``: another family's cell), and where the engine wrote no
``moe:load`` span.
"""

import inspect
import statistics

from perfbench import moe_load, readers


def read(run):
    ms = readers.program_ms(run, readers.DECODE_STEP)
    steps = readers.counters_delta(run, "steps")
    floor = run.family.shapes.decode_step_bytes
    takes = inspect.signature(floor).parameters
    if not ms or not steps or "depths" not in takes \
            or "experts_touched" not in takes \
            or not hasattr(run.family.shapes, "ring_rows"):
        return None
    touched = moe_load.experts_touched_per_layer_step(run)
    depths = [len(r.prompt) + i for r in run.raw["requests"] if r.arrivals
              for i in range(len(r.tokens))]
    if touched is None or not depths:
        return None
    batch = readers.counters_delta(run, "tokens") / steps
    nbytes = floor(run.config, batch * statistics.mean(depths),
                   experts_touched=touched, depths=depths)
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / (ms / 1e3)
