"""decode_step_roofline.think: The decode step's share of its memory
roofline for a model most of whose layers carry a MATRIX of state a head: the
family's FLOOR of the bytes a step must move (bf16 weights outside the routed
experts once, the head among them; the held experts a layer the run's live
rows touched, as the engine counted them (``moe:load``); for each live slot
the latents at its depth on the full layers; and on the KDA layers each live
slot's float32 state and convolution inputs ONCE READ AND ONCE WRITTEN: the
write is the layer's mathematics, `kimi_linear/shapes.py`
``decode_step_bytes``) over the HBM peak, over the step's device time.  The
floor is taken at the run's mean batch and over the depths its slots really
stood at (one an emitted token), which is what says how many states so many
live rows belong to.  Memory bound.  None where the family's
``decode_step_bytes`` takes no depths or no counted experts or counts no
state (`state_bytes`), and where the engine wrote no ``moe:load`` span.
"""

import inspect
import statistics

from perfbench import moe_load, readers


def read(run):
    ms = readers.program_ms(run, readers.DECODE_STEP)
    steps = readers.counters_delta(run, "steps")
    shapes = run.family.shapes
    takes = inspect.signature(shapes.decode_step_bytes).parameters
    if not ms or not steps or "depths" not in takes \
            or "experts_touched" not in takes \
            or not hasattr(shapes, "state_bytes"):
        return None
    touched = moe_load.experts_touched_per_layer_step(run)
    depths = [len(r.prompt) + i for r in run.raw["requests"] if r.arrivals
              for i in range(len(r.tokens))]
    if touched is None or not depths:
        return None
    batch = readers.counters_delta(run, "tokens") / steps
    nbytes = shapes.decode_step_bytes(
        run.config, batch * statistics.mean(depths),
        experts_touched=touched, depths=depths)
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / (ms / 1e3)
