"""decode_step_roofline.chat: The WHOLE decode step's share of its memory
roofline for a dense model whose every layer carries a MATRIX of state a
head beside rows of keys and values: the family's FLOOR of the bytes a step
must move (bf16 weights once, the head among them; for each live slot the
keys and values at its depth on every layer; and each live slot's float32
state and convolution inputs ONCE READ AND ONCE WRITTEN on every layer: the
write is the layer's mathematics, `falcon_h1/shapes.py`
``decode_step_bytes``) over the HBM peak, over the step's device time.  The
floor is taken at the run's mean batch and over the depths its slots really
stood at (one an emitted token), which is what says how many states so many
live rows belong to.  Memory bound.  None where the family's
``decode_step_bytes`` takes no depths, counts experts (those cells have
``.think`` and ``.agent``) or counts no state (`state_bytes`).
"""

import inspect
import statistics

from perfbench import readers


def read(run):
    ms = readers.program_ms(run, readers.DECODE_STEP)
    steps = readers.counters_delta(run, "steps")
    shapes = run.family.shapes
    takes = inspect.signature(shapes.decode_step_bytes).parameters
    if not ms or not steps or "depths" not in takes \
            or "experts_touched" in takes \
            or not hasattr(shapes, "state_bytes"):
        return None
    depths = [len(r.prompt) + i for r in run.raw["requests"] if r.arrivals
              for i in range(len(r.tokens))]
    if not depths:
        return None
    batch = readers.counters_delta(run, "tokens") / steps
    nbytes = shapes.decode_step_bytes(
        run.config, batch * statistics.mean(depths), depths=depths)
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / (ms / 1e3)
