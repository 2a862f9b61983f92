"""decode_step_roofline.deepreason: The WHOLE decode step's share of its
memory roofline for a model whose last layers READ ANOTHER LAYER'S CACHE: the
family's FLOOR of the bytes a step must move (bf16 weights once, the tied
embedding as the head among them; the ONE full layer's rows at each live
slot's depth once a READING layer, eight passes in all; the window layers'
rings up to the window; each live slot's float32 selective-scan state and
convolution inputs once read and once written a mamba layer:
`phi4flash/shapes.py` ``decode_step_bytes``) over the HBM peak, over the
step's device time.  The floor is taken at the run's mean batch and over the
depths its slots really stood at (one an emitted token).  Memory bound.  None
where the family names no ``shared_row_readers`` (every other family has a
reader of its own) and in an untraced run.
"""

import statistics

from perfbench import readers


def read(run):
    ms = readers.program_ms(run, readers.DECODE_STEP)
    steps = readers.counters_delta(run, "steps")
    shapes = run.family.shapes
    if not ms or not steps or not hasattr(shapes, "shared_row_readers"):
        return None
    depths = [len(r.prompt) + i for r in run.raw["requests"] if r.arrivals
              for i in range(len(r.tokens))]
    if not depths:
        return None
    batch = readers.counters_delta(run, "tokens") / steps
    nbytes = shapes.decode_step_bytes(
        run.config, batch * statistics.mean(depths), depths=depths)
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / (ms / 1e3)
