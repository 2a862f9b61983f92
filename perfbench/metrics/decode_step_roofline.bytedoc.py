"""decode_step_roofline.bytedoc: The decode step's share of its memory
roofline for a model whose cache has NO full layer: the family's floor of a
step's bytes (bf16 weights once; of the cache what the live slots ATTEND: a
slot at depth t its window's ``t % window + 1`` ring rows and a summary row
for each chunk of the windows before, never the dense arrays the program
reads to get them) over the HBM peak, over the step's device time.  The
floor is taken at the run's mean batch and over the depths its slots really
stood at (one an emitted token: where in its window a slot stands decides
what it reads, which `readers.decode_step_bytes`' slots x depth cannot say).
Memory bound.  None where the family's ``decode_step_bytes`` takes no depths.
"""

import inspect
import statistics

from perfbench import readers


def read(run):
    ms = readers.program_ms(run, readers.DECODE_STEP)
    steps = readers.counters_delta(run, "steps")
    floor = run.family.shapes.decode_step_bytes
    if not ms or not steps \
            or "depths" not in inspect.signature(floor).parameters:
        return None
    batch = readers.counters_delta(run, "tokens") / steps
    depths = [len(r.prompt) + i for r in run.raw["requests"] if r.arrivals
              for i in range(len(r.tokens))]
    if not depths:
        return None
    nbytes = floor(run.config, batch * statistics.mean(depths),
                   depths=depths)
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / (ms / 1e3)
