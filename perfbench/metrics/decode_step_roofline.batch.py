"""decode_step_roofline.batch: The decode step's share of its memory roofline:
the bytes a step must read (bf16 weights once, keys and values of the live
cache rows at the run's mean batch and depth) over the HBM peak, over the
step's device time.  Memory bound.
"""

from perfbench import readers


def read(run):
    ms = readers.program_ms(run, readers.DECODE_STEP)
    nbytes = readers.decode_step_bytes(run)
    if not ms or not nbytes:
        return None
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / (ms / 1e3)
