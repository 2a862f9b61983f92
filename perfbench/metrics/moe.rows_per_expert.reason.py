"""moe.rows_per_expert.reason: Rows a touched expert gets in one decode
step's grouped matmul: ``pairs`` over ``experts_touched`` of the window's
``moe:load`` ring spans (both summed over expert layers and steps).  The
grouped kernel reads a touched expert's weights once whatever its rows, so
this is the regime it runs in: near 1 every pair pays for a whole expert,
at 4 the 128 pairs of 32 slots share 32 experts' bytes.
"""

from perfbench import moe_load


def read(run):
    s = moe_load.window_sums(run)
    if s is None or not s["experts_touched"]:
        return None
    return s["pairs"] / s["experts_touched"]
