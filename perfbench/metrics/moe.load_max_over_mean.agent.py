"""moe.load_max_over_mean.agent: The fullest expert's pairs over the mean
expert's (pairs / experts), a layer a decode step: ``load_max`` over ``pairs``
/ ``experts`` of the window's ``moe:load`` ring spans.  1 is an even spread;
the grouped expert matmul's longest row block is this many times the mean.
"""

from perfbench import moe_load


def read(run):
    s = moe_load.window_sums(run)
    if s is None or not s["pairs"]:
        return None
    return s["load_max"] / (s["pairs"] / s["experts"])
