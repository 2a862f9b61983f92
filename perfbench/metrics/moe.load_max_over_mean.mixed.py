"""moe.load_max_over_mean.mixed: The fullest HELD expert's pairs over the
mean held expert's, a layer a decode step, where the chip holds a share of
the experts: ``load_max`` over ``pairs`` / ``experts`` of the window's
``moe:load`` ring spans, ``pairs`` being the pairs that LANDED on an expert
held here (the step counts them on the device since the layer is told which
experts it holds) and ``experts`` the experts held.  1 is an even spread.
The same reduction as ``moe.load_max_over_mean.agent``, which stays listed
for the cell that holds every expert (a test of the benchmark's pins one
metric to that cell alone).
"""

from perfbench import moe_load


def read(run):
    s = moe_load.window_sums(run)
    if s is None or not s["pairs"]:
        return None
    return s["load_max"] / (s["pairs"] / s["experts"])
