"""moe.experts_touched.agent: Experts of one expert layer that one decode
step's live rows touched, of the model's ``n_routed_experts``: the engine's
``moe:load`` ring spans of the window, ``experts_touched`` over ``steps`` x
expert layers.  A decode step must read that many experts' weights a layer.
"""

from perfbench import moe_load


def read(run):
    return moe_load.experts_touched_per_layer_step(run)
