"""setup.runtime_up_s: Process start until ray_tpu.init returned (controller,
nodelet, no TPU probe).
"""

from perfbench import readers


def read(run):
    return readers.phase(run, "runtime_up_s")
