"""setup.weights_s: Chip holder's start until the weights (and the optimizer
state) are on the device: first use of the chip and the jitted initialiser.
"""

from perfbench import readers


def read(run):
    return readers.phase(run, "weights_s")
