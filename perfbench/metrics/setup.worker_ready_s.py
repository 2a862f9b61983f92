"""setup.worker_ready_s: init done until the chip holder's own code started:
fit() reached the loop, or the replica's constructor ran.
"""

from perfbench import readers


def read(run):
    return readers.phase(run, "worker_ready_s")
