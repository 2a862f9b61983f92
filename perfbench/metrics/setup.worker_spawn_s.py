"""setup.worker_spawn_s: The nodelet's ``setup:worker_spawn`` span of the chip holder: the nodelet
asked for the process -> its runtime registered.  Lies in setup.worker_ready_s.
"""

from perfbench import spans


def read(run):
    return spans.setup_span_s(run, "setup:worker_spawn")
