"""collective.exposed_ms.train: Per optimizer step, the time a collective
operation ran on a device and no other operation did (mean over devices).  0
on one chip.
"""

from perfbench import readers, xplane


def read(run):
    if run.trace is None:
        return None
    steps = xplane.program(run.trace, readers.TRAIN_STEP)["count"]
    if not steps:
        return None
    return 1e3 * run.trace["collective_exposed_s"] / steps
