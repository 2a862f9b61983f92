"""engine.prefill_share.agent: The prefill chunk programs' share of all
program time on the device in the traced window (``XLA Modules`` line): what
of the chip's work is prompts, the rest being decode steps and the small
programs of admission.
"""

from perfbench import readers, xplane


def read(run):
    if run.trace is None:
        return None
    every = xplane.program(run.trace, r"")["device_s"]
    if not every:
        return None
    chunks = xplane.program(run.trace, readers.PREFILL_CHUNK)["device_s"]
    return 100.0 * chunks / every
