"""decode_step.device_ms.chat: Device time of one execution of the engine's
decode step program.
"""

from perfbench import readers


def read(run):
    return readers.program_ms(run, readers.DECODE_STEP)
