"""step.device_ms.train: Device time of one execution of the train step program
(mean over devices and steps).
"""

from perfbench import readers


def read(run):
    return readers.program_ms(run, readers.TRAIN_STEP)
