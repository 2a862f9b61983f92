"""device.idle_share.chat: 1 minus the union of the device's operation
intervals over the traced window, mean over devices.
"""

from perfbench import readers


def read(run):
    return readers.idle_share(run)
