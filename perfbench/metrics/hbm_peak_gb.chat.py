"""hbm_peak_gb.chat: peak_bytes_in_use of the fullest chip as the chip holder's
memory_stats() gave it after the window.
"""

from perfbench import readers


def read(run):
    return readers.hbm_peak_gb(run)
