"""setup_s: Process start to the window's first instant, host clock, seconds;
compile-cache loads included.
"""

from perfbench import readers


def read(run):
    return readers.phase(run, "setup_s")
