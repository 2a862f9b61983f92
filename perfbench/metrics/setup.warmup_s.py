"""setup.warmup_s: Weights made until every shape of the window has run once:
compilation or loads from the persistent cache.
"""

from perfbench import readers


def read(run):
    return readers.phase(run, "warmup_s")
