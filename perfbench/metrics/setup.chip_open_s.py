"""setup.chip_open_s: The chip holder's ``setup:chip_open`` span: the first initialisation of
the TPU backend, made by the entry point before the cell's own code runs.  Lies
in setup.worker_ready_s.
"""

from perfbench import spans


def read(run):
    return spans.setup_span_s(run, "setup:chip_open")
