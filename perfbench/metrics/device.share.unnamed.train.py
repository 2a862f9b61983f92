"""device.share.unnamed.train: What is left of 100 beside the ten parts:
operations with no map, no ``op_name`` or no scope in it (compiler-made
copies, loop counters, residual adds), and the time of a program in which no
operation ran.  Near 100 it says the executables came from a compile cache
filled before the scopes existed, as a share of all programs' device seconds
in the traced window (`perfbench/parts.py`: the ``XLA Ops`` events placed by
the op maps the program's compile ledger left, each marked by a
``program:compiled`` span).  None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "unnamed")
