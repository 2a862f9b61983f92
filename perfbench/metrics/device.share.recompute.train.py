"""device.share.recompute.train: Every part's operations under a
``rematted_computation`` component of their ``op_name``: the forward that
`jax.checkpoint` runs again in the backward pass, as a share of all programs'
device seconds in the traced window (`perfbench/parts.py`: the ``XLA Ops``
events placed by the op maps the program's compile ledger left, each marked by
a ``program:compiled`` span).  None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "recompute")
