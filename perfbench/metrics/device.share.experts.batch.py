"""device.share.experts.batch: The ``experts`` scope: the routed experts: router,
sort and gathers, the grouped matmuls, the scatter back (`ops/moe.py`
`sigmoid_route`, `routed_ffn`, `moe_ffn`), as a share of all programs' device
seconds in the traced window (`perfbench/parts.py`: the ``XLA Ops`` events
placed by the op maps the program's compile ledger left, each marked by a
``program:compiled`` span).  None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "experts")
