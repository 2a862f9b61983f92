"""device.share.head.train: The ``head`` scope: the unembedding matmul and what
follows it: the loss over the vocabulary in training (`lm_loss`, chunked or
not), the argmax tail of the engine's fused step in serving, as a share of all
programs' device seconds in the traced window (`perfbench/parts.py`: the ``XLA
Ops`` events placed by the op maps the program's compile ledger left, each
marked by a ``program:compiled`` span).  None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "head")
