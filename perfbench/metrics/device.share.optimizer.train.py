"""device.share.optimizer.train: The ``optimizer`` scope: the optimizer's update,
the gradient norm and the accumulation across micro-batches
(`models/transformer.py` `make_train_step`), as a share of all programs'
device seconds in the traced window (`perfbench/parts.py`: the ``XLA Ops``
events placed by the op maps the program's compile ledger left, each marked by
a ``program:compiled`` span).  None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "optimizer")
