"""device.share.attention.train: The ``attention`` scope: scores, mask, softmax
and values over the cache in the served programs (`models/generate.py`
`attend_mha`, `ops/latent_attention.py` `attend_*`); in training
`ops/attention.py` `multi_head_attention`: the flash kernels and the copies
XLA puts around them, or the plain path, as a share of all programs' device
seconds in the traced window (`perfbench/parts.py`: the ``XLA Ops`` events
placed by the op maps the program's compile ledger left, each marked by a
``program:compiled`` span).  None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "attention")
