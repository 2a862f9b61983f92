"""device.share.ffn.train: The ``ffn`` scope: the dense and the shared feed-
forwards (`models/transformer.py` `_glu`), as a share of all programs' device
seconds in the traced window (`perfbench/parts.py`: the ``XLA Ops`` events
placed by the op maps the program's compile ledger left, each marked by a
``program:compiled`` span).  None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "ffn")
