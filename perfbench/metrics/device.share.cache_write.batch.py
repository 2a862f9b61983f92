"""device.share.cache_write.batch: The ``cache_write`` scope: the updates of the
slot and batch-1 caches (`models/generate.py`: the `write[kind]` functions,
`place`, `column_writes`, `_ring_write_chunk`, `_place_state`, the slot insert
and gather), as a share of all programs' device seconds in the traced window
(`perfbench/parts.py`: the ``XLA Ops`` events placed by the op maps the
program's compile ledger left, each marked by a ``program:compiled`` span).
None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "cache_write")
