"""device.gmu_share.batch: The ``gmu`` scope: ALL of a gated memory unit
(`ray_tpu/models/transformer.py` `gmu_operator`: the projection in, the
product with the memory a selective scan made, the projection out), as a
share of all programs' device seconds in the traced window.  The scope stands
AROUND parts of `perfbench/parts.py` (its projections count among
``projections``, the product in ``attention``).  None in an untraced run,
where the program left no map, and where no operation of any map is in the
scope (a program without such layers: the parent).
"""

from perfbench import scopes

SCOPE = "gmu"


def read(run):
    found = scopes.seconds(run, SCOPE)
    return None if found is None else 100.0 * found[0] / found[1]
