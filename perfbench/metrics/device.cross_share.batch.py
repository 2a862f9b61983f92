"""device.cross_share.batch: The ``cross`` scope: ALL of a cross layer's
operator (`ray_tpu/models/transformer.py` `cross_scope`,
`ray_tpu/models/generate.py` `attend_mha`: its own query projection, the
attention over the LAST FULL layer's cached rows, the pairs' subtraction and
norm, the output projection), as a share of all programs' device seconds in
the traced window.  The scope stands AROUND parts of `perfbench/parts.py`
(its projections count among ``projections``, the rest in ``attention``).
None in an untraced run, where the program left no map, and where no
operation of any map is in the scope (a program without such layers: the
parent).
"""

from perfbench import scopes

SCOPE = "cross"


def read(run):
    found = scopes.seconds(run, SCOPE)
    return None if found is None else 100.0 * found[0] / found[1]
