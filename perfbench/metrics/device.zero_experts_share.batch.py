"""device.zero_experts_share.batch: The ``zero_experts`` scope: what the
IDENTITY experts of a router cost the device (`ray_tpu/ops/moe.py`
`routed_ffn`: the chosen identity weights' sum a token times the token's own
row, added in the routed sum's float32 combine; no matmul, no group of the
grouped matmul), as a share of all programs' device seconds in the traced
window.  The scope stands INSIDE ``experts`` (`perfbench/parts.py` counts its
operations there).  An expert that computes nothing should read well under
1 %.  None in an untraced run, where the program left no map, and where no
operation of any map is in the scope (a router without identity outputs: the
parent).
"""

from perfbench import scopes

SCOPE = "zero_experts"


def read(run):
    found = scopes.seconds(run, SCOPE)
    return None if found is None else 100.0 * found[0] / found[1]
