"""device.share.projections.train: The ``projections`` scope: q/k/v, gate and
output projections, the latent projections, rotary and the per-head q/k norms
(`models/transformer.py` `_qkv`, `_attn_out`; `ops/latent_attention.py`
`queries`, `latents`; `ops/rotary.py`), as a share of all programs' device
seconds in the traced window (`perfbench/parts.py`: the ``XLA Ops`` events
placed by the op maps the program's compile ledger left, each marked by a
``program:compiled`` span).  None where the program left no map.
"""

from perfbench import parts


def read(run):
    return parts.share(run, "projections")
