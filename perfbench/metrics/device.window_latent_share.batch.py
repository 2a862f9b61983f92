"""device.window_latent_share.batch: The ``window_latent`` scope: ALL of a
window layer's operator over a LATENT cache (`ray_tpu/models/transformer.py`
`latent_scope`, `ray_tpu/models/generate.py` `attend_mla`: the projections
down and up at the window layers' own sizes, the write into the ring, the
absorbed attention over the ring's rows under the position mask, the gate a
head, the output projection), as a share of all programs' device seconds in
the traced window.  The scope stands AROUND parts of `perfbench/parts.py`
(its projections count among ``projections``, its ring write in
``cache_write``, the rest in ``attention``: ten parts, and this is none of
them): this reader takes the operations whose ``op_name`` path holds a
``window_latent`` component apart, whatever part they fall in.  None in an
untraced run, where the program left no map, and where no operation of any
map is in the scope (a program without such layers: the parent).
"""

from perfbench import scopes

SCOPE = "window_latent"


def read(run):
    found = scopes.seconds(run, SCOPE)
    return None if found is None else 100.0 * found[0] / found[1]
