"""device.kda_share.batch: The ``kda`` scope: what a gated delta rule adds to
a layer beside its five big projections (`ray_tpu/models/transformer.py`
`kda_operator`, `ray_tpu/ops/delta_rule.py`: the three convolutions, the
normalisations of queries and keys, the decay's and the gate's small
projections, the recurrence a step or the products and the triangular solve a
block of a chunk, the heads' norm and gate), as a share of all programs'
device seconds in the traced window.  The scope stands INSIDE ``attention``
(its convolutions inside ``conv``), where `perfbench/parts.py` (which names
ten parts and not this one) counts it: this reader takes the operations whose
``op_name`` path holds a ``kda`` component apart, whatever part they fall in.
None in an untraced run, where the program left no map, and where no
operation of any map is in the scope (a program without KDA layers).
"""

from perfbench import parts, spans, xplane

SCOPE = "kda"


def read(run):
    if run.trace is None:
        return None
    maps = parts.load_maps(spans.session_dir(run))
    if not maps or not any(SCOPE in path.split("/") for found in
                           maps.values() for m in found
                           for path in m.values()):
        return None
    r = parts.by_part(
        xplane.read(xplane.find(run.raw["trace"]["dir"]))["devices"], maps)
    if not r["total_s"]:
        return None
    seconds = 0.0
    for (program, _, _), ops in r["ops"].items():
        m = parts._map_for(program, ops, maps)
        seconds += sum(s for op, s in ops.items()
                       if SCOPE in m.get(op, "").split("/"))
    return 100.0 * seconds / r["total_s"]
