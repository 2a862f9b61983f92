"""device.indexer_share.batch: The ``indexer`` scope: what learned sparse
attention adds to a layer (`ray_tpu/ops/sparse_index.py`: the index queries,
keys and head weights, every visible row's score, the exact choice of the
``index_topk`` best), as a share of all programs' device seconds in the
traced window.  The scope stands INSIDE ``attention``, where
`perfbench/parts.py` (which names ten parts and not this one) counts it (the
rotary turn of the index queries and keys is `ops/rotary.py`'s and falls in
``projections``): this reader takes the operations whose ``op_name`` path
holds an ``indexer`` component apart, whatever part they fall in.  None in an
untraced run, where the program left no map, and where no operation of any
map is in the scope (a program without an indexer).
"""

from perfbench import parts, spans, xplane

SCOPE = "indexer"


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
