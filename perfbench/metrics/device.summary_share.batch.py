"""device.summary_share.batch: The ``summary`` scope: a summary layer's
pooling of the chunks its new tokens reach (the softmax of a chunk's 16 keys
against a learned vector, the weighted sums) and the write of their rows
(`models/generate.py` `_summary_write`, `ops/eva_attention.py`
`pool_chunks`), as a share of all programs' device seconds in the traced
window.  The scope stands INSIDE ``cache_write``, where `perfbench/parts.py`
(which names ten parts and not this one) counts it: this reader takes the
operations whose ``op_name`` path holds a ``summary`` component apart.  None
in an untraced run, where the program left no map, and where no operation of
any map is in the scope (a program without summaries).
"""

from perfbench import parts, spans, xplane

SCOPE = "summary"


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
