"""device.ssm_share.batch: The ``ssm`` scope: ALL of a state-space mixer
(`ray_tpu/models/transformer.py` `ssm_operator`, `ray_tpu/ops/ssd.py`: the
projections in and out, the convolution, the gates, the scan of a chunk or
the step's update, the gate and the norm by group), as a share of all
programs' device seconds in the traced window.  The scope stands AROUND
parts of `perfbench/parts.py` (its projections count among ``projections``,
its convolution in ``conv``, the rest in ``attention``, which names ten
parts and not this one): this reader takes the operations whose ``op_name``
path holds an ``ssm`` component apart, whatever part they fall in.  None in
an untraced run, where the program left no map, and where no operation of
any map is in the scope (a program without such layers).
"""

from perfbench import parts, spans, xplane

SCOPE = "ssm"


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
