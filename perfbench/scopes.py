"""Device time of the operations that stand in a `jax.named_scope` which is
none of the model's parts (`perfbench/parts.py` `PARTS`) but stands AROUND
some of them (``window_latent``; ``ssm``, ``kda`` and ``indexer`` are read
the same way by their own readers): the op maps the programs left say which
operations those are.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

from perfbench import parts, spans, xplane


def seconds(run, scope: str,
            keep: Callable[[str, str], bool] = lambda program, path: True
            ) -> Optional[Tuple[float, float]]:
    """(device seconds of the traced programs' operations whose ``op_name``
    path holds the component ``scope`` and that ``keep(program, path)``
    takes, all programs' device seconds), each the mean over devices; None in
    an untraced run, where the program left no map, where no operation of any
    map is in the scope (a program without such layers: the parent of the PR
    that added the scope) and where no program ran in the trace."""
    if run.trace is None:
        return None
    maps = parts.load_maps(spans.session_dir(run))
    if not maps or not any(scope in path.split("/") for found in
                           maps.values() for m in found
                           for path in m.values()):
        return None
    r = parts.by_part(
        xplane.read(xplane.find(run.raw["trace"]["dir"]))["devices"], maps)
    if not r["total_s"]:
        return None
    total = 0.0
    for (program, _, _), ops in r["ops"].items():
        m = parts._map_for(program, ops, maps)
        total += sum(s for op, s in ops.items()
                     if scope in m.get(op, "").split("/")
                     and keep(program, m[op]))
    return total, r["total_s"]
