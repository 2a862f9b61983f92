"""Device time by program, part of the model and direction, from the trace a
``--trace 1`` run of a cell left in .perfbench_out and the op maps its
session's processes left (`perfbench/parts.py`), with the five largest
operations of each part and of ``unnamed``, and the session's
``program:compiled`` spans (what each op map cost to make).  Printed, and
written to chiprun_out/<cell>.parts.json.

    python3 -m perfbench.tools.parts_dump <cell> [<session_dir>]

Without ``<session_dir>``: the newest session that holds op maps.  Run it in
the call that made the trace: the session's files are gone with the machine.
"""

import glob
import json
import os
import sys

from perfbench import manifest as mf
from perfbench import parts, runner, spans, xplane


def newest_session() -> str:
    from ray_tpu.core.node import sessions_base
    found = [d for d in glob.glob(os.path.join(sessions_base(), "session_*"))
             if glob.glob(os.path.join(d, "programs", "*.json"))]
    if not found:
        raise SystemExit("parts_dump: no session with op maps under "
                         + sessions_base())
    return max(found, key=os.path.getmtime)


def table(r, maps, top: int = 5):
    """`parts.by_part`'s result as rows, largest first: program, part,
    direction, percent of all programs' device seconds, its largest
    operations with their ``op_name``."""
    total = r["total_s"]
    rows = []
    for key, secs in sorted(r["seconds"].items(), key=lambda kv: -kv[1]):
        program, part, direction = key
        m = parts._map_for(program, r["ops"][key], maps)
        largest = sorted(r["ops"][key].items(), key=lambda kv: -kv[1])[:top]
        rows.append({"program": program, "part": part,
                     "direction": direction, "pct": 100.0 * secs / total,
                     "ops": [[op, 100.0 * s / total, m.get(op, "")]
                             for op, s in largest]})
    return rows


def main(cell: str, session_dir: str = "") -> int:
    session_dir = session_dir or newest_session()
    maps = parts.load_maps(session_dir)
    trace_dir = os.path.join(runner.OUT_DIR, cell, "trace")
    r = parts.by_part(xplane.read(xplane.find(trace_dir))["devices"], maps)
    rows = table(r, maps)
    by_part = {}
    for row in rows:
        by_part[row["part"]] = by_part.get(row["part"], 0.0) + row["pct"]
    compiled = [e["args"] for e in spans._ring_spans(session_dir)
                if e.get("name") == "program:compiled"]
    out = {"cell": cell, "session_dir": session_dir,
           "compiled": compiled,
           "total_s": r["total_s"],
           "no_op_running_pct": 100.0 * r["idle_s"] / r["total_s"],
           "by_part": dict(sorted(by_part.items(), key=lambda kv: -kv[1])),
           "rows": rows}
    path = os.path.join(mf.ROOT, "chiprun_out", cell + ".parts.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"{cell}: {r['total_s']:.3f} device s in programs, "
          f"{out['no_op_running_pct']:.2f} % with no op running")
    print("  ".join(f"{k} {v:.2f}" for k, v in out["by_part"].items()))
    for c in compiled:
        print(f"  map of {c['program']} ({c['module']}): "
              f"{c.get('named', 0)} of {c['instructions']} instructions in "
              f"a part, {c.get('seconds', 0.0)} s")
    for row in rows:
        if row["pct"] < 0.05:
            continue
        print(f"{row['pct']:6.2f} %  {row['program']:<22} {row['part']:<12} "
              f"{row['direction']}")
        for op, pct, name in row["ops"]:
            print(f"    {pct:6.2f}  {op:<42} {name[-70:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
