"""The longest idle holes of a traced run, one by one: for each, when it
began, how long it was, which device program ended before it and which
started after it, and the host spans open in it (the engine thread's phase
among them).  Reads the trace a ``--trace 1`` run of the cell left in
.perfbench_out and writes chiprun_out/<cell>.holes.json.

    python3 -m perfbench.tools.idle_holes <cell> [<how many>]
"""

import bisect
import json
import os
import sys

from perfbench import manifest as mf
from perfbench import runner, spans, xplane


def main(cell: str, top: int = 40) -> int:
    raw = xplane.read(xplane.find(os.path.join(runner.OUT_DIR, cell,
                                               "trace")))
    dev = raw["devices"][sorted(raw["devices"])[0]]
    merged = xplane.union((a, b) for a, b, _ in dev["ops"])
    holes = [h for h in xplane.gaps(merged)
             if h[1] - h[0] >= xplane.GAP_FLOOR_S]
    t0 = merged[0][0]
    modules = sorted(dev["modules"])
    starts = [m[0] for m in modules]
    rows = []
    for a, b in sorted(holes, key=lambda h: h[0] - h[1])[:top]:
        i = bisect.bisect_right(starts, a)
        before = modules[i - 1][2] if i else None
        after = modules[i][2] if i < len(modules) else None
        open_ = [(n, round(1e3 * (max(a, sa) - a), 3),
                  round(1e3 * (min(b, sb) - a), 3))
                 for sa, sb, n in sorted(raw["spans"])
                 if sa < b and sb > a]
        rows.append({"at_ms": round(1e3 * (a - t0), 3),
                     "ms": round(1e3 * (b - a), 3),
                     "program_before": before, "program_after": after,
                     "spans_open_from_to_ms": open_})
    lengths = sorted(b - a for a, b in holes)
    out = {"holes": len(holes), "seconds": sum(lengths),
           "by_phase": spans.idle_by_phase(xplane.gaps(merged),
                                           raw["spans"]),
           "length_ms_quantiles": {
               q: round(1e3 * lengths[int(q * (len(lengths) - 1))], 3)
               for q in (0.5, 0.9, 0.99, 1.0)} if lengths else {},
           "longest": rows}
    path = os.path.join(mf.ROOT, "chiprun_out", cell + ".holes.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "longest"}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2
                  else 40))
