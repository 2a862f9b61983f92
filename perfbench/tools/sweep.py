"""Find the knee of an open-loop cell once: one process, one set-up, then a
short window at each of a few rates, lowest first, each drained before the
next.  The rate that goes into the traffic file is four fifths of the
highest rate at which the backlog did not grow (time to first token in the
window's last third about what it was in its first third, everything done
soon after the window's end).

    python3 -m perfbench.tools.sweep <cell> --rates 2,3,4,5,6,8 [--seconds 20]
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    from perfbench import runner
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2_300_000_011)
    a = ap.parse_args(argv)
    extra = {"raw": True,
             "sweep": {"rates": [float(r) for r in a.rates.split(",")],
                       "seconds": a.seconds}}
    raw = runner.main(["--workload", a.cell, "--seed", str(a.seed),
                       "--seconds", "1"], extra=extra)
    for row in raw["sweep"]:
        print(json.dumps(row), flush=True)
    print(json.dumps({"cell": a.cell, "device": raw["worker"]["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
