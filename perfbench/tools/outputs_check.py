"""The readings a limit of ``correct`` is set from, made on the chip in one
process with one set-up: the program's numbers on a dozen seeds or more
(weights made again on the device for each) and the control's on the first
few, the control being the plain reference in fp8 put in the program's
place.  A limit belongs between the program's largest and the control's
smallest, and the control's smallest has to be three times the program's
largest or more (see PERF.md).

    python3 -m perfbench.tools.outputs_check <cell> [--seeds 12] [--control 3] [--first-seed N]
"""

from __future__ import annotations

import argparse
import json
import sys


def summarise(rows):
    out = {}
    for name in rows[0]["program"]:
        prog = [r["program"][name] for r in rows]
        ctl = [r["control"][name] for r in rows if "control" in r]
        out[name] = {"program_max": max(prog), "program_min": min(prog),
                     "control_min": min(ctl) if ctl else None,
                     "separation": (min(ctl) / max(prog)
                                    if ctl and max(prog) > 0 else None)}
    return out


def main(argv=None) -> int:
    from perfbench import runner
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    a = ap.parse_args(argv)
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    raw = runner.main(
        ["--workload", a.cell, "--seed", str(seeds[0]), "--seconds", "1"],
        extra={"check_seeds": seeds, "control_seeds": a.control})
    for row in raw["rows"]:
        print(json.dumps(row), flush=True)
    print(json.dumps({"cell": a.cell, "device": raw["worker"]["device"],
                      "summary": summarise(raw["rows"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
