"""What stopped the processes of a finished run, from the span files every
run leaves, traced or not: each process's ``host:gc`` (a collection of 1 ms
or more), ``host:late_wakeup`` (its watch thread woke 50 ms or more late) and
``engine:long_read`` (a decode step's read of 250 ms or more) ring spans in
time order, and for every long read the rule's verdict: no late wake-up
beside it = the device or the transfer held the read, one or more = the
interpreter was held or the host did not run the process (`ray_tpu/util/
tracing.py`, `ray_tpu/serve/decode_session.py` `_read`).  A late wake-up
names how many OTHER processes of the session woke late at the same moment:
every one of them = the HOST stood still (each has an interpreter of its
own), none = this process's interpreter was held.

    python3 -m perfbench.tools.stalls [<session_dir>]

Without ``<session_dir>``: the newest session that holds span files.  Run it
in the call that made the run: the session's files are gone with the machine.
"""

import glob
import os
import sys
from typing import Any, Dict, List

from perfbench import spans

NAMES = ("host:gc", "host:late_wakeup", "engine:long_read")


def newest_session() -> str:
    from ray_tpu.core.node import sessions_base
    found = [d for d in glob.glob(os.path.join(sessions_base(), "session_*"))
             if glob.glob(os.path.join(d, "spans", "*.json"))]
    if not found:
        raise SystemExit("stalls: no session with span files under "
                         + sessions_base())
    return max(found, key=os.path.getmtime)


def verdict(read: Dict[str, Any], mine: List[Dict[str, Any]],
            events: List[Dict[str, Any]]) -> str:
    """The rule for one ``engine:long_read`` span, with the spans of its
    own process (``mine``) that overlap it named beside the verdict; where
    another process woke late at the same moment, the host."""
    a, b = read["ts"], read["ts"] + read["dur"]
    beside = [e for e in mine if e["name"] != "engine:long_read"
              and e["ts"] < b and e["ts"] + e["dur"] > a]
    if not read.get("args", {}).get("late_wakeups"):
        return "device/transfer"
    shared = any(late_beside(e, events) for e in beside
                 if e["name"] == "host:late_wakeup")
    names = sorted({e["name"] for e in beside})
    return ("host" if shared else "interpreter/host") + (
        " (" + ", ".join(names) + ")" if names else "")


def late_beside(e: Dict[str, Any], events: List[Dict[str, Any]]) -> int:
    """The OTHER processes with a ``host:late_wakeup`` span that overlaps
    ``e``: each has an interpreter of its own, so what held them all was
    the host."""
    a, b = e["ts"], e["ts"] + e["dur"]
    return len({o.get("tid") for o in events
                if o.get("name") == "host:late_wakeup"
                and o.get("tid") != e.get("tid")
                and o["ts"] < b and o["ts"] + o["dur"] > a})


def by_process(events: List[Dict[str, Any]]
               ) -> Dict[tuple, List[Dict[str, Any]]]:
    """(process label, pid as text) -> its spans of `NAMES`, oldest first."""
    out: Dict[tuple, List[Dict[str, Any]]] = {}
    for e in sorted(events, key=lambda e: e.get("ts", 0)):
        if e.get("name") in NAMES:
            out.setdefault((str(e.get("pid")), str(e.get("tid"))),
                           []).append(e)
    return out


def lines(events: List[Dict[str, Any]]) -> List[str]:
    if not events:
        return ["no span"]
    t0 = min(e.get("ts", 0) for e in events)
    others = len({e.get("tid") for e in events}) - 1
    out = []
    for (label, pid), mine in sorted(by_process(events).items()):
        total = {n: sum(e["dur"] for e in mine if e["name"] == n) * 1e-3
                 for n in NAMES}
        out.append(f"{label} pid {pid}: " + ", ".join(
            f"{n} {sum(1 for e in mine if e['name'] == n)} x "
            f"{total[n]:.1f} ms" for n in NAMES))
        for e in mine:
            args = e.get("args", {})
            what = {"host:gc": "generation %s, %s collected" % (
                        args.get("generation", 0), args.get("collected", 0)),
                    "host:late_wakeup": "with %d of %d other processes" % (
                        late_beside(e, events), others),
                    "engine:long_read": "step %s, %s live: %s" % (
                        args.get("step", 0), args.get("live", 0),
                        verdict(e, mine, events))}[e["name"]]
            out.append(f"  +{(e['ts'] - t0) * 1e-6:9.3f} s  "
                       f"{e['dur'] * 1e-3:9.1f} ms  {e['name']:<17}{what}")
    return out or ["no host:gc, host:late_wakeup or engine:long_read span"]


def main(session_dir: str = "") -> int:
    session_dir = session_dir or newest_session()
    print(session_dir)
    print("\n".join(lines(spans._ring_spans(session_dir))))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
