"""engine.idle_pct.none.batch: Device 0's idle holes of 50 us or more with no ``engine:`` span open
(the engine thread waited with nothing to do, or was between two phases), % of
the traced window; with the five phases it makes the idle time in such holes.
"""

from perfbench import spans


def read(run):
    return spans.engine_idle_pct(run, "none")
