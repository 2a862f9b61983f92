"""cache.rows_read_share.mixed: Of the cache rows the decode steps' live
slots would attend were every layer a full one, the share they did attend:
``rows_read`` over ``rows_if_full`` of the window's ``cache:rows`` ring
spans.  A window layer stops at its window, so the share falls as the
contexts grow past it; 100 where no layer has a window.
"""

from perfbench import cache_rows


def read(run):
    s = cache_rows.window_sums(run)
    if s is None or not s["rows_if_full"]:
        return None
    return 100.0 * s["rows_read"] / s["rows_if_full"]
