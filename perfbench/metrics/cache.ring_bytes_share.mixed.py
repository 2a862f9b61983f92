"""cache.ring_bytes_share.mixed: The window layers' rings' share of the slot
cache's bytes: ``bytes_ring`` over ``bytes_ring + bytes_full`` of the
window's last ``cache:rows`` ring span.  A ring holds the window and one
chunk of rows a slot whatever the context, so the share falls as ``max_len``
grows; 0 where no layer has a window.
"""

from perfbench import cache_rows


def read(run):
    s = cache_rows.window_sums(run)
    if s is None or not s["bytes_ring"] + s["bytes_full"]:
        return None
    return 100.0 * s["bytes_ring"] / (s["bytes_ring"] + s["bytes_full"])
