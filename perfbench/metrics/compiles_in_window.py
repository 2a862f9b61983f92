"""compiles_in_window: Programs JAX compiled or loaded from its persistent
cache in the chip holder between the window's opening and its end (JAX's own
backend-compile event).  Must be 0.
"""

def read(run):
    return run.raw.get("counters", {}).get("compiles_in_window")
