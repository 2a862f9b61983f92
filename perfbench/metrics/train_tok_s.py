"""train_tok_s: Tokens of the optimizer steps completed in the window over the
window's length, all chips together.  The window runs from its opening to
the end of the first step that ends at or after --seconds; a step has ended
when `block_until_ready` on its result returns, with the mix's
``steps_in_flight`` - 1 later steps already queued on the chip.
"""

def read(run):
    m = run.raw.get("train")
    if not m:
        return None
    return len(m["step_ends"]) * m["tokens_per_step"] / run.window_s
