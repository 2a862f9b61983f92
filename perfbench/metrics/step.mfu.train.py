"""step.mfu.train: An end-to-end utilization, not a kernel's: operations the
forward and backward passes need per token (recomputation not counted) times
tokens per step, over the median step time on the host clock, over chips
times the bf16 peak.
"""

from perfbench import readers


def read(run):
    step_s = readers.median_step_s(run)
    if step_s is None:
        return None
    m = run.raw["train"]
    flops = run.family.shapes.train_flops_per_token(
        run.config, run.traffic["seq_len"]) * m["tokens_per_step"]
    return 100.0 * flops / step_s / (
        run.device["count"] * run.peaks()["bf16_flops"])
