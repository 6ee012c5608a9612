"""Training FLOPs of the traced window's rounds over what the chips could
do in the window at their bf16 peak: real samples x FLOPs per sample
(forward, weight and input gradients, from ``work.py``) / (window seconds
x chips x peak).  Eval, aggregation and padded or empty device slots are
not counted as work.  Moves ``samples_per_s``."""


def read(run):
    if run.trace.window_s <= 0 or run.work["train_flops"] <= 0:
        return None
    return 100.0 * run.work["train_flops"] / (
        run.trace.window_s * run.chips * run.peak["bf16_flops_per_s"])
