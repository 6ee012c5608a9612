"""Training FLOPs of the traced window's rounds over what the chips could
do in the window at their bf16 peak: real samples x the model's FLOPs per
sample (forward, weight and input gradients, from its module's
``train_flops_per_sample``) / (window seconds x chips x peak).  Eval,
aggregation and padded or empty device slots are not counted as work.
Moves ``samples_per_s``."""


def read(run):
    flops = run.train_samples * run.model.train_flops_per_sample(run.setting)
    if run.trace.window_s <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (
        run.trace.window_s * run.chips * run.peak["bf16_flops_per_s"])
