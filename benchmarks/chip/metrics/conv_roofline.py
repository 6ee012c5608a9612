"""The conv blocks' share of their roofline: the least time of their work
(``work.conv_work``: training forward and backward of the window's real
samples, and the eval forward of the test split, bytes-bound at the
paper's widths, 72 FLOP per byte against a v5e ridge of 240) over the
device time of the ops that do it.  Those are the ops whose call stack
passes through the fused conv kernel or the XLA im2col conv that stands in
for it.  Moves ``samples_per_s``."""

#: (file, function) frames and op names of the conv work.
FRAMES = [("kernels/conv3x3.py", "*"),
          ("kernels/dispatch.py", "conv3x3_bias_relu"),
          ("models/cnn.py", "im2col3x3"),
          ("models/cnn.py", "_im2col_bwd"),
          ("models/cnn.py", "_conv3x3_same_im2col")]
OP_NAMES = ["_fwd_call", "_bwd_call"]


def read(run):
    t = run.trace.attributed_s(FRAMES, OP_NAMES)
    if t <= 0:
        return None
    least, _ = run.least_time(run.work["conv_flops"], run.work["conv_bytes"])
    return 100.0 * least / t
