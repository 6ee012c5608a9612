"""The conv blocks' share of their roofline, for a model with conv
blocks: the least time of their work (the model module's ``conv_work``:
training forward and backward of the window's real samples, and the eval
forward of the test split; for the paper's CNN bytes-bound, 72 FLOP per
byte against a v5e ridge of 240) over the device time of the ops that do
it.  Those are the ops whose call stack passes through the program's conv
block, XLA's own convolution on a TPU or the im2col conv on the CPU.
``None`` for a model without ``conv_work``.  Moves ``samples_per_s``."""

#: (file, function) frames of the conv work.
FRAMES = [("kernels/dispatch.py", "conv3x3_bias_relu"),
          ("models/cnn.py", "im2col3x3"),
          ("models/cnn.py", "_im2col_bwd"),
          ("models/cnn.py", "_conv3x3_same_im2col")]


def read(run):
    conv_work = getattr(run.model, "conv_work", None)
    if conv_work is None:
        return None
    t = run.trace.attributed_s(FRAMES)
    if t <= 0:
        return None
    least, _ = run.least_time(*conv_work(run.setting, run.train_samples,
                                         run.eval_samples))
    return 100.0 * least / t
