"""The work a window asks of the chip, counted from the configuration's
shapes: the FLOPs of training, and the least FLOPs and bytes of the conv
and aggregation work, for the rooflines.

The CNN (arXiv:2308.01296 Sec. 6.1.5): conv 3x3 (1 -> c1) + ReLU, conv
3x3 (c1 -> c2) + ReLU, 2x2 max-pool, dense (hw/2 * hw/2 * c2 -> classes),
SAME padding, float32.  A multiply-add is two FLOPs.
"""
from __future__ import annotations

F32 = 4
#: FLOPs per participant element of HieAvg's warm mix and history update
#: (estimate, weighted sum, new previous model, new running-mean delta).
AGG_FLOPS_PER_ELEMENT = 15


def conv_layers(s: dict) -> list[tuple[int, int, int, bool]]:
    """``(pixels, c_in, c_out, needs_dx)`` of each conv layer.  The first
    layer's input is data, so its input gradient is never computed."""
    px = s["image_hw"] ** 2
    return [(px, 1, s["cnn_c1"], False), (px, s["cnn_c1"], s["cnn_c2"], True)]


def n_params(s: dict) -> int:
    dense_in = (s["image_hw"] // 2) ** 2 * s["cnn_c2"]
    return sum(9 * ci * co + co for _, ci, co, _ in conv_layers(s)) \
        + dense_in * s["n_classes"] + s["n_classes"]


def train_flops_per_sample(s: dict) -> int:
    """Forward, weight gradients and input gradients of one training
    sample (88.36 MFLOP at the paper's widths)."""
    dense = (s["image_hw"] // 2) ** 2 * s["cnn_c2"] * s["n_classes"]
    fwd = sum(px * 9 * ci * co for px, ci, co, _ in conv_layers(s)) + dense
    dx = sum(px * 9 * ci * co for px, ci, co, dxn in conv_layers(s) if dxn) \
        + dense
    return 2 * (fwd + fwd + dx)


def conv_work(s: dict, train_samples: int, eval_samples: int
              ) -> tuple[float, float]:
    """Least ``(FLOPs, bytes)`` of the conv blocks (matmul, bias, ReLU)
    for ``train_samples`` forward and backward and ``eval_samples``
    forward.  Bytes: forward reads the input and writes the output once;
    backward reads the output gradient, the output (for the ReLU mask) and
    the input (for the weight gradient), and writes the input gradient
    where it is needed; each training step of ``batch_size`` samples
    reads the weights twice and writes their gradient once.  Activations
    are float32, as the configuration states."""
    flops = bytes_ = 0.0
    for px, ci, co, dxn in conv_layers(s):
        mac = px * 9 * ci * co
        w = F32 * (9 * ci * co + co)
        fwd_b = F32 * px * (ci + co)
        bwd_b = F32 * px * (2 * co + ci + (ci if dxn else 0))
        flops += train_samples * 2 * mac * (3 if dxn else 2) \
            + eval_samples * 2 * mac
        bytes_ += train_samples * (fwd_b + bwd_b + 3 * w / s["batch_size"]) \
            + eval_samples * fwd_b
    return flops, bytes_


def aggregate_work(s: dict, participants: int, outputs: int
                   ) -> tuple[float, float]:
    """Least ``(FLOPs, bytes)`` of warm HieAvg aggregations that mix
    ``participants`` models in all into ``outputs`` models: each
    participant's submission, previous model and mean delta are read once
    and its new previous model and mean delta written once, and each
    output written once."""
    p = n_params(s)
    return (float(AGG_FLOPS_PER_ELEMENT * p * participants),
            float(F32 * p * (5 * participants + outputs)))


def least_time(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth,
    and which of the two bounds it."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = bytes_ / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
