"""The model-free part of the work a window asks of the chip: HieAvg's
aggregation over the model's parameters, and the least time of a count
of FLOPs and bytes.  What a model's own layers cost, its module in
``models/`` counts (``train_flops_per_sample`` and, optionally, the work
of its kernels).
"""
from __future__ import annotations

import math

F32 = 4
#: FLOPs per participant element of HieAvg's warm mix and history update
#: (estimate, weighted sum, new previous model, new running-mean delta).
AGG_FLOPS_PER_ELEMENT = 15


def n_params(shapes: dict) -> int:
    """Parameters of a model whose leaves have ``shapes`` (a model
    module's ``param_shapes``)."""
    return sum(math.prod(shape) for shape in shapes.values())


def aggregate_work(n_params: int, participants: int, outputs: int
                   ) -> tuple[float, float]:
    """Least ``(FLOPs, bytes)`` of warm HieAvg aggregations of a model of
    ``n_params`` float32 parameters that mix ``participants`` models in
    all into ``outputs`` models: each participant's submission, previous
    model and mean delta are read once and its new previous model and
    mean delta written once, and each output written once."""
    return (float(AGG_FLOPS_PER_ELEMENT * n_params * participants),
            float(F32 * n_params * (5 * participants + outputs)))


def least_time(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth,
    and which of the two bounds it."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = bytes_ / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
