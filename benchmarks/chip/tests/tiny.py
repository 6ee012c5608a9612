"""A cell cut to a size a test can hold: the sec6.hieavg deployment's
configuration and traffic with 2 edges x 3 devices, 5 rounds, batch 8,
2 SGD steps, 600 training and 100 test images, at the paper's widths."""
from __future__ import annotations

import copy
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import cell as cells  # noqa: E402


def tiny_cell(workload: str = "sec6.hieavg") -> "cells.Cell":
    base = cells.Cell.named(workload)
    cfg = copy.deepcopy(base.config)
    cfg["setting"].update(n_edges=2, j_per_edge=3, t_global_rounds=5,
                          batch_size=8)
    cfg["simulator"].update(n_train=600, n_test=100, steps_per_epoch=2)
    if "population" in cfg:
        cfg["population"].update(size=1000, j_cohort=3)
    return dataclasses.replace(base, config=cfg)
