"""A cell cut to a size a test can hold: the sec6.hieavg deployment's
configuration and traffic with 2 edges x 3 devices, 5 rounds, batch 8,
2 SGD steps, 600 training and 100 test images, at the paper's widths.
Also what the tests of the trace readers share: the recorded traces and
the per-layer metric readers."""
from __future__ import annotations

import copy
import dataclasses
import gzip
import importlib.util
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import cell as cells  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
#: The seed ``record_trace.py`` runs the tiny cell with.
RECORDED_SEED = 5


def tiny_cell(workload: str = "sec6.hieavg") -> "cells.Cell":
    base = cells.Cell.named(workload)
    cfg = copy.deepcopy(base.config)
    cfg["setting"].update(n_edges=2, j_per_edge=3, t_global_rounds=5,
                          batch_size=8)
    cfg["simulator"].update(n_train=600, n_test=100, steps_per_epoch=2)
    if "population" in cfg:
        cfg["population"].update(size=1000, j_cohort=3)
    return dataclasses.replace(base, config=cfg)


def read_trace(name: str, tmp: Path):
    """The trace recorded in ``data/<name>`` (the profiler's
    ``.xplane.pb`` and the window program's text, both gzipped), unpacked
    under ``tmp`` as the profiler lays it out."""
    from devtrace import Trace

    prof = tmp / "plugins" / "profile" / "recorded"
    prof.mkdir(parents=True)
    with gzip.open(DATA / name / "window.xplane.pb.gz") as src, \
            open(prof / "window.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(DATA / name / "window.hlo.txt.gz", "rt") as f:
        return Trace.read(str(tmp), f.read())


def reader(name: str):
    """The per-layer metric module ``metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), cells.HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
