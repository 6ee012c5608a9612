"""The comparison that decides ``correct``.

Set-up drives the window's own compiled one-round program from the seed
through the first ``t_cold_boot + 1`` global rounds (the cold-boot rounds
and the first HieAvg round); the plain reference follows the same rounds
from the same initial weights and input planes.  Each number below is
compared with its limit in ``limits/<workload>.json``, where the readings
it was set from are kept beside it.

A global round stands for a step: the program's state is the global
model, and its change is read per leaf by norm, the gap between the
program's norm and the reference's (not the norm of their difference)
over the reference's norm of that leaf or of the median leaf, whichever
is larger.
"""
from __future__ import annotations

import numpy as np

#: A leaf whose first-round change in the reference is under this share of
#: the median leaf's is nought to rounding and left out of the changes.
STILL_LEAF = 1e-3


def _norms(models: dict, base: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(models[k], np.float64)
                                    - np.asarray(base[k], np.float64)))
            for k in base}


def leaf_gap(prog: dict, ref: dict, w0: dict, ref_first: dict) -> float:
    """Worst leaf's gap between the norms of the program's and the
    reference's change from ``w0``."""
    first = _norms(ref_first, w0)
    med_first = float(np.median(list(first.values())))
    keep = [k for k, v in first.items() if v >= STILL_LEAF * med_first]
    np_, nr = _norms(prog, w0), _norms(ref, w0)
    med = float(np.median([nr[k] for k in keep]))
    return max(abs(np_[k] - nr[k]) / max(nr[k], med, 1e-30) for k in keep)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def numbers(prog: dict, ref: dict, w0: dict) -> dict:
    """The compared numbers of program outputs ``prog`` against reference
    outputs ``ref``; both hold per-round ``loss``, ``correct`` (test
    images right), ``clock``, ``energy`` and the global ``models``."""
    return {
        "loss_gap": _rel(prog["loss"], ref["loss"]),
        "update1_gap": leaf_gap(prog["models"][0], ref["models"][0], w0,
                                ref["models"][0]),
        "update3_gap": leaf_gap(prog["models"][-1], ref["models"][-1], w0,
                                ref["models"][0]),
        "test_images_gap": float(np.max(np.abs(
            np.asarray(prog["correct"], np.float64) - ref["correct"]))),
        "clock_gap": _rel(prog["clock"], ref["clock"]),
        "energy_gap": _rel(prog["energy"], ref["energy"]),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: every number finite and within its limit.
    ``checks`` maps each name to its value and limit."""
    checks, ok = {}, True
    for name, v in values.items():
        lim = float(limits["numbers"][name]["limit"])
        checks[name] = {"value": v, "limit": lim}
        ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, checks
