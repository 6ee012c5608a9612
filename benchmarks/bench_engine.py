"""Engine vs legacy-loop throughput — writes ``BENCH_engine.json``.

Measures rounds/sec for the same REDUCED (N=5 edges) deployment driven by

  * the legacy per-edge Python loop (``BHFLSimulator.run_legacy``), and
  * the fully-jitted batched engine (``BHFLSimulator.run`` →
    ``repro.fl.engine.run_engine``),

plus a Fig. 3-style 4-point grid as one ``run_sweep`` batched call.
Competing variants share an ``interleaved_best_of`` timing loop (legacy
and engine back-to-back each rep, likewise the two sweep paths) so slow
drift in box load never reads as a path difference; each row is
best-of-``REPS`` after a warm-up run (jit caches hot), so the numbers
track steady-state orchestration cost, not compile time.

The local-step budget is 1 SGD step per epoch: the engine's advantage is the
orchestration it eliminates (per-edge dispatch, host-side batching, per-round
syncs), and heavier local compute is identical FLOPs on both paths — see
EXPERIMENTS.md §Perf for the step-budget sensitivity.

The JSON also records the kernel-plane coverage of the engine rows — which
round phases run as fused Pallas kernels under the resolved ``kernel_mode``
(``repro.kernels.fused_phase_coverage``), the ``padded_flop_frac``-style
column for the kernel plane.  On CPU ``auto`` resolves to ``xla`` and the
fraction is 0.0; on a TPU the same rows report full fused coverage.

  PYTHONPATH=src python -m benchmarks.run --only engine --emit-json
"""
from __future__ import annotations

import dataclasses
import json

from repro.configs.bhfl_cnn import REDUCED
from repro.fl import BHFLSimulator, run_sweep
from repro.kernels import fused_phase_coverage, resolve_kernel_mode

from .common import Csv, interleaved_best_of

T_ROUNDS = 20
KW = dict(n_train=2000, n_test=400, steps_per_epoch=1, normalize=True)
REPS = 3


def _setting():
    return dataclasses.replace(REDUCED, t_global_rounds=T_ROUNDS)


def _sim(**kw):
    return BHFLSimulator(_setting(), "hieavg", "temporary", "temporary",
                         **KW, **kw)


def kernel_plane_record(mode: str = "auto") -> dict:
    """The kernel-plane coverage block shared by the BENCH_*.json emitters:
    resolved mode, per-phase fused flags, and the fused fraction."""
    resolved = resolve_kernel_mode(mode)
    cov = fused_phase_coverage(mode)
    frac = sum(cov.values()) / len(cov) if cov else 0.0
    return {"kernel_mode": mode, "resolved": resolved,
            "fused_phases": cov,
            "fused_phase_frac": round(frac, 3)}


def main(emit_json: bool = True) -> dict:
    csv = Csv("bench_engine")
    kp = kernel_plane_record("auto")
    csv.row("path", "seconds", "rounds_per_sec", "fused_phase_frac")

    # head-to-head: legacy loop vs jitted engine, reps interleaved
    single = interleaved_best_of({
        "legacy_loop": lambda: _sim().run_legacy(),
        "jitted_engine": lambda: _sim().run(),
    }, REPS)
    t_legacy, t_engine = single["legacy_loop"], single["jitted_engine"]
    csv.row("legacy_loop", f"{t_legacy:.2f}", f"{T_ROUNDS / t_legacy:.2f}",
            "0.000")
    csv.row("jitted_engine", f"{t_engine:.2f}", f"{T_ROUNDS / t_engine:.2f}",
            f"{kp['fused_phase_frac']:.3f}")

    # Fig. 3-style grid: 2 straggler fractions x 2 seeds, one batched call
    overrides = [{"straggler_frac": f} for f in (0.2, 0.4)]
    seeds = (0, 1)
    n_pts = len(overrides) * len(seeds)

    def sweep_legacy():
        for ov in overrides:
            for seed in seeds:
                BHFLSimulator(dataclasses.replace(_setting(), **ov), "hieavg",
                              "temporary", "temporary", seed=seed,
                              **KW).run_legacy()

    sweep = interleaved_best_of({
        "legacy_4pt_sweep": sweep_legacy,
        "engine_4pt_sweep": lambda: run_sweep(
            _setting(), seeds=seeds, overrides=overrides, **KW),
    }, REPS)
    t_sweep_legacy = sweep["legacy_4pt_sweep"]
    t_sweep_engine = sweep["engine_4pt_sweep"]
    sweep_rounds = n_pts * T_ROUNDS
    csv.row("legacy_4pt_sweep", f"{t_sweep_legacy:.2f}",
            f"{sweep_rounds / t_sweep_legacy:.2f}", "0.000")
    csv.row("engine_4pt_sweep", f"{t_sweep_engine:.2f}",
            f"{sweep_rounds / t_sweep_engine:.2f}",
            f"{kp['fused_phase_frac']:.3f}")

    out = {
        "setting": "REDUCED",
        "n_edges": _setting().n_edges,
        "t_global_rounds": T_ROUNDS,
        "steps_per_epoch": KW["steps_per_epoch"],
        "reps": REPS,
        "timing": "interleaved_best_of",
        "kernel_plane": kp,
        "legacy_rounds_per_sec": round(T_ROUNDS / t_legacy, 3),
        "engine_rounds_per_sec": round(T_ROUNDS / t_engine, 3),
        "speedup": round(t_legacy / t_engine, 2),
        "sweep_points": n_pts,
        "sweep_legacy_rounds_per_sec": round(sweep_rounds / t_sweep_legacy, 3),
        "sweep_engine_rounds_per_sec": round(sweep_rounds / t_sweep_engine, 3),
        "sweep_speedup": round(t_sweep_legacy / t_sweep_engine, 2),
    }
    if emit_json:
        with open("BENCH_engine.json", "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote BENCH_engine.json (speedup {out['speedup']}x, "
              f"sweep {out['sweep_speedup']}x)")
    csv.done()
    return out


if __name__ == "__main__":
    main()
